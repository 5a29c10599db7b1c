"""span-leak rule (ISSUE 6 satellite): `obs.span(...)` must be closed.

A span begun without a guaranteed close corrupts nothing (the tracer
pops leaked children when the enclosing span exits) but silently loses
the interval it was supposed to measure — and on the serving/executor
hot paths a leak means the one trace the ROADMAP perf items depend on
lies about where time went.  The rule enforces the two closed shapes:

* `with obs.span(...):` / `with obs.span(...) as s:` — the context
  manager is the canonical form; `__exit__` records even when the body
  raises.
* `return obs.span(...)` — delegation (a factory handing the span to
  its caller, e.g. `obs.span()` itself wrapping `TRACER.span()`); the
  CALLER is then in rule scope and must use a `with`.

Anything else — `s = obs.span(...)` then manual `__enter__`, a span
passed as an argument, a bare expression statement — is flagged.
Retroactive recording (`obs.add_span`) needs no closure and is the
escape hatch for call sites that only know a span existed after the
fact.  Suppress a reviewed exception with `# span-ok: <why>` or the
generic `# tpulint: disable=span-leak`.

Watched modules: the obs package itself plus every subsystem the
tentpole instrumented — the shipped tree must stay clean
(tests/test_obs.py asserts it).
"""

from __future__ import annotations

import ast
import os
from typing import List

from . import LintContext, LintFinding, register_rule

RULE = "span-leak"
MARKER = "# span-ok"

# files/dirs whose span() call sites the rule enforces
WATCHED = [
    "paddle_tpu/obs",
    "paddle_tpu/obs/telemetry.py",  # explicit: the live-telemetry layer
    # stays covered even if the obs dir entry is ever narrowed
    "paddle_tpu/obs/devprof.py",  # explicit: same reasoning for the
    # measured device-time profiler (ISSUE 12)
    "paddle_tpu/obs/memprof.py",  # explicit: same reasoning for the
    # HBM memory ledger (ISSUE 14)
    "paddle_tpu/obs/numerics.py",  # explicit: same reasoning for the
    # numeric-health layer (ISSUE 15)
    "paddle_tpu/ckpt",
    "paddle_tpu/profiler",
    "paddle_tpu/fluid/executor.py",
    "paddle_tpu/parallel/compiler.py",
    "paddle_tpu/parallel/quant_collectives.py",  # explicit: the int8
    # codec traces inside the jitted step (ISSUE 16) — span misuse
    # there would wrap device-side code in host timers
    "paddle_tpu/dataset/feed_pipeline.py",
    "paddle_tpu/fluid/aot_cache.py",  # explicit: the persistent AOT
    # cache times its own load/store (ISSUE 17) — a leaked span there
    # would misattribute disk I/O to whichever compile wrapped it
    "paddle_tpu/serving",  # covers registry.py (multi-tenant fleet)
    "paddle_tpu/ops/pallas/attention.py",  # explicit: the ragged
    # paged-attention dispatch seam (ISSUE 20) traces inside the
    # decode jit — a leaked span there would wrap device-side kernel
    # work in a host timer on every decoded token
    "paddle_tpu/transforms/__init__.py",
    "paddle_tpu/analysis/verifier.py",
    "bench.py",
]


def _is_span_call(node: ast.Call) -> bool:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id in ("span", "obs_span")
    if isinstance(fn, ast.Attribute):
        return fn.attr == "span"
    return False


def _closed_call_ids(tree: ast.Module) -> set:
    """ids of span() Call nodes in a sanctioned position: a with-item
    context expression, or the value of a return (delegation)."""
    ok = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if isinstance(item.context_expr, ast.Call):
                    ok.add(id(item.context_expr))
        elif isinstance(node, ast.Return) and isinstance(node.value,
                                                         ast.Call):
            ok.add(id(node.value))
    return ok


def check_source(rel: str, ctx: LintContext) -> List[LintFinding]:
    tree = ctx.tree(rel)
    closed = _closed_call_ids(tree)
    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_span_call(node)):
            continue
        if id(node) in closed:
            continue
        if ctx.suppressed(rel, node.lineno, RULE, MARKER):
            continue
        findings.append(LintFinding(
            RULE, rel, node.lineno,
            "span begun outside a `with` (or `return` delegation): the "
            "interval is lost if this path raises — use "
            "`with obs.span(...):`, record retroactively with "
            f"obs.add_span, or mark a reviewed exception "
            f"'{MARKER}: <why>'"))
    return findings


@register_rule(RULE,
               help_str="obs.span(...) begun without context-manager/"
                        "return closure in the instrumented modules "
                        f"(suppress with '{MARKER}: <why>')",
               marker=MARKER)
def rule(ctx: LintContext) -> List[LintFinding]:
    findings = []
    for target in WATCHED:
        full = os.path.join(ctx.root, target)
        if not os.path.exists(full):
            findings.append(LintFinding(
                RULE, target, 0, "watched path missing — update "
                                 "span_leak.WATCHED if it moved"))
            continue
        for rel in ctx.iter_py(target):
            findings.extend(check_source(rel, ctx))
    return findings
