"""Program->Program graph-transform pass pipeline (ISSUE 5 tentpole).

The reference framework runs whole-graph rewrites as C++ IR passes
(multi_devices_graph_pass, the fuse_* family); TensorFlow's Grappler
makes the same argument for layout + fusion as graph-level passes
(arxiv 1605.08695).  This package is the TPU-native transform twin of
the `analysis.verifier` pass pipeline: same registration and provenance
idioms, but the passes MUTATE the Program they are handed instead of
reporting findings.

Contract (docs/graph_transforms.md):

* `apply_transforms(program, ...)` clones the program and runs every
  enabled pass over the CLONE, in registration order — the caller's
  program is never touched, so the Executor's compile-cache key (built
  from the original `(id, version)`) stays stable across steps and the
  pipeline runs exactly once per compile-cache miss.
* `maybe_transform_program` is the Executor._prepare /
  CompiledProgram._compile hook: gated by `FLAGS_graph_transforms`,
  wall time booked on the `transform_ms` profiler timer and per-pass
  rewrite counts on `transform_<pass>_rewrites` stats — all provably
  flat on cache-hit steps.
* Transforms run immediately BEFORE verification, so every rewrite is
  checked by the PR-3 verifier's ERROR-tier passes.

Shipped passes:

* `layout_optimize` (on) — rewrite NCHW conv/pool/batch_norm/interp
  chains to NHWC so channels stay on the TPU lanes
  (transforms/layout.py).
* `fold_bn` (off) — fold inference-mode batch_norm into the preceding
  conv's weights/bias (transforms/fold_bn.py).  Off by default because
  an eval program folded mid-training would not see later updates to
  the running stats; inference/export paths opt in.
* `dead_op_elim` (on) — actually remove the dead / write-never-read
  ops the verifier only warns about (transforms/dce.py).

`FLAGS_graph_transforms` grammar: "on" (default set), "off" (disable
everything), or comma-separated per-pass overrides —
"on,fold_bn=on", "layout_optimize=off", "fold_bn=on".
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, Iterable, List, Optional

_EMPTY = "@EMPTY@"  # framework.EMPTY_VAR_NAME (kept import-free)

# name -> {"fn", "default", "help"}; insertion order is execution order
_PASSES: "Dict[str, dict]" = {}


def register_transform(name: str, default: bool = True, help_str: str = ""):
    """Register `fn(ctx: TransformContext) -> int` under `name`; the
    return value is the number of ops the pass rewrote/removed (its
    `ops_rewritten` counter)."""

    def deco(fn: Callable):
        _PASSES[name] = {"fn": fn, "default": default, "help": help_str}
        return fn

    return deco


def registered_transforms() -> List[str]:
    return list(_PASSES)


def transform_info(name: str) -> dict:
    info = _PASSES[name]
    return {"default": info["default"], "help": info["help"]}


class TransformContext:
    """Everything a pass may consult/mutate.  `feed_names` /
    `fetch_names` are None when unknown — passes must degrade
    conservatively (e.g. dead_op_elim is a no-op without fetch info).
    `scope` is optional and read-only: passes must NOT require runtime
    values (the pipeline also runs for standalone tooling)."""

    def __init__(self, program, feed_names=None, fetch_names=None,
                 scope=None):
        self.program = program
        self.feed_names = set(feed_names) if feed_names is not None \
            else None
        self.fetch_names = list(fetch_names) if fetch_names is not None \
            else None
        self.scope = scope

    @property
    def fetch_set(self):
        return set(self.fetch_names or ())


def _grad_section(op) -> bool:
    """Backward/optimizer-section ops: synthesized by append_backward /
    minimize.  The layout pass leaves them alone — gradients flow
    through jax.vjp of the (rewritten) forward rules, so rewriting the
    forward is sufficient and the backward stays consistent for free."""
    if op.attr("fwd_op_id") is not None:
        return True
    # OpRole.Backward=1 | Optimize=2 (| Loss=256 combinations)
    return bool(op.attr("op_role", 0) & 3)


def _find_var(block, name: str):
    try:
        return block._var_recursive(name)
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# Provenance stamping (obs/opprof.py, docs/observability.md)
# ---------------------------------------------------------------------------
#
# apply_transforms clones the program, and the clone gets a FRESH
# prog_id — so before any pass runs, every cloned op is stamped with
# its SOURCE program's provenance (`op_provenance` attr, consumed by
# ops/registry.op_provenance at lowering).  Passes that rewrite an op
# call tag_provenance(op, pass_name) to append a `[pass=<name>]` tag,
# and passes that INSERT ops call inherit_provenance(new_op, src_op,
# pass_name) so the synthesized op attributes to the source op it
# replaces — obs.op_profile then reports rewritten/folded cost against
# identities the user can grep in their build script.

def stamp_provenance(program, src_prog_id: int) -> None:
    """Stamp every op of `program` (a fresh clone) with provenance
    naming `src_prog_id`; ops already carrying one keep it (a clone of
    a transformed program keeps pointing at the ORIGINAL source)."""
    for blk in program.blocks:
        for op in blk.ops:
            if not op.attrs.get("op_provenance"):
                op.attrs["op_provenance"] = (
                    f"program#{src_prog_id}/block{blk.idx}"
                    f"/op{op.id}:{op.type}")


def tag_provenance(op, pass_name: str) -> None:
    """Append `[pass=<name>]` to the op's provenance (merging into an
    existing tag list), marking it rewritten by `pass_name`."""
    from ..ops.registry import op_provenance

    prov = op_provenance(op)
    if prov.endswith("]") and "[pass=" in prov:
        base, tags = prov[:-1].rsplit("[pass=", 1)
        names = tags.split(",")
        if pass_name not in names:
            names.append(pass_name)
        prov = f"{base}[pass={','.join(names)}]"
    else:
        prov = f"{prov}[pass={pass_name}]"
    op.attrs["op_provenance"] = prov


def inherit_provenance(new_op, src_op, pass_name: str) -> None:
    """A pass-synthesized op attributes to the source op it replaces,
    tagged with the pass that minted it."""
    from ..ops.registry import op_provenance

    new_op.attrs["op_provenance"] = op_provenance(src_op)
    tag_provenance(new_op, pass_name)


# import the pass modules AFTER the registry exists (registration side
# effect, verifier idiom).  Import order IS execution order: fold_bn
# must see the NCHW graph (it rewrites conv+bn pairs), layout_optimize
# then NHWC-ifies whatever survives, dead_op_elim sweeps up.
from . import fold_bn  # noqa: E402,F401
from . import transpose_sink  # noqa: E402,F401
from . import layout  # noqa: E402,F401
from . import dce  # noqa: E402,F401


_WARNED_UNKNOWN: set = set()
_SPEC_CACHE: Dict[str, tuple] = {}


def _resolve_spec(spec: str) -> tuple:
    """Parse a FLAGS_graph_transforms value -> ((name, enabled), ...);
    memoized per spec string so the per-step cache-key read costs one
    dict probe."""
    cached = _SPEC_CACHE.get(spec)
    if cached is not None:
        return cached
    defaults = {n: i["default"] for n, i in _PASSES.items()}
    if spec in ("off", "0", "false", "no", "none"):
        out = tuple((n, False) for n in defaults)
        _SPEC_CACHE[spec] = out
        return out
    overrides: Dict[str, bool] = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok or tok in ("on", "1", "true", "yes", "default"):
            continue
        if "=" in tok:
            name, val = (s.strip() for s in tok.split("=", 1))
            want = val in ("on", "1", "true", "yes")
        elif tok.startswith(("+", "-")):
            name, want = tok[1:], tok.startswith("+")
        else:
            name, want = tok, True
        if name not in defaults:
            if name not in _WARNED_UNKNOWN:
                _WARNED_UNKNOWN.add(name)
                warnings.warn(
                    f"FLAGS_graph_transforms: unknown pass {name!r} "
                    f"(registered: {sorted(defaults)})", stacklevel=3)
            continue
        overrides[name] = want
    out = tuple((n, overrides.get(n, d)) for n, d in defaults.items())
    _SPEC_CACHE[spec] = out
    return out


def _current_spec() -> str:
    from ..fluid.flags import flag

    return str(flag("graph_transforms", "on")).strip().lower()


def enabled_passes() -> Dict[str, bool]:
    """Resolve FLAGS_graph_transforms into {pass_name: enabled}."""
    return dict(_resolve_spec(_current_spec()))


def enabled_signature() -> tuple:
    """The enabled-pass set as a hashable compile-cache key component:
    flipping FLAGS_graph_transforms changes what gets lowered, so it is
    part of the compiled program's identity (Executor._cache_key), the
    same way FLAGS_check_nan_inf is.  The obs.numerics instrumentation
    mode joins the same signature when armed: stat collection changes
    the traced computation, so flipping PADDLE_OBS_NUMERICS must be a
    compile-cache miss too — and `off` contributes nothing, keeping
    the uninstrumented signature byte-identical to pre-numerics."""
    sig = tuple(n for n, on in _resolve_spec(_current_spec()) if on)
    try:
        from ..obs import numerics

        m = numerics.mode()
    except Exception:  # noqa: BLE001 - obs unavailable (minimal env)
        m = "off"
    if m != "off":
        sig = sig + (f"numerics={m}",)
    try:
        from ..parallel import quant_collectives as _qc

        tok = _qc.signature_token()
    except Exception:  # noqa: BLE001 - parallel unavailable (minimal env)
        tok = None
    if tok is not None:
        sig = sig + (tok,)
    return sig


class TransformDebugError(RuntimeError):
    """Raised under FLAGS_transform_debug when the per-pass bisection
    pinpoints the transform pass whose rewrite broke shape/dtype
    consistency."""

    def __init__(self, pass_name: str, findings):
        self.pass_name = pass_name
        self.findings = list(findings)
        lines = "\n".join(f"  {f}" for f in self.findings)
        super().__init__(
            f"transform pass {pass_name!r} broke shape/dtype "
            f"consistency ({len(self.findings)} finding(s), "
            f"FLAGS_transform_debug bisection):\n{lines}")


def _debug_check(program, feed_names, fetch_names):
    from ..analysis import shape_check

    return shape_check.check_program(
        program, feed=feed_names, fetch_list=fetch_names)


def apply_transforms(program, feed_names=None, fetch_names=None,
                     scope=None, passes: Optional[Iterable[str]] = None):
    """Run the transform pipeline over a CLONE of `program`.

    Returns `(transformed_program, {pass_name: ops_rewritten})`.  The
    input program is never mutated; op ids are preserved by the clone so
    grad-op `fwd_op_id` links stay valid.

    Under FLAGS_transform_debug, the shape-consistency check runs after
    EVERY pass (bisection mode): the first pass whose rewrite breaks
    the graph raises TransformDebugError naming it — instead of the
    post-pipeline verifier reporting a failure nothing attributes."""
    wanted = list(passes) if passes is not None else [
        n for n, on in enabled_passes().items() if on]
    from ..fluid.flags import flag

    debug = bool(flag("transform_debug", False))
    clone = program.clone()
    # provenance must name the SOURCE program (the clone's prog_id is
    # fresh), and must be stamped BEFORE passes rewrite anything
    stamp_provenance(clone, program.prog_id)
    ctx = TransformContext(clone, feed_names=feed_names,
                           fetch_names=fetch_names, scope=scope)
    # a program that is already inconsistent BEFORE any pass must not
    # get the first pass blamed for it
    baseline_clean = debug and not _debug_check(clone, feed_names,
                                                fetch_names)
    stats: Dict[str, int] = {}
    for name in _PASSES:
        if name not in wanted:
            continue
        stats[name] = int(_PASSES[name]["fn"](ctx))
        if baseline_clean:
            findings = _debug_check(clone, feed_names, fetch_names)
            if findings:
                raise TransformDebugError(name, findings)
    return clone, stats


def maybe_transform_program(program, feed_names=None, fetch_names=None,
                            scope=None):
    """Compile-cache-miss hook for Executor._prepare /
    CompiledProgram._compile: run the enabled passes under the
    FLAGS_graph_transforms gate, immediately before verification.
    Returns the transformed clone (or the original program untouched
    when every pass is disabled).  Never runs on a cache hit — callers
    sit behind the compile cache — and books its wall time on the
    `transform_ms` profiler timer (and the start-up phase
    `setup.transform`) plus per-pass
    `transform_<pass>_rewrites` counters so tests can assert the hot
    path pays zero transform time."""
    enabled = [n for n, on in enabled_passes().items() if on]
    if not enabled:
        return program
    from ..obs import span as obs_span
    from ..profiler import stage, stat_add

    with obs_span("transforms.apply"), \
            stage("setup.transform", "transform_ms"):
        out, stats = apply_transforms(program, feed_names=feed_names,
                                      fetch_names=fetch_names,
                                      scope=scope, passes=enabled)
        stat_add("transform_runs")
        for name, n in stats.items():
            if n:
                stat_add(f"transform_{name}_rewrites", n)
    return out
