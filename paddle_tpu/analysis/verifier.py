"""Program verifier: a pass pipeline over the Program IR (ISSUE 3
tentpole, part 1).

The reference framework validates ProgramDesc invariants in C++ (op
registry checks, var def-use, block linkage) before execution; our
pure-Python IR previously lowered unchecked, so a malformed graph
surfaced as an opaque JAX/XLA trace error with no op-level provenance.
This module restores that validation layer, TPU-native:

* **Structural passes** (ERROR tier): every op type resolves in
  `ops/registry`, inputs are defined before use under block scoping
  rules, control-flow `sub_block` references resolve, and block parent
  links are acyclic and in range.
* **Dataflow passes**: donation/aliasing safety (a var that is both
  fetched and donated is an error — the donated buffer can be
  invalidated while a LazyFetch handle still references it) and
  cross-replica collective-order consistency (every program path must
  issue `c_allreduce`/`c_broadcast`/... in the same ring-id order, so
  collectives under a conditional sub-block are an error — replicas
  whose condition differs would issue them in different order and the
  pjit lowering deadlocks/diverges across hosts).  WARNING-tier passes
  flag dead ops, vars written-never-read, and unreachable blocks.

Findings carry `program#<id> block<idx> op<id> (<type>)` provenance —
greppable — plus the nearest Python construction stack when the
Program recorded one (`FLAGS_op_callstack`).

Integration: `Executor._prepare` and `CompiledProgram._compile` call
`maybe_verify_program` once per compile-cache miss (the hot path pays
nothing on a cache hit), gated by `FLAGS_verify_program`
("on" raises on ERROR findings, "warn" is the warn-only escape hatch,
"off" disables).  Verification wall time accumulates on the
`verify_ms` profiler timer so tests can assert zero verifier time on
cache-hit steps.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, Iterable, List, Optional, Set

ERROR = "error"
WARNING = "warning"
INFO = "info"

_EMPTY = "@EMPTY@"  # framework.EMPTY_VAR_NAME (kept import-free)

# collective op families that must be issued in identical order on every
# replica (matches CompiledProgram._has_collective_ops)
_COLLECTIVE_EXTRA = {"barrier", "alltoall", "send_v2", "recv_v2",
                     "mp_allreduce_sum"}

# point-to-point ops are NOT order-checked: they are pairwise-matched at
# lowering by the p2p queue (ops/collective_ops.py raises "no data
# source" on a mis-pairing), and a send/recv pair inside one
# conditional sub-block is a supported pattern — only ring collectives
# require every replica to issue them on every path
_P2P = {"send_v2", "recv_v2"}

# op types whose value is their side effect — never "dead"
_EFFECT_OPS = {"print", "assert", "py_func", "while",
               "conditional_block", "run_program", "save", "load"}

_CONDITIONAL_OWNERS = {"conditional_block"}
_LOOP_OWNERS = {"while"}


def _is_collective(op_type: str) -> bool:
    return op_type.startswith("c_") or op_type in _COLLECTIVE_EXTRA


class Finding:
    """One verifier finding with op-level provenance."""

    __slots__ = ("severity", "pass_name", "message", "prog_id",
                 "block_idx", "op_id", "op_type", "var", "callstack")

    def __init__(self, severity: str, pass_name: str, message: str,
                 prog_id: int, block_idx: Optional[int] = None,
                 op_id: Optional[int] = None,
                 op_type: Optional[str] = None,
                 var: Optional[str] = None,
                 callstack: Optional[List[str]] = None):
        self.severity = severity
        self.pass_name = pass_name
        self.message = message
        self.prog_id = prog_id
        self.block_idx = block_idx
        self.op_id = op_id
        self.op_type = op_type
        self.var = var
        self.callstack = callstack

    @property
    def location(self) -> str:
        loc = f"program#{self.prog_id}"
        if self.block_idx is not None:
            loc += f" block{self.block_idx}"
        if self.op_id is not None:
            loc += f" op{self.op_id}"
        if self.op_type:
            loc += f" ({self.op_type})"
        if self.var:
            loc += f" var {self.var!r}"
        return loc

    def __str__(self):
        s = (f"{self.location}: [{self.pass_name}/{self.severity}] "
             f"{self.message}")
        if self.callstack:
            s += "".join(f"\n    at {fr}" for fr in self.callstack)
        return s

    __repr__ = __str__


class ProgramVerificationError(RuntimeError):
    """Raised by maybe_verify_program when ERROR findings exist and
    FLAGS_verify_program is 'on'."""

    def __init__(self, findings: List[Finding]):
        self.findings = findings
        lines = "\n".join(f"  {f}" for f in findings)
        super().__init__(
            f"program verifier found {len(findings)} error(s) "
            f"(set FLAGS_verify_program=warn to continue anyway, "
            f"FLAGS_op_callstack=1 for construction stacks):\n{lines}")


class VerifyContext:
    """Everything a pass may consult.  `feed_names` / `scope_names` /
    `fetch_names` / `donated` are None when unknown (standalone
    verification) — passes must degrade gracefully rather than
    false-positive."""

    def __init__(self, program, feed_names=None, fetch_names=None,
                 scope_names=None, donated=None):
        self.program = program
        self.feed_names = set(feed_names) if feed_names is not None \
            else None
        self.fetch_names = list(fetch_names) if fetch_names is not None \
            else None
        self.scope_names = set(scope_names) if scope_names is not None \
            else None
        self.donated = set(donated) if donated is not None else set()

    @property
    def prog_id(self) -> int:
        return getattr(self.program, "prog_id", id(self.program))

    def external_names(self) -> Set[str]:
        out: Set[str] = set()
        if self.feed_names:
            out |= self.feed_names
        if self.scope_names:
            out |= self.scope_names
        return out

    def finding(self, severity, pass_name, message, block=None, op=None,
                var=None) -> Finding:
        callstack = None
        if op is not None and isinstance(op.attrs.get("op_callstack"),
                                         (list, tuple)):
            callstack = list(op.attrs["op_callstack"])
        return Finding(
            severity, pass_name, message, self.prog_id,
            block_idx=(block.idx if block is not None
                       else (op.block.idx if op is not None else None)),
            op_id=op.id if op is not None else None,
            op_type=op.type if op is not None else None,
            var=var, callstack=callstack)


# ---------------------------------------------------------------------------
# Pass registry
# ---------------------------------------------------------------------------

# name -> (tier, fn); insertion order is execution order
_PASSES: "Dict[str, tuple]" = {}


def register_pass(name: str, tier: str = ERROR):
    """Register `fn(ctx: VerifyContext) -> List[Finding]` under `name`.
    ERROR-tier passes run on every compile-cache miss; WARNING-tier
    passes only run through explicit `verify_program` calls (tpulint,
    tests, tooling)."""

    def deco(fn: Callable):
        _PASSES[name] = (tier, fn)
        return fn

    return deco


def registered_passes(tier: Optional[str] = None) -> List[str]:
    return [n for n, (t, _f) in _PASSES.items()
            if tier is None or t == tier]


# ---------------------------------------------------------------------------
# Structural passes (ERROR tier)
# ---------------------------------------------------------------------------

@register_pass("op-registry")
def check_op_registry(ctx: VerifyContext) -> List[Finding]:
    """Every op type must resolve to a lowering rule in ops/registry
    (grad ops resolve through their forward type)."""
    from ..ops import registry

    out = []
    for blk in ctx.program.blocks:
        for op in blk.ops:
            if op.attr("fwd_op_id") is not None:
                ft = op.attr("fwd_op_type") or (
                    op.type[:-5] if op.type.endswith("_grad")
                    else op.type)
                if registry.has_op(ft) or registry.has_grad(ft):
                    continue
                out.append(ctx.finding(
                    ERROR, "op-registry",
                    f"grad op references forward type {ft!r} which has "
                    f"no registered lowering", op=op))
            elif not registry.has_op(op.type):
                out.append(ctx.finding(
                    ERROR, "op-registry",
                    f"op type {op.type!r} has no lowering rule in "
                    f"ops/registry — lowering this block would fail",
                    op=op))
    return out


def _safe_parent(program, blk):
    p = blk.parent_idx
    if isinstance(p, int) and 0 <= p < len(program.blocks) \
            and p != blk.idx:
        return program.blocks[p]
    return None


def _resolvable(program, blk, name: str) -> bool:
    """Whether `name` resolves in the block-scoped symbol table
    (corruption-tolerant: never raises on bad parent links)."""
    seen = set()
    b = blk
    while b is not None and b.idx not in seen:
        if name in b.vars:
            return True
        seen.add(b.idx)
        b = _safe_parent(program, b)
    return False


@register_pass("def-before-use")
def check_def_before_use(ctx: VerifyContext) -> List[Finding]:
    """Inputs must be defined before use under block scoping rules:
    produced by an earlier op (this block or an ancestor at the
    sub-block's call site), declared as data/persistable (fed or
    scope-resident at run time), or — inside a `while` body — a
    loop-carried var that resolves outside the loop."""
    prog = ctx.program
    findings: List[Finding] = []
    ext = ctx.external_names()
    all_written = {n for blk in prog.blocks for op in blk.ops
                   for n in op.output_arg_names() if n != _EMPTY}

    def block_entry(blk) -> Set[str]:
        return {v.name for v in blk.vars.values()
                if getattr(v, "is_data", False) or v.persistable}

    def walk(blk, avail: Set[str], owner_type: Optional[str],
             visited: Set[int]):
        avail = set(avail) | block_entry(blk) | ext
        entry_avail = set(avail)
        first_write: Dict[str, int] = {}
        for i, op in enumerate(blk.ops):
            for n in op.output_arg_names():
                if n != _EMPTY and n not in first_write:
                    first_write[n] = i
        for i, op in enumerate(blk.ops):
            for n in op.input_arg_names():
                if n == _EMPTY or n in avail:
                    continue
                fw = first_write.get(n)
                if fw is not None:
                    # written in this block, but only at op index >= i
                    loop_carried = (owner_type in _LOOP_OWNERS
                                    and (n in entry_avail
                                         or _resolvable(prog, blk, n)))
                    if not loop_carried:
                        findings.append(ctx.finding(
                            ERROR, "def-before-use",
                            f"input {n!r} is read before it is written "
                            f"(first write is op{blk.ops[fw].id} "
                            f"{blk.ops[fw].type!r} at position {fw})",
                            op=op))
                        avail.add(n)  # report once per name
                elif _resolvable(prog, blk, n) or n in all_written:
                    # declared somewhere: the value must arrive via
                    # feed or scope at run time — the executor's own
                    # "neither fed nor initialized" check owns that
                    # diagnosis when feed/scope info says otherwise
                    pass
                else:
                    findings.append(ctx.finding(
                        ERROR, "def-before-use",
                        f"input {n!r} is not defined in any reachable "
                        f"block scope and no op ever writes it",
                        op=op))
                    avail.add(n)
            sb = op.attr("sub_block")
            if isinstance(sb, int) and 0 < sb < len(prog.blocks) \
                    and sb not in visited:
                walk(prog.blocks[sb], avail, op.type, visited | {sb})
            for n in op.output_arg_names():
                if n != _EMPTY:
                    avail.add(n)

    if prog.blocks:
        walk(prog.blocks[0], set(), None, {0})
    return findings


@register_pass("block-linkage")
def check_block_linkage(ctx: VerifyContext) -> List[Finding]:
    """Control-flow sub-block references resolve; parent links are in
    range and acyclic; unreferenced non-root blocks are flagged."""
    prog = ctx.program
    n = len(prog.blocks)
    out: List[Finding] = []
    for pos, blk in enumerate(prog.blocks):
        if blk.idx != pos:
            out.append(ctx.finding(
                ERROR, "block-linkage",
                f"block at position {pos} carries idx {blk.idx}",
                block=blk))
        p = blk.parent_idx
        if blk.idx == 0:
            if p != -1:
                out.append(ctx.finding(
                    ERROR, "block-linkage",
                    f"global block has parent_idx {p} (must be -1)",
                    block=blk))
            continue
        if not isinstance(p, int) or not (-1 <= p < n) or p == blk.idx:
            out.append(ctx.finding(
                ERROR, "block-linkage",
                f"dangling parent link: parent_idx {p} does not "
                f"resolve", block=blk))
            continue
        seen: Set[int] = set()
        b = blk
        while b is not None:
            if b.idx in seen:
                out.append(ctx.finding(
                    ERROR, "block-linkage",
                    f"parent chain of block {blk.idx} is cyclic",
                    block=blk))
                break
            seen.add(b.idx)
            b = _safe_parent(prog, b)

    referenced: Set[int] = set()
    for blk in prog.blocks:
        for op in blk.ops:
            if not op.has_attr("sub_block"):
                continue
            sb = op.attr("sub_block")
            if not isinstance(sb, int) or not (0 < sb < n):
                out.append(ctx.finding(
                    ERROR, "block-linkage",
                    f"sub_block attr {sb!r} does not resolve to a "
                    f"block (program has {n})", op=op))
                continue
            referenced.add(sb)
            if prog.blocks[sb].parent_idx != blk.idx:
                out.append(ctx.finding(
                    WARNING, "block-linkage",
                    f"sub-block {sb} has parent {prog.blocks[sb].parent_idx}, "
                    f"not the owning block {blk.idx}", op=op))
    for blk in prog.blocks[1:]:
        if blk.idx not in referenced:
            out.append(ctx.finding(
                WARNING, "block-linkage",
                f"block {blk.idx} is referenced by no sub_block attr "
                f"(unreachable)", block=blk))
    return out


# ---------------------------------------------------------------------------
# Dataflow passes
# ---------------------------------------------------------------------------

@register_pass("donation-safety")
def check_donation_safety(ctx: VerifyContext) -> List[Finding]:
    """A var that is both fetched and donated is an error: the donated
    buffer may be reused by XLA while a LazyFetch handle still
    references it (the Executor shields its own state donation with a
    device-side copy; explicitly donated feeds have no such shield)."""
    if not ctx.donated or not ctx.fetch_names:
        return []
    out = []
    for name in sorted(set(ctx.donated) & set(ctx.fetch_names)):
        out.append(ctx.finding(
            ERROR, "donation-safety",
            f"variable {name!r} is both fetched and donated — the "
            f"LazyFetch handle would reference a buffer XLA is free to "
            f"reuse; fetch a copy or drop the donation", var=name))
    return out


@register_pass("collective-order")
def check_collective_order(ctx: VerifyContext) -> List[Finding]:
    """Cross-replica collective-order consistency: every program path
    must issue collectives in the same ring-id order.  A collective
    under a conditional sub-block executes on some paths and not
    others, so replicas whose condition differs deadlock (or silently
    mismatch rings); a collective in a `while` body is order-consistent
    only if the trip count is replica-uniform, which cannot be proven
    statically — flagged as a warning.  Point-to-point send/recv are
    exempt: the p2p pairing queue at lowering owns their diagnosis."""
    prog = ctx.program
    out: List[Finding] = []

    def walk(blk, in_cond: bool, in_loop: bool, visited: Set[int]):
        for op in blk.ops:
            if _is_collective(op.type) and op.type not in _P2P:
                ring = op.attr("ring_id", 0)
                if in_cond:
                    out.append(ctx.finding(
                        ERROR, "collective-order",
                        f"collective issued under a conditional "
                        f"sub-block (ring {ring}): replicas whose "
                        f"condition differs issue collectives in "
                        f"different order and the lowering is "
                        f"nondeterministic across hosts — hoist it out "
                        f"of the branch", op=op))
                elif in_loop:
                    out.append(ctx.finding(
                        WARNING, "collective-order",
                        f"collective inside a while body (ring {ring}): "
                        f"the trip count must be identical on every "
                        f"replica or collective order diverges", op=op))
            sb = op.attr("sub_block")
            if isinstance(sb, int) and 0 < sb < len(prog.blocks) \
                    and sb not in visited:
                walk(prog.blocks[sb],
                     in_cond or op.type in _CONDITIONAL_OWNERS,
                     in_loop or op.type in _LOOP_OWNERS,
                     visited | {sb})

    if prog.blocks:
        walk(prog.blocks[0], False, False, {0})
    return out


def _global_reads(prog) -> Set[str]:
    return {n for blk in prog.blocks for op in blk.ops
            for n in op.input_arg_names() if n != _EMPTY}


def _var_of(prog, blk, name: str):
    seen = set()
    b = blk
    while b is not None and b.idx not in seen:
        if name in b.vars:
            return b.vars[name]
        seen.add(b.idx)
        b = _safe_parent(prog, b)
    return None


@register_pass("dead-op", tier=WARNING)
def check_dead_ops(ctx: VerifyContext) -> List[Finding]:
    """Ops whose outputs are never read, fetched, or persisted do pure
    wasted work (XLA DCEs them, but they still cost trace time and
    usually indicate a graph-construction bug).  Needs fetch info —
    skipped when `fetch_names` is unknown."""
    if ctx.fetch_names is None:
        return []
    prog = ctx.program
    reads = _global_reads(prog)
    fetch = set(ctx.fetch_names)
    out = []
    for blk in prog.blocks:
        for op in blk.ops:
            if op.type in _EFFECT_OPS or _is_collective(op.type) \
                    or op.has_attr("sub_block"):
                continue
            outs = [n for n in op.output_arg_names() if n != _EMPTY]
            if not outs:
                continue  # no-output ops are presumed effectful
            live = False
            for n in outs:
                v = _var_of(prog, blk, n)
                if n in reads or n in fetch \
                        or (v is not None and v.persistable):
                    live = True
                    break
            if not live:
                out.append(ctx.finding(
                    WARNING, "dead-op",
                    f"dead op: outputs {outs} are never read, fetched, "
                    f"or persisted", op=op))
    return out


@register_pass("write-never-read", tier=WARNING)
def check_write_never_read(ctx: VerifyContext) -> List[Finding]:
    """Vars written but never read anywhere (and not fetched /
    persistable / data) — usually a dangling output slot.  Needs fetch
    info — skipped when `fetch_names` is unknown."""
    if ctx.fetch_names is None:
        return []
    prog = ctx.program
    reads = _global_reads(prog)
    fetch = set(ctx.fetch_names)
    out = []
    reported: Set[str] = set()
    for blk in prog.blocks:
        for op in blk.ops:
            for n in op.output_arg_names():
                if n == _EMPTY or n in reads or n in fetch \
                        or n in reported:
                    continue
                v = _var_of(prog, blk, n)
                if v is not None and (v.persistable
                                      or getattr(v, "is_data", False)):
                    continue
                reported.add(n)
                out.append(ctx.finding(
                    WARNING, "write-never-read",
                    f"variable {n!r} is written but never read",
                    op=op, var=n))
    return out


@register_pass("partition-spec", tier=WARNING)
def check_partition_specs(ctx: VerifyContext) -> List[Finding]:
    """SPMD layout sanity (docs/spmd.md): a registered PartitionSpec
    override or a ZeRO `_sharding_axes` annotation that names an axis
    absent from the active mesh, or whose sharded dim does not divide
    the var's dim, silently degrades to replicated at compile — flag it
    here instead.  Needs an active mesh (`parallel.mesh.current_mesh`)
    — skipped outside any mesh context."""
    try:
        from ..parallel import mesh as mesh_lib
        from ..parallel import spec_layout
    except Exception:  # noqa: BLE001 - jax-less tooling environments
        return []
    mesh = mesh_lib.current_mesh()
    if mesh is None:
        return []
    prog = ctx.program
    overrides = spec_layout.registered_specs()

    # post-propagation shapes (ISSUE 18 satellite): a var whose shape a
    # transform rewrote is validated against what actually flows, not
    # the stale declared metadata.  Computed lazily — only when some
    # var carries a spec to check.
    _prop: Dict[str, tuple] = {}
    _prop_done = [False]

    def actual_shape(v, declared: tuple) -> tuple:
        if not _prop_done[0]:
            _prop_done[0] = True
            try:
                from . import shard_check
                _prop.update(shard_check.propagated_shapes(prog))
            except Exception:  # noqa: BLE001 - degrade to declared
                pass
        got = _prop.get(v.name)
        if got is None:
            return declared
        shape = got[0]
        if shape is None or len(shape) != len(declared):
            return declared
        # keep declared dims where propagation went symbolic
        return tuple(d if p in (-1, None) else int(p)
                     for p, d in zip(shape, declared))

    out = []
    seen: Set[str] = set()
    for blk in prog.blocks:
        for name, v in blk.vars.items():
            if name in seen:
                continue
            seen.add(name)
            shape = tuple(int(s) for s in (v.shape or ()))
            if shape and (name in overrides
                          or getattr(v, "_sharding_axes", None)):
                shape = actual_shape(v, shape)
            problems: List[str] = []
            if name in overrides:
                problems = spec_layout.validate_spec(
                    overrides[name], shape, mesh)
            else:
                axes = getattr(v, "_sharding_axes", None)
                if axes and shape and shape[0] > 1:
                    fits = [ax for ax in axes if ax in mesh.axis_names
                            and shape[0] % mesh.shape[ax] == 0]
                    if not fits:
                        missing = [ax for ax in axes
                                   if ax not in mesh.axis_names]
                        if missing:
                            problems.append(
                                f"sharding axes {tuple(axes)} name "
                                f"{missing} absent from mesh axes "
                                f"{tuple(mesh.axis_names)}")
                        else:
                            problems.append(
                                f"dim 0 of size {shape[0]} not divisible "
                                f"by any of its sharding axes "
                                f"{tuple(axes)} on mesh "
                                f"{dict(mesh.shape)}")
            if not problems:
                continue
            # provenance: the first op that touches the var
            op = None
            for o in blk.ops:
                if name in o.output_arg_names() \
                        or name in o.input_arg_names():
                    op = o
                    break
            for p in problems:
                out.append(ctx.finding(
                    WARNING, "partition-spec",
                    f"partition spec for {name!r} degrades to "
                    f"replicated: {p}", block=blk, op=op, var=name))
    # repeated verifications of one program version (eval clones,
    # cache-miss storms) re-reported identical misfits on every run —
    # dedup through the same registry as the warn-mode fix, cleared by
    # reset_finding_dedup()
    if len(_REPORTED) > _MAX_REPORTED:
        _REPORTED.clear()
    fresh = []
    for f in out:
        key = _finding_key(prog, f)
        if key not in _REPORTED:
            _REPORTED.add(key)
            fresh.append(f)
    return fresh


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _scope_name_set(scope) -> Optional[Set[str]]:
    if scope is None:
        return None
    names: Set[str] = set()
    s = scope
    while s is not None:
        vs = getattr(s, "_vars", None)
        if vs is None:
            break
        names.update(vs)
        s = getattr(s, "parent", None)
    return names


def _fetch_name(v) -> str:
    return v.name if hasattr(v, "name") else str(v)


def verify_program(program, feed=None, fetch_list=None, scope=None,
                   donated=None, passes: Optional[Iterable[str]] = None,
                   tiers: Optional[Iterable[str]] = None) \
        -> List[Finding]:
    """Run the verifier pipeline; returns the findings (empty = clean).

    feed:       feed dict or iterable of feed names (None = unknown)
    fetch_list: Variables or names the caller will fetch (None = unknown)
    scope:      executor Scope whose vars count as defined-at-entry
    donated:    var names whose buffers are donated to XLA
    passes:     restrict to these pass names
    tiers:      restrict to these tiers (e.g. ("error",))
    """
    feed_names = None
    if feed is not None:
        feed_names = set(feed.keys() if hasattr(feed, "keys") else feed)
    fetch_names = None
    if fetch_list is not None:
        fetch_names = [_fetch_name(v) for v in fetch_list]
    ctx = VerifyContext(program, feed_names=feed_names,
                        fetch_names=fetch_names,
                        scope_names=_scope_name_set(scope),
                        donated=donated)
    tiers = set(tiers) if tiers is not None else None
    wanted = set(passes) if passes is not None else None
    findings: List[Finding] = []
    for name, (tier, fn) in _PASSES.items():
        if wanted is not None and name not in wanted:
            continue
        if tiers is not None and tier not in tiers:
            continue
        findings.extend(fn(ctx))
    return findings


# warn-mode finding dedup (ISSUE 11 satellite): repeated
# maybe_verify_program calls on the same program — or on clone-identical
# programs (an eval clone re-verified under a new feed signature) —
# previously re-warned the identical findings on every compile-cache
# miss.  Keyed on (clone family, program version, finding identity), so
# a finding re-surfaces only when the program actually changes.
_REPORTED: Set[tuple] = set()
_MAX_REPORTED = 4096  # bounded: clear-on-full beats unbounded growth


def _finding_key(program, f: Finding) -> tuple:
    root = getattr(program, "clone_root",
                   getattr(program, "prog_id", id(program)))
    return (root, getattr(program, "version", 0), f.pass_name,
            f.severity, f.block_idx, f.op_id, f.op_type, f.var,
            f.message)


def reset_finding_dedup() -> None:
    """Forget which findings were already warned about (tests)."""
    _REPORTED.clear()


def maybe_verify_program(program, feed_names=None, fetch_names=None,
                         scope=None, donated=None) -> None:
    """Compile-cache-miss hook for Executor._prepare /
    CompiledProgram._compile: run the ERROR-tier passes under the
    FLAGS_verify_program gate.  Raises ProgramVerificationError on
    ERROR findings ('on'), warns and continues ('warn'), or is a no-op
    ('off').  Never runs on a cache hit — callers sit behind the
    compile cache — and books its wall time on the `verify_ms`
    profiler timer (and the start-up phase `setup.verify`) so the hot
    path stays provably free."""
    from ..fluid.flags import flag

    mode = str(flag("verify_program", "on")).lower()
    if mode in ("off", "0", "false", "no"):
        return
    from ..obs import span as obs_span
    from ..profiler import stage, stat_add

    with obs_span("verifier.run"), stage("setup.verify", "verify_ms"):
        findings = verify_program(program, feed=feed_names,
                                  fetch_list=fetch_names, scope=scope,
                                  donated=donated, tiers=(ERROR,))
        errors = [f for f in findings if f.severity == ERROR]
        warns = [f for f in findings if f.severity == WARNING]
        stat_add("verifier_runs")
        if errors:
            stat_add("verifier_errors", len(errors))
        if warns:
            # ERROR-tier passes may emit WARNING-severity findings
            # (shard-consistency clamps / resharding predictions);
            # previously these were silently dropped here
            stat_add("verifier_warnings", len(warns))
    if warns and len(_REPORTED) <= _MAX_REPORTED:
        fresh_warns = []
        for f in warns:
            key = _finding_key(program, f)
            if key not in _REPORTED:
                _REPORTED.add(key)
                fresh_warns.append(f)
        if fresh_warns:
            import logging
            logging.getLogger("paddle_tpu.verifier").warning(
                "program verifier warnings:\n%s",
                "\n".join(f"  {f}" for f in fresh_warns))
    if not errors:
        return
    if mode in ("warn", "warning"):
        if len(_REPORTED) > _MAX_REPORTED:
            _REPORTED.clear()
        fresh = []
        for f in errors:
            key = _finding_key(program, f)
            if key not in _REPORTED:
                _REPORTED.add(key)
                fresh.append(f)
        if not fresh:
            return  # every finding already reported for this version
        warnings.warn(
            "program verifier found {} error(s) "
            "(FLAGS_verify_program=warn):\n{}".format(
                len(fresh), "\n".join(f"  {f}" for f in fresh)),
            RuntimeWarning, stacklevel=3)
        return
    raise ProgramVerificationError(errors)
