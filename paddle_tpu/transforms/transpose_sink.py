"""transpose_sink: sink transpose2 ops through elementwise chains and
cancel inverse pairs.

Why: the measured roofline (obs.roofline, PR 12) verdicts relayout-
bound ops — time spent permuting HBM instead of computing.  The
biggest source in user graphs is NCHW-external boundaries built with
explicit `transpose2` ops: NCHW -> NHWC -> (elementwise work) -> NCHW
chains where the two permutes bracket ops that do not care about
layout at all.  Sinking a transpose through its layout-agnostic
single consumer moves it next to its inverse, where the pair cancels
and the relayout disappears from the lowered HLO entirely.

Two rewrites, looped to fixpoint over the global block:

1. **Sink**: `transpose2(a) -> t; f(t) -> u` with `f` a shape-
   preserving coordinate-independent elementwise op (SINK_THROUGH)
   and `t` read by nothing else becomes `f(a) -> t; transpose2(t) ->
   u` — same values, the permute one op later.
2. **Cancel**: `transpose2(a, p) -> t; transpose2(t, q) -> u` with
   `q∘p` the identity and `t` read only by the second transpose: every
   reader of `u` re-points at `a` and both ops vanish.

Off by default: whether eliminating the permutes beats XLA's own
fusion of them is a MEASURED question per program, and no chip run
has asked it yet: opt in with FLAGS_graph_transforms and compare the
step time.  Like fold_bn, programs carrying grad ops are never touched
(the backward replays jax.vjp of the forward, but declared `@GRAD`
shape metadata would drift).
"""

from __future__ import annotations

from typing import List, Set

from . import (TransformContext, _find_var, register_transform,
               tag_provenance)

# layout.UNARY_FOLLOWERS minus dropout, spelled out rather than
# imported: registration order IS execution order, and a top-level
# `from .layout import ...` here would pull layout_optimize into the
# registry ahead of this pass.  dropout is excluded because its
# stateless mask hashes COORDINATES — permuting its input permutes
# which elements drop, so a transpose is not inert through it.
SINK_THROUGH = frozenset({
    "relu", "relu6", "leaky_relu", "gelu", "sigmoid", "tanh", "elu",
    "silu", "swish", "mish", "hard_swish", "hard_sigmoid", "softplus",
    "scale", "cast", "clip", "square", "abs", "sqrt", "exp",
})

_MAX_ROUNDS = 64  # fixpoint safety bound; real chains converge in a few


def _readers(block, name: str) -> List:
    return [op for op in block.ops if name in op.input_arg_names()]


def _perm_of(op, block) -> List[int]:
    x = op.input("X")[0]
    v = _find_var(block, x)
    rank = len(v.shape) if v is not None and v.shape is not None else 0
    return [int(a) for a in op.attr("axis", list(range(rank))[::-1])]


def _identity_pair(p: List[int], q: List[int]) -> bool:
    """transpose(transpose(x, p), q) == x  <=>  [p[i] for i in q] is
    the identity permutation."""
    if len(p) != len(q) or not p:
        return False
    try:
        return [p[i] for i in q] == list(range(len(p)))
    except IndexError:
        return False


def _externals(ctx: TransformContext) -> Set[str]:
    """Vars observable from outside the rewritten region: fetch
    targets and anything a control-flow sub-block touches."""
    prog = ctx.program
    ext = set(ctx.fetch_set)
    for blk in prog.blocks[1:]:
        for op in blk.ops:
            ext.update(op.input_arg_names())
            ext.update(op.output_arg_names())
    return ext


def _movable(block, name: str, external: Set[str]) -> bool:
    if name in external:
        return False
    v = _find_var(block, name)
    return v is not None and not v.persistable \
        and not getattr(v, "is_data", False)


def _xshape_dead(block, op, external: Set[str]) -> bool:
    """transpose2's XShape side output is a zero-row shape carrier for
    the grad op; in the grad-free programs this pass touches it is
    dead weight — but only removable when truly unobserved."""
    for n in op.output("XShape") or []:
        if n in external or _readers(block, n):
            return False
    return True


def _sink_one(ctx: TransformContext, external: Set[str]) -> bool:
    block = ctx.program.global_block()
    for tp in block.ops:
        if tp.type not in ("transpose2", "transpose"):
            continue
        if len(tp.input("X")) != 1 or len(tp.output("Out")) != 1:
            continue
        tname = tp.output("Out")[0]
        if not _movable(block, tname, external):
            continue
        readers = _readers(block, tname)
        if len(readers) != 1 or readers[0].type not in SINK_THROUGH:
            continue
        follower = readers[0]
        if len(follower.input("X")) != 1 \
                or follower.input("X") != [tname] \
                or len(follower.output("Out")) != 1:
            continue
        aname = tp.input("X")[0]
        avar, tvar = _find_var(block, aname), _find_var(block, tname)
        if avar is None or tvar is None or avar.shape is None:
            continue
        # reorder: follower consumes `a` directly and writes `t`
        # (re-declared at a's shape); the transpose then permutes the
        # follower's output into the original downstream var
        uname = follower.output("Out")[0]
        follower.inputs["X"] = [aname]
        follower.outputs["Out"] = [tname]
        tp.inputs["X"] = [tname]
        tp.outputs["Out"] = [uname]
        tvar.shape = tuple(avar.shape)
        pos = block.ops.index(tp)
        block.ops.remove(follower)
        block.ops.insert(pos, follower)
        tag_provenance(follower, "transpose_sink")
        tag_provenance(tp, "transpose_sink")
        return True
    return False


def _cancel_one(ctx: TransformContext, external: Set[str]) -> bool:
    prog = ctx.program
    block = prog.global_block()
    for t1 in block.ops:
        if t1.type not in ("transpose2", "transpose"):
            continue
        if len(t1.input("X")) != 1 or len(t1.output("Out")) != 1:
            continue
        tname = t1.output("Out")[0]
        if not _movable(block, tname, external):
            continue
        readers = _readers(block, tname)
        if len(readers) != 1 \
                or readers[0].type not in ("transpose2", "transpose"):
            continue
        t2 = readers[0]
        if t2 is t1 or len(t2.output("Out")) != 1:
            continue
        if not _identity_pair(_perm_of(t1, block), _perm_of(t2, block)):
            continue
        uname = t2.output("Out")[0]
        if not _movable(block, uname, external):
            continue  # the round-tripped value itself is observed
        if not (_xshape_dead(block, t1, external)
                and _xshape_dead(block, t2, external)):
            continue
        aname = t1.input("X")[0]
        for op in _readers(block, uname):
            for slot, names in op.inputs.items():
                op.inputs[slot] = [aname if n == uname else n
                                   for n in names]
            tag_provenance(op, "transpose_sink")
        block.ops.remove(t1)
        block.ops.remove(t2)
        return True
    return False


@register_transform(
    "transpose_sink", default=False,
    help_str="sink transpose2 ops through elementwise chains and "
             "cancel inverse pairs at NCHW-external boundaries; opt in "
             "via FLAGS_graph_transforms='transpose_sink=on'")
def run(ctx: TransformContext) -> int:
    prog = ctx.program
    for blk in prog.blocks:
        for op in blk.ops:
            if op.attr("fwd_op_id") is not None:
                return 0  # training/backward program: never touched
    external = _externals(ctx)
    rewrites = 0
    for _ in range(_MAX_ROUNDS):
        if _cancel_one(ctx, external):
            rewrites += 1
            continue
        if _sink_one(ctx, external):
            rewrites += 1
            continue
        break
    if rewrites:
        prog._bump_version()
    return rewrites
