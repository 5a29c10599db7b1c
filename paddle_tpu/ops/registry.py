"""Op lowering registry: the TPU-native replacement for the reference's
OpKernel machinery.

The reference registers per-(place, dtype, layout) kernel functors into a
global OpInfoMap (/root/reference/paddle/fluid/framework/op_registry.h:256,
operator.h:465) and dispatches them one-by-one from an interpreter loop
(executor.cc:474).  Here an op type maps to a single *lowering rule*: a
Python function that emits jax/XLA operations.  The Executor traces every op
of a block through these rules into ONE jitted XLA computation; XLA then does
the fusion/layout/memory work the reference implements by hand (fusion
passes, allocators, GC — SURVEY.md §7).

Gradients are generic: `append_backward` (fluid/backward.py) emits
`<type>_grad` ops carrying a `fwd_op_id` attr.  During block tracing, the
forward op is evaluated under `jax.vjp` (only when some grad op references
it) and the vjp function is cached so the backward op reuses the forward
residuals — i.e. exact reverse-mode AD over the program IR, with zero
recompute inside one XLA computation.  Ops can still register a custom grad
lowering (`register_grad`) when the vjp of the forward rule is not the right
derivative (or a Pallas kernel is faster).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..fluid import core
from ..fluid.framework import EMPTY_VAR_NAME, Operator

# slot-name-map of jnp values: {"X": [arr], "Y": [arr0, arr1], ...}
InsOuts = Dict[str, List[Any]]

_FORWARD: Dict[str, Callable] = {}
_GRAD: Dict[str, Callable] = {}
# ops whose lowering rule *intentionally* mutates no state and has no
# outputs (e.g. barriers); the tracer skips env assignment for them.


def register_op(op_type: str):
    """Register the forward lowering rule for `op_type`.

    Rule signature: fn(ctx: LowerCtx, op: Operator, ins: InsOuts) -> InsOuts
    """

    def deco(fn):
        _FORWARD[op_type] = fn
        return fn

    return deco


def register_grad(op_type: str):
    """Register a custom grad lowering for `<op_type>_grad`, overriding the
    generic vjp path.  Signature:
    fn(ctx, grad_op, fwd_ins, fwd_outs, out_grads) -> {input_slot: [grads]}
    where out_grads maps fwd output slots to cotangents (None if absent)."""

    def deco(fn):
        _GRAD[op_type] = fn
        return fn

    return deco


def has_op(op_type: str) -> bool:
    if op_type in _FORWARD:
        return True
    if op_type.endswith("_grad") and op_type[: -len("_grad")] in _FORWARD:
        return True
    return False


def has_grad(op_type: str) -> bool:
    """Whether a custom grad lowering is registered for `op_type`
    (consulted by the program verifier's op-registry pass)."""
    return op_type in _GRAD


def registered_ops() -> List[str]:
    return sorted(_FORWARD)


class LowerCtx:
    """Per-trace context: deterministic RNG, vjp cache, distributed axis
    info.  One instance per block trace."""

    def __init__(self, base_key, block=None, mesh_axes: Optional[dict] = None,
                 abstract: bool = False):
        self.base_key = base_key
        self.block = block
        # fwd op id -> (out_struct, vjp_fn, diff_paths) for grad reuse
        self.vjp_cache: Dict[int, tuple] = {}
        # fwd op ids referenced by *_grad ops in the block being traced
        self.need_vjp: set = set()
        # axis names available for collectives when tracing under shard_map
        self.mesh_axes = mesh_axes or {}
        self.abstract = abstract  # True during eval_shape-based InferShape
        # in-flight send_v2 payloads per ring, consumed FIFO by recv_v2
        # (functional p2p pairing, collective_ops.py)
        self.p2p_queue: Dict[int, list] = {}
        # numeric-health collection (obs.numerics): when the executor
        # arms PADDLE_OBS_NUMERICS this is a list lower_op appends
        # (provenance, var_name, stats_vec) rows to; None = off, and
        # the traced computation is byte-identical to the uninstrumented
        # one (the compile-cache signature pins that contract)
        self.numerics: Optional[list] = None

    def rng_key(self, op: Operator):
        """Deterministic per-op key: seed attr wins (OpTest reproducibility),
        else fold the op id into the per-step base key.  `base_key` may be a
        thunk (eager tracer) so key construction is lazy."""
        seed = op.attr("seed", 0)
        if seed:
            return jax.random.PRNGKey(seed)
        base = self.base_key() if callable(self.base_key) else self.base_key
        return jax.random.fold_in(base, op.id & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# Helpers for lowering rules
# ---------------------------------------------------------------------------

def first(ins: InsOuts, slot: str, default=None):
    vals = ins.get(slot) or []
    return vals[0] if vals else default


_NARROW_64 = {jnp.dtype("int64"): jnp.dtype("int32"),
              jnp.dtype("uint64"): jnp.dtype("uint32"),
              jnp.dtype("float64"): jnp.dtype("float32")}


def jdt(dtype_name) -> jnp.dtype:
    """Canonical dtype for lowerings.  TPU-native policy: x64 stays
    off, so 64-bit requests narrow to 32-bit HERE — explicitly, once —
    instead of inside JAX, where every creation/astype call with a
    64-bit dtype emits a truncation warning.  Out-of-range int64 feed
    VALUES are rejected loudly at the feed boundary
    (executor feed normalization), so the narrowing is safe by the
    time a lowering sees the data."""
    import jax
    dt = jnp.dtype(core.np_dtype(dtype_name))
    if not jax.config.jax_enable_x64:
        dt = _NARROW_64.get(dt, dt)
    return dt


_LOW_PRECISION = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


def mxu_accum_dtype(*arrays):
    """(preferred_element_type, out_dtype) for an MXU contraction.

    amp-O2 contract: bf16/f16 operands must ACCUMULATE in fp32 on the
    MXU (`preferred_element_type=float32`) and round once to the
    operand precision on the way out — bf16 accumulation loses ~3
    effective mantissa bits over a long K dimension.  Full-precision
    operands return (None, None): no override, no extra cast."""
    dt = jnp.result_type(*arrays)
    if jnp.dtype(dt) in _LOW_PRECISION:
        return jnp.float32, jnp.dtype(dt)
    return None, None


def _is_diff(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


# ---------------------------------------------------------------------------
# Layout adapters (transforms/layout.py, docs/graph_transforms.md)
# ---------------------------------------------------------------------------
#
# The NHWC layout-optimization pass never inserts transpose OPS into the
# Program — a separate op would need its own grad op wired into the
# backward chain.  Instead it annotates existing ops with adapter attrs
# and the registry applies them around the op's own lowering rule, so
# jax.vjp differentiates straight through the boundary transposes and
# the backward pass stays layout-consistent for free:
#
#   attrs["nhwc_in"]  = [slot, ...]  transpose those 4-D inputs
#                                    NCHW->NHWC before the rule runs
#   attrs["nchw_in"]  = [slot, ...]  transpose NHWC->NCHW (defensive:
#                                    an NHWC value reaching an op the
#                                    pass could not rewrite)
#   attrs["nhwc_out"] = [slot, ...]  the rule computed NHWC; deliver the
#                                    listed outputs transposed to NCHW
#
# Interior ops of a rewritten chain carry none of these: they consume
# and produce NHWC values directly (their data_format/data_layout attr
# says so), which is what makes the trunk transpose-free.

_TO_NHWC = (0, 2, 3, 1)
_TO_NCHW = (0, 3, 1, 2)


def _transpose_slot(vals, perm):
    return [jnp.transpose(v, perm)
            if v is not None and jnp.ndim(v) == 4 else v for v in vals]


def _layout_adapted(fn, op: Operator):
    """Wrap a lowering rule with the op's layout-adapter attrs; identity
    when the op carries none (the common case costs one dict probe)."""
    nhwc_in = op.attr("nhwc_in") or ()
    nchw_in = op.attr("nchw_in") or ()
    nhwc_out = op.attr("nhwc_out") or ()
    if not (nhwc_in or nchw_in or nhwc_out):
        return fn

    def adapted(ctx, op_, ins):
        ins = dict(ins)
        for slot in nhwc_in:
            if slot in ins:
                ins[slot] = _transpose_slot(ins[slot], _TO_NHWC)
        for slot in nchw_in:
            if slot in ins:
                ins[slot] = _transpose_slot(ins[slot], _TO_NCHW)
        outs = fn(ctx, op_, ins)
        for slot in nhwc_out:
            if slot in outs:
                outs[slot] = _transpose_slot(outs[slot], _TO_NCHW)
        return outs

    return adapted


# ---------------------------------------------------------------------------
# Provenance threading (obs/opprof.py, docs/observability.md)
# ---------------------------------------------------------------------------
#
# Every op lowers inside jax.named_scope(op_provenance(op)), so each
# HLO instruction XLA emits for it carries the source op in its
# metadata op_name — the seam obs.op_profile folds per-instruction
# FLOPs/bytes back through, and obs.devprof joins MEASURED device
# time back through (profiler event name -> HLO instruction -> this
# op_name).  Transform passes stamp `op_provenance` attrs on
# rewritten clones (with the SOURCE program's identity plus a
# [pass=...] tag); un-transformed ops compute it from their own ids.
# Renaming this scope format breaks BOTH attributions at once —
# tests/test_opprof.py and tests/test_devprof.py pin it.

def op_provenance(op: Operator) -> str:
    """Greppable provenance string for `op`
    (`program#<id>/block<idx>/op<id>:<type>`, the verifier identity in
    scope-path form).  A transform-stamped `op_provenance` attr wins —
    it names the SOURCE op a rewritten clone descends from."""
    prov = op.attrs.get("op_provenance")
    if prov:
        return prov
    blk = op.block
    prog_id = getattr(getattr(blk, "program", None), "prog_id", 0)
    blk_idx = getattr(blk, "idx", 0)
    return f"program#{prog_id}/block{blk_idx}/op{op.id}:{op.type}"


# ---------------------------------------------------------------------------
# Block tracing
# ---------------------------------------------------------------------------

def scan_need_vjp(block) -> set:
    """Forward op ids whose vjp must be cached (referenced by grad ops that
    have no custom grad lowering)."""
    need = set()
    for op in block.ops:
        fid = op.attr("fwd_op_id", None)
        if fid is None:
            continue
        fwd_type = op.attr("fwd_op_type", "")
        if fwd_type not in _GRAD:
            need.add(fid)
    return need


def lower_block(ctx: LowerCtx, block, env: Dict[str, Any]) -> None:
    """Trace every op of `block` into jax ops, reading/writing `env`
    (var name -> traced value).  This is the single-XLA-computation
    replacement for the reference's interpreter hot loop
    (executor.cc:474)."""
    ctx.need_vjp |= scan_need_vjp(block)
    for op in block.ops:
        lower_op(ctx, op, env)


def _gather_ins(op: Operator, env) -> InsOuts:
    ins: InsOuts = {}
    for slot, names in op.inputs.items():
        ins[slot] = [env[n] if n != EMPTY_VAR_NAME else None for n in names]
    return ins


def _bind_outs(op: Operator, outs: InsOuts, env) -> None:
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for i, name in enumerate(names):
            if name == EMPTY_VAR_NAME:
                continue
            if i < len(vals) and vals[i] is not None:
                env[name] = vals[i]


def lower_op(ctx: LowerCtx, op: Operator, env: Dict[str, Any]) -> None:
    # provenance scope: every jax op this rule emits carries the source
    # Program op in its HLO metadata (obs.op_profile's attribution seam)
    prov = op_provenance(op)
    with jax.named_scope(prov):
        _lower_op_inner(ctx, op, env)
        # numeric-health stats (obs.numerics): emitted INSIDE the
        # provenance scope so the stat reductions attribute to the op
        # they measure.  The block-identity guard keeps sub-block
        # tracers (control flow lowered under scan/cond) from leaking
        # into the top-level stats list.
        if ctx.numerics is not None and not ctx.abstract \
                and (ctx.block is None or op.block is ctx.block):
            _collect_numeric_stats(ctx, op, prov, env)


def _collect_numeric_stats(ctx: LowerCtx, op: Operator, prov: str,
                           env: Dict[str, Any]) -> None:
    """Append one fused [nan_count, inf_count, absmax, l2] reduction
    per float output of `op`.  Device-side only — the stacked result is
    fetched asynchronously at dispatch end (obs.numerics.drain), so the
    instrumented step stays zero-sync."""
    seen = set()
    for names in op.outputs.values():
        for name in names:
            if name == EMPTY_VAR_NAME or name in seen:
                continue
            seen.add(name)
            v = env.get(name)
            # structured bindings (TensorArrayVal, LoD tuples, ...) are
            # not one array — only instrument dtype/shape-carrying values
            if v is None or not (hasattr(v, "dtype")
                                 and hasattr(v, "shape")):
                continue
            if not jnp.issubdtype(v.dtype, jnp.floating):
                continue
            if any(int(d) == 0 for d in v.shape):
                continue
            x = jnp.asarray(v)
            finite = jnp.isfinite(x)
            xf = jnp.where(finite, x, 0).astype(jnp.float32)
            vec = jnp.stack([
                jnp.sum(jnp.isnan(x)).astype(jnp.float32),
                jnp.sum(jnp.isinf(x)).astype(jnp.float32),
                jnp.max(jnp.abs(xf)),
                jnp.sqrt(jnp.sum(xf * xf)),
            ])
            ctx.numerics.append((prov, name, vec))


def _lower_op_inner(ctx: LowerCtx, op: Operator,
                    env: Dict[str, Any]) -> None:
    if op.attr("fwd_op_id", None) is not None:
        _lower_grad_op(ctx, op, env)
        return
    fn = _FORWARD.get(op.type)
    if fn is None:
        raise NotImplementedError(f"no lowering registered for op {op.type!r}")

    # layout-adapter attrs wrap the rule BEFORE the vjp split so grad
    # ops differentiate through the boundary transposes automatically
    fn = _layout_adapted(fn, op)
    if op.id in ctx.need_vjp:
        outs = _eval_with_vjp(ctx, op, fn, _gather_ins(op, env))
    else:
        outs = fn(ctx, op, _gather_ins(op, env))
    _bind_outs(op, outs, env)


def _eval_with_vjp(ctx: LowerCtx, op: Operator, fn, ins: InsOuts) -> InsOuts:
    """Evaluate a forward op under jax.vjp, caching the vjp function so the
    matching grad op later in the same trace reuses residuals."""
    diff_paths = []  # (slot, index)
    diff_vals = []
    for slot, vals in ins.items():
        for i, v in enumerate(vals):
            if v is not None and _is_diff(v):
                diff_paths.append((slot, i))
                diff_vals.append(v)

    def f(dvals):
        merged = {s: list(vs) for s, vs in ins.items()}
        for (slot, i), v in zip(diff_paths, dvals):
            merged[slot][i] = v
        return fn(ctx, op, merged)

    outs, vjp_fn = jax.vjp(f, diff_vals)
    ctx.vjp_cache[op.id] = (outs, vjp_fn, diff_paths)
    return outs


def _zeros_like_out(v):
    return jnp.zeros(jnp.shape(v), jnp.result_type(v)) if v is not None else None


def _lower_grad_op(ctx: LowerCtx, op: Operator, env) -> None:
    fwd_type = op.attr("fwd_op_type")
    fwd_id = op.attr("fwd_op_id")

    # Split grad-op inputs into forward inputs/outputs and output-cotangents.
    fwd_ins: InsOuts = {}
    fwd_outs: InsOuts = {}
    out_grads: InsOuts = {}
    fwd_in_slots = set(op.attr("fwd_input_slots", []))
    fwd_out_slots = set(op.attr("fwd_output_slots", []))
    for slot, names in op.inputs.items():
        vals = [env.get(n) if n != EMPTY_VAR_NAME else None for n in names]
        if slot.endswith("@GRAD"):
            out_grads[slot[: -len("@GRAD")]] = vals
        elif slot in fwd_in_slots:
            fwd_ins[slot] = vals
        elif slot in fwd_out_slots:
            fwd_outs[slot] = vals

    custom = _GRAD.get(fwd_type)
    if custom is not None:
        in_grads = custom(ctx, op, fwd_ins, fwd_outs, out_grads)
        _bind_outs(op, {f"{s}@GRAD": v for s, v in in_grads.items()}, env)
        return

    cached = ctx.vjp_cache.get(fwd_id)
    if cached is None:
        # Backward-only program (e.g. a pruned grad block): re-lower the
        # forward op under vjp now.  XLA CSE dedupes any recompute that
        # overlaps the forward pass.
        fwd_op = Operator(op.block, fwd_id, fwd_type, {}, {},
                          {k: v for k, v in op.attrs.items()
                           if k not in ("fwd_op_id", "fwd_op_type",
                                        "fwd_input_slots", "fwd_output_slots")})
        fwd_op.inputs = {s: [f"__in_{s}_{i}" for i in range(len(v))]
                         for s, v in fwd_ins.items()}
        fn = _FORWARD[fwd_type]
        _eval_with_vjp(ctx, fwd_op, fn, fwd_ins)
        cached = ctx.vjp_cache[fwd_id]

    outs, vjp_fn, diff_paths = cached
    # Build cotangent pytree matching `outs` structure.
    ct = {}
    for slot, vals in outs.items():
        g = out_grads.get(slot)
        ct[slot] = [
            (g[i] if g is not None and i < len(g) and g[i] is not None
             else _zeros_like_out(v))
            for i, v in enumerate(vals)
        ]
    (d_in_vals,) = vjp_fn(ct)

    grads: InsOuts = {}
    for (slot, i), g in zip(diff_paths, d_in_vals):
        grads.setdefault(f"{slot}@GRAD", [])
        lst = grads[f"{slot}@GRAD"]
        while len(lst) <= i:
            lst.append(None)
        lst[i] = g
    _bind_outs(op, grads, env)


# ---------------------------------------------------------------------------
# Build-time shape inference via eval_shape (framework.Block._infer_shapes)
# ---------------------------------------------------------------------------

def eval_op_shape(op: Operator, block, batch_probe: int,
                  lookup=None) -> Dict[str, list]:
    """Abstractly evaluate one op's lowering with -1 dims replaced by
    `batch_probe`; returns {slot: [ShapeDtypeStruct,...]}.

    `lookup(name) -> (shape, dtype) | None` overrides where input
    shapes come from — the shape-consistency pass passes its abstract
    env so inference REPLAYS through the graph instead of re-reading
    declared shapes (analysis/shape_check.py).  Default: the declared
    shapes via `block._var_recursive`.  The op's layout-adapter attrs
    (`nhwc_in`/`nchw_in`/`nhwc_out`) are applied around the rule, same
    as at lowering time, so transformed NHWC graphs evaluate with their
    real boundary transposes."""
    specs: InsOuts = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n == EMPTY_VAR_NAME:
                vals.append(None)
                continue
            shape = dtype = None
            if lookup is not None:
                got = lookup(n)
                if got is not None:
                    shape, dtype = got
            if shape is None:
                v = block._var_recursive(n)
                if v.shape is None:
                    raise ValueError(f"input {n} has unknown shape")
                shape, dtype = v.shape, v.dtype
            shape = tuple(batch_probe if d == -1 else d for d in shape)
            vals.append(jax.ShapeDtypeStruct(shape, jdt(dtype)))
        specs[slot] = vals
    fn = _FORWARD.get(op.type)
    if fn is None:
        raise NotImplementedError(op.type)
    fn = _layout_adapted(fn, op)

    ctx = LowerCtx(jax.random.PRNGKey(0), block=block, abstract=True)

    def f(ins):
        return fn(ctx, op, ins)

    return jax.eval_shape(f, specs)
