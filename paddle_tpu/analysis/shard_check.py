"""Static sharding analyzer: PartitionSpec propagation, predicted
collective cost, and re-shard feasibility prechecks (ISSUE 18
tentpole).

Today the only sharding feedback is `collective_bytes_spmd_*` counters
AFTER first dispatch, and `spec_layout._fit` silently clamps misfit
specs to replicated.  This module runs GSPMD-style spec propagation
(arXiv 2105.04663) as an abstract interpreter over the FINAL
(post-transform) Program under a plain `{axis: size}` mesh dict —
the shape_check.py idiom, riding the same `_Env` block chaining,
`while`-body widening, and `infer_op_outputs` shape replay:

* every var carries `(shape, dtype, entries)` where `entries` is a
  `spec_rules` tuple (`None | axis | (axes,)` per dim; `None` for the
  whole triple slot = unknown layout);
* params/optimizer state seed from the `parallel/spec_rules` registry
  resolution (the same table `spec_layout.spec_for` applies at
  compile), feeds from the `mesh.batch_spec` twin;
* op rules: elementwise preserve, broadcast-aware meet, matmul/conv
  contract-dim handling, reshape factor-group carry, transpose
  permute, collectives per their declared semantics, `@GRAD`
  mirroring at the first strip, loop-carried widening to replicated;
* a layout conflict never fails propagation — the meet resolves it
  and *records the resharding event* XLA SPMD would insert.

Three consumers:

1. the `shard-consistency` verifier pass (ERROR tier, once per
   compile-cache miss when a mesh is current): ERRORs for
   axis-used-twice-in-one-spec, sharded-dim-not-divisible after
   propagation, and collectives whose ring axis is not on the mesh;
   WARNINGs for large tensors forced replicated (byte floor
   `PADDLE_SHARDCHECK_REPLICATED_FLOOR`, default 1 MiB), every
   explicit-spec clamp, and every predicted resharding event — all
   with `program#<id> block<idx> op<id>` provenance;
2. `comm_report(program, mesh_axes)`: static per-collective predicted
   wire bytes, quant-collectives-aware (`signature_token()`), which
   tests hold within ±25% of measured `collective_bytes_spmd_*`;
3. `feasibility(program, old_mesh, new_mesh)`: the elastic-resharding
   precheck — re-solves the spec registry over a candidate mesh and
   reports fits/clamps/bytes-per-device delta without compiling.

Module scope imports ONLY the stdlib (spec_rules/quant config load
lazily, with a by-path fallback), so `tools/shardcheck.py` can load it
on a box without jax — the tpulint loading idiom.
"""

from __future__ import annotations

import logging
import os
from typing import Callable, Dict, List, Optional, Set, Tuple

from .shape_check import (OPAQUE_OPS, ProgramView, ShapeInferBail,
                          _Env, canon_dtype, infer_op_outputs)
from .verifier import (ERROR, WARNING, Finding, VerifyContext,
                       register_pass)

_EMPTY = "@EMPTY@"  # framework.EMPTY_VAR_NAME (kept import-free)
_GRAD_SUFFIX = "@GRAD"

logger = logging.getLogger("paddle_tpu.shard_check")

_MAX_FINDINGS = 25  # per program: one bad spec cascades; cap the noise

_LOOP_OWNERS = {"while"}

# canonical dtype -> bytes per element (x32 policy: 64-bit already
# narrowed by canon_dtype)
_DTYPE_SIZE = {
    "float32": 4, "int32": 4, "uint32": 4, "complex64": 8,
    "float16": 2, "bfloat16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
}

# comm bootstrap / sync ops: no payload, exempt from the ring-axis check
_COMM_NOOPS = {
    "c_comm_init", "c_comm_init_all", "c_gen_nccl_id",
    "c_wait_calc_stream", "c_wait_comm_stream", "c_sync_calc_stream",
    "c_sync_comm_stream", "barrier",
}

_MATMUL_OPS = {"mul", "matmul", "matmul_v2"}
_EMBEDDING_OPS = {"lookup_table", "lookup_table_v2"}
_RESHAPE_OPS = {"reshape", "reshape2"}
_TRANSPOSE_OPS = {"transpose", "transpose2"}

# ops that materialize fresh (host-fed constants / RNG) values: outputs
# are replicated until something reshards them
_FRESH_REPLICATED_OPS = {
    "fill_constant", "fill_zeros_like", "gaussian_random",
    "uniform_random", "truncated_gaussian_random", "range",
    "assign_value", "eye", "one_hot", "one_hot_v2",
}

_BLOCK = 256  # quant_collectives.BLOCK twin (stdlib-only)


# ---------------------------------------------------------------------------
# Lazy config: spec registry rules + quant-collectives signature
# ---------------------------------------------------------------------------

_SPEC_RULES = None


def _spec_rules():
    """parallel.spec_rules, tolerant of the by-path package load that
    tools/shardcheck.py uses (where relative imports cannot escape the
    loaded `analysis` package)."""
    global _SPEC_RULES
    if _SPEC_RULES is not None:
        return _SPEC_RULES
    try:
        from ..parallel import spec_rules as sr
        _SPEC_RULES = sr
        return sr
    except Exception:  # noqa: BLE001 - standalone by-path load
        pass
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "parallel", "spec_rules.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu_spec_rules", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _SPEC_RULES = mod
    return mod


def _registered_overrides() -> Dict[str, tuple]:
    """`register_spec` overrides as plain entry tuples; empty when
    spec_layout (jax) is unavailable (the CLI path)."""
    try:
        from ..parallel import spec_layout
        return {k: tuple(v) for k, v in
                spec_layout.registered_specs().items()}
    except Exception:  # noqa: BLE001 - jax-free load
        return {}


def quant_config() -> Tuple[Optional[str], int, Optional[str]]:
    """(mode, min_bytes, signature_token) for the quantized-collective
    wire model, via parallel.quant_collectives when importable, else
    the env twin (same defaults)."""
    try:
        from ..parallel import quant_collectives as qc
        return qc.mode(), qc.min_bytes(), qc.signature_token()
    except Exception:  # noqa: BLE001 - standalone by-path load
        mode = os.environ.get("PADDLE_QUANT_COLLECTIVES", "").strip().lower()
        mode = mode if mode in ("int8",) else None
        try:
            floor = int(os.environ.get(
                "PADDLE_QUANT_COLLECTIVES_MIN_BYTES", "1024"))
        except ValueError:
            floor = 1024
        token = f"quant_collectives={mode},min={floor}" if mode else None
        return mode, floor, token


def replicated_floor() -> int:
    """Byte floor above which a fully-replicated tensor draws a
    WARNING (`PADDLE_SHARDCHECK_REPLICATED_FLOOR`, default 1 MiB)."""
    try:
        return int(os.environ.get(
            "PADDLE_SHARDCHECK_REPLICATED_FLOOR", str(1 << 20)))
    except ValueError:
        return 1 << 20


def _dtype_bytes(dtype: Optional[str]) -> int:
    return _DTYPE_SIZE.get(canon_dtype(dtype or "float32"), 4)


def _static_nbytes(shape, dtype) -> Optional[int]:
    """Total bytes for a static shape; None when any dim is symbolic."""
    if shape is None:
        return None
    n = 1
    for d in shape:
        if d is None or int(d) < 0:
            return None
        n *= int(d)
    return n * _dtype_bytes(dtype)


def _quant_phase_bytes(nelems: int, axis_size: int) -> int:
    """Wire bytes of ONE phase (all_to_all or all_gather) of the
    two-phase quantized gradient reduction — the stdlib twin of
    `quant_collectives.wire_bytes(x, axis_size=n)`: int8 codes + one
    fp32 scale per block, over ceil(nelems/axis_size) chunks."""
    chunk = max(1, -(-int(nelems) // int(axis_size)) if nelems else 1)
    be = min(_BLOCK, chunk)
    cb = -(-chunk // be)
    return axis_size * cb * be + axis_size * cb * 4


def _quant_plain_bytes(nelems: int) -> int:
    """`quant_collectives.wire_bytes(x)` twin (no axis split): int8
    codes + fp32 scale sidecar over the whole payload."""
    size = max(1, int(nelems))
    be = min(_BLOCK, size)
    nblocks = -(-size // be)
    return nblocks * be + nblocks * 4


# ---------------------------------------------------------------------------
# Entries algebra
# ---------------------------------------------------------------------------
#
# The abstract value is (shape, dtype, entries):
#   shape   tuple with -1 symbolic dims, or None (unknown)
#   dtype   canonical dtype string, or None
#   entries spec_rules entries tuple (trimmed, per-dim None|axis|tuple),
#           or None = layout unknown (propagation degraded)

AbstractShard = Tuple[Optional[tuple], Optional[str], Optional[tuple]]

REPLICATED: tuple = ()


def _ent(entries: Optional[tuple], dim: int):
    if entries is None:
        return None
    return entries[dim] if 0 <= dim < len(entries) else None


def _trim(entries) -> tuple:
    out = list(entries)
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def _entries_equal(a, b) -> bool:
    return _trim(a or ()) == _trim(b or ())


class _ShardChecker:
    """One analysis run: findings + predicted communication events."""

    def __init__(self, ctx: VerifyContext, mesh_axes: Dict[str, int],
                 ring_axes: Optional[Dict[str, str]] = None,
                 batch_rows: Optional[int] = None,
                 floor: Optional[int] = None):
        self.ctx = ctx
        self.prog = ctx.program
        self.mesh_axes = {str(k): int(v) for k, v in
                          (mesh_axes or {}).items()}
        self.ring_axes = dict(ring_axes or {})
        self.batch_rows = batch_rows
        self.floor = replicated_floor() if floor is None else int(floor)
        self.rules = _spec_rules()
        self.overrides = _registered_overrides()
        self.findings: List[Finding] = []
        self.events: List[dict] = []
        self.clamps: List[dict] = []
        # last known (shape, dtype) per var name across the walk —
        # the post-propagation shapes the partition-spec pass consults
        self.var_shapes: Dict[str, Tuple[tuple, str]] = {}
        self.bailed = 0
        self._reported: Set[tuple] = set()
        self._quant = quant_config()
        self._muted = False  # pass-1 while-body replay: no findings/events

    # -- findings ----------------------------------------------------------
    def _find(self, severity, message, op=None, block=None, var=None,
              dedup_key=None) -> None:
        if self._muted or len(self.findings) >= _MAX_FINDINGS:
            return
        if dedup_key is not None:
            if dedup_key in self._reported:
                return
            self._reported.add(dedup_key)
        self.findings.append(self.ctx.finding(
            severity, "shard-consistency", message, op=op, block=block,
            var=var))

    def _emit(self, kind: str, var: str, nbytes: Optional[int],
              axes, reason: str, op=None, warn: bool = False) -> None:
        """Record one predicted communication event; `warn=True` marks
        it a resharding event (layout conflict) and surfaces a WARNING
        finding on top of the event record."""
        if self._muted:
            return
        f = self.ctx.finding(WARNING, "shard-consistency", reason, op=op,
                             var=var)
        self.events.append({
            "kind": kind, "var": var,
            "bytes": int(nbytes) if nbytes else 0,
            "axes": sorted(set(axes or ())), "reason": reason,
            "location": f.location,
        })
        if warn:
            self._find(WARNING, f"predicted resharding: {reason}",
                       op=op, var=var,
                       dedup_key=("reshard", var, kind, reason))

    # -- spec seeding ------------------------------------------------------
    def _resolve_seed(self, name: str, shape, var, op=None,
                      block=None) -> Optional[tuple]:
        """Registry resolution for one persistable/external var, with
        the duplicate-axis ERROR on the RAW spec and a WARNING per
        clamp (satellite: a typo'd register_spec is no longer silent)."""
        rules = self.rules
        override = self.overrides.get(name)
        annotation = getattr(var, "_sharding_axes", None) \
            if var is not None else None
        raw = override if override is not None else None
        if raw is not None:
            for p in rules.duplicate_axis_problems(raw):
                self._find(ERROR,
                           f"partition spec {raw!r} for {name!r}: {p}",
                           op=op, block=block, var=name,
                           dedup_key=("dup", name, p))
        if shape is None:
            return None
        entries, clamps = rules.resolve_entries(
            name, [0 if d == -1 else d for d in shape], self.mesh_axes,
            override=override,
            annotation=tuple(annotation) if annotation else None)
        for c in clamps:
            self.clamps.append({"var": name, "reason": c,
                                "mesh_axes": dict(self.mesh_axes)})
            self._find(WARNING,
                       f"partition spec for {name!r} clamped on mesh "
                       f"{self.mesh_axes}: {c}", op=op, block=block,
                       var=name, dedup_key=("clamp", name, c))
        return entries

    def _first_touch(self, block, name):
        for o in block.ops:
            if name in o.output_arg_names() or name in o.input_arg_names():
                return o
        return None

    def _seed_entry(self, env: _Env) -> None:
        rules = self.rules
        external = self.ctx.external_names()
        total_devices = 1
        for v in self.mesh_axes.values():
            total_devices *= int(v)
        for v in env.block.vars.values():
            if v.shape is None or v.name in env.vals:
                continue
            shape = tuple(v.shape)
            dt = canon_dtype(v.dtype)
            if getattr(v, "is_data", False):
                nrows = self.batch_rows
                if nrows is None and shape and shape[0] not in (-1, None):
                    nrows = int(shape[0])
                entries = rules.batch_entries(self.mesh_axes, nrows)
                env.vals[v.name] = (shape, dt, entries)
            elif v.persistable or v.name in external:
                op = self._first_touch(env.block, v.name)
                entries = self._resolve_seed(v.name, shape, v, op=op,
                                             block=env.block)
                env.vals[v.name] = (shape, dt, entries)
                nbytes = _static_nbytes(shape, dt)
                if (entries is not None and not _trim(entries)
                        and v.persistable and total_devices > 1
                        and dt.startswith("float")
                        and nbytes is not None and nbytes >= self.floor):
                    self._find(
                        WARNING,
                        f"large tensor {v.name!r} ({nbytes} bytes) is "
                        f"fully replicated on mesh {self.mesh_axes} — "
                        f"every device holds a full copy (floor "
                        f"{self.floor})", op=op, block=env.block,
                        var=v.name, dedup_key=("repl", v.name))

    # -- input/output plumbing --------------------------------------------
    def _declared(self, block, name):
        blk = block
        seen = set()
        while blk is not None and id(blk) not in seen:
            seen.add(id(blk))
            v = blk.vars.get(name)
            if v is not None:
                if v.shape is None:
                    return None
                return tuple(v.shape), canon_dtype(v.dtype)
            blk = getattr(blk, "parent_block", None)
        return None

    def _val(self, env: _Env, block, name) -> AbstractShard:
        v = env.lookup(name)
        if v is not None:
            return v
        d = self._declared(block, name)
        if d is None:
            return (None, None, None)
        return (d[0], d[1], None)

    def _bind(self, env: _Env, block, op, name: str,
              val: AbstractShard) -> None:
        shape, dt, entries = val
        if shape is not None and dt is not None:
            self.var_shapes[name] = (shape, dt)
        if shape is not None and entries:
            for dim, entry in enumerate(entries):
                if entry is None or dim >= len(shape):
                    continue
                size = shape[dim]
                if size is None or size < 0:
                    continue
                extent = self.rules.axis_extent(self.mesh_axes, entry)
                if extent > 1 and size % extent != 0:
                    self._find(
                        ERROR,
                        f"var {name!r}: sharded dim {dim} of size "
                        f"{size} not divisible by {entry!r} extent "
                        f"{extent} after propagation", op=op,
                        var=name, dedup_key=("div", name, dim))
                    entries = _trim(tuple(
                        e if i != dim else None
                        for i, e in enumerate(entries)))
        env.bind(name, (shape, dt, entries))

    # -- meets -------------------------------------------------------------
    def _meet(self, vals: List[AbstractShard], out_shape, var, op) \
            -> Optional[tuple]:
        """Broadcast-aware elementwise meet, right-aligned on the
        output rank.  Two different concrete layouts on one dim is the
        conflict GSPMD resolves with a reshard — recorded as an event,
        first layout wins.  Unknown (None) absorbs."""
        if out_shape is None:
            known = [v for v in vals if v[2] is not None]
            if len(known) == 1:
                return known[0][2]
            return None
        rank = len(out_shape)
        out: List[object] = [None] * rank
        unknown = False
        for shape, dt, entries in vals:
            if entries is None:
                if shape is not None and len(shape) == rank:
                    unknown = True
                continue
            if shape is None:
                unknown = True
                continue
            off = rank - len(shape)
            for i in range(len(shape)):
                e = _ent(entries, i)
                if e is None:
                    continue
                j = off + i
                if j < 0 or j >= rank:
                    continue
                if out[j] is None:
                    out[j] = e
                elif out[j] != e:
                    nbytes = _static_nbytes(out_shape, dt)
                    self._emit(
                        "all_to_all", var, nbytes,
                        self.rules.entry_names(e),
                        f"operands of {op.type!r} disagree on dim {j} "
                        f"layout ({out[j]!r} vs {e!r}); SPMD reshards "
                        f"one operand", op=op, warn=True)
        if unknown and not any(e is not None for e in out):
            return None
        return _trim(out)

    # -- op spec rules -----------------------------------------------------
    def _grad_entries(self, op, env, block) -> Dict[str, Optional[tuple]]:
        out: Dict[str, Optional[tuple]] = {}
        for name in op.output_arg_names():
            if name == _EMPTY or _GRAD_SUFFIX not in name:
                continue
            base = name.split(_GRAD_SUFFIX, 1)[0]
            out[name] = self._val(env, block, base)[2]
        return out

    def _ring_axis(self, op) -> str:
        ring = op.attr("ring_id", 0) or 0
        key = f"ring_{ring}"
        if key in self.ring_axes:
            return str(self.ring_axes[key])
        return str(self.ring_axes.get("data", "data"))

    def _collective_entries(self, op, env, block, ins) \
            -> Dict[str, Optional[tuple]]:
        t = op.type
        axis = self._ring_axis(op)
        if t not in _COMM_NOOPS and self.mesh_axes \
                and axis not in self.mesh_axes:
            self._find(
                ERROR,
                f"collective {t!r} (ring {op.attr('ring_id', 0) or 0}) "
                f"resolves to mesh axis {axis!r}, which is absent from "
                f"mesh axes {tuple(self.mesh_axes)}", op=op,
                dedup_key=("ring", t, axis))
        x = ins[0] if ins else (None, None, None)
        shape, dt, entries = x
        nelems = None
        if shape is not None and all(d is not None and d >= 0
                                     for d in shape):
            nelems = 1
            for d in shape:
                nelems *= int(d)
        payload = (nelems * _dtype_bytes(dt)) if nelems is not None \
            else None
        mode, floor, _token = self._quant
        n = int(self.mesh_axes.get(axis, 1))
        outs: Dict[str, Optional[tuple]] = {}
        out_names = [nm for nm in op.output_arg_names() if nm != _EMPTY]
        primary = out_names[0] if out_names else None

        def wire_default():
            return payload

        if t.startswith("c_allreduce") or t == "mp_allreduce_sum":
            wire = payload
            if (t == "c_allreduce_sum" and mode == "int8"
                    and dt == "float32" and payload is not None
                    and payload >= floor and n > 1 and nelems):
                wire = _quant_phase_bytes(nelems, n) \
                    + _quant_phase_bytes(nelems, n)
            if primary:
                self._emit(t, primary, wire, (axis,),
                           f"explicit {t} on ring axis {axis!r}", op=op)
                outs[primary] = entries
        elif t == "c_allgather":
            wire = payload
            if (mode == "int8" and dt == "float32" and payload is not None
                    and payload >= floor and nelems):
                wire = _quant_plain_bytes(nelems)
            if primary:
                self._emit(t, primary, wire, (axis,),
                           f"explicit {t} on ring axis {axis!r}", op=op)
                # gathered output: dim 0 de-sharded
                outs[primary] = _trim((None,) + tuple(
                    (entries or ())[1:])) if entries is not None else None
        elif t == "c_reducescatter":
            wire = payload
            if (mode == "int8" and dt == "float32" and payload is not None
                    and payload >= floor and n > 1 and nelems):
                wire = _quant_phase_bytes(nelems, n)
            if primary:
                self._emit(t, primary, wire, (axis,),
                           f"explicit {t} on ring axis {axis!r}", op=op)
                # explicit-collective programs declare PER-SHARD
                # shapes, so the scatter is already materialized in the
                # declared metadata: layout unknown, not (axis,)
                outs[primary] = None
        elif t in ("send_v2", "recv_v2"):
            if t == "send_v2":
                self._emit(t, op.input_arg_names()[0] if
                           op.input_arg_names() else "?", payload,
                           (axis,), f"explicit {t} on ring axis "
                           f"{axis!r}", op=op)
            if primary:
                outs[primary] = None
        elif t in ("alltoall", "c_split", "c_concat"):
            if primary:
                self._emit(t, primary, wire_default(), (axis,),
                           f"explicit {t} on ring axis {axis!r}", op=op)
                outs[primary] = None
        else:
            # broadcast / identity / sync family: layout-preserving
            for nm in out_names:
                outs[nm] = entries
        return outs

    def _matmul_entries(self, op, env, block) -> Dict[str, Optional[tuple]]:
        x_names = op.inputs.get("X") or []
        y_names = op.inputs.get("Y") or []
        x = self._val(env, block, x_names[0]) if x_names \
            else (None, None, None)
        y = self._val(env, block, y_names[0]) if y_names \
            else (None, None, None)
        xs, _xd, xe = x
        ys, yd, ye = y
        out_names = [nm for nm in op.output_arg_names() if nm != _EMPTY]
        if not out_names:
            return {}
        out_name = out_names[0]
        # weight contract/width sharded -> XLA gathers the weight (or
        # equivalently reduce-scatters partials); the calibrated cost
        # model charges the FULL weight bytes once per use
        if ye is not None and _trim(ye) and ys is not None:
            wb = _static_nbytes(ys, yd)
            if wb is not None and y_names:
                axes = [n for e in ye for n in
                        self.rules.entry_names(e)]
                self._emit("weight_gather", y_names[0], wb, axes,
                           f"sharded weight {y_names[0]!r} consumed by "
                           f"{op.type!r}: SPMD gathers/rescatters it "
                           f"around the matmul", op=op)
        # activation contract dim sharded -> partial sums all-reduced
        if xe is not None and xs is not None and len(xs) >= 1:
            ce = _ent(xe, len(xs) - 1)
            if ce is not None:
                d = self._declared(block, out_name)
                ob = _static_nbytes(d[0], d[1]) if d else None
                self._emit("partial_allreduce", out_name,
                           (2 * ob) if ob else 0,
                           self.rules.entry_names(ce),
                           f"contract dim of {op.type!r} input is "
                           f"sharded over {ce!r}: partial sums are "
                           f"all-reduced", op=op)
        # out[row from x dim 0, col from y last dim], dropping a col
        # entry whose axes the row entry already uses
        row = _ent(xe, 0) if xe is not None else None
        col = _ent(ye, len(ys) - 1) if (ye is not None and ys) else None
        if col is not None and row is not None:
            used = set(self.rules.entry_names(row))
            if used & set(self.rules.entry_names(col)):
                col = None
        if xe is None and ye is None:
            return {out_name: None}
        return {out_name: _trim((row, col))}

    def _embedding_entries(self, op, env, block) \
            -> Dict[str, Optional[tuple]]:
        w_names = op.inputs.get("W") or []
        id_names = op.inputs.get("Ids") or []
        w = self._val(env, block, w_names[0]) if w_names \
            else (None, None, None)
        ids = self._val(env, block, id_names[0]) if id_names \
            else (None, None, None)
        ws, wd, we = w
        out_names = [nm for nm in op.output_arg_names() if nm != _EMPTY]
        if not out_names:
            return {}
        if we is not None and _trim(we) and ws is not None and w_names:
            wb = _static_nbytes(ws, wd)
            if wb is not None:
                axes = [n for e in we for n in self.rules.entry_names(e)]
                self._emit("weight_gather", w_names[0], wb, axes,
                           f"sharded embedding table {w_names[0]!r}: "
                           f"SPMD gathers rows across the vocab shards",
                           op=op)
        # out = ids layout + replicated embedding dim
        ide = ids[2]
        if ide is None and we is None:
            return {out_names[0]: None}
        base = tuple(ide or ())
        return {out_names[0]: _trim(base)}

    def _reshape_entries(self, op, env, block) \
            -> Dict[str, Optional[tuple]]:
        in_names = op.inputs.get("X") or []
        x = self._val(env, block, in_names[0]) if in_names \
            else (None, None, None)
        xs, xd, xe = x
        out_names = [nm for nm in op.output_arg_names() if nm != _EMPTY]
        data_outs = [nm for nm in out_names if "XShape" not in nm
                     and not nm.endswith("@XSHAPE")]
        if not data_outs:
            return {}
        out_name = data_outs[0]
        d = self._declared(block, out_name)
        os_ = d[0] if d else None
        res: Dict[str, Optional[tuple]] = {
            nm: REPLICATED for nm in out_names if nm != out_name}
        if xe is None or xs is None or os_ is None:
            res[out_name] = None if xe is None else (
                xe if xs is None else None)
            return res
        if not _trim(xe):
            res[out_name] = REPLICATED
            return res
        out_entries = self._reshape_carry(op, xs, os_, xe, xd,
                                          in_names[0])
        res[out_name] = out_entries
        return res

    def _reshape_carry(self, op, in_shape, out_shape, entries, dtype,
                       var) -> Optional[tuple]:
        """Factor-group walk: map sharded input dims onto output dims.
        A sharded dim that leads its factor group carries its entry to
        the group's leading output dim; a sharded INTERIOR dim cannot
        keep its layout — SPMD reshuffles the tensor (all_to_all),
        recorded as a resharding event."""
        ins = [int(d) for d in in_shape]
        outs = [int(d) for d in out_shape]
        ents: List[object] = [None] * len(outs)

        # symbolic shapes: carry dim 0 <-> dim 0 when both lead with
        # the symbolic batch dim; other sharded dims carry only on an
        # exact right-aligned suffix match
        if any(d < 0 for d in ins) or any(d < 0 for d in outs):
            if ins and outs and ins[0] < 0 and outs[0] < 0:
                ents[0] = _ent(entries, 0)
            k = 0
            while (k < len(ins) - 1 and k < len(outs) - 1
                   and ins[-1 - k] == outs[-1 - k] and ins[-1 - k] >= 0):
                e = _ent(entries, len(ins) - 1 - k)
                if e is not None:
                    ents[len(outs) - 1 - k] = e
                k += 1
            for i in range(1, len(ins) - k):
                e = _ent(entries, i)
                if e is not None:
                    nb = _static_nbytes(tuple(in_shape), dtype)
                    self._emit(
                        "all_to_all", var, nb,
                        self.rules.entry_names(e),
                        f"reshape moves sharded dim {i} across factor "
                        f"groups; SPMD redistributes the tensor", op=op,
                        warn=True)
            return _trim(ents)

        i = j = 0
        while i < len(ins) and j < len(outs):
            gi, gj = [i], [j]
            pi, pj = ins[i], outs[j]
            while pi != pj:
                if pi < pj:
                    i += 1
                    if i >= len(ins):
                        break
                    gi.append(i)
                    pi *= ins[i]
                else:
                    j += 1
                    if j >= len(outs):
                        break
                    gj.append(j)
                    pj *= outs[j]
            if pi != pj:
                return None  # ragged factorization: give up, unknown
            lead_in = gi[0]
            for k, dim in enumerate(gi):
                e = _ent(entries, dim)
                if e is None:
                    continue
                if dim == lead_in:
                    ents[gj[0]] = e
                else:
                    nb = _static_nbytes(tuple(in_shape), dtype)
                    self._emit(
                        "all_to_all", var, nb,
                        self.rules.entry_names(e),
                        f"reshape folds sharded interior dim {dim} "
                        f"(group {tuple(gi)} -> {tuple(gj)}); SPMD "
                        f"redistributes the tensor", op=op, warn=True)
            i += 1
            j += 1
        return _trim(ents)

    def _reduce_entries(self, op, env, block) \
            -> Dict[str, Optional[tuple]]:
        in_names = op.inputs.get("X") or []
        x = self._val(env, block, in_names[0]) if in_names \
            else (None, None, None)
        xs, xd, xe = x
        out_names = [nm for nm in op.output_arg_names() if nm != _EMPTY]
        if not out_names:
            return {}
        out_name = out_names[0]
        if xe is None:
            return {out_name: None}
        if xs is None:
            return {out_name: None}
        rank = len(xs)
        dims = op.attr("dim", None)
        if op.attr("reduce_all", False) or dims is None or dims == []:
            reduced = set(range(rank))
        else:
            if isinstance(dims, int):
                dims = [dims]
            reduced = {(d + rank) % rank for d in dims}
        keep = bool(op.attr("keep_dim", False))
        out: List[object] = []
        for i in range(rank):
            e = _ent(xe, i)
            if i in reduced:
                if e is not None:
                    d = self._declared(block, out_name)
                    ob = _static_nbytes(d[0], d[1]) if d else None
                    self._emit("partial_allreduce", out_name,
                               (2 * ob) if ob else 0,
                               self.rules.entry_names(e),
                               f"{op.type!r} reduces sharded dim {i}: "
                               f"partial results are all-reduced",
                               op=op)
                if keep:
                    out.append(None)
            else:
                out.append(e)
        return {out_name: _trim(out)}

    def _default_entries(self, op, env, block, out_shapes) \
            -> Dict[str, Optional[tuple]]:
        """In-place name match first; then single-primary preserve when
        shapes agree; n-ary elementwise meet for same-rank operands;
        all-replicated-in => replicated out; else unknown."""
        in_vals: List[Tuple[str, AbstractShard]] = []
        for nm in op.input_arg_names():
            if nm != _EMPTY:
                in_vals.append((nm, self._val(env, block, nm)))
        out: Dict[str, Optional[tuple]] = {}
        in_names = {nm for nm, _v in in_vals}
        for name in op.output_arg_names():
            if name == _EMPTY:
                continue
            if name in in_names:  # in-place update (optimizer ops)
                out[name] = self._val(env, block, name)[2]
                continue
            oshape = out_shapes.get(name)
            if oshape is None:
                d = self._declared(block, name)
                oshape = d[0] if d else None
            cands = [v for _nm, v in in_vals
                     if v[0] is not None and oshape is not None
                     and len(v[0]) == len(oshape)]
            if not in_vals:
                out[name] = REPLICATED
            elif cands:
                met = self._meet(cands, oshape, name, op)
                # this is a heuristic carry (the op has no dedicated
                # rule): an entry that does not divide its output dim
                # is a bad guess, not a layout contract — drop it
                # rather than let _bind report a phantom ERROR
                if met and oshape is not None:
                    met = _trim(tuple(
                        None if (e is not None and i < len(oshape)
                                 and oshape[i] is not None
                                 and oshape[i] >= 0
                                 and self.rules.axis_extent(
                                     self.mesh_axes, e) > 1
                                 and oshape[i] % self.rules.axis_extent(
                                     self.mesh_axes, e) != 0)
                        else e
                        for i, e in enumerate(met)))
                out[name] = met
            elif all(v[2] is not None and not _trim(v[2])
                     for _nm, v in in_vals):
                out[name] = REPLICATED
            else:
                # rank-changing op with no dedicated rule: unknown
                out[name] = None
                self.bailed += 1
        return out

    def _entries_for_op(self, op, env, block, out_shapes) \
            -> Dict[str, Optional[tuple]]:
        t = op.type
        if op.attr("fwd_op_id", None) is not None:
            return self._grad_entries(op, env, block)
        from .verifier import _is_collective
        if _is_collective(t):
            ins = [self._val(env, block, nm)
                   for nm in op.input_arg_names() if nm != _EMPTY]
            return self._collective_entries(op, env, block, ins)
        if t in _MATMUL_OPS:
            return self._matmul_entries(op, env, block)
        if t in _EMBEDDING_OPS:
            return self._embedding_entries(op, env, block)
        if t in _RESHAPE_OPS:
            return self._reshape_entries(op, env, block)
        if t in _TRANSPOSE_OPS:
            in_names = op.inputs.get("X") or []
            x = self._val(env, block, in_names[0]) if in_names \
                else (None, None, None)
            xs, _xd, xe = x
            perm = op.attr("axis", None)
            out_names = [nm for nm in op.output_arg_names()
                         if nm != _EMPTY]
            data_outs = [nm for nm in out_names if "XShape" not in nm]
            res: Dict[str, Optional[tuple]] = {
                nm: REPLICATED for nm in out_names
                if nm not in data_outs[:1]}
            if data_outs:
                if xe is None or not perm:
                    res[data_outs[0]] = None if xe is None else xe
                else:
                    res[data_outs[0]] = _trim(
                        [_ent(xe, int(p)) for p in perm])
            return res
        if t.startswith("reduce_") or t == "mean":
            return self._reduce_entries(op, env, block)
        if t == "softmax_with_cross_entropy":
            logits = (op.inputs.get("Logits") or [None])[0]
            lv = self._val(env, block, logits) if logits \
                else (None, None, None)
            out: Dict[str, Optional[tuple]] = {}
            for nm in op.output_arg_names():
                if nm == _EMPTY:
                    continue
                if "Softmax" in [s for s, ns in op.outputs.items()
                                 if nm in ns]:
                    out[nm] = lv[2]
                else:  # Loss: [batch, 1] keeps the batch entry
                    out[nm] = _trim((_ent(lv[2], 0),)) \
                        if lv[2] is not None else None
            return out
        if t == "layer_norm":
            xn = (op.inputs.get("X") or [None])[0]
            xv = self._val(env, block, xn) if xn else (None, None, None)
            out: Dict[str, Optional[tuple]] = {}
            for slot, names in op.outputs.items():
                for nm in names:
                    if nm == _EMPTY:
                        continue
                    if slot == "Y":
                        out[nm] = xv[2]
                    else:  # Mean/Variance: flattened rows keep dim 0
                        out[nm] = _trim((_ent(xv[2], 0),)) \
                            if xv[2] is not None else None
            return out
        if t in _FRESH_REPLICATED_OPS:
            return {nm: REPLICATED for nm in op.output_arg_names()
                    if nm != _EMPTY}
        return self._default_entries(op, env, block, out_shapes)

    # -- per-op ------------------------------------------------------------
    def _check_op(self, env: _Env, block, op, owner_type) -> None:
        if op.type in OPAQUE_OPS:
            for name in op.output_arg_names():
                if name == _EMPTY or env.lookup(name) is not None:
                    continue
                d = self._declared(block, name)
                if d is not None:
                    env.bind(name, (d[0], d[1], None))
            return

        def shape_lookup(name):
            v = env.lookup(name)
            if v is not None and v[0] is not None:
                return (v[0], v[1] or "float32")
            return self._declared(block, name)

        out_shapes: Dict[str, tuple] = {}
        try:
            inferred = infer_op_outputs(op, block, lookup=shape_lookup)
            out_shapes = {k: v[0] for k, v in inferred.items()}
            out_dtypes = {k: v[1] for k, v in inferred.items()}
        except ShapeInferBail:
            out_dtypes = {}
        except Exception:  # noqa: BLE001 - checker bug must not kill compile
            out_dtypes = {}

        try:
            out_entries = self._entries_for_op(op, env, block, out_shapes)
        except Exception:  # noqa: BLE001 - checker bug must not kill compile
            logger.debug("shard rule failed for op %r", op.type,
                         exc_info=True)
            out_entries = {}
            self.bailed += 1

        for name in op.output_arg_names():
            if name == _EMPTY:
                continue
            shape_dt = out_shapes.get(name), out_dtypes.get(name)
            if shape_dt[0] is None:
                d = self._declared(block, name)
                shape_dt = (d[0], d[1]) if d is not None else (None, None)
            self._bind(env, block, op, name,
                       (shape_dt[0], shape_dt[1],
                        out_entries.get(name)))

    # -- walk --------------------------------------------------------------
    def _walk(self, block, env: _Env, owner_type, visited) -> None:
        for op in block.ops:
            sb = op.attr("sub_block")
            if isinstance(sb, int) and 0 < sb < len(self.prog.blocks) \
                    and sb not in visited:
                self._descend(env, block, op, sb, visited)
                for name in op.output_arg_names():
                    if name == _EMPTY or env.lookup(name) is not None:
                        continue
                    d = self._declared(block, name)
                    if d is not None:
                        env.bind(name, (d[0], d[1], None))
                continue
            self._check_op(env, block, op, owner_type)

    def _descend(self, env: _Env, block, op, sb: int, visited) -> None:
        sub = self.prog.blocks[sb]
        if op.type in _LOOP_OWNERS:
            # pass 1 muted: diff the loop-carried writes, widen shape
            # changes to symbolic and layout changes to replicated
            saved = [(e, dict(e.vals)) for e in env.chain()]
            muted, self._muted = self._muted, True
            child = _Env(sub, parent=env)
            self._seed_entry(child)
            self._walk(sub, child, op.type, visited | {sb})
            self._muted = muted
            for e, before in saved:
                for name, new in list(e.vals.items()):
                    old = before.get(name)
                    if old is None or old == new:
                        continue
                    oshape, odt, oent = old
                    nshape, _ndt, nent = new
                    if oshape is not None and nshape is not None \
                            and len(oshape) == len(nshape):
                        wshape = tuple(a if a == b else -1
                                       for a, b in zip(oshape, nshape))
                    else:
                        wshape = oshape
                    went = oent if _entries_equal(oent, nent) \
                        else REPLICATED  # loop-carried layout widens
                    e.vals[name] = (wshape, odt, went)
            child = _Env(sub, parent=env)
            self._seed_entry(child)
            self._walk(sub, child, op.type, visited | {sb})
        else:
            saved = [(e, dict(e.vals)) for e in env.chain()]
            child = _Env(sub, parent=env)
            self._seed_entry(child)
            self._walk(sub, child, op.type, visited | {sb})
            for e, before in saved:
                for name, new in list(e.vals.items()):
                    old = before.get(name)
                    if old is None or old == new:
                        continue
                    oshape, odt, oent = old
                    nshape, _ndt, nent = new
                    if oshape is not None and nshape is not None \
                            and len(oshape) == len(nshape):
                        wshape = tuple(a if a == b else -1
                                       for a, b in zip(oshape, nshape))
                        went = oent if _entries_equal(oent, nent) \
                            else REPLICATED
                        e.vals[name] = (wshape, odt, went)
                    else:
                        e.vals.pop(name, None)

    def run(self) -> "ShardAnalysis":
        if self.prog.blocks:
            root = _Env(self.prog.blocks[0])
            self._seed_entry(root)
            self._walk(self.prog.blocks[0], root, None, {0})
        if self.bailed:
            try:
                from ..profiler import stat_add
                stat_add("shard_check_bailouts", self.bailed)
            except Exception:  # noqa: BLE001 - stdlib-only standalone load
                pass
        return ShardAnalysis(
            findings=self.findings, events=self.events,
            clamps=self.clamps, var_shapes=dict(self.var_shapes),
            mesh_axes=dict(self.mesh_axes), bailed=self.bailed)


class ShardAnalysis:
    """Result of one propagation run."""

    __slots__ = ("findings", "events", "clamps", "var_shapes",
                 "mesh_axes", "bailed")

    def __init__(self, findings, events, clamps, var_shapes, mesh_axes,
                 bailed):
        self.findings = findings
        self.events = events
        self.clamps = clamps
        self.var_shapes = var_shapes
        self.mesh_axes = mesh_axes
        self.bailed = bailed

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == ERROR]

    @property
    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == WARNING]


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def analyze(program, mesh_axes, *, ring_axes=None, batch_rows=None,
            feed=None, fetch_list=None, scope_names=None,
            floor=None) -> ShardAnalysis:
    """Propagate PartitionSpecs through `program` under a plain
    `{axis: size}` mesh dict; returns findings + predicted events."""
    feed_names = None
    if feed is not None:
        feed_names = set(feed.keys() if hasattr(feed, "keys") else feed)
    fetch_names = None
    if fetch_list is not None:
        fetch_names = [v.name if hasattr(v, "name") else str(v)
                       for v in fetch_list]
    ctx = VerifyContext(program, feed_names=feed_names,
                        fetch_names=fetch_names, scope_names=scope_names)
    return _ShardChecker(ctx, mesh_axes, ring_axes=ring_axes,
                         batch_rows=batch_rows, floor=floor).run()


def check_program(program, mesh_axes, *, ring_axes=None,
                  batch_rows=None, feed=None, fetch_list=None,
                  scope_names=None, floor=None) -> List[Finding]:
    """Standalone entry: findings only (tools/shardcheck.py, tests)."""
    return analyze(program, mesh_axes, ring_axes=ring_axes,
                   batch_rows=batch_rows, feed=feed,
                   fetch_list=fetch_list, scope_names=scope_names,
                   floor=floor).findings


def check_program_dict(d, mesh_axes, **kw) -> List[Finding]:
    """Check a serialized Program (Program.to_dict() / its JSON)."""
    return check_program(ProgramView(d), mesh_axes, **kw)


def propagated_shapes(program, feed=None, fetch_list=None,
                      scope_names=None) -> Dict[str, Tuple[tuple, str]]:
    """`{var: (shape, dtype)}` after replaying inference over the
    final graph (mesh-independent) — what the partition-spec pass
    consults instead of declared metadata alone."""
    return analyze(program, {}, feed=feed, fetch_list=fetch_list,
                   scope_names=scope_names).var_shapes


# calibration constants for the SPMD wire model, fitted against
# measured `collective_bytes_spmd_*` on the PR-13 acceptance
# transformer over {data:2,fsdp:2,tp:2} (tests/test_shard_check.py
# holds both quant modes within ±25%):
#  - a sharded weight consumed in the forward pass is gathered for
#    fwd AND re-gathered for the bwd remat -> 2x its bytes per use;
#    under the quantized two-jit gradient split the bwd re-gather is
#    partially shared -> 1.5x
_GATHER_FACTOR_FULL = 2.0
_GATHER_FACTOR_QUANT_SPLIT = 1.5


def comm_report(program, mesh_axes, *, ring_axes=None, batch_rows=None,
                feed=None, fetch_list=None, scope_names=None) -> dict:
    """Static predicted collective wire bytes for one compiled step of
    `program` under `mesh_axes` — BEFORE any compile.

    Two regimes:
    * programs containing explicit collective ops predict per-op-type
      bytes matching the `collective_bytes_<op_type>` counters;
    * SPMD programs (no explicit collectives) predict the
      `collective_bytes_spmd_*` counters XLA SPMD materializes:
      weight gathers from propagation events, gradient reduction per
      trainable param (quantized two-phase all_to_all+all_gather when
      the EQuARX path engages, full-width 2x all_reduce otherwise).
    """
    analysis = analyze(program, mesh_axes, ring_axes=ring_axes,
                       batch_rows=batch_rows, feed=feed,
                       fetch_list=fetch_list, scope_names=scope_names)
    rules = _spec_rules()
    mode, floor, token = quant_config()
    mesh = analysis.mesh_axes

    explicit = [e for e in analysis.events
                if e["kind"].startswith("c_")
                or e["kind"] in ("alltoall", "send_v2", "recv_v2",
                                 "mp_allreduce_sum")]
    if explicit:
        predicted: Dict[str, int] = {}
        for e in explicit:
            predicted[e["kind"]] = predicted.get(e["kind"], 0) \
                + int(e["bytes"])
        return {"mode": "explicit", "mesh_axes": dict(mesh),
                "quant": token, "predicted": predicted,
                "predicted_total": sum(predicted.values()),
                "events": analysis.events, "params": []}

    # ---- SPMD regime ----
    # trainable params: persistable float vars whose @GRAD is written
    grads_written = {
        n for blk in program.blocks for op in blk.ops
        for n in op.output_arg_names()
        if n != _EMPTY and _GRAD_SUFFIX in n}
    params: List[dict] = []
    seen: Set[str] = set()
    for blk in program.blocks:
        for name, v in blk.vars.items():
            if name in seen or not getattr(v, "persistable", False):
                continue
            if (name + _GRAD_SUFFIX) not in grads_written:
                continue
            dt = canon_dtype(getattr(v, "dtype", "float32"))
            if not dt.startswith("float"):
                continue
            nbytes = _static_nbytes(tuple(v.shape or ()), dt)
            if nbytes is None:
                continue
            seen.add(name)
            nelems = nbytes // _dtype_bytes(dt)
            params.append({"name": name, "nbytes": nbytes,
                           "nelems": nelems, "dtype": dt})

    # does gradient reduction happen at all? Only when the batch is
    # actually sharded (data/fsdp extents on the mesh)
    batch = rules.batch_entries(mesh, batch_rows)
    n_batch = rules.sharded_extent(batch, mesh)
    quant_split = (mode == "int8" and n_batch > 1)

    gather = 0.0
    all_to_all = 0.0
    all_reduce = 0.0
    factor = _GATHER_FACTOR_QUANT_SPLIT if quant_split \
        else _GATHER_FACTOR_FULL
    for e in analysis.events:
        if e["kind"] == "weight_gather":
            gather += factor * e["bytes"]
        elif e["kind"] == "partial_allreduce":
            all_reduce += e["bytes"]
        elif e["kind"] == "all_to_all":
            all_to_all += e["bytes"]

    for p in params:
        if n_batch <= 1:
            continue
        if quant_split and p["dtype"] == "float32" \
                and p["nbytes"] >= floor:
            q = _quant_phase_bytes(p["nelems"], n_batch)
            all_to_all += q
            gather += q
            p["quantized"] = True
        else:
            # opprof convention: all-reduce wire = 2x payload
            all_reduce += 2 * p["nbytes"]
            p["quantized"] = False

    predicted = {"all_gather": int(gather),
                 "all_reduce": int(all_reduce),
                 "all_to_all": int(all_to_all)}
    return {"mode": "spmd", "mesh_axes": dict(mesh), "quant": token,
            "predicted": predicted,
            "predicted_total": sum(predicted.values()),
            "events": analysis.events, "params": params,
            "n_batch": n_batch, "quant_split": quant_split}


def _axes_of(mesh) -> Dict[str, int]:
    """Accept a jax Mesh or a plain `{axis: size}` dict."""
    if hasattr(mesh, "axis_names"):
        return {str(n): int(mesh.shape[n]) for n in mesh.axis_names}
    return {str(k): int(v) for k, v in dict(mesh).items()}


def feasibility(program, old_mesh, new_mesh, *, batch_rows=None) -> dict:
    """Elastic-resharding precheck (ROADMAP elastic item): re-solve the
    spec registry over a candidate mesh and report fits/clamps and the
    per-device bytes delta WITHOUT compiling.  `feasible: False` means
    the restore path must refuse the candidate (today's behavior) —
    with the problems named instead of a bare mismatch error."""
    rules = _spec_rules()
    old_axes = _axes_of(old_mesh)
    new_axes = _axes_of(new_mesh)
    overrides = _registered_overrides()
    problems: List[str] = []
    clamps: List[str] = []

    def devcount(axes):
        n = 1
        for v in axes.values():
            n *= int(v)
        return n

    old_n, new_n = devcount(old_axes), devcount(new_axes)

    # the batch must still divide over the surviving mesh's batch axes
    if batch_rows is not None:
        old_batch = rules.sharded_extent(
            rules.batch_entries(old_axes, batch_rows), old_axes)
        new_batch = rules.sharded_extent(
            rules.batch_entries(new_axes, batch_rows), new_axes)
        want = 1
        for ax in ("data", "fsdp"):
            if ax in new_axes:
                want *= int(new_axes[ax])
        if want > 1 and batch_rows % want != 0:
            problems.append(
                f"batch of {batch_rows} rows does not divide over the "
                f"new mesh batch extent {want} "
                f"(axes {dict(new_axes)}) — old extent was {old_batch}")
        elif old_batch > 1 and new_batch <= 1:
            problems.append(
                f"batch parallelism collapses on the new mesh "
                f"{dict(new_axes)} (batch extent {new_batch}, was "
                f"{old_batch})")

    vars_out: List[dict] = []
    old_total = 0
    new_total = 0
    seen: Set[str] = set()
    for blk in program.blocks:
        for name, v in blk.vars.items():
            if name in seen or not getattr(v, "persistable", False):
                continue
            if v.shape is None:
                continue
            shape = tuple(int(s) for s in v.shape)
            if any(d < 0 for d in shape):
                continue
            seen.add(name)
            dt = canon_dtype(getattr(v, "dtype", "float32"))
            nbytes = _static_nbytes(shape, dt) or 0
            annotation = getattr(v, "_sharding_axes", None)
            override = overrides.get(name)
            old_e, _c0 = rules.resolve_entries(
                name, shape, old_axes, override=override,
                annotation=tuple(annotation) if annotation else None)
            new_e, c1 = rules.resolve_entries(
                name, shape, new_axes, override=override,
                annotation=tuple(annotation) if annotation else None)
            for c in c1:
                clamps.append(f"{name}: {c}")
            old_pd = nbytes // max(1, rules.sharded_extent(old_e,
                                                          old_axes))
            new_pd = nbytes // max(1, rules.sharded_extent(new_e,
                                                          new_axes))
            old_total += old_pd
            new_total += new_pd
            vars_out.append({
                "name": name, "nbytes": nbytes,
                "old_entries": list(old_e), "new_entries": list(new_e),
                "old_bytes_per_device": old_pd,
                "new_bytes_per_device": new_pd,
            })

    return {
        "feasible": not problems,
        "problems": problems,
        "clamps": clamps,
        "old_mesh_axes": old_axes, "new_mesh_axes": new_axes,
        "old_devices": old_n, "new_devices": new_n,
        "old_bytes_per_device": old_total,
        "new_bytes_per_device": new_total,
        "delta_bytes_per_device": new_total - old_total,
        "vars": vars_out,
    }


# ---------------------------------------------------------------------------
# The verifier pass (ERROR tier: runs once per compile-cache miss)
# ---------------------------------------------------------------------------

@register_pass("shard-consistency")
def shard_consistency_pass(ctx: VerifyContext) -> List[Finding]:
    """PartitionSpec propagation over the final graph under the CURRENT
    mesh: spec misfits are ERRORs before the compile instead of silent
    replication after it.  Skipped outside any mesh context."""
    try:
        from ..parallel import mesh as mesh_lib
    except Exception:  # noqa: BLE001 - jax-less tooling environments
        return []
    mesh = mesh_lib.current_mesh()
    if mesh is None:
        return []
    mesh_axes = {str(n): int(mesh.shape[n]) for n in mesh.axis_names}
    try:
        return _ShardChecker(ctx, mesh_axes).run().findings
    except Exception:  # noqa: BLE001 - analyzer bug must not kill compile
        logger.warning("shard-consistency pass failed", exc_info=True)
        return []
