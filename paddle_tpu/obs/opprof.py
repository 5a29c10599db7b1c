"""Per-op cost attribution: Program->HLO provenance folded back onto ops.

PR 6 gave the stack whole-program FLOPs/bytes (`obs.cost`); a 42.3%-MFU
BERT step was still ONE opaque number.  This module closes the loop the
TF paper (arxiv 1605.08695) treats as a first-class dataflow concern —
graph-node-level cost attribution:

* **Provenance threading** happens at lowering time: `ops/registry`
  wraps every op's lowering rule in `jax.named_scope` with the op's
  greppable provenance string (`program#<id>/block<idx>/op<id>:<type>`,
  the PR-3 verifier's identity in scope-path form), so every HLO
  instruction XLA emits for that op carries the source op in its
  `metadata={op_name=...}` — and survives XLA's own fusion/rewrites,
  because metadata is propagated through them.

* **The HLO walk** (`profile_hlo_text`) parses the AOT-compiled
  executable's optimized HLO (`compiled.as_text()`, captured once per
  compile-cache miss by `obs.cost.compile_with_cost`) and folds
  per-instruction FLOP/byte estimates, fusion membership, transpose/
  relayout copies and collective payload bytes back onto the Program
  ops named in the metadata.  Instruction FLOPs use the standard
  analytic model (dot = 2*M*N*K, conv = 2*out*kernel/Cout, elementwise
  = |out|); totals are then normalized to the executable's own XLA
  `cost_analysis` numbers so the table sums to the whole-program truth
  and per-op rows are shares of it (`flops_raw` keeps the unscaled
  estimate).  Instructions with no provenance metadata land in the
  `unattributed` bin — never silently dropped.

* **Transform survival**: `transforms.apply_transforms` stamps every
  cloned op with its SOURCE program's provenance before passes run, and
  rewriting passes append `[pass=<name>]` tags — so the table answers
  "which op still relayouts after NHWC" directly, against source-op
  identities the user can grep in their build script.

stdlib-only ON PURPOSE (the tracing.py idiom): `tools/tracetool.py
top-ops` loads this module by file path and can profile a raw HLO dump
in environments without jax.
"""

from __future__ import annotations

import collections
import os
import re
import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

_OPPROF_ENV = "PADDLE_OBS_OPPROF"

# provenance minted by ops/registry.op_provenance and stamped by
# transforms; the [pass=...] suffix is appended by rewriting passes
PROVENANCE_RE = re.compile(
    r"program#(\d+)/block(\d+)/op(\d+):([A-Za-z0-9_.]+)"
    r"(?:\[pass=([A-Za-z0-9_,.\-]+)\])?")

UNATTRIBUTED = "unattributed"


def opprof_enabled() -> bool:
    return os.environ.get(_OPPROF_ENV, "1").lower() not in ("0", "off",
                                                            "false")


def format_provenance(prog_id: int, block_idx: int, op_id: int,
                      op_type: str, passes: Iterable[str] = ()) -> str:
    s = f"program#{prog_id}/block{block_idx}/op{op_id}:{op_type}"
    passes = [p for p in passes if p]
    if passes:
        s += f"[pass={','.join(passes)}]"
    return s


def parse_provenance(s: str) -> Optional[dict]:
    """Last (deepest-scoped) provenance occurrence in `s`, or None."""
    last = None
    for m in PROVENANCE_RE.finditer(s):
        last = m
    if last is None:
        return None
    prog, blk, op, typ, passes = last.groups()
    return {"prog": int(prog), "block": int(blk), "op": int(op),
            "type": typ, "passes": passes.split(",") if passes else []}


# the Program op types that apply an update (ops/optimizer_ops.py)
_OPTIMIZER_OP_TYPES = frozenset({
    "sgd", "momentum", "adam", "adamw", "adagrad", "rmsprop", "adadelta",
    "adamax", "lamb", "lars_momentum", "dpsgd", "dgc", "decayed_adagrad",
    "proximal_gd", "proximal_adagrad", "ftrl",
    "check_finite_and_unscale", "update_loss_scaling"})
# `jit(step)`, `jvp(bert)`, `transpose(jvp(bert))`: a JAX transform
# around (part of) the scope path
_TRANSFORM_RE = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
_REMAT_SCOPES = ("checkpoint", "rematted_computation")


def _scope_parts(text: str, out: List[str]) -> bool:
    """Appends the scope names of an `op_name` (or of the inside of a
    transform) to `out`; True when it passed through `transpose(`."""
    bwd = False
    depth = start = 0
    for i, ch in enumerate(text + "/"):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            part, start = text[start:i], i + 1
            m = _TRANSFORM_RE.match(part)
            if m is None:
                if part and part not in _REMAT_SCOPES:
                    out.append(part)
            elif m.group(1) not in ("jit", "pjit"):
                # jit(f) names a function, not a scope; the others
                # wrap the scopes that were open when they were applied
                bwd |= m.group(1) == "transpose"
                bwd |= _scope_parts(m.group(2), out)
    return bwd


def scope_name(op_name: str) -> Optional[Tuple[str, str]]:
    """`(phase, path)` of an HLO instruction's `op_name`, or None when
    the program gave it no name.

    A Program op (`program#…/op<n>:<type>`, ops/registry.py) has its
    type for a path and its phase from it: `*_grad` is `bwd`, an
    optimizer op `optimizer`, the rest `fwd`.  Else the path is
    `op_name` without the `jit(…)` / `jvp(…)` / `transpose(…)` /
    `checkpoint` wrappers and the trailing primitive
    (`bert/encoder/layers/3/self_attn/q_proj`); the phase is
    `optimizer` / `loss` where the path starts so, `bwd` where `op_name`
    passed through `transpose(`, else `fwd`."""
    prov = parse_provenance(op_name)
    if prov is not None:
        typ = prov["type"]
        if typ in _OPTIMIZER_OP_TYPES:
            return "optimizer", typ
        return ("bwd" if typ.endswith("_grad") else "fwd"), typ
    parts: List[str] = []
    bwd = _scope_parts(op_name, parts)
    if parts and not op_name.endswith(")"):
        parts.pop()                     # the primitive: `dot_general`
    if not parts:
        return None
    if parts[0] in ("optimizer", "loss"):
        return parts[0], "/".join(parts)
    return ("bwd" if bwd else "fwd"), "/".join(parts)


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
    "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "token": 0, "opaque": 0,
}

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s+\([^=]*\)\s*->")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([a-z0-9?]+)_([a-z0-9?]+)->"
                            r"([a-z0-9?]+)")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")

# out-elems-cost elementwise/transcendental opcodes (1 flop/elem, the
# same convention xla::HloCostAnalysis uses)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "and", "or", "xor", "not", "negate", "abs", "sign", "compare",
    "select", "clamp", "exponential", "exponential-minus-one", "log",
    "log-plus-one", "logistic", "tanh", "sine", "cosine", "tan",
    "sqrt", "rsqrt", "cbrt", "power", "atan2", "remainder", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "is-finite",
    "shift-left", "shift-right-arithmetic", "shift-right-logical",
    "popcnt", "clz", "erf", "expm1", "log1p",
}
_REDUCES = {"reduce", "reduce-window", "select-and-scatter"}
_RELAYOUT = {"transpose", "copy"}
_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute", "all-reduce-start",
                "all-gather-start", "collective-permute-start"}
# free/bookkeeping opcodes: never cost flops or bytes
_FREE = {"parameter", "constant", "bitcast", "tuple",
         "get-tuple-element", "after-all", "reshape", "broadcast",
         "iota", "custom-call", "fusion", "call", "while",
         "conditional", "get-dimension-size", "partition-id",
         "replica-id", "rng-bit-generator", "rng", "infeed", "outfeed",
         "optimization-barrier", "domain", "add-dependency"}


class _Shape:
    __slots__ = ("elems", "nbytes")

    def __init__(self, elems: int, nbytes: int):
        self.elems = elems
        self.nbytes = nbytes


def _parse_shape(text: str) -> _Shape:
    """Element/byte count of a result type string ('f32[64,256]{1,0}',
    '(f32[2]{0}, s32[])', 'token[]' ...).  Tuples sum their leaves."""
    elems = 0
    nbytes = 0
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue  # layout annotations like {1,0:T(8,128)} match too
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dtype]
    return _Shape(elems, nbytes)


def _take_balanced(s: str, start: int) -> Tuple[str, int]:
    """Substring of `s` from the '(' at `start` through its matching
    ')'; returns (inner_text, index_after)."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return s[start + 1:i], i + 1
    return s[start + 1:], len(s)


class _Instr:
    __slots__ = ("name", "opcode", "shape", "operands", "args",
                 "op_name", "line", "comp")

    def __init__(self, name, opcode, shape, operands, args, op_name,
                 line, comp):
        self.name = name
        self.opcode = opcode
        self.shape = shape
        self.operands = operands
        self.args = args
        self.op_name = op_name
        self.line = line
        self.comp = comp


_BLOCK_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _parse_instructions(text: str) -> List[_Instr]:
    out: List[_Instr] = []
    comp = ""
    for raw in text.splitlines():
        # strip /*index=N*/ position comments FIRST: any computation
        # with >5 tuple params/outputs carries them, and their "=" made
        # the header check (and _COMP_RE's `[^=]*` params group) reject
        # the ENTRY line — every entry instruction then inherited the
        # last interior computation and vanished from the join map
        line = _BLOCK_COMMENT_RE.sub("", raw).rstrip()
        if not line or line.lstrip().startswith(("//", "#")):
            continue
        if line.endswith("{") and "=" not in line.split("{")[0]:
            mc = _COMP_RE.match(line)
            if mc:
                comp = mc.group(2)
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name = m.group(1)
        rest = line[m.end():]
        # result type: balanced parens for tuple shapes, else one token
        if rest.startswith("("):
            shape_txt, idx = _take_balanced(rest, 0)
        else:
            idx = rest.find(" ")
            if idx < 0:
                continue
            shape_txt = rest[:idx]
        tail = rest[idx:].lstrip()
        mo = re.match(r"([a-zA-Z][\w\-]*)\s*\(", tail)
        if mo is None:
            continue
        opcode = mo.group(1)
        args, _ = _take_balanced(tail, mo.end() - 1)
        operands = re.findall(r"%([\w.\-]+)", args)
        mn = _OPNAME_RE.search(line)
        out.append(_Instr(name, opcode, _parse_shape(shape_txt),
                          operands, args, mn.group(1) if mn else "",
                          line, comp))
    return out


def _instr_flops(ins: _Instr, shapes: Dict[str, _Shape]) -> float:
    op = ins.opcode
    if op == "dot":
        # contraction size K from the lhs operand's declared type,
        # which rides in the args text: dot(f32[64,128]{1,0} %a, ...)
        k = 1
        m = _LHS_CDIMS_RE.search(ins.line)
        dims_m = _SHAPE_RE.search(ins.args)
        if m and dims_m and dims_m.group(2):
            lhs_dims = [int(d) for d in dims_m.group(2).split(",")]
            for di in (m.group(1) or "").split(","):
                if di and int(di) < len(lhs_dims):
                    k *= lhs_dims[int(di)]
        return 2.0 * ins.shape.elems * k
    if op == "convolution":
        kernel_elems = None
        if len(ins.operands) >= 2:
            kshape = shapes.get(ins.operands[1])
            if kshape is not None:
                kernel_elems = kshape.elems
        if kernel_elems is None:
            return 2.0 * ins.shape.elems
        out_features = 1
        ml = _DIM_LABELS_RE.search(ins.line)
        if ml:
            out_labels = ml.group(3)
            f_idx = out_labels.find("f")
            mo = _SHAPE_RE.search(ins.line)
            if f_idx >= 0 and mo and mo.group(2):
                dims = [int(d) for d in mo.group(2).split(",")]
                if f_idx < len(dims):
                    out_features = max(1, dims[f_idx])
        return 2.0 * ins.shape.elems * kernel_elems / out_features
    if op in _ELEMENTWISE:
        return float(ins.shape.elems)
    if op in _REDUCES:
        src = shapes.get(ins.operands[0]) if ins.operands else None
        return float(src.elems if src is not None else ins.shape.elems)
    return 0.0


def _instr_bytes(ins: _Instr, shapes: Dict[str, _Shape]) -> float:
    """HBM-traffic estimate for one top-level instruction: output bytes
    plus every operand's bytes (fused interiors are excluded by the
    caller — only computation-boundary values move memory)."""
    total = float(ins.shape.nbytes)
    for o in ins.operands:
        s = shapes.get(o)
        if s is not None:
            total += s.nbytes
    return total


def _collective_wire_bytes(ins: _Instr, shapes: Dict[str, _Shape]) -> float:
    """Wire-true ICI traffic for one collective instruction.

    The per-device output shape understates some collectives: a ring
    all-reduce moves ~2x its payload (reduce-scatter phase + all-gather
    phase), and reduce-scatter's OUTPUT is 1/n of the payload that
    crossed the wire.  Counting these truthfully is what makes the
    quantized-collective drop (docs/spmd.md, FLAGS_quant_collectives)
    provable from `collective_bytes_spmd_*`: the int8 lowering
    decomposes into all-to-all + all-gather whose shapes ARE their wire
    payloads."""
    if ins.opcode == "all-reduce":
        return 2.0 * float(ins.shape.nbytes)
    if ins.opcode == "reduce-scatter":
        op0 = shapes.get(ins.operands[0]) if ins.operands else None
        if op0 is not None:
            return float(op0.nbytes)
    # -start variants carry (operand, result) tuple shapes that already
    # sum both phases; all-gather / all-to-all / collective-permute
    # outputs equal their wire payloads
    return float(ins.shape.nbytes)


def _new_row(key: str) -> dict:
    return {"op": key, "flops_raw": 0.0, "bytes_raw": 0.0,
            "instructions": 0, "fusions": 0, "transposes": 0,
            "transpose_bytes": 0.0, "collective_bytes": 0.0}


def _format_prov(p: dict) -> str:
    return format_provenance(p["prog"], p["block"], p["op"], p["type"],
                             p["passes"])


# what XLA inserts without metadata: relayouts, the asynchronous copies
# and slices that prefetch an operand for the op that reads it, and the
# custom calls that stitch them (`ConcatBitcast`)
_MOVES = _RELAYOUT | {"copy-start", "copy-done", "slice-start",
                      "slice-done", "bitcast"}
_INHERIT_OPS = _MOVES | {"fusion", "reshape", "broadcast", "convert",
                         "custom-call"}


def _inherit_from_consumers(instrs, fused_comps, consumers, key_of) -> None:
    """A top-level relayout/fusion/reshape with no key of its own takes
    its consumers' when they all agree (fixpoint over short
    copy->fusion->op chains); a pure data movement whose consumers
    have none or differ (the copy of a result to its output buffer, a
    prefetch read forward and backward) takes its operands'.  Updates
    `key_of` in place."""
    by_name = {ins.name: ins for ins in instrs}
    for _ in range(8):
        changed = False
        for ins in instrs:
            if key_of.get(ins.name) is not None \
                    or ins.comp in fused_comps \
                    or ins.opcode not in _INHERIT_OPS:
                continue
            got = {key_of[c] for c in consumers.get(ins.name, ())
                   if key_of.get(c) is not None}
            if len(got) != 1 and ins.opcode in _MOVES:
                got = {key_of[o] for o in ins.operands
                       if key_of.get(o) is not None
                       and by_name[o].comp == ins.comp}
            if len(got) == 1:
                key_of[ins.name] = got.pop()
                changed = True
        if not changed:
            break


def _name_xla_kernels(instrs, fused_comps, consumers, key_of) -> None:
    """A Mosaic kernel XLA itself made (`jax.lax.ragged_dot` becomes
    `ragged-dot-none` / `ragged-dot-metadata` calls) carries XLA's
    `op_name` and no scope of the program's.  It is named by that
    `op_name` under the scope its neighbours share: the longest common
    path of the instructions that make its operands and read its
    result — `…/moe/…/experts/ragged-dot-none`, or one level up where a
    neighbour was fused into another scope's instruction — `bwd` where
    any of them is.  Which neighbour a fusion swallowed no longer
    decides where a kernel's time is booked.  Updates `key_of`."""
    by_name = {ins.name: ins for ins in instrs}
    kernels = {
        ins.name for ins in instrs
        if ins.comp not in fused_comps and ins.opcode == "custom-call"
        and re.fullmatch(r"[\w.\-]+", ins.op_name or "")
        and 'custom_call_target="tpu_custom_call"' in ins.line}
    for name in kernels:
        ins = by_name[name]
        near = [key_of[n] for n in list(ins.operands)
                + consumers.get(name, [])
                if n in by_name and n not in kernels
                and by_name[n].comp == ins.comp
                and isinstance(key_of.get(n), tuple)]
        if not near:
            continue        # `ragged-dot-metadata`: its consumer's name
        common = []
        for parts in zip(*(k[1].split("/") for k in near)):
            if len(set(parts)) != 1:
                break
            common.append(parts[0])
        phase = "bwd" if any(k[0] == "bwd" for k in near) else near[0][0]
        key_of[name] = (phase, "/".join(common + [ins.op_name]))


def _join_map(instrs, fused_comps, key_of) -> dict:
    """`{top-level instruction: key}`: its own (or inherited) key; a
    fusion without one takes the dominant key of its interior
    instructions; else UNATTRIBUTED."""
    interior: Dict[str, collections.Counter] = \
        collections.defaultdict(collections.Counter)
    for ins in instrs:
        if ins.comp in fused_comps and key_of.get(ins.name) is not None:
            interior[ins.comp][key_of[ins.name]] += 1
    out = {}
    for ins in instrs:
        if ins.comp in fused_comps:
            continue
        key = key_of.get(ins.name)
        if key is None and ins.opcode == "fusion":
            mc = _CALLS_RE.search(ins.line)
            cnt = interior.get(mc.group(1)) if mc else None
            if cnt:
                key = min(cnt.items(), key=lambda kv: (-kv[1], kv[0]))[0]
        out[ins.name] = key if key is not None else UNATTRIBUTED
    return out


def profile_hlo_text(text: str, label: str = "",
                     cost: Optional[Dict[str, float]] = None) -> dict:
    """Fold an optimized-HLO dump into a per-Program-op cost table.

    `cost` is the executable's own `cost_analysis` {"flops",
    "bytes_accessed"}; when present the raw estimates are normalized so
    the table sums to the compiler's whole-program numbers (per-op rows
    become shares of the truth; `*_raw` keeps the estimate)."""
    instrs = _parse_instructions(text)
    shapes = {i.name: i.shape for i in instrs}

    # computations reached via a fusion's calls= are interior: their
    # instructions cost flops (with their own metadata) but move no
    # HBM bytes; the fusion instruction itself moves the bytes
    fused_comps = set()
    fusion_instr: Dict[str, _Instr] = {}  # fused comp -> fusion instr
    for ins in instrs:
        if ins.opcode == "fusion":
            mc = _CALLS_RE.search(ins.line)
            if mc:
                fused_comps.add(mc.group(1))
                fusion_instr[mc.group(1)] = ins

    # direct provenance, then consumer inheritance: XLA rewrites
    # (conv canonicalization, layout copies) create metadata-less
    # relayout chains — a transpose/copy/fusion with no provenance of
    # its own inherits from its consumers when they all agree, so
    # "which op still relayouts" points at the op PAYING for the
    # relayout instead of an anonymous bin
    consumers: Dict[str, List[str]] = collections.defaultdict(list)
    for ins in instrs:
        if ins.comp in fused_comps:
            continue
        for o in ins.operands:
            consumers[o].append(ins.name)
    prov_key: Dict[str, Optional[str]] = {}
    prov_of_key: Dict[str, dict] = {}
    for ins in instrs:
        p = parse_provenance(ins.op_name)
        prov_key[ins.name] = _format_prov(p) if p else None
        if p:
            prov_of_key[prov_key[ins.name]] = p
    _inherit_from_consumers(instrs, fused_comps, consumers, prov_key)

    rows: Dict[str, dict] = collections.OrderedDict()
    fusion_sets: Dict[str, set] = collections.defaultdict(set)
    raw_flops_total = 0.0
    raw_bytes_total = 0.0
    # per-opcode collective traffic: who is moving bytes — the
    # attribution seam the SPMD partitioner's inserted all-gathers /
    # reduce-scatters surface through (docs/spmd.md)
    coll_by_op: Dict[str, float] = {}

    for ins in instrs:
        in_fused = ins.comp in fused_comps
        key = prov_key.get(ins.name)
        if key is None and in_fused:
            # interior instruction without metadata: inherit the
            # fusion's representative provenance
            fi = fusion_instr.get(ins.comp)
            key = prov_key.get(fi.name) if fi is not None else None
        prov = prov_of_key.get(key)
        key = key or UNATTRIBUTED

        flops = _instr_flops(ins, shapes)
        nbytes = 0.0
        if not in_fused and ins.opcode not in ("parameter", "constant",
                                               "tuple",
                                               "get-tuple-element",
                                               "bitcast"):
            nbytes = _instr_bytes(ins, shapes)
        if flops <= 0.0 and nbytes <= 0.0 \
                and ins.opcode not in _RELAYOUT \
                and ins.opcode not in _COLLECTIVES:
            continue

        row = rows.get(key)
        if row is None:
            row = rows[key] = _new_row(key)
            if prov:
                row["source"] = prov
        row["instructions"] += 1
        row["flops_raw"] += flops
        row["bytes_raw"] += nbytes
        raw_flops_total += flops
        raw_bytes_total += nbytes
        if ins.opcode == "fusion":
            row["fusions"] += 1
        elif in_fused:
            fusion_sets[key].add(ins.comp)
        if ins.opcode in _RELAYOUT:
            row["transposes"] += 1
            row["transpose_bytes"] += ins.shape.nbytes
        if ins.opcode in _COLLECTIVES:
            wire = _collective_wire_bytes(ins, shapes)
            row["collective_bytes"] += wire
            coll_by_op[ins.opcode] = (coll_by_op.get(ins.opcode, 0)
                                      + wire)

    for key, comps in fusion_sets.items():
        rows[key]["fusions"] = max(rows[key]["fusions"], len(comps))

    # instruction -> key for EVERY top-level instruction (zero-cost ops
    # included): obs/devprof.py joins the profiler's event names to
    # these maps.  `instr_prov` keeps the source op's identity (the
    # cost rows' and obs/memprof.py's key); `instr_name` is
    # `scope_name`'s `(phase, path)`, which a functional step has too.
    instr_prov = _join_map(instrs, fused_comps, prov_key)
    name_key: Dict[str, Optional[Tuple[str, str]]] = {
        ins.name: scope_name(ins.op_name) for ins in instrs}
    _name_xla_kernels(instrs, fused_comps, consumers, name_key)
    _inherit_from_consumers(instrs, fused_comps, consumers, name_key)
    instr_name = _join_map(instrs, fused_comps, name_key)

    cost = cost or {}
    cost_flops = float(cost.get("flops", 0.0) or 0.0)
    cost_bytes = float(cost.get("bytes_accessed", 0.0) or 0.0)
    fscale = cost_flops / raw_flops_total \
        if cost_flops > 0.0 and raw_flops_total > 0.0 else 1.0
    bscale = cost_bytes / raw_bytes_total \
        if cost_bytes > 0.0 and raw_bytes_total > 0.0 else 1.0

    table: List[dict] = []
    attributed_flops = 0.0
    for key, row in rows.items():
        row["flops"] = row["flops_raw"] * fscale
        row["bytes"] = row["bytes_raw"] * bscale
        row["flops_pct"] = (row["flops_raw"] / raw_flops_total * 100.0
                            if raw_flops_total > 0.0 else 0.0)
        if key != UNATTRIBUTED:
            attributed_flops += row["flops_raw"]
        table.append(row)
    table.sort(key=lambda r: -r["flops_raw"])

    module = re.match(r"\s*HloModule\s+([^\s,]+)", text)
    return {
        "label": label,
        "module": module.group(1) if module else "",
        "rows": table,
        "instruction_count": len(instrs),
        "total_flops": cost_flops or raw_flops_total,
        "total_flops_raw": raw_flops_total,
        "total_bytes": cost_bytes or raw_bytes_total,
        "total_bytes_raw": raw_bytes_total,
        "attributed_flops_pct": (
            attributed_flops / raw_flops_total * 100.0
            if raw_flops_total > 0.0 else 0.0),
        "transposes": sum(r["transposes"] for r in table),
        "collective_bytes": sum(r["collective_bytes"] for r in table),
        "collective_bytes_by_op": dict(coll_by_op),
        "instr_prov": instr_prov,
        "instr_name": instr_name,
    }


def top_ops(profile: dict, k: int = 10,
            key: str = "flops") -> List[dict]:
    """Top-k rows of a profile by `key` (flops | bytes | transposes |
    collective_bytes), unattributed bin excluded."""
    rows = [r for r in profile.get("rows", []) if r["op"] != UNATTRIBUTED]
    rows.sort(key=lambda r: -float(r.get(key, 0.0) or 0.0))
    return rows[:k]


def trim_profile(profile: dict, k: int = 12) -> dict:
    """Snapshot-sized view: top-k rows + the unattributed bin + totals
    (the full table stays in the registry)."""
    keep = top_ops(profile, k)
    unattr = [r for r in profile.get("rows", [])
              if r["op"] == UNATTRIBUTED]
    # instr_prov is join plumbing for obs/devprof.py, not snapshot data
    out = {kk: v for kk, v in profile.items()
           if kk not in ("rows", "instr_prov", "instr_name")}
    out["rows"] = [_round_row(r) for r in keep + unattr]
    for f in ("total_flops", "total_flops_raw", "total_bytes",
              "total_bytes_raw", "attributed_flops_pct"):
        if f in out:
            out[f] = round(float(out[f]), 3)
    return out


def _round_row(r: dict) -> dict:
    out = dict(r)
    for f in ("flops", "flops_raw", "bytes", "bytes_raw", "flops_pct",
              "transpose_bytes", "collective_bytes"):
        if f in out:
            out[f] = round(float(out[f]), 3)
    return out


# ---------------------------------------------------------------------------
# Profile registry (the ProgramCost idiom: bounded, insertion-ordered)
# ---------------------------------------------------------------------------

_PROFILES: "collections.OrderedDict[str, dict]" = \
    collections.OrderedDict()
_PROFILES_LOCK = threading.Lock()
_PROFILES_CAP = 64


def register_profile(label: str, profile: dict) -> dict:
    with _PROFILES_LOCK:
        _PROFILES[label] = profile
        _PROFILES.move_to_end(label)
        while len(_PROFILES) > _PROFILES_CAP:
            _PROFILES.popitem(last=False)
    return profile


def profiles() -> "collections.OrderedDict[str, dict]":
    with _PROFILES_LOCK:
        return collections.OrderedDict(_PROFILES)


def reset_profiles() -> None:
    with _PROFILES_LOCK:
        _PROFILES.clear()


def profile_for(prog_id: Optional[int] = None,
                label: Optional[str] = None) -> Optional[dict]:
    """Most recent registered profile, optionally filtered by the
    SOURCE program id its rows attribute to, or by exact label."""
    with _PROFILES_LOCK:
        items = list(_PROFILES.items())
    for lab, prof in reversed(items):
        if label is not None:
            if lab == label:
                return prof
            continue
        if prog_id is None:
            return prof
        for row in prof.get("rows", []):
            src = row.get("source")
            if src and src.get("prog") == prog_id:
                return prof
    return None


def profile_compiled(compiled, label: str,
                     cost: Optional[Dict[str, float]] = None,
                     register: bool = True) -> Optional[dict]:
    """Walk an AOT-compiled executable's HLO and register the per-op
    table.  Duck-typed on `.as_text()` so this module stays jax-free;
    returns None (never raises) when the backend can't dump HLO."""
    if not opprof_enabled():
        return None
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 - optional on some PJRT plugins
        return None
    if not text:
        return None
    try:
        prof = profile_hlo_text(text, label=label, cost=cost)
    except Exception:  # noqa: BLE001 - attribution must never break a run
        return None
    if register:
        register_profile(label, prof)
    # attribute SPMD-inserted collectives to the counter table
    # (cost.record_collective): the explicit shard_map path records
    # per-op at lower time; the jit-SPMD path only learns what the
    # partitioner inserted here, from the optimized HLO.  Prefixed
    # spmd_* so the two attribution sources stay distinguishable.
    for opcode, nbytes in (prof.get("collective_bytes_by_op")
                           or {}).items():
        if nbytes > 0:
            from .cost import record_collective

            record_collective("spmd_" + opcode.replace("-", "_"),
                              int(nbytes))
    return prof


def snapshot(top: int = 12) -> Dict[str, Any]:
    """The op-profile block of obs.snapshot(): one trimmed table per
    registered executable, most recent last."""
    with _PROFILES_LOCK:
        items = list(_PROFILES.items())
    return {label: trim_profile(prof, top) for label, prof in items}
