"""XLA cost attribution: per-executable FLOPs/bytes -> live MFU gauges.

Every perf item on the ROADMAP is blocked on measurement: the MFU gap
wants a live number beside the benchmark's FLOPs formulas,
and the quantized-collectives item (EQuARX, arxiv 2506.17615) needs
per-collective bytes-on-wire counters to prove a win.  This module
supplies both seams:

* **Compile-time cost capture** (`compile_with_cost`): lower+compile a
  jitted step AOT and read `cost_analysis()` off the executable —
  FLOPs and bytes-accessed for exactly the program XLA will run.  The
  Executor calls this ONCE per compile-cache miss (the entry's first
  dispatch) and caches the result with the `CompileCache` entry, so
  cost attribution costs nothing at steady state.  Only ONE compile
  happens: the AOT executable replaces the jit call path for that
  entry (the jit wrapper stays as the fallback if the cached
  executable ever rejects an argument signature).

* **Live utilization gauges** (`ProgramCost.observe_dispatch`): the
  measured inter-dispatch interval (steady-state step time — no sync,
  no transfer) combines with the cached FLOPs/bytes into `mfu_pct` and
  `hbm_bw_pct` per program, visible in `obs.snapshot()`.

* **Bytes-on-wire counters** (`record_collective`): the collective op
  lowerings (ops/collective_ops.py) record the logical payload bytes
  each collective moves, at lowering (trace) time, under
  `collective_bytes_<op_type>` in the profiler StatRegistry.  A
  quantized all-reduce lowering will shrink exactly this number — the
  assertion seam for the ROADMAP item.

Peaks live in ONE table keyed by jax `device_kind` (`DEVICE_PEAKS`),
each with its source; a TPU kind that is not in the table raises
instead of borrowing another chip's numbers.  Off-chip (the CPU test
mesh) the gauges run against `HOST_NOMINAL`, a labelled placeholder
that keeps them nonzero and assertable in tier-1; `device_class` and
`peak_source` in the snapshot say which regime produced a number, and
nothing computed against the placeholder is a device metric.
"""

from __future__ import annotations

import collections
import os
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

# per-chip peaks by `jax.devices()[0].device_kind` (chip_smoke.py and
# tools/aot_analysis.py read this table)
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "flops": 197e12, "hbm_bps": 819e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 "
                  "TFLOP/s bf16, 819 GB/s HBM per chip"},
}
# NOT a device peak: keeps the live gauges nonzero on the CPU test mesh
HOST_NOMINAL: Dict[str, Any] = {
    "flops": 2e11, "hbm_bps": 5e10,
    "source": "nominal host placeholder (tier-1 only, not measured)"}

_COST_ENV = "PADDLE_OBS_COST"


def cost_capture_enabled() -> bool:
    return os.environ.get(_COST_ENV, "1").lower() not in ("0", "off",
                                                          "false")


def device_class() -> str:
    """"tpu" on a real chip, else "cpu-fallback" — the label stamped
    beside every gauge so on-chip numbers are never mixed with
    off-chip ones."""
    import jax

    return "tpu" if jax.default_backend() == "tpu" else "cpu-fallback"


def require_chip():
    """`(device, peak row)` of the first device when it is a TPU the
    peak table knows; RuntimeError otherwise.  The entry points that
    prove something on the chip (chip_smoke.py) call this first: JAX
    itself falls back to the CPU without a chip and exits 0."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"no chip found — JAX reports platform {d.platform!r}")
    if d.device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no peak numbers for TPU device_kind {d.device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}): add a row with its "
            "source to obs.cost.DEVICE_PEAKS")
    return d, DEVICE_PEAKS[d.device_kind]


def device_peaks() -> Dict[str, Any]:
    """The peak row of the device this process runs on."""
    import jax

    if jax.default_backend() != "tpu":
        return HOST_NOMINAL
    return require_chip()[1]


def peak_flops() -> float:
    return device_peaks()["flops"]


def peak_hbm_bps() -> float:
    return device_peaks()["hbm_bps"]


def cost_of_compiled(compiled) -> Optional[Dict[str, float]]:
    """{"flops", "bytes_accessed"} from an AOT executable's XLA
    cost_analysis, or None when the backend does not report one."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - optional on some PJRT plugins
        return None
    if not cost:
        return None
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return {"flops": flops, "bytes_accessed": nbytes}


class ProgramCost:
    """Cached compile-time cost + live dispatch-rate gauges for one
    compiled executable."""

    __slots__ = ("label", "flops", "bytes_accessed", "dispatches",
                 "_t_first", "_t_last", "step_ms", "mfu_pct",
                 "hbm_bw_pct", "_lock")

    def __init__(self, label: str, flops: float, bytes_accessed: float):
        self.label = label
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.dispatches = 0
        self._t_first = None
        self._t_last = None
        self.step_ms = 0.0
        self.mfu_pct = 0.0
        self.hbm_bw_pct = 0.0
        self._lock = threading.Lock()

    def observe_dispatch(self, now: Optional[float] = None) -> None:
        """One dispatch of this executable at perf_counter time `now`.
        Steady-state step time is the mean inter-dispatch interval —
        measured on the host, no device sync — which the cached FLOPs
        turn into a live MFU estimate."""
        if now is None:
            now = time.perf_counter()
        with self._lock:
            self.dispatches += 1
            if self._t_first is None:
                self._t_first = self._t_last = now
                return
            self._t_last = now
            elapsed = now - self._t_first
            n = self.dispatches - 1
            if elapsed <= 0.0 or n <= 0:
                return
            step_s = elapsed / n
            self.step_ms = step_s * 1e3
            peaks = device_peaks()
            pf, pb = peaks["flops"], peaks["hbm_bps"]
            if self.flops > 0.0 and pf > 0.0:
                self.mfu_pct = self.flops / step_s / pf * 100.0
            if self.bytes_accessed > 0.0 and pb > 0.0:
                self.hbm_bw_pct = self.bytes_accessed / step_s / pb * 100.0

    def as_dict(self) -> Dict[str, Any]:
        # 8 decimals: a toy CPU program's MFU is ~1e-5 % and must not
        # round to a zero that reads as "no cost model"
        return {"label": self.label,
                "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "dispatches": self.dispatches,
                "step_ms": round(self.step_ms, 4),
                "mfu_pct": round(self.mfu_pct, 8),
                "hbm_bw_pct": round(self.hbm_bw_pct, 8)}


# bounded registry of every ProgramCost this process created, for
# obs.snapshot()'s "MFU per program"; insertion-ordered so
# eviction drops the oldest program first
_PROGRAMS: "collections.OrderedDict[str, ProgramCost]" = \
    collections.OrderedDict()
_PROGRAMS_LOCK = threading.Lock()
_PROGRAMS_CAP = 256


def register_program(label: str, cost: Optional[Dict[str, float]]) \
        -> Optional[ProgramCost]:
    """Create (or refresh) the ProgramCost gauge slot for `label`."""
    if not cost:
        return None
    pc = ProgramCost(label, cost.get("flops", 0.0),
                     cost.get("bytes_accessed", 0.0))
    with _PROGRAMS_LOCK:
        _PROGRAMS[label] = pc
        _PROGRAMS.move_to_end(label)
        while len(_PROGRAMS) > _PROGRAMS_CAP:
            _PROGRAMS.popitem(last=False)
    return pc


def programs() -> List[ProgramCost]:
    with _PROGRAMS_LOCK:
        return list(_PROGRAMS.values())


def reset_programs() -> None:
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def compile_with_cost(jitted, args: Tuple, label: str):
    """AOT-compile `jitted` for `args` and read its cost_analysis.

    Returns `(compiled, ProgramCost | None)`; `(None, None)` when
    capture is disabled or lowering/compiling fails — the caller then
    stays on the plain jit path.  The compiled executable is the SAME
    compilation the jit call would have performed (one compile total);
    donation and shardings declared on the jit carry through."""
    if not cost_capture_enabled():
        return None, None
    try:
        with warnings.catch_warnings():
            # donation warnings are the jit path's business; the AOT
            # twin must not duplicate them
            warnings.filterwarnings("ignore", message=".*donat.*")
            compiled = jitted.lower(*args).compile()
    except Exception:  # noqa: BLE001 - cost capture must never break a run
        return None, None
    cost = cost_of_compiled(compiled)
    # per-op attribution (obs/opprof.py): walk the executable's HLO
    # once, here on the compile-cache miss, and fold per-instruction
    # FLOPs/bytes back onto the Program ops named in the metadata
    from . import memprof, opprof

    op_prof = opprof.profile_compiled(compiled, label, cost=cost)
    # static memory attribution (obs/memprof.py): same compile-miss
    # seam, reusing opprof's instruction->provenance join so FLOP and
    # temp-byte attribution can never disagree about fusion ownership
    memprof.capture_compiled(compiled, label, opprof_profile=op_prof)
    return compiled, register_program(label, cost)


def record_collective(op_type: str, nbytes: int) -> None:
    """Bytes-on-wire seam: logical payload bytes one collective op
    moves, recorded at lowering (trace) time — once per compiled
    program, under `collective_bytes_<op_type>` (+ a sibling op count).
    A quantized lowering (EQuARX ROADMAP item) shrinks this number; the
    accuracy-guard test will assert exactly that."""
    from ..profiler import stat_add

    stat_add(f"collective_bytes_{op_type}", int(nbytes))
    stat_add(f"collective_count_{op_type}")


def collective_snapshot(stats: Optional[Dict[str, int]] = None) \
        -> Dict[str, int]:
    if stats is None:
        from ..profiler import get_int_stats

        stats = get_int_stats()
    pre = "collective_bytes_"
    return {k[len(pre):]: v for k, v in stats.items()
            if k.startswith(pre)}


def snapshot() -> Dict[str, Any]:
    """The cost-attribution block of obs.snapshot(): device regime,
    per-program gauges, and the headline live MFU (the most recently
    dispatched program with a cost model)."""
    progs = programs()
    live = None
    for pc in progs:
        if pc.dispatches > 1 and (live is None
                                  or (pc._t_last or 0) > (live._t_last or 0)):
            live = pc
    peaks = device_peaks()
    return {
        "device_class": device_class(),
        "peak_flops": peaks["flops"],
        "peak_hbm_bps": peaks["hbm_bps"],
        "peak_source": peaks["source"],
        "mfu_pct": round(live.mfu_pct, 8) if live else 0.0,
        "hbm_bw_pct": round(live.hbm_bw_pct, 8) if live else 0.0,
        "programs": [pc.as_dict() for pc in progs],
        "collective_bytes": collective_snapshot(),
    }
