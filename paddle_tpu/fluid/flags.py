"""Global runtime flags.

Reference: the 32 gflags in paddle/fluid/platform/flags.cc exposed to
Python through global_value_getter_setter.cc and `fluid.set_flags` /
`FLAGS_*` environment variables (SURVEY.md §5.9).

TPU-native: a Python registry seeded from the environment; flags that
map onto jax/XLA knobs forward to them on set (e.g. check_nan_inf ->
jax_debug_nans).  Unknown FLAGS_* names raise, like the reference.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict

from .compile_cache import persistent_cache_dirs

_REGISTRY: Dict[str, dict] = {}


def _define(name, default, help_str="", on_set: Callable = None,
            typ=None, env_var=None):
    """`env_var` names an additional environment source checked BEFORE
    the generic FLAGS_<name> (the PADDLE_CKPT_* contract rides this)."""
    typ = typ or type(default)
    env = None
    if env_var is not None:
        env = os.environ.get(env_var)
    if env is None:
        env = os.environ.get(f"FLAGS_{name}")
    value = default
    if env is not None:
        if typ is bool:
            value = env.lower() in ("1", "true", "yes")
        else:
            value = typ(env)
    _REGISTRY[name] = {"value": value, "default": default, "help": help_str,
                       "type": typ, "on_set": on_set}
    if on_set is not None and value != default:
        on_set(value)


def _set_debug_nans(v):
    # Intentionally NOT forwarded to jax_debug_nans anymore: that knob
    # re-checks every dispatch synchronously, which would defeat the
    # async dispatch-ahead executor loop (ISSUE 1).  The Executor now
    # compiles a device-side finite scan into the step and drains it on
    # a background thread; the dygraph tracer keeps its own eager check.
    pass


def _set_deterministic(v):
    # XLA is deterministic by construction on TPU; keep the knob for
    # API parity (the reference's FLAGS_cudnn_deterministic)
    pass


# -- the flag set (mirrors flags.cc categories) ------------------------------
_define("check_nan_inf", False,
        "scan op outputs for NaN/Inf after each eager op / executor run "
        "(flags.cc:44); the executor scan is device-side + async",
        _set_debug_nans)
_define("cudnn_deterministic", False,
        "deterministic kernels (flags.cc:98); TPU/XLA is deterministic",
        _set_deterministic)
_define("allocator_strategy", "auto_growth",
        "host-staging allocator strategy (flags.cc:316); XLA owns device "
        "memory on TPU")
_define("eager_delete_tensor_gb", 0.0,
        "GC threshold (flags.cc:257); XLA buffer liveness replaces it")
_define("fraction_of_gpu_memory_to_use", 0.92,
        "device memory fraction; TPU: XLA preallocation policy")
_define("paddle_num_threads", 1, "intra-op host threads")
_define("sync_nccl_allreduce", True,
        "collective sync mode; XLA schedules collectives")
_define("benchmark", False, "per-op benchmark mode")
_define("max_inplace_grad_add", 0, "grad accumulation inplace threshold")
_define("sort_sum_gradient", False,
        "deterministic gradient sum order (flags.cc:521)")
_define("use_pinned_memory", True, "host staging uses pinned buffers")
_define("init_allocated_mem", False, "poison fresh allocations")
_define("free_idle_chunk", False, "release idle allocator chunks")
_define("tracer_profile_fname", "", "imperative tracer profile output")
_define("check_numerics", False,
        "per-op numeric check, softer than check_nan_inf")
_define("verify_program", "on",
        "run the analysis.verifier ERROR-tier passes once per "
        "compile-cache miss (docs/static_analysis.md): 'on' raises "
        "ProgramVerificationError on ERROR findings, 'warn' reports "
        "and continues (the escape hatch), 'off' disables")
_define("graph_transforms", "on",
        "Program->Program transform pass pipeline run once per "
        "compile-cache miss, immediately before verification "
        "(docs/graph_transforms.md): 'on' runs the default-enabled "
        "passes (layout_optimize, dead_op_elim), 'off' disables all, "
        "per-pass overrides compose as e.g. 'on,fold_bn=on' or "
        "'layout_optimize=off'")
# -- fault-tolerant training (paddle_tpu.ckpt, docs/fault_tolerance.md):
# the PADDLE_CKPT_* env contract configures the auto-checkpoint loop on
# Executor.train_from_dataset without touching the training script
_define("ckpt_dir", "",
        "auto-checkpoint root for train_from_dataset: when set, the "
        "loop saves async sharded checkpoints and resumes from the "
        "newest complete one (paddle_tpu.ckpt)", env_var="PADDLE_CKPT_DIR")
_define("ckpt_every_steps", 0,
        "auto-checkpoint every N steps (0 = only the end-of-pass save)",
        env_var="PADDLE_CKPT_EVERY_STEPS")
_define("ckpt_every_secs", 0.0,
        "auto-checkpoint every N seconds (0 = disabled; composes with "
        "ckpt_every_steps — whichever fires first)",
        env_var="PADDLE_CKPT_EVERY_SECS")
_define("ckpt_keep", 3,
        "retention: newest N complete checkpoints kept, older ones and "
        "half-written tmp dirs garbage-collected on each commit",
        env_var="PADDLE_CKPT_KEEP")
_define("ckpt_max_in_flight", 2,
        "bounded checkpoint write queue: beyond N pending snapshots "
        "save_async backpressures (ckpt_stall_ms)",
        env_var="PADDLE_CKPT_MAX_IN_FLIGHT")
_define("ckpt_resume", True,
        "resume train_from_dataset from the newest complete checkpoint "
        "under ckpt_dir (scope state + executor step + exact remaining "
        "feed order)", env_var="PADDLE_CKPT_RESUME")
# -- live telemetry (paddle_tpu.obs.telemetry, docs/observability.md):
# the PADDLE_OBS_* env contract turns on the always-on metrics sampler,
# /metrics + /healthz endpoint and anomaly watchdog without touching
# the training or serving script
_define("obs_sample_s", 1.0,
        "telemetry sampler period in seconds: the background collector "
        "folds profiler counters/timers and cost gauges into bounded "
        "ring-buffer time series every N seconds",
        env_var="PADDLE_OBS_SAMPLE_S")
_define("obs_http_port", -1,
        "telemetry HTTP port serving /metrics, /healthz, /snapshot and "
        "/debug/trace on train_from_dataset and serving.Engine "
        "(0 = ephemeral port, -1 = telemetry off)",
        env_var="PADDLE_OBS_HTTP_PORT")
_define("obs_flight_dir", "artifacts/flight",
        "flight-recorder artifacts dir: a firing watchdog rule "
        "atomically publishes a post-mortem bundle (trace + snapshot + "
        "op-profile + series window) here",
        env_var="PADDLE_OBS_FLIGHT_DIR")
_define("obs_flight_keep", 5,
        "flight-recorder retention: newest N bundles kept, older ones "
        "and half-written tmp dirs garbage-collected on each dump",
        env_var="PADDLE_OBS_FLIGHT_KEEP")
_define("obs_flight_min_interval_s", 60.0,
        "flight-recorder rate limit: at most one bundle per N seconds "
        "(further firings only update /healthz)",
        env_var="PADDLE_OBS_FLIGHT_MIN_INTERVAL_S")
_define("transform_debug", False,
        "per-pass transform bisection (docs/graph_transforms.md): run "
        "the shape-consistency check after EVERY transform pass inside "
        "apply_transforms and raise naming the first pass whose rewrite "
        "broke the graph — instead of one post-pipeline failure that "
        "does not say which pass did it")
_define("op_callstack", False,
        "record the Python construction stack on every appended op "
        "(attrs['op_callstack']); verifier findings then point at the "
        "user line that built the offending op")
_define("quant_collectives", "off",
        "quantized collectives over ICI (docs/spmd.md): off | int8. "
        "int8 routes c_allreduce_sum / c_reducescatter / c_allgather "
        "and the SPMD gradient reductions through a blockwise "
        "quantize->reduce->dequantize path (~4x less wire traffic); "
        "joins the compile-cache signature so flips never reuse a "
        "stale executable",
        env_var="PADDLE_QUANT_COLLECTIVES")
_define("quant_collectives_min_bytes", 1024,
        "per-tensor floor for FLAGS_quant_collectives: payloads "
        "smaller than this many bytes stay full-width (quantizing "
        "tiny tensors costs more in scales+padding than it saves)",
        env_var="PADDLE_QUANT_COLLECTIVES_MIN_BYTES")
# -- persistent AOT executable cache (fluid/aot_cache.py,
# docs/serving.md "Multi-tenant fleet"): a fresh process serving a
# previously-compiled model loads the serialized XLA executable from
# disk instead of recompiling — compile time is an availability number
# at restart
_define("aot_cache", "on",
        "persistent on-disk AOT executable cache: 'on' consults "
        "aot_cache_dir on every compile-cache miss and stores freshly "
        "compiled executables there; 'off' is byte-identical to the "
        "pre-cache behavior (every signature component — transforms, "
        "numerics, quant mode, jax/backend fingerprint — keys the "
        "entry, so drift is a hard miss, never a stale load)",
        env_var="PADDLE_AOT_CACHE")
_define("aot_cache_dir", persistent_cache_dirs()[1],
        "root directory of the persistent AOT executable cache "
        "(entries commit via tmp-dir + os.replace, the ckpt idiom); "
        "the default follows compile_cache.persistent_cache_dirs(); "
        "empty disables the cache like FLAGS_aot_cache='off'",
        env_var="PADDLE_AOT_CACHE_DIR")


def get_flags(flags):
    """get_flags(['FLAGS_x', ...]) -> {name: value}
    (reference: fluid get_flags)."""
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {n!r}")
        out[n] = _REGISTRY[key]["value"]
    return out[names[0]] if single else out


def set_flags(flags: Dict[str, Any]):
    """set_flags({'FLAGS_x': v}) (reference: fluid.set_flags)."""
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {n!r}")
        entry = _REGISTRY[key]
        entry["value"] = entry["type"](v) if entry["type"] is not bool \
            else bool(v)
        if entry["on_set"] is not None:
            entry["on_set"](entry["value"])


def flag(name, default=None):
    """Internal fast read."""
    e = _REGISTRY.get(name)
    return e["value"] if e is not None else default
