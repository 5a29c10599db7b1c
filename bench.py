#!/usr/bin/env python
"""Benchmark entry point: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": ...}.

Benchmark: BERT-base pretraining MFU on the TPU (BASELINE.json
north_star: >=45% MFU).  One fused XLA train step (fwd+bwd+AdamW, bf16
activations, fp32 master weights, Pallas flash attention) — seq 512,
per-chip batch sized for one v5e chip.

vs_baseline = achieved MFU / 45 (the north-star target).

It measures on a chip or not at all: without a TPU whose device_kind
is in the peak table (paddle_tpu/obs/cost.py) it exits non-zero and
prints no metric; a kernel that fails its preflight, or a sub-bench
that raises, fails the run.  One process holds the chip: `--mode
fleet` runs its cold-start worker processes BEFORE this process
touches JAX.
"""

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np


def bench_feed_pipeline(jax, jnp):
    """Feed-pipeline micro-exercise (ISSUE 4): stream synthetic batches
    through the per-host sharded pipeline's device ring while a jitted
    step consumes them, then report the overlap counters.  The numbers
    make a stall attributable from the BENCH JSON alone
    (`stall_attribution`: compute-bound = ring backpressure, the
    healthy state; parser-/transfer-bound = the feed is the
    bottleneck), and on a pod slice each host's entry lands under its
    process index in `per_host_feed_ms`."""
    import numpy as np

    from paddle_tpu import profiler
    from paddle_tpu.dataset import feed_pipeline as fp

    for name in ("parser_wait_ms", "ring_full_wait_ms",
                 "ring_empty_wait_ms", "host_feed_ms", "shard_skew_ms"):
        profiler.time_reset(name)
    profiler.stat_reset("ring_occupancy_max")

    n_batches = 32
    rng = np.random.RandomState(0)
    pool = [{"x": rng.randn(256, 256).astype(np.float32)}
            for _ in range(8)]
    source = (pool[i % len(pool)] for i in range(n_batches))

    @jax.jit
    def step(x):
        return (x @ x.T).sum()

    def stage(feed):
        with profiler.timed("host_feed_ms"):
            return {k: jax.device_put(v) for k, v in feed.items()}

    pipe = fp.FeedPipeline(stage, source)
    out = None
    for staged in pipe:
        out = step(staged["x"])
    if out is not None:
        float(out)  # one sanctioned sync, at the end of the stream
    report = pipe.feed_report()
    report["batches"] = n_batches
    report["per_host_feed_ms"] = {str(report["host"]):
                                  report["host_feed_ms"]}
    return report


def bert_step_flops(cfg, batch, seq, n_masked):
    """Model FLOPs for one train step (fwd + bwd ~= 3x fwd cost)."""
    h, l, inter, v = (cfg.hidden_size, cfg.num_hidden_layers,
                      cfg.intermediate_size, cfg.vocab_size)
    per_layer = 4 * h * h + 2 * h * inter          # qkvo + ffn weights
    matmul_params = l * per_layer
    fwd_tok = 2 * matmul_params + l * 4 * seq * h  # + attention scores/pv
    fwd = batch * seq * fwd_tok
    fwd += 2 * batch * n_masked * h * v            # MLM head matmul
    return 3 * fwd


def _kernel_preflight(jax, jnp):
    """Run the flash kernel against the XLA oracle on the chip before
    timing (the bench-side half of the TPU test lane,
    tests/test_tpu_kernels.py).  A kernel that does not compile, or
    compiles but is WRONG, fails the run: the bench never times the
    XLA attention path under the kernel's name."""
    from paddle_tpu.ops.pallas.attention import (
        _flash_ok, _xla_attention, flash_attention)

    # bf16 + key-bias, the dtype/branch family the BERT bench runs
    # (dropout is excluded only because no oracle matches its RNG)
    q = jnp.asarray(np.random.RandomState(0).randn(2, 512, 4, 64),
                    jnp.bfloat16)
    kb = jnp.broadcast_to(
        jnp.where(jnp.arange(512)[None, :] < 400, 0.0, -1e9),
        (2, 512)).astype(jnp.float32)
    if not _flash_ok(q, q):
        raise RuntimeError("bench: Mosaic refused the flash kernel")
    out = flash_attention(q, q, q, key_bias=kb).astype(jnp.float32)
    ref = _xla_attention(q, q, q,
                         mask=kb[:, None, None, :]).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)))
    if err > 5e-2:
        raise RuntimeError(f"bench: flash/XLA mismatch {err:.3g}")
    return f"flash vs XLA max err {err:.2e}"


def _flash_really_active():
    """Post-run truth: the traced step committed to a flash kernel
    instance (a True exact-probe entry) and no shape gave way to XLA.
    The exact probe cache legitimately holds False entries for refused
    head-block ladder rungs, so `all(...)` would misreport."""
    from paddle_tpu import profiler
    from paddle_tpu.ops.pallas import attention as att

    return (any(v is True for v in att._EXACT_PROBE_CACHE.values())
            and profiler.get_int_stats().get("flash_fallback_total",
                                             0) == 0)


def _time_step(run_once, steps, reps, warmup_steps=2):
    """Shared timing harness: explicit warmup/compile phase, then
    min-of-reps mean step time.  `run_once()` advances one step and
    returns the loss scalar; the sync is a host transfer of that scalar
    (`float`), which materializes the end of the dispatched chain.

    Warmup is SEPARATE from the timed region by construction (ISSUE 1):
    the first warmup step pays trace+compile, later warmup steps settle
    caches; none of it can leak into the reported step time.  The timed
    loop is the dispatch-ahead shape — `steps` dispatches in flight,
    ONE sync at the end — so the per-rep host dispatch time is also the
    overlap evidence.  Returns (best_step_seconds, final_loss, pipe)
    where pipe carries warmup/compile split + per-step host dispatch_ms
    and sync_ms for the bench JSON detail."""
    t0 = time.perf_counter()
    final_loss = float(run_once())  # trace + compile + first step
    compile_s = time.perf_counter() - t0
    for _ in range(warmup_steps - 1):
        final_loss = float(run_once())
    warmup_s = time.perf_counter() - t0

    best = float("inf")
    dispatch_s = sync_s = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = run_once()
        t1 = time.perf_counter()  # all steps dispatched, none synced
        final_loss = float(loss)  # host sync; forces the whole chain
        t2 = time.perf_counter()
        dispatch_s += t1 - t0
        sync_s += t2 - t1
        best = min(best, (t2 - t0) / steps)
    n = max(reps * steps, 1)
    pipe = {
        "warmup_steps": warmup_steps,
        "compile_s": round(compile_s, 3),
        "warmup_s": round(warmup_s, 3),
        # host time to enqueue one step (the dispatch-ahead cost) vs
        # the single end-of-rep sync amortized per step
        "dispatch_ms": round(dispatch_s / n * 1e3, 4),
        "sync_ms": round(sync_s / n * 1e3, 4),
        # the timed loop keeps `steps` dispatches in flight per sync
        "prefetch_depth": steps,
    }
    return best, final_loss, pipe


def _obs_detail():
    """BENCH JSON `detail.obs` (ISSUE 6): the structured observability
    snapshot — cost gauges (live MFU per program), bytes-on-wire
    counters, span summary, profiler tables."""
    from paddle_tpu import obs

    return obs.snapshot()


def _memory_detail():
    """BENCH JSON `detail.memory` (ISSUE 14): the device-memory ledger
    + the peak byte count tools/bench_diff.py gates as
    `hbm_peak_bytes`."""
    from paddle_tpu.obs import memprof

    led = memprof.memory_ledger()
    return {
        "hbm_peak_bytes": int(led.get("peak_bytes") or 0),
        "bytes_in_use": led.get("bytes_in_use"),
        "unattributed": led.get("unattributed"),
        "static_temp_bytes": led.get("static_temp_bytes"),
        "ledger_total_bytes": led.get("total"),
        "ledger": led.get("entries", {}),
        "profiles": {lab: memprof.trim_profile(p)
                     for lab, p in memprof.profiles().items()},
    }


def bench_telemetry():
    """`detail.telemetry` (ISSUE 10 satellite): the live-telemetry
    sampler's own cost.  Drives Collector.sample_once over the REAL
    in-process sources (profiler tables + cost gauges — exactly what
    the background thread folds every PADDLE_OBS_SAMPLE_S seconds) and
    reports the mean per-sample overhead so tools/bench_diff.py can
    gate it, plus samples/drops/rules_fired for the record."""
    from paddle_tpu.obs import telemetry

    wd = telemetry.Watchdog(artifacts_dir=None)
    col = telemetry.Collector(sources=telemetry.default_sources(),
                              sample_s=1.0, watchdog=wd)
    n = 50
    fired = 0
    for _ in range(n):
        fired += len(col.sample_once())
    return {
        "sampler_overhead_ms": round(col.sampler_overhead_ms / n, 4),
        "samples": col.samples,
        "drops": col.drops(),
        "rules_fired": fired,
        "series": len(col.store.names()),
    }


def bench_checkpoint(jax, jnp):
    """`detail.ckpt` (ISSUE 8 satellite): async-checkpoint overhead on
    a live train loop.  Times N jitted steps with auto-checkpointing
    OFF, then the same N with a save every 2 steps, and reports the
    subsystem's own timers — save_ms (writer thread), stall_ms (the
    only training-thread cost: snapshot + backpressure) and the
    in-flight overlap high-water — so tools/bench_diff.py can gate
    checkpoint overhead once an on-chip record exists."""
    import tempfile

    import numpy as np

    from paddle_tpu import profiler
    from paddle_tpu.ckpt import CheckpointManager

    for name in ("ckpt_save_ms", "ckpt_stall_ms"):
        profiler.time_reset(name)
    for name in ("ckpt_inflight_max", "ckpt_saves_total"):
        profiler.stat_reset(name)

    rng = np.random.RandomState(0)
    state = {f"w_{i}": jax.device_put(
        rng.randn(256, 256).astype(np.float32)) for i in range(4)}

    @jax.jit
    def step(s):
        return {k: v + 1e-3 * (v @ v.T) for k, v in s.items()}

    state = step(state)  # compile outside the timed windows
    jax.block_until_ready(state["w_0"])
    n_steps, every = 16, 2

    def loop(mgr):
        s = state
        t0 = time.perf_counter()
        for i in range(1, n_steps + 1):
            s = step(s)
            if mgr is not None and i % every == 0:
                mgr.save_async(s, step=i)
        jax.block_until_ready(s["w_0"])
        if mgr is not None:
            mgr.wait()
        return (time.perf_counter() - t0) * 1e3 / n_steps

    step_ms_off = loop(None)
    with tempfile.TemporaryDirectory() as root:
        mgr = CheckpointManager(root, keep=2)
        step_ms_on = loop(mgr)
        mgr.close()
    times = profiler.get_time_stats()
    stats = profiler.get_int_stats()
    overhead = (step_ms_on / step_ms_off - 1.0) * 100.0 \
        if step_ms_off > 0 else 0.0
    return {
        "steps": n_steps,
        "every_steps": every,
        "save_ms": round(times.get("ckpt_save_ms", 0.0), 3),
        "stall_ms": round(times.get("ckpt_stall_ms", 0.0), 3),
        "inflight_max": stats.get("ckpt_inflight_max", 0),
        "saves": stats.get("ckpt_saves_total", 0),
        "step_ms_off": round(step_ms_off, 4),
        "step_ms_on": round(step_ms_on, 4),
        "overhead_pct": round(overhead, 2),
    }


def bench_numerics(jax, jnp):
    """`detail.numerics` (ISSUE 15 satellite): per-op numeric-stats
    collection cost on a live fluid train loop.  Times N executor
    steps with PADDLE_OBS_NUMERICS=off, then the same loop with stats
    collection on — the mode joins the compile-cache signature, so the
    flip is a clean recompile, never a stale cache hit — and reports
    the on-vs-off overhead plus the training-health gauges the
    instrumented run produced (grad_norm_total, update_ratio, AMP
    loss_scale) so tools/bench_diff.py can gate
    `numerics_overhead_pct`."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.obs import numerics

    feed = {"x": np.random.RandomState(0)
            .randn(8, 64).astype(np.float32)}
    n_steps = 12

    def run(mode):
        prev = os.environ.get("PADDLE_OBS_NUMERICS")
        os.environ["PADDLE_OBS_NUMERICS"] = mode
        try:
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup):
                x = fluid.data("x", [8, 64], "float32")
                h = fluid.layers.fc(x, size=64, act="relu",
                                    name="num_fc1")
                h = fluid.layers.fc(h, size=64, name="num_fc2")
                loss = fluid.layers.reduce_mean(h)
                fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(main_prog, feed=feed,
                    fetch_list=[loss.name])  # compile, outside timing
            t0 = time.perf_counter()
            for _ in range(n_steps):
                exe.run(main_prog, feed=feed, fetch_list=[loss.name])
            return (time.perf_counter() - t0) * 1e3 / n_steps
        finally:
            if prev is None:
                os.environ.pop("PADDLE_OBS_NUMERICS", None)
            else:
                os.environ["PADDLE_OBS_NUMERICS"] = prev

    numerics.reset()
    step_ms_off = run("off")
    step_ms_on = run("on")
    gauges = numerics.health_gauges()  # drains the pending refs
    doc = numerics.numerics_doc()
    overhead = (step_ms_on / step_ms_off - 1.0) * 100.0 \
        if step_ms_off > 0 else 0.0
    return {
        "mode": "on",
        "steps": n_steps,
        "step_ms_off": round(step_ms_off, 4),
        "step_ms_on": round(step_ms_on, 4),
        "overhead_pct": round(overhead, 2),
        "ops_tracked": len(doc.get("ops") or []),
        "nonfinite_ops_total": doc.get("nonfinite_ops_total"),
        "grad_norm_total": gauges.get("grad_norm_total"),
        "update_ratio": gauges.get("update_ratio"),
        "loss_scale": doc.get("loss_scale"),
    }


def bench_sharding(jax, jnp):
    """`detail.sharding` (ISSUE 13 satellite): SPMD named-axis layout
    numbers on a small fluid train loop — the mesh axes used, params /
    optimizer-state bytes resident per device (via
    `.addressable_shards`), how many registry specs applied, and the
    SPMD-inserted collective traffic.  tools/bench_diff.py gates
    `optimizer_bytes_per_device` on these (any rise fails on-chip)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import profiler
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.compiler import BuildStrategy

    n_dev = len(jax.devices())
    if n_dev % 4 == 0:
        axes = {"data": n_dev // 4, "fsdp": 2, "tp": 2}
    elif n_dev % 2 == 0:
        axes = {"data": n_dev // 2, "fsdp": 2}
    else:
        axes = {"data": n_dev}
    profiler.stat_reset("spmd_specs_applied")
    main, startup, scope = framework.Program(), framework.Program(), Scope()
    try:
        with framework.program_guard(main, startup), \
                unique_name.guard(), scope_guard(scope):
            x = fluid.data("x", [-1, 64], "float32")
            label = fluid.data("label", [-1, 1], "int64")
            h = fluid.layers.fc(x, 128, act="relu")
            h2 = fluid.layers.fc(h, 128, act="relu")
            pred = fluid.layers.fc(h2, 8)
            loss = fluid.layers.reduce_mean(
                fluid.layers.loss.softmax_with_cross_entropy(pred, label))
            fluid.optimizer.Adam(1e-3).minimize(loss)
            exe = fluid.Executor()
            exe.run(startup)
            bs = BuildStrategy()
            bs.mesh_axes = axes
            compiled = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, build_strategy=bs)
            rng = np.random.RandomState(0)
            X = rng.rand(32, 64).astype("float32")
            L = rng.randint(0, 8, (32, 1)).astype("int64")
            pre = profiler.get_int_stats()
            for _ in range(3):
                out = exe.run(compiled, feed={"x": X, "label": L},
                              fetch_list=[loss])
            param_bytes = opt_bytes = 0
            for v in main.list_vars():
                if not v.persistable:
                    continue
                arr = scope.get(v.name)
                shards = getattr(arr, "addressable_shards", None)
                if not shards:
                    continue
                per_dev = {}
                for s in shards:
                    per_dev[s.device] = (per_dev.get(s.device, 0)
                                         + s.data.nbytes)
                nbytes = max(per_dev.values())
                if getattr(v, "_optimizer_state_of", None):
                    opt_bytes += nbytes
                else:
                    param_bytes += nbytes
            stats = profiler.get_int_stats()
            spmd_coll = sum(v for k, v in stats.items()
                            if k.startswith("collective_bytes_spmd_"))
            from paddle_tpu.parallel import quant_collectives as qc

            # static predicted wire bytes (ISSUE 18): comm_report on
            # the same program/mesh vs the measured counter delta (the
            # spmd counters book once per compile, not per step) —
            # err_pct drift is gated by tools/bench_diff.py
            measured = sum(
                v - pre.get(k, 0) for k, v in stats.items()
                if k.startswith("collective_bytes_spmd_"))
            from paddle_tpu.analysis import comm_report
            rep = comm_report(main, axes, batch_rows=32,
                              feed=["x", "label"])
            predicted = int(rep["predicted_total"])
            err_pct = (abs(predicted - measured) / measured * 100.0
                       if measured > 0 else 0.0)

            return {
                "mesh_axes": axes,
                "devices": n_dev,
                "params_bytes_per_device": int(param_bytes),
                "optimizer_bytes_per_device": int(opt_bytes),
                "specs_applied": stats.get("spmd_specs_applied", 0),
                "spmd_collective_bytes": int(spmd_coll),
                # flag stamp: tools/bench_diff.py treats a stamp flip as
                # a deliberate collective_bytes baseline reset
                "quant_collectives": qc.mode(),
                "predicted_collective_bytes": predicted,
                "prediction": {
                    "predicted_total": predicted,
                    "measured_total": int(measured),
                    "err_pct": round(err_pct, 2),
                },
                "loss": float(np.asarray(out[0]).reshape(-1)[0]),
            }
    finally:
        # the bench process keeps running other sections — don't leak
        # the mesh context into them
        mesh_lib.set_current_mesh(None)


def _collective_fns(jax, mesh):
    """The two jitted all-reduces `--mode collective` times on `mesh`'s
    "data" axis: full-width psum and the int8 blockwise path."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import quant_collectives as qc

    def over_data(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False))

    return (over_data(lambda s: jax.lax.psum(s, "data")),
            over_data(lambda s: qc.quant_allreduce_sum(s, "data")))


def bench_collective(jax, jnp):
    """`--mode collective` (docs/spmd.md): ring all-reduce bytes/ms at
    a ladder of tensor sizes, full-width fp32 vs the int8 blockwise
    path, on a 1-axis mesh over every local device.  Emits
    `detail.collective` rows (bytes_on_wire, quant_overhead_ms,
    effective_GBps) for tools/bench_diff.py to gate later.  Wire bytes
    use the same wire-true convention as the opprof counters: a ring
    all-reduce moves ~2x its payload; the quantized path moves its
    all_to_all + all_gather payloads (int8 codes + fp32 scales)."""
    import time as _time

    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.parallel import quant_collectives as qc

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("data",))
    full, int8 = _collective_fns(jax, mesh)

    def _timed(fn, x, iters=5):
        out = fn(x)
        jax.block_until_ready(out)  # compile outside the clock
        t0 = _time.perf_counter()
        for _ in range(iters):
            out = fn(x)
        jax.block_until_ready(out)
        return (_time.perf_counter() - t0) * 1e3 / iters

    rows = []
    for elems in (1 << 14, 1 << 16, 1 << 18, 1 << 20):
        rng = np.random.RandomState(7)
        x = rng.randn(n, elems // n).astype("float32")

        full_ms = _timed(full, x)
        int8_ms = _timed(int8, x)
        payload = (elems // n) * 4  # per-device logical payload bytes
        wire_full = 2 * payload
        wire_int8 = 2 * qc.wire_bytes(x[0], axis_size=n)
        rows.append({
            "elems_per_device": elems // n,
            "size_bytes": payload,
            "bytes_on_wire_full": int(wire_full),
            "bytes_on_wire_int8": int(wire_int8),
            "full_ms": round(full_ms, 4),
            "int8_ms": round(int8_ms, 4),
            "quant_overhead_ms": round(int8_ms - full_ms, 4),
            "effective_GBps_full": round(
                wire_full / max(full_ms, 1e-6) / 1e6, 3),
            "effective_GBps_int8": round(
                wire_int8 / max(int8_ms, 1e-6) / 1e6, 3),
        })
    top = rows[-1]
    return {
        "devices": n,
        "mode": qc.mode(),
        "block": qc.BLOCK,
        "sizes": rows,
        "headline_GBps": top["effective_GBps_full"],
        "wire_reduction_x": round(top["bytes_on_wire_full"]
                                  / max(1, top["bytes_on_wire_int8"]), 2),
    }


def resnet50_fwd_flops(batch, hw, classes):
    """Analytic fallback: ResNet-50 v1 forward ~4.1 GMACs at 224^2
    (scales with spatial area), 2 flops/MAC, + the fc head."""
    base = 4.1e9 * 2.0 * (hw / 224.0) ** 2
    return batch * (base + 2 * 2048 * classes)


def _resnet_layout_detail():
    """`detail.layout` (ISSUE 5 satellite): what the graph-transform
    pipeline does to the ResNet-50 Program — layout chosen, interior
    activation transposes left in the lowered trunk, and the pipeline's
    wall time.  Measured on a toy-width program OUTSIDE the timed
    region (shape-only jaxpr trace, no device work)."""
    import time as _time

    import paddle_tpu.fluid as pfluid
    from paddle_tpu import transforms
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import resnet as presnet
    from paddle_tpu.transforms import debug as tdebug

    with framework.program_guard(pfluid.Program(), pfluid.Program()), \
            unique_name.guard():
        main, _startup, _feeds, fetches = presnet.build_train_program(
            depth=50, class_num=10, image_shape=(3, 32, 32),
            batch_size=2, width=4)
    infer = main.clone(for_test=True)
    t0 = _time.perf_counter()
    tprog, stats = transforms.apply_transforms(
        infer, feed_names=["image", "label"],
        fetch_names=[fetches[0].name],
        passes=["layout_optimize", "dead_op_elim"])
    transform_ms = (_time.perf_counter() - t0) * 1e3
    rep = tdebug.layout_report(
        tprog, {"image": ((2, 3, 32, 32), "float32"),
                "label": ((2, 1), "int64")},
        [fetches[0].name], transform_stats=stats)
    rep["transform_ms"] = round(transform_ms, 2)
    return rep


def _resnet_op_profile_detail():
    """`detail.op_profile` (ISSUE 7 tentpole): per-op cost attribution
    for the TRANSFORMED (NHWC + fold_bn) ResNet-50 Program — compile a
    toy-width clone through the Executor (one real compile-cache miss,
    so obs walks the AOT HLO) and report attribution coverage plus the
    top ops by FLOPs and by transpose count.  This is the acceptance
    measurement: >=95% of cost_analysis FLOPs must resolve to named
    Program ops, and the table names which op still relayouts after
    NHWC.  Outside the timed region."""
    import paddle_tpu
    import paddle_tpu.fluid as pfluid
    from paddle_tpu import obs
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import resnet as presnet
    from paddle_tpu.obs import opprof

    with framework.program_guard(pfluid.Program(), pfluid.Program()), \
            unique_name.guard():
        main, startup, _feeds, fetches = presnet.build_train_program(
            depth=50, class_num=10, image_shape=(3, 32, 32),
            batch_size=2, width=4)
    infer = main.clone(for_test=True)
    old = paddle_tpu.get_flags(["FLAGS_graph_transforms"])[
        "FLAGS_graph_transforms"]
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on,fold_bn=on"})
    try:
        scope = pfluid.executor.Scope()
        with pfluid.executor.scope_guard(scope):
            exe = pfluid.Executor()
            exe.run(startup)
            exe.run(infer,
                    feed={"image": np.zeros((2, 3, 32, 32),
                                            np.float32),
                          "label": np.zeros((2, 1), np.int64)},
                    fetch_list=[fetches[0].name])
    finally:
        paddle_tpu.set_flags({"FLAGS_graph_transforms": old})
    prof = obs.op_profile(infer)
    if prof is None:
        return {"error": "no profile captured (PADDLE_OBS_OPPROF "
                         "or PADDLE_OBS_COST off?)"}
    passes = sorted({p for r in prof["rows"]
                     for p in (r.get("source") or {}).get("passes",
                                                          ())})
    return {
        "attributed_flops_pct": round(prof["attributed_flops_pct"],
                                      2),
        "total_flops": prof["total_flops"],
        "total_flops_raw": prof["total_flops_raw"],
        "instruction_count": prof["instruction_count"],
        # HLO-level relayout instructions (transpose + layout
        # copies, incl. weight relayouts) — NOT the jaxpr-level
        # activation count in detail.layout.interior_transposes
        "hlo_relayouts": prof["transposes"],
        "passes_seen": passes,
        "top_flops": [{"op": r["op"],
                       "flops_pct": round(r["flops_pct"], 2)}
                      for r in opprof.top_ops(prof, 8, "flops")],
        "top_transposes": [{"op": r["op"],
                            "transposes": r["transposes"]}
                           for r in opprof.top_ops(prof, 5,
                                                   "transposes")
                           if r["transposes"]],
    }


def _device_profile_detail():
    """`detail.device_profile` (ISSUE 12 tentpole): MEASURED device
    time for the transformed toy ResNet-50 — compile through the
    Executor outside the capture window, then profile two dispatches
    under `obs.profile_window` and report the measured/attributed split
    plus the top ops by measured time with their roofline verdicts.
    This is the measured counterpart of `detail.op_profile` (analytic
    FLOPs): the two tables disagreeing is the signal the roofline
    exists to surface.  Outside the timed region."""
    import paddle_tpu
    import paddle_tpu.fluid as pfluid
    from paddle_tpu import obs
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import resnet as presnet

    with framework.program_guard(pfluid.Program(), pfluid.Program()), \
            unique_name.guard():
        main, startup, _feeds, fetches = presnet.build_train_program(
            depth=50, class_num=10, image_shape=(3, 32, 32),
            batch_size=2, width=4)
    infer = main.clone(for_test=True)
    feed = {"image": np.zeros((2, 3, 32, 32), np.float32),
            "label": np.zeros((2, 1), np.int64)}
    old = paddle_tpu.get_flags(["FLAGS_graph_transforms"])[
        "FLAGS_graph_transforms"]
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on,fold_bn=on"})
    try:
        scope = pfluid.executor.Scope()
        with pfluid.executor.scope_guard(scope):
            exe = pfluid.Executor()
            exe.run(startup)
            # compile (cache miss) OUTSIDE the window so the capture
            # holds steady-state dispatches only
            exe.run(infer, feed=feed, fetch_list=[fetches[0].name])
            with obs.profile_window(label="bench.device_profile"):
                for _ in range(2):
                    exe.run(infer, feed=feed,
                            fetch_list=[fetches[0].name])
    finally:
        paddle_tpu.set_flags({"FLAGS_graph_transforms": old})
    from paddle_tpu.obs import devprof

    res = devprof.last_result()
    if res is None:
        return {"error": "no devprof window captured"}
    if res.get("error"):
        return {"error": res["error"]}
    roof = res.get("roofline") or {}
    rows = [r for r in roof.get("ops", [])
            if r["op"] != devprof.UNATTRIBUTED][:8]
    unattr = next((r for r in roof.get("ops", [])
                   if r["op"] == devprof.UNATTRIBUTED), None)
    return {
        "capture_ms": round(res["capture_ms"], 2),
        "device_class": res["device_class"],
        "runs": res["runs"],
        "events": res["events"],
        "measured_ms": round(res["measured_ms"], 3),
        "attributed_pct": round(res["attributed_pct"], 2),
        "unattributed_ms": round(unattr["time_ms"], 3) if unattr
        else 0.0,
        "top_time": [{"op": r["op"],
                      "share_pct": round(r["share_pct"], 2),
                      "bound": r["bound"]} for r in rows],
    }


def bench_resnet50(jax, jnp, peak_flops, batch=128):
    """ResNet-50 train-step throughput, images/sec/chip (BASELINE.md
    row 1; reference anchor: the book image-classification fixture
    family, /root/reference/python/paddle/fluid/tests/book/
    test_image_classification.py:1).  One fused XLA step: fwd + bwd +
    momentum SGD, bf16 activations, fp32 master weights, BN batch
    stats in train mode.  vs_baseline is the achieved MFU over the
    45% north star — same basis as the BERT line (the reference tree
    publishes no ResNet number; BASELINE.json row 1 is 'to be
    measured on our build')."""
    import numpy as np

    from paddle_tpu.jit import functional_call, functional_state
    from paddle_tpu.vision import models as vmodels

    hw, classes = 224, 1000
    steps, reps = 10, 3

    model = vmodels.resnet50(num_classes=classes)
    model.train()

    def is_buf(k):
        return k.endswith("._mean") or k.endswith("._variance")

    params = {k: jnp.array(v)
              for k, v in functional_state(model).items()}
    vel = {k: jnp.zeros_like(v) for k, v in params.items()
           if not is_buf(k)}

    def loss_fn(p, x, y):
        cast = {k: (v.astype(jnp.bfloat16)
                    if v.dtype == jnp.float32 and not is_buf(k)
                    else v)
                for k, v in p.items()}
        logits, new_state = functional_call(model, cast, x)
        ll = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.take_along_axis(ll, y[:, None], axis=1).mean()
        bufs = {k: v.astype(jnp.float32)
                for k, v in new_state.items() if is_buf(k)}
        return loss, bufs

    momentum = 0.9

    def step(state, x, y, lr):
        p = state["params"]
        (loss, bufs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, x, y)
        # same structure fix as the BERT step: keep dW convs out of the
        # f32 optimizer elementwise fusions
        grads = jax.lax.optimization_barrier(grads)
        new_vel = {k: momentum * state["vel"][k] + grads[k]
                   for k in state["vel"]}
        new_p = {k: (bufs[k] if k in bufs else
                     (v - lr * new_vel[k] if k in new_vel else v))
                 for k, v in p.items()}
        return {"params": new_p, "vel": new_vel}, loss

    step = jax.jit(step, donate_argnums=0)
    rng = np.random.RandomState(0)
    t_feed = time.perf_counter()
    x = jnp.asarray(rng.randn(batch, 3, hw, hw).astype("float32"),
                    jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, classes, batch).astype("int32"))
    host_feed_ms = (time.perf_counter() - t_feed) * 1e3
    lr = jnp.float32(0.1)
    state = {"params": params, "vel": vel}

    # MFU numerator from XLA cost_analysis (ISSUE 6): AOT-compile the
    # step ONCE and read FLOPs off the executable — the compiled
    # callable replaces the jit path, so this is the same single
    # compile the first step would have paid.  Analytic count stays
    # as the fallback when the backend reports no cost model.
    from paddle_tpu.obs import cost as obs_cost

    flops = 3 * resnet50_fwd_flops(batch, hw, classes)
    flops_source = "analytic"
    compiled, pc = obs_cost.compile_with_cost(
        step, (state, x, y, lr), "bench.resnet50_step")
    if compiled is not None:
        step = compiled
    if pc is not None and pc.flops > 0:
        flops = pc.flops
        flops_source = "xla_cost_analysis"

    holder = {"state": state}

    def run_once():
        if pc is not None:
            pc.observe_dispatch()  # feeds the live mfu_pct gauge
        holder["state"], loss = step(holder["state"], x, y, lr)
        return loss

    best, final_loss, pipe = _time_step(run_once, steps, reps)
    images_sec = batch / best
    mfu = flops / best / peak_flops * 100.0
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(mfu / 45.0, 4),
        "detail": {"batch": batch, "image_hw": hw,
                   "device_class": "tpu",
                   "step_ms": round(best * 1e3, 2),
                   "mfu_pct": round(mfu, 2),
                   "flops_per_step": float(flops),
                   "flops_source": flops_source,
                   "host_feed_ms": round(host_feed_ms, 3),
                   **pipe,
                   "layout": _resnet_layout_detail(),
                   "op_profile": _resnet_op_profile_detail(),
                   "device_profile": _device_profile_detail(),
                   "memory": _memory_detail(),
                   "loss": final_loss},
    }


SERVING_TARGET_P99_MS = 50.0  # north-star interactive-serving budget


def _decode_detail(jax, jnp):
    """Autoregressive fast-decode scenario (ISSUE 20 satellite): a toy
    LM through the AutoregressiveEngine — decode-step latency at
    steady state, chunked-prefill chunk time, time-to-first-token for
    a long prompt admitted mid-decode-flood, and the lazy-growth
    pages-per-sequence footprint.  `decode_token_ms` is gated by
    bench_diff (rise > 10% fails on-chip)."""
    from paddle_tpu import profiler, serving
    from paddle_tpu.serving import metrics as smetrics

    V, D = 64, 16
    rng = np.random.RandomState(7)
    emb = jnp.asarray(rng.randn(V, D).astype(np.float32))
    w = jnp.asarray(rng.randn(D, V).astype(np.float32))

    def qkv_fn(tokens, positions):
        x = emb[tokens]
        q = x[:, :, None, :]
        return q, q, q

    def out_fn(attn):
        return attn[:, :, 0, :] @ w

    eng = serving.AutoregressiveEngine(
        qkv_fn, out_fn, num_heads=1, head_dim=D, num_pages=256,
        page_size=4, max_slots=4, max_pages_per_seq=32,
        prompt_buckets=(8, 16), prefill_chunk=8)
    try:
        # warm the prefill/chunk/decode compile caches so the timed
        # window measures dispatch, not tracing — max_new_tokens must
        # match the flood's budget: the out_tokens ring is sized to
        # the largest live budget and resizing retraces _decode_fn
        eng.generate(np.arange(40) % V, max_new_tokens=8)
        eng.generate(np.arange(5) % V, max_new_tokens=96)
        smetrics.reset_latency("serving_prefill_chunk_ms")
        smetrics.reset_latency("serving_ttft_ms")

        # decode flood: fill every other slot with long generations
        flood = [eng.submit(rng.randint(0, V, size=5).astype(np.int32),
                            max_new_tokens=96) for _ in range(3)]
        for _ in range(8):   # admit + prefill: all slots decoding
            eng.step()
        step_ms = []
        for _ in range(32):  # steady state: one token per step
            t0 = time.perf_counter()
            eng.step()
            step_ms.append((time.perf_counter() - t0) * 1e3)

        # long prompt admitted mid-flood: chunked prefill interleaves
        # with the decode batch instead of head-of-line blocking it
        long_req = eng.submit(
            rng.randint(0, V, size=40).astype(np.int32),
            max_new_tokens=8)
        pages_per_seq = []
        while not long_req.done():
            eng.step()
            seqs = eng.kv.table.seqs
            if seqs:
                pages_per_seq.append(eng.kv.table.in_use / seqs)
        eng.run_until_idle()
        long_req.result(timeout=60)
        for r in flood:
            r.result(timeout=60)

        step_ms.sort()

        def pct(p):
            i = min(len(step_ms) - 1,
                    int(round(p / 100.0 * (len(step_ms) - 1))))
            return step_ms[i]

        chunk = smetrics.latency_stats("serving_prefill_chunk_ms") or {}
        ttft = smetrics.latency_stats("serving_ttft_ms") or {}
        stats = profiler.get_int_stats()
        return {
            "decode_token_ms": round(pct(50.0), 3),
            "decode_token_p99_ms": round(pct(99.0), 3),
            "prefill_chunk_ms": round(chunk.get("mean_ms", 0.0), 3),
            "prefill_chunks": stats.get("serving_prefill_chunks", 0),
            "ttft_long_prompt_ms": round(ttft.get("max_ms", 0.0), 3),
            "kv_pages_per_seq": round(
                sum(pages_per_seq) / len(pages_per_seq), 2)
            if pages_per_seq else 0.0,
            "ragged_fallbacks": stats.get(
                "serving_ragged_fallback_total", 0),
        }
    finally:
        eng.shutdown(drain=False)


def bench_serving(jax, jnp):
    """Continuous-batching serving scenario (ISSUE 2 satellite): mixed
    batch-size requests from concurrent clients through the
    paddle_tpu.serving Engine; emits p50/p99 request latency and batch
    occupancy in the BENCH JSON detail."""
    import threading

    from paddle_tpu import profiler
    from paddle_tpu import serving
    from paddle_tpu.serving import metrics as smetrics

    d_in, d_h = 1024, 4096
    rng = np.random.RandomState(0)
    w1 = jnp.asarray(rng.randn(d_in, d_h).astype(np.float32)
                     / np.sqrt(d_in))
    w2 = jnp.asarray(rng.randn(d_h, d_in).astype(np.float32)
                     / np.sqrt(d_h))

    def model(x):
        return jnp.tanh(x @ w1) @ w2

    cfg = serving.EngineConfig(max_batch_size=16, max_queue_delay_ms=1.0,
                               max_queue=512, max_in_flight=2)
    clients, per_client = 4, 64
    eng = serving.Engine(model, cfg)
    try:
        # warm every bucket so the timed window measures dispatch, not
        # compilation (compiles are counted separately in the detail)
        for b in cfg.buckets:
            eng.infer([np.zeros((b, d_in), np.float32)], timeout=120)
        smetrics.reset_latency("serving_request_ms")
        smetrics.reset_occupancy()
        s0 = profiler.get_int_stats()

        def client(seed):
            r = np.random.RandomState(seed)
            for _ in range(per_client):
                rows = int(r.randint(1, cfg.max_batch_size + 1))
                x = r.randn(rows, d_in).astype(np.float32)
                eng.infer([x], timeout=120)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lat = smetrics.latency_stats("serving_request_ms") or {}
        s1 = profiler.get_int_stats()

        def delta(name):
            return s1.get(name, 0) - s0.get(name, 0)

        batches = max(1, delta("serving_batches_total"))
        n_req = clients * per_client
        p99 = lat.get("p99_ms", 0.0)
        detail = {
            "backend": "tpu",
            "device_class": "tpu",
            "obs": _obs_detail(),
            "clients": clients,
            "requests": n_req,
            "throughput_rps": round(n_req / wall, 1),
            "p50_ms": round(lat.get("p50_ms", 0.0), 3),
            "p99_ms": round(p99, 3),
            "mean_ms": round(lat.get("mean_ms", 0.0), 3),
            "batches": batches,
            "occupancy_mean": round(
                delta("serving_batch_requests_total") / batches, 2),
            "occupancy_max": s1.get("serving_batch_occupancy_max", 0),
            "pad_rows": delta("serving_pad_rows_total"),
            "rejected": delta("serving_rejected_total"),
            "trace_count": eng.model.runner.trace_count,
            "buckets": list(cfg.buckets),
            "feature_dim": d_in,
            "decode": _decode_detail(jax, jnp),
        }
        return {
            "metric": "serving_p99_latency_ms",
            "value": round(p99, 3),
            "unit": "ms",
            # latency: lower is better, so the ratio inverts
            "vs_baseline": round(SERVING_TARGET_P99_MS / p99, 4)
            if p99 else 0.0,
            "detail": detail,
        }
    finally:
        eng.shutdown(drain=False)


# `--mode fleet` cold-start worker: one fresh process compiling (or
# AOT-loading) a small two-layer program through the executor seam.
# Run three ways — aot_cache absent (off), cold (empty dir), warm
# (populated dir) — the compile_ms deltas ARE the cold-start story.
_FLEET_WORKER = r"""
import json, sys
import jax
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu import profiler
from paddle_tpu.fluid import framework

d = int(sys.argv[1])
main, startup = framework.Program(), framework.Program()
with framework.program_guard(main, startup):
    x = fluid.data("x", [-1, d], "float32")
    h = fluid.layers.fc(x, size=d, act="tanh")
    y = fluid.layers.fc(h, size=d)
exe = fluid.Executor(fluid.TPUPlace(0))  # raises without a chip
exe.run(startup)
(out,) = exe.run(main, feed={"x": np.ones((4, d), np.float32)},
                 fetch_list=[y])
t = profiler.get_time_stats()
s = profiler.get_int_stats()
print(json.dumps({
    "checksum": round(float(np.asarray(out).sum()), 6),
    "compile_ms": round(t.get("compile_ms", 0.0), 3),
    "aot_cache_load_ms": round(t.get("aot_cache_load_ms", 0.0), 3),
    "aot_cache_hits": s.get("aot_cache_hits", 0),
    "aot_cache_misses": s.get("aot_cache_misses", 0),
    "aot_cache_stores": s.get("aot_cache_stores", 0),
}))
"""


def _fleet_cold_start(d: int) -> dict:
    """The cold-start ladder: absent / cold / warm aot_cache, one
    fresh process each (the persistent cache only matters ACROSS
    processes; in-process the CompileCache already de-dups).  Each
    worker needs the chip, and a chip belongs to one process: main()
    runs this BEFORE the bench process touches JAX.  A worker that
    fails fails the run."""
    import tempfile

    root = os.path.dirname(os.path.abspath(__file__))
    ladder = {}
    with tempfile.TemporaryDirectory(prefix="bench_aot_") as tmp:
        for name, extra in (
                ("absent", {"PADDLE_AOT_CACHE": "off"}),
                ("cold", {"PADDLE_AOT_CACHE": "on",
                          "PADDLE_AOT_CACHE_DIR": tmp}),
                ("warm", {"PADDLE_AOT_CACHE": "on",
                          "PADDLE_AOT_CACHE_DIR": tmp})):
            env = dict(os.environ)
            env.update(extra)
            proc = subprocess.run(
                [sys.executable, "-c", _FLEET_WORKER, str(d)],
                capture_output=True, text=True, env=env, cwd=root,
                timeout=600)
            if proc.returncode != 0:
                sys.exit(f"bench: fleet cold-start worker {name!r} "
                         f"failed:\n{proc.stderr[-2000:]}")
            ladder[name] = json.loads(
                proc.stdout.strip().splitlines()[-1])
    warm, cold = ladder["warm"], ladder["cold"]
    # the number bench_diff gates: first-dispatch latency of a fresh
    # process WITH a warm persistent cache
    ladder["cold_start_compile_ms"] = warm["compile_ms"]
    if warm["compile_ms"] and cold["compile_ms"]:
        ladder["warm_vs_cold"] = round(
            warm["compile_ms"] / cold["compile_ms"], 4)
    return ladder


FLEET_D_IN, FLEET_D_H = 1024, 4096


def bench_fleet(jax, jnp, cold_start):
    """`--mode fleet` (multi-tenant fleet + persistent AOT cache):

    1. cold-start ladder — three fresh processes (aot_cache absent /
       cold / warm) report first-dispatch compile_ms + aot_cache
       hit/miss/load stats (`cold_start`, from `_fleet_cold_start`);
    2. co-tenancy — three named models behind one ModelRegistry under
       concurrent per-tenant load; per-tenant p50/p99 + rejection and
       occupancy series in the detail.
    """
    import threading

    from paddle_tpu import profiler, serving
    from paddle_tpu.serving import metrics as smetrics

    d_in, d_h = FLEET_D_IN, FLEET_D_H

    rng = np.random.RandomState(0)
    w1 = jnp.asarray(rng.randn(d_in, d_h).astype(np.float32)
                     / np.sqrt(d_in))
    w2 = jnp.asarray(rng.randn(d_h, d_in).astype(np.float32)
                     / np.sqrt(d_h))

    models = {
        "ranker": lambda x: [jnp.tanh(x @ w1) @ w2],
        "embedder": lambda x: [jnp.tanh(x @ w1)],
        "scorer": lambda x: [(x @ w1).max(axis=-1, keepdims=True)],
    }
    cfg = serving.EngineConfig(max_batch_size=16,
                               max_queue_delay_ms=1.0, max_queue=512,
                               max_in_flight=2)
    clients_per_tenant, per_client = 2, 24
    reg = serving.ModelRegistry(cfg)
    try:
        for i, (name, fn) in enumerate(models.items()):
            reg.register(name, fn, quota=256, priority=float(i))
            # warm every bucket off the timed window
            for b in cfg.buckets:
                reg.infer(name, [np.zeros((b, d_in), np.float32)],
                          timeout=300)
        for name in models:
            smetrics.reset_latency(
                smetrics.tenant_stat(name, "request_ms"))
        s0 = profiler.get_int_stats()

        def client(name, seed):
            r = np.random.RandomState(seed)
            for _ in range(per_client):
                rows = int(r.randint(1, cfg.max_batch_size + 1))
                x = r.randn(rows, d_in).astype(np.float32)
                reg.infer(name, [x], timeout=300)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(name, 31 * i + j))
            for i, name in enumerate(models)
            for j in range(clients_per_tenant)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        s1 = profiler.get_int_stats()

        tenants = {}
        worst_p99 = 0.0
        for name in models:
            lat = smetrics.latency_stats(
                smetrics.tenant_stat(name, "request_ms")) or {}
            p99 = lat.get("p99_ms", 0.0)
            worst_p99 = max(worst_p99, p99)

            def delta(stat):
                return s1.get(stat, 0) - s0.get(stat, 0)

            tenants[name] = {
                "p50_ms": round(lat.get("p50_ms", 0.0), 3),
                "p99_ms": round(p99, 3),
                "mean_ms": round(lat.get("mean_ms", 0.0), 3),
                "completed": delta(
                    smetrics.tenant_stat(name, "completed_total")),
                "rejected": delta(
                    smetrics.tenant_stat(name, "rejected_total")),
            }
        n_req = len(models) * clients_per_tenant * per_client
        detail = {
            "backend": "tpu",
            "device_class": "tpu",
            "fleet": {
                "cold_start": cold_start,
                "tenants": tenants,
                "models": len(models),
                "requests": n_req,
                "throughput_rps": round(n_req / wall, 1),
            },
        }
        return {
            "metric": "fleet_p99_latency_ms",
            "value": round(worst_p99, 3),
            "unit": "ms",
            "vs_baseline": round(SERVING_TARGET_P99_MS / worst_p99, 4)
            if worst_p99 else 0.0,
            "detail": detail,
        }
    finally:
        reg.close(drain=False)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["bert", "resnet50", "both"],
                    default="both")
    ap.add_argument("--mode",
                    choices=["train", "serving", "collective", "fleet"],
                    default="train",
                    help="train: MFU bench (default); serving: "
                    "continuous-batching latency/occupancy bench; "
                    "collective: ring all-reduce microbench, full-width "
                    "vs int8 blockwise (docs/spmd.md); fleet: "
                    "multi-tenant co-tenancy latency + persistent "
                    "AOT-cache cold-start ladder (docs/serving.md)")
    args = ap.parse_args()

    # a chip belongs to one process: the fleet ladder's worker
    # processes each need it, so they run before this one touches JAX
    cold_start = _fleet_cold_start(FLEET_D_IN) \
        if args.mode == "fleet" else None
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid.compile_cache import enable_persistent_cache
    from paddle_tpu.obs.cost import require_chip

    try:
        device, peaks = require_chip()
    except RuntimeError as e:
        sys.exit(f"bench: {e}; bench.py measures on a TPU only and "
                 "prints no metric without one")
    enable_persistent_cache()

    def emit(result):
        result["device"] = {"platform": device.platform,
                            "kind": device.device_kind,
                            "count": len(jax.devices())}
        print(json.dumps(result))

    if args.mode == "serving":
        return emit(bench_serving(jax, jnp))

    if args.mode == "fleet":
        return emit(bench_fleet(jax, jnp, cold_start))

    if args.mode == "collective":
        det = bench_collective(jax, jnp)
        return emit({
            "metric": "collective_allreduce_effective_GBps",
            "value": det["headline_GBps"],
            "unit": "GB/s",
            "detail": {"device_class": "tpu", "collective": det}})

    from paddle_tpu.models import bert

    if args.model == "resnet50":
        # standalone ResNet line (`python bench.py --model resnet50`)
        out = bench_resnet50(jax, jnp, peaks["flops"])
        out["detail"]["feed_pipeline"] = bench_feed_pipeline(jax, jnp)
        out["detail"]["ckpt"] = bench_checkpoint(jax, jnp)
        out["detail"]["obs"] = _obs_detail()
        out["detail"]["telemetry"] = bench_telemetry()
        return emit(out)
    # full production config: attention dropout 0.1 AND a variable-length
    # padding mask — both run inside the Pallas kernel, so real BERT
    # inputs stay on the fast path
    cfg = bert.BertConfig.base()
    batch, seq, n_masked = 32, 512, 76
    steps, reps = 10, 3

    flash_note = _kernel_preflight(jax, jnp)

    model = bert.BertForPretraining(cfg)

    # the timed unit is K=5 chained train steps compiled as one program
    # (lax.scan over the step — the standard JAX train-loop shape),
    # which amortizes host dispatch over K steps; dispatch_ms below is
    # what the un-scanned Executor loop has to hide instead (ROADMAP
    # Speed 7).  Loss/trajectory stay real: state threads through the
    # scan carry.
    steps_per_call = 5

    step, state = bert.build_pretrain_step(model, bf16=True)
    t_feed = time.perf_counter()
    b = jax.device_put(bert.fake_batch(cfg, batch, seq,
                                       num_masked=n_masked))
    host_feed_ms = (time.perf_counter() - t_feed) * 1e3
    lr = jnp.float32(1e-4)

    fn = step.__wrapped__ if hasattr(step, "__wrapped__") else step

    @functools.partial(jax.jit, donate_argnums=0)
    def run_step(s, b, lr):
        def body(carry, _):
            s2, loss = fn(carry, b, lr)
            return s2, loss

        s, losses = jax.lax.scan(body, s, None, length=steps_per_call)
        return s, losses[-1]

    # AOT-compile the timed unit once and read its XLA cost_analysis
    # (ISSUE 6): the executable replaces the jit call, so the MFU
    # numerator comes from the compiler's own FLOP count — not the
    # hand-maintained bert_step_flops formula — at no extra compile
    from paddle_tpu.obs import cost as obs_cost

    compiled, pc = obs_cost.compile_with_cost(
        run_step, (state, b, lr), "bench.bert_step")
    if compiled is not None:
        run_step = compiled
    holder = {"state": state}

    def run_once():
        if pc is not None:
            pc.observe_dispatch()  # feeds the live mfu_pct gauge
        holder["state"], loss = run_step(holder["state"], b, lr)
        return loss

    dt, final_loss, pipe = _time_step(run_once, steps, reps)
    # normalize the pipeline numbers to per-MODEL-step like dt: one
    # run_once dispatch carries `steps_per_call` scanned steps, and the
    # timed loop keeps steps*steps_per_call of them in flight per sync
    dt /= steps_per_call
    pipe["dispatch_ms"] = round(pipe["dispatch_ms"] / steps_per_call, 4)
    pipe["sync_ms"] = round(pipe["sync_ms"] / steps_per_call, 4)
    pipe["prefetch_depth"] = steps * steps_per_call
    pipe["host_feed_ms"] = round(host_feed_ms, 3)

    # pc covers one run_step call = steps_per_call model steps
    flops_measured = pc.flops / steps_per_call \
        if pc is not None and pc.flops > 0 else None
    flops = flops_measured or bert_step_flops(cfg, batch, seq, n_masked)
    mfu = flops / dt / peaks["flops"] * 100.0
    tokens_per_sec = batch * seq / dt

    if not _flash_really_active():
        raise RuntimeError("bench: the timed step did not run the "
                           "flash kernels (flash_fallback_total > 0 or "
                           "no kernel instance was committed)")
    detail = {"backend": device.platform, "batch": batch, "seq": seq,
              "device_class": "tpu",
              "flops_per_step": float(flops),
              "flops_source": ("xla_cost_analysis" if flops_measured
                               else "analytic"),
              "step_ms": round(dt * 1e3, 2),
              "tokens_per_sec": round(tokens_per_sec, 1),
              "flash_attention": True,
              "flash_note": flash_note,
              **pipe,
              "loss": final_loss}
    # everything below runs AFTER the timed region, so it cannot
    # perturb the primary metric.  Pod-scale input-pipeline fields
    # (ISSUE 4): ring occupancy, shard skew, per-host feed time + stall
    # attribution
    detail["feed_pipeline"] = bench_feed_pipeline(jax, jnp)
    # checkpoint-overlap numbers (ISSUE 8)
    detail["ckpt"] = bench_checkpoint(jax, jnp)
    detail["obs"] = _obs_detail()
    # live-telemetry sampler cost (ISSUE 10) over the real in-process
    # sources, gated by bench_diff
    detail["telemetry"] = bench_telemetry()
    # numeric-stats collection cost (ISSUE 15): on-vs-off overhead of
    # the instrumented lowering + the health gauges the run produced;
    # bench_diff gates numerics_overhead_pct on this
    detail["numerics"] = bench_numerics(jax, jnp)
    # measured device time + roofline (ISSUE 12): jax.profiler.trace
    # around the toy ResNet dispatches
    detail["device_profile"] = _device_profile_detail()
    # SPMD sharding layout numbers (ISSUE 13); bench_diff gates
    # optimizer_bytes_per_device on these
    detail["sharding"] = bench_sharding(jax, jnp)
    # HBM ledger + peak (ISSUE 14): read AFTER every sub-bench so the
    # peak covers the whole session; bench_diff gates hbm_peak_bytes
    detail["memory"] = _memory_detail()
    result = {
        "metric": "bert_base_pretrain_mfu",
        "value": round(mfu, 2),
        "unit": "%",
        "vs_baseline": round(mfu / 45.0, 4),
        "detail": detail,
    }
    if args.model == "both":
        # second metric: rides in detail so the one-JSON-line contract
        # holds
        result["detail"]["resnet50"] = bench_resnet50(
            jax, jnp, peaks["flops"])
    emit(result)


if __name__ == "__main__":
    sys.exit(main())
