#!/usr/bin/env python
"""Compile the bench BERT step for v5e without a chip, and analyse it.

libtpu describes a TPU topology and compiles for it on any host
(`jax.experimental.topologies.get_topology_desc`), so the EXACT bench
computation (BERT-base, batch 32, seq 512, bf16, fused fwd+bwd+AdamW)
can be compiled FOR v5e in a CPU sandbox and interrogated: XLA's cost
model (flops, bytes accessed), executable memory stats, and the
optimized-HLO structure.  Output: artifacts/aot_v5e_analysis*.json plus
a roofline summary against the v5e peaks of paddle_tpu/obs/cost.py.
Nothing here is a measurement: the times in the output are the
compiler's bounds, not the chip's.

Without --flash the default backend is the CPU, so attention appears
as plain XLA ops.  With --flash the Pallas kernels' compile probes are
pointed at the topology (`compile_target`): Mosaic accepts or
refuses every kernel instance and head-block rung exactly as on the
chip, the accepted rung compiles into the executable, and the tool
fails if attention gave way to XLA anyway.

Usage: JAX_PLATFORMS=cpu python tools/aot_analysis.py
           [--tiny] [--remat] [--flash] [--rbg] [--batch N]
(libtpu is single-tenant on a host: while another process holds it,
e.g. a running pytest, set ALLOW_MULTIPLE_LIBTPU_LOAD=1.)
"""

import collections
import contextlib
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "artifacts")
sys.path.insert(0, REPO)  # run from anywhere


def main():
    import jax
    import numpy as np

    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.obs.cost import DEVICE_PEAKS
    from paddle_tpu.ops.pallas import _common as pallas_common

    rbg = "--rbg" in sys.argv
    if rbg:
        # TPU-native RNG: threefry spends ~1.7k scalar bit-op HLOs per
        # step generating dropout masks; rbg lowers to the hardware
        # RngBitGenerator.  Must be set before any key is traced.
        jax.config.update("jax_default_prng_impl", "rbg")

    flash = "--flash" in sys.argv

    from paddle_tpu.models import bert

    import bench as bench_mod

    tiny = "--tiny" in sys.argv
    remat = "--remat" in sys.argv
    if tiny:
        cfg = bert.BertConfig.tiny()
        batch, seq, n_masked = 8, 128, 20
    else:
        cfg = bert.BertConfig.base()
        batch, seq, n_masked = 32, 512, 76
    if "--batch" in sys.argv:
        # n_masked is PER SAMPLE (fake_batch masked_positions is
        # (batch, num_masked)): unchanged when batch scales
        try:
            batch = int(sys.argv[sys.argv.index("--batch") + 1])
        except (IndexError, ValueError):
            sys.exit("usage: aot_analysis.py [--flash] [--remat] "
                     "[--tiny] [--rbg] [--batch N]")

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x4")
    peaks = DEVICE_PEAKS[topo.devices[0].device_kind]
    mesh = Mesh(np.array(topo.devices[:1]), ("d",))
    sh = NamedSharding(mesh, P())
    model = bert.BertForPretraining(cfg)
    step, state = bert.build_pretrain_step(model, bf16=True,
                                           remat=remat)
    b = bert.fake_batch(cfg, batch, seq, num_masked=n_masked)
    lr = jnp.float32(1e-4)

    shardings = jax.tree_util.tree_map(lambda _: sh, (state, b, lr))
    fn = step.__wrapped__ if hasattr(step, "__wrapped__") else step
    t0 = time.time()
    # the kernels probe and dispatch at trace time: inside the target
    with pallas_common.compile_target(sh) if flash \
            else contextlib.nullcontext():
        comp = jax.jit(fn, in_shardings=shardings).lower(state, b, lr) \
            .compile()
    compile_s = time.time() - t0

    ca = comp.cost_analysis() or {}
    ma = comp.memory_analysis()
    model_flops = bench_mod.bert_step_flops(cfg, batch, seq, n_masked)
    xla_flops = float(ca.get("flops", 0.0))
    xla_bytes = float(ca.get("bytes accessed", 0.0))

    # HLO structure: op-kind histogram + the fattest top-level ops by
    # their declared output bytes (a proxy for HBM traffic per fusion:
    # every fusion result is an HBM write, and an HBM read at each use)
    txt = comp.as_text()
    mosaic_calls = sum("tpu_custom_call" in ln for ln in txt.splitlines())
    if flash:
        from paddle_tpu.profiler import get_int_stats

        gave_way = get_int_stats().get("flash_fallback_total", 0)
        if gave_way or not mosaic_calls:
            sys.exit(f"--flash: attention gave way to XLA "
                     f"(flash_fallback_total={gave_way}, "
                     f"{mosaic_calls} Mosaic calls in the executable)")
    kinds = collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (\w[\w\-]*)\(",
            txt, re.M))
    top_kinds = kinds.most_common(20)

    DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
                "f64": 8, "s64": 8, "u64": 8}

    def shape_bytes(sig):
        total = 0
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", sig):
            if dt not in DT_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DT_BYTES[dt]
        return total

    fusions = []
    line_re = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) "
        r"(fusion|custom-call|convolution|dot|all-reduce|copy)\(")
    meta_re = re.compile(r'op_name="([^"]*)"')
    for line in txt.splitlines():
        m = line_re.match(line)
        if not m:
            continue
        name, sig, kind = m.groups()
        nbytes = shape_bytes(sig)
        if not nbytes:
            continue
        mm = meta_re.search(line)
        fusions.append((nbytes, kind, name,
                        (mm.group(1) if mm else "")[:90]))
    fusions.sort(reverse=True)
    grouped = collections.Counter()
    for nbytes, kind, name, op_name in fusions:
        # aggregate repeated per-layer instances by op_name stem
        stem = re.sub(r"\d+", "N", op_name or name)
        grouped[stem] += nbytes
    top_fusions = [
        {"group": g, "output_gb": round(v / 1e9, 3)}
        for g, v in grouped.most_common(25)]

    compute_s = model_flops / peaks["flops"]
    hbm_s = xla_bytes / peaks["hbm_bps"]
    roofline_s = max(compute_s, hbm_s)
    result = {
        "config": {"model": "bert-base" if not tiny else "bert-tiny",
                   "batch": batch, "seq": seq, "bf16": True,
                   "remat": remat,
                   "flash_attention": flash,
                   "mosaic_calls": mosaic_calls,
                   "prng_impl": "rbg" if rbg else "threefry",
                   "peak_source": peaks["source"],
                   "note": (
                       "Pallas flash kernels compiled by Mosaic into "
                       "the executable; bytes counted at the "
                       "custom-call boundary = their HBM traffic"
                       if flash else
                       "attention is plain XLA ops here (CPU default "
                       "backend); on the chip the flash kernels "
                       "replace them")},
        "compile_seconds": round(compile_s, 1),
        "model_flops_per_step": model_flops,
        "xla_counted_flops": xla_flops,
        "xla_bytes_accessed": xla_bytes,
        "roofline": {
            "compute_bound_ms": round(compute_s * 1e3, 2),
            "hbm_bound_ms": round(hbm_s * 1e3, 2),
            "roofline_ms": round(roofline_s * 1e3, 2),
            "mfu_at_roofline_pct": round(
                model_flops / roofline_s / peaks["flops"] * 100, 2),
        },
        "hlo_op_kinds_top20": top_kinds,
        "top_output_byte_groups": top_fusions,
        "memory": {
            "argument_mb": round(ma.argument_size_in_bytes / 1e6, 1),
            "output_mb": round(ma.output_size_in_bytes / 1e6, 1),
            "temp_mb": round(ma.temp_size_in_bytes / 1e6, 1),
            "generated_code_mb": round(
                ma.generated_code_size_in_bytes / 1e6, 1),
        },
    }
    os.makedirs(ART, exist_ok=True)
    suffix = ("_tiny" if tiny else "") + ("_remat" if remat else "") \
        + ("_flash" if flash else "") + ("_rbg" if rbg else "") \
        + (f"_b{batch}" if "--batch" in sys.argv else "")
    out = os.path.join(ART, f"aot_v5e_analysis{suffix}.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["roofline"]))
    print(f"written: {out}")


if __name__ == "__main__":
    sys.exit(main())
