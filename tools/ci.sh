#!/usr/bin/env bash
# CI gate (the TPU port of the reference's paddle_build.sh test stages +
# tools/check_* gatekeeping): unit tests on the 8-device virtual CPU
# mesh, op-test coverage floor, and — when a chip is present — the chip
# smoke and the TPU kernel lane.  Performance is measured by the
# benchmark (benchmark/run.py, benchmark/README.md), not here.
#
# Usage: tools/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== fault tolerance: kill-and-resume smoke (docs/fault_tolerance.md) =="
# SIGKILL a training subprocess mid-epoch and prove it resumes from the
# newest complete checkpoint with a contiguous step trajectory — the
# fast canary for the crash-injection suite in tests/test_checkpoint.py
python -m pytest tests/test_checkpoint.py -q -k smoke

echo "== unit tests (8-dev virtual CPU mesh) =="
python -m pytest tests/ -x -q

echo "== SPMD sharding: dp vs dp*fsdp*tp parity on 8 virtual devices (docs/spmd.md) =="
# the named-axis mesh lowering must train to the same losses as plain
# data-parallel while holding ~4x less optimizer state per device
python -m pytest tests/test_spmd_sharding.py -q

echo "== quantized collectives: int8 vs full-width parity + ~4x wire drop (docs/spmd.md) =="
# the blockwise int8 path must match full-width collectives within
# quantization tolerance, keep the health series within 5%, and drop
# the collective_bytes counters >=3.5x
python -m pytest tests/test_quant_collectives.py -q

echo "== static analysis: tpulint rules + op-test coverage floor + shape-consistency sweep =="
python tools/run_lints.py --shape-check

echo "== static analysis: shard-consistency sweep (fixture + book zoos x 3 meshes, docs/spmd.md) =="
python tools/run_lints.py --skip-op-coverage --shard-check

echo "== static analysis: shapecheck selftest (jax-free dump checker) =="
python tools/shapecheck.py --selftest

echo "== static analysis: shardcheck selftest (jax-free sharding checker) =="
python tools/shardcheck.py --selftest

echo "== multi-tenant fleet smoke: 2 models, restart, AOT warm start (docs/serving.md) =="
# two named models through one ModelRegistry, then a process restart
# against the same persistent AOT cache dir: the second process must
# LOAD its bucket executables (aot_cache_hits >= 1), not recompile
FLEET_DIR=$(mktemp -d /tmp/ci_fleet.XXXXXX)
for FLEET_RUN in cold warm; do
  PADDLE_AOT_CACHE=on PADDLE_AOT_CACHE_DIR="$FLEET_DIR" \
  FLEET_RUN="$FLEET_RUN" python - <<'EOF'
import os
import numpy as np
import jax.numpy as jnp
from paddle_tpu import serving
from paddle_tpu.profiler import get_int_stats

reg = serving.ModelRegistry(serving.EngineConfig(max_batch_size=8))
reg.register("ranker", lambda x: [jnp.tanh(x)], quota=16,
             aot_token="ci-fleet-ranker")
reg.register("scorer", lambda x: [x * 2.0], quota=16,
             aot_token="ci-fleet-scorer")
x = np.ones((2, 8), np.float32)
a = reg.infer("ranker", [x], timeout=300)
b = reg.infer("scorer", [x], timeout=300)
assert abs(float(a[0][0, 0]) - np.tanh(1.0)) < 1e-6
assert float(b[0][0, 0]) == 2.0
s = get_int_stats()
run = os.environ["FLEET_RUN"]
print(f"fleet smoke [{run}]: aot_cache_hits={s.get('aot_cache_hits', 0)}"
      f" misses={s.get('aot_cache_misses', 0)}"
      f" stores={s.get('aot_cache_stores', 0)}")
if run == "warm":
    assert s.get("aot_cache_hits", 0) >= 1, \
        "warm restart did not hit the persistent AOT cache"
reg.close()
EOF
done
rm -rf "$FLEET_DIR"

echo "== fast-decode smoke: chunked prefill + decode flood, zero per-token d2h (docs/serving.md) =="
# a long prompt admitted during a decode flood must prefill in chunks
# (serving_prefill_chunks >= 2) while the flood keeps decoding, and
# the whole run must keep the zero device->host-transfers-per-token
# contract: executor_sync_count only moves at response boundaries
# (one materialization per retired request)
python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from paddle_tpu import serving
from paddle_tpu.profiler import get_int_stats, stat_reset

V, D = 32, 8
rng = np.random.RandomState(0)
emb = jnp.asarray(rng.randn(V, D).astype(np.float32))
w = jnp.asarray(rng.randn(D, V).astype(np.float32))


def qkv_fn(tokens, positions):
    x = emb[tokens]
    q = x[:, :, None, :]
    return q, q, q


def out_fn(attn):
    return attn[:, :, 0, :] @ w


eng = serving.AutoregressiveEngine(
    qkv_fn, out_fn, num_heads=1, head_dim=D, num_pages=128,
    page_size=4, max_slots=4, max_pages_per_seq=24,
    prompt_buckets=(8, 16), prefill_chunk=8)
eng.generate(np.arange(40) % V, max_new_tokens=4)  # warm compiles
eng.generate(np.arange(5) % V, max_new_tokens=32)
stat_reset("executor_sync_count")
stat_reset("serving_prefill_chunks")
flood = [eng.submit(rng.randint(0, V, size=5).astype(np.int32),
                    max_new_tokens=32) for _ in range(3)]
for _ in range(8):
    eng.step()
long_req = eng.submit(rng.randint(0, V, size=40).astype(np.int32),
                      max_new_tokens=8)
eng.run_until_idle()
toks = long_req.result(timeout=60)
assert len(toks) == 8, toks
for r in flood:
    assert len(r.result(timeout=60)) == 32
s = get_int_stats()
chunks = s.get("serving_prefill_chunks", 0)
syncs = s.get("executor_sync_count", 0)
print(f"decode smoke: prefill_chunks={chunks} sync_count={syncs} "
      f"decode_steps={s.get('serving_decode_steps', 0)}")
assert chunks >= 2, "long prompt did not prefill in chunks"
# 4 retired requests -> exactly 4 sanctioned materializations; any
# more means a per-token device->host transfer crept into the loop
assert syncs == 4, f"expected 4 response-boundary syncs, got {syncs}"
eng.shutdown(drain=False)
EOF

# -- chip stages -------------------------------------------------------
# A chip belongs to one process at a time.  The probe, chip_smoke.py
# and the pytest lane below are separate processes that run
# strictly one after another, each exiting (and releasing the chip)
# before the next starts, and none of them starts a child that needs
# the chip while it holds it.  They share one compile cache, placed by
# the rule of paddle_tpu/fluid/compile_cache.py.
export JAX_COMPILATION_CACHE_DIR="$(python -c \
  'from paddle_tpu.fluid.compile_cache import persistent_cache_dirs; print(persistent_cache_dirs()[0])')"
if python - <<'EOF'
import jax
import sys
sys.exit(0 if jax.devices()[0].platform == "tpu" else 1)
EOF
then
  echo "== chip smoke: trainer, BERT-base step, generation engine =="
  python chip_smoke.py
  echo "== TPU kernel lane (non-interpret Mosaic) =="
  PADDLE_TPU_TEST_LANE=1 python -m pytest tests -q -m tpu
else
  echo "== no chip: chip smoke and TPU lane skipped =="
fi

echo "CI PASS"
