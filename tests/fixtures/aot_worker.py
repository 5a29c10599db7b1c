"""AOT-cache subprocess worker (tests/test_aot_cache.py).

Builds a small two-layer fluid program, runs ONE executor dispatch —
the first dispatch is exactly where the persistent AOT cache seam sits
(fluid/aot_cache.compile_entry_with_cache) — and dumps the fetched
output plus every aot_cache_* counter/timer as JSON to argv[1].

The cache configuration comes entirely from the environment
(PADDLE_AOT_CACHE / PADDLE_AOT_CACHE_DIR / PADDLE_QUANT_COLLECTIVES),
so the calling test composes cold / warm / off / drifted runs from the
same deterministic program.
"""

import json
import os
import sys

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import profiler
from paddle_tpu.fluid import framework


def main(out_path: str) -> None:
    d = int(os.environ.get("AOT_DIM", "16"))
    main_prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(main_prog, startup):
        x = fluid.data("x", [-1, d], "float32")
        h = fluid.layers.fc(x, size=d, act="tanh")
        y = fluid.layers.fc(h, size=d)
    exe = fluid.Executor()
    exe.run(startup)
    feed = {"x": np.linspace(-1.0, 1.0, 4 * d,
                             dtype=np.float32).reshape(4, d)}
    (out,) = exe.run(main_prog, feed=feed, fetch_list=[y])
    t = profiler.get_time_stats()
    s = profiler.get_int_stats()
    with open(out_path, "w") as f:
        json.dump({
            "out": np.asarray(out).tolist(),
            "compile_ms": t.get("compile_ms", 0.0),
            "aot_cache_load_ms": t.get("aot_cache_load_ms", 0.0),
            "phase_totals": profiler.phase_totals(),
            "stats": {k: v for k, v in s.items()
                      if k.startswith("aot_cache")},
        }, f)


if __name__ == "__main__":
    main(sys.argv[1])
