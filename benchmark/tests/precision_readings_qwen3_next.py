#!/usr/bin/env python3
"""The readings the tolerances of benchmark/reference/qwen3_next.py are
set from, beside the system's own (PERF.md §6): the reference
against ITSELF in a lower precision, at the cell's sizes, on the chip,
by hand:

    python3 benchmark/tests/precision_readings_qwen3_next.py [--seed N]
        [--seq S] [--controls bfloat16,float8_e4m3fn,gate_bfloat16]

`bfloat16` rounds every matmul operand to the precision the
configuration states (float32 accumulation stays): a second path to the
system's own error, with no kernel and no chunk in it.  `float8_e4m3fn`
is the nearest precision below it, and `gate_bfloat16` keeps the scan's
cumulated decay G in bfloat16 (the decay of a token taken from the
rounded sums over chunks of 64, everything else float32): the
comparison has to call both not correct.  One sequence of the first
pool batch of `--seed` on the weights the builder seeds (its own
`build_model`), both sides on the float32 reference's top-k; the
control goes through the harness's own `reference.compare` and
`reference.compare_gradients`, limits and all.  Prints one JSON line a
control."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = {"bfloat16": {"operand_dtype": "bfloat16"},
            "float8_e4m3fn": {"operand_dtype": "float8_e4m3fn"},
            "gate_bfloat16": {"gate_cumsum_dtype": "bfloat16"}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args()

    import numpy as np

    from benchmark import run as harness
    from benchmark.reference import qwen3_next as reference
    from paddle_tpu.jit import functional_state
    from paddle_tpu.models import qwen3_next

    base = os.path.join(ROOT, "benchmark")
    builder = harness.load_module(os.path.join(base, "configs",
                                               "qwen3_next.py"))
    config = harness.load_json(os.path.join(base, "configs",
                                            "qwen3_next_80b_a3b.json"))
    traffic = harness.load_json(os.path.join(base, "traffic",
                                             "ar_s16384.json"))
    seq = args.seq or traffic["seq"]
    params = dict(functional_state(builder.build_model(config, args.seed)))
    batch = builder.make_batch(config, 1, seq,
                               np.random.default_rng([args.seed, 0]))
    cfg = builder.reference_config(config)
    names = builder.grad_leaves(config)
    pos = qwen3_next.probe_positions(seq, traffic["probe"])
    k = cfg["num_experts_per_tok"]

    ref = reference.forward(cfg, params, batch, probe=pos)
    routing = list(ref["experts"])
    want = reference.grads(cfg, params, batch, routing, wrt=names,
                           remat=True)
    summary = lambda r: {"ce": float(r["ce"]),
                         "logits": np.asarray(r["logits"])}
    for name in filter(None, args.controls.split(",")):
        low = {**cfg, **CONTROLS[name]}
        got = reference.forward(low, params, batch, routing, probe=pos)
        out = reference.compare(summary(got), summary(ref))
        # what this control's own router would have picked, against the
        # float32 scores: the reading NEAR_TIE lies under
        out["routing_max_gap"] = max(
            reference.routing_agreement(
                np.argpartition(-np.asarray(c), k - 1, axis=1)[:, :k],
                e, q, reference.NEAR_TIE)["max_gap"]
            for c, e, q in zip(got["choose_by"], ref["experts"],
                               ref["choose_by"]))
        out["gradients"] = reference.compare_gradients(
            reference.grads(low, params, batch, routing, wrt=names,
                            remat=True), want)
        print(json.dumps({"control": name, "seed": args.seed, "seq": seq,
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
