#!/usr/bin/env python3
"""The readings the tolerances of benchmark/reference/joyai_flash.py are
set from, beside the system's own (PERF.md §6, PR 32): the reference
against ITSELF with every matmul operand rounded to a lower precision
(float32 accumulation stays), at the cell's sizes, on the chip, by
hand:

    python3 benchmark/tests/precision_readings_joyai.py [--seed N]
        [--controls bfloat16,float8_e4m3fn,router_bfloat16,unscaled]

`bfloat16` is the precision the configuration states: a second path to
the system's own error, with no kernel and no chunk walk in it.
`float8_e4m3fn` is the nearest precision below it: the comparison has
to call it not correct.  `router_bfloat16` rounds the router's scores
alone to bfloat16 before the top-k (a bfloat16 router);
`unscaled` leaves out the routed scaling factor (x 1 for x 2.5).  One
sequence of the first pool batch of `--seed`, the weights the builder
seeds (its own `build_model`) and the compared step's selection biases
(`comparison_biases`), both sides on the float32 reference's top-k; the
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
            "router_bfloat16": {"router_dtype": "bfloat16"},
            "unscaled": {"routed_scaling_factor": 1.0}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args()

    import numpy as np

    from benchmark import run as harness
    from benchmark.reference import joyai_flash as reference
    from paddle_tpu.jit import functional_state
    from paddle_tpu.models import joyai_flash

    base = os.path.join(ROOT, "benchmark")
    builder = harness.load_module(os.path.join(base, "configs",
                                               "joyai_flash.py"))
    config = harness.load_json(os.path.join(base, "configs",
                                            "joyai_llm_flash.json"))
    traffic = harness.load_json(os.path.join(base, "traffic",
                                             "ar_mtp_s8192.json"))
    seq = args.seq or traffic["seq"]
    params = dict(functional_state(builder.build_model(config, args.seed)))
    params.update(builder.comparison_biases(
        config, joyai_flash.bias_names(params), args.seed))
    batch = builder.make_batch(config, 1, seq,
                               np.random.default_rng([args.seed, 0]))
    cfg = builder.reference_config(config)
    names = builder.grad_leaves(config)
    pos = joyai_flash.probe_positions(seq, traffic["probe"])
    k = cfg["num_experts_per_tok"]

    ref = reference.forward(cfg, params, batch)
    routing = list(ref["experts"])
    want = reference.grads(cfg, params, batch, routing, wrt=names,
                           remat=True)
    summary = lambda r: {"ce": float(r["ce"]), "mtp_ce": float(r["mtp_ce"]),
                         "logits": np.asarray(r["logits"])[0][pos],
                         "mtp_logits": np.asarray(r["mtp_logits"])[0][pos]}
    for name in filter(None, args.controls.split(",")):
        low = {**cfg, **CONTROLS[name]}
        got = reference.forward(low, params, batch, routing)
        out = reference.compare(summary(got), summary(ref))
        # what this control's own router would have picked, against the
        # float32 scores + bias: the reading NEAR_TIE lies under
        out["routing_max_gap"] = max(
            reference.routing_agreement(
                np.argpartition(-np.asarray(c), k - 1, axis=1)[:, :k],
                e, q)["max_gap"]
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
