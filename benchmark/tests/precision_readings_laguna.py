#!/usr/bin/env python3
"""The readings the tolerances of benchmark/reference/laguna.py are set
from, beside the system's own (PERF.md §6, PR 38): the reference against
ITSELF under a control, at the cell's sizes, on the chip, by hand:

    python3 benchmark/tests/precision_readings_laguna.py [--seed N]
        [--seq S] [--controls bfloat16,float8_e4m3fn,rope_bfloat16,...]

`bfloat16` rounds every matmul operand to the precision the
configuration states (float32 accumulation stays): a second path to the
system's own error, with no kernel in it.  `float8_e4m3fn` is the
nearest precision below it, `rope_bfloat16` rounds the rotation's angles
(position x inverse frequency) to bfloat16 before cos and sin,
`no_lower_bound` lets the window layers see every key j <= i: the
comparison has to call each of the three not correct, and `fails_by`
lists the limits that do.  `gate_bfloat16` (the per-head gate's logits
and its sigmoid rounded to bfloat16) and `router_bfloat16` (the router's
softmax scores rounded to bfloat16) are here to show that NO limit can
(they read under the system's own bfloat16 noise), which is why the
builder asks the executable for them (`gates_in_float32`,
`routers_choose_in_float32`).  One sequence of the first pool batch of
`--seed`, the weights the builder seeds (its own `build_model`), both
sides on the float32 reference's top-k; the control goes through the
harness's own `reference.compare` and `reference.compare_gradients`,
limits and all.  Prints one JSON line a control."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = {"bfloat16": {"operand_dtype": "bfloat16"},
            "float8_e4m3fn": {"operand_dtype": "float8_e4m3fn"},
            "rope_bfloat16": {"rope_angle_dtype": "bfloat16"},
            "gate_bfloat16": {"gate_dtype": "bfloat16"},
            "router_bfloat16": {"router_dtype": "bfloat16"},
            "no_lower_bound": {"window_lower_bound": False}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args()

    import numpy as np

    from benchmark import run as harness
    from benchmark.reference import laguna as reference
    from paddle_tpu.jit import functional_state
    from paddle_tpu.models import laguna

    base = os.path.join(ROOT, "benchmark")
    builder = harness.load_module(os.path.join(base, "configs", "laguna.py"))
    config = harness.load_json(os.path.join(base, "configs",
                                            "laguna_xs2.json"))
    traffic = harness.load_json(os.path.join(base, "traffic",
                                             "ar_s16384.json"))
    seq = args.seq or traffic["seq"]
    params = dict(functional_state(builder.build_model(config, args.seed)))
    batch = builder.make_batch(config, 1, seq,
                               np.random.default_rng([args.seed, 0]))
    cfg = builder.reference_config(config)
    names = builder.grad_leaves(config)
    pos = laguna.probe_positions(seq, traffic["probe"])
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
                e, q)["max_gap"]
            for c, e, q in zip(got["choose_by"], ref["experts"],
                               ref["choose_by"]))
        out["gradients"] = reference.compare_gradients(
            cfg, reference.grads(low, params, batch, routing, wrt=names,
                                 remat=True), want)
        # which limits call it not correct (the floor is no limit of
        # precision: a control that rounds one narrow tensor reads under
        # it against this file, as no system does)
        rel, limit = (out["gradients"][k] for k in ("rel_l2", "limit"))
        out["fails_by"] = (
            ["logits"] * (out["logits_rel_rms"] >= reference.LOGITS_TOLERANCE)
            + ["loss"] * (out["ce_rel"] >= reference.LOSS_TOLERANCE)
            + ["near_tie"] * (out["routing_max_gap"] > reference.NEAR_TIE)
            + [k for k in rel if not rel[k] < limit[k]])
        print(json.dumps({"control": name, "seed": args.seed, "seq": seq,
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
