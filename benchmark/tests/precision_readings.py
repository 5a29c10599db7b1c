#!/usr/bin/env python3
"""The readings the tolerances of benchmark/reference/sdar_moe.py are
set from, beside the system's own (PERF.md §6, PR 28): the reference
against ITSELF with every matmul operand rounded to a lower precision
(float32 accumulation stays), at the cell's sizes, on the chip, by
hand:

    python3 benchmark/tests/precision_readings.py [--seed N]
        [--dtypes bfloat16,float8_e4m3fn] [--program-float32]

`bfloat16` is the precision the configuration states: a second path to
the system's own error, with no kernel and no chunk walk in it.
`float8_e4m3fn` is the nearest precision below it: every comparison
has to call it not correct.  One sequence of the first pool batch of
`--seed`, the weights the builder seeds (its own `build_model`), both
sides on the float32 reference's top-k; the low-precision side goes
through the harness's own `reference.compare` and
`reference.compare_gradients`, limits and all.  Prints one JSON line a
dtype, with a fingerprint of the weights it ran on.
`--program-float32` adds the program's own loss function with its
bfloat16 cast off and matmuls at `highest`, against the reference: the
gradient gaps that are the stated precision's are gone from it."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--dtypes", default="bfloat16,float8_e4m3fn")
    ap.add_argument("--program-float32", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark import run as harness
    from benchmark.reference import sdar_moe as reference
    from paddle_tpu.jit import functional_state
    from paddle_tpu.models import sdar_moe

    base = os.path.join(ROOT, "benchmark")
    builder = harness.load_module(os.path.join(base, "configs",
                                               "sdar_moe.py"))
    config = harness.load_json(os.path.join(base, "configs",
                                            "sdar_30b_a3b.json"))
    traffic = harness.load_json(os.path.join(base, "traffic",
                                             "blockdiff_s4096.json"))
    model = builder.build_model(config, args.seed)
    params = functional_state(model)
    batch = builder.make_batch(config, 1, traffic["seq"],
                               np.random.default_rng([args.seed, 0]))
    cfg = builder.reference_config(config)
    names = [n.format(last=cfg["num_hidden_layers"] - 1)
             for n in builder._GRAD_LEAVES]
    rms = lambda a: float(np.sqrt(np.mean(np.square(np.asarray(a)))))
    rows = params["model.embed_tokens.weight"]
    weights = {"embed_rows_rms": rms(rows[:-1]), "mask_row_rms": rms(rows[-1]),
               "q_norm_scale": rms(
                   params["model.layers.0.self_attn.q_norm.weight"]),
               "out_proj_rms": rms(
                   params["model.layers.0.self_attn.out_proj.weight"])}

    ref = reference.forward(cfg, params, batch)
    routing = list(ref["experts"])
    want = reference.grads(cfg, params, batch, routing, wrt=names,
                           remat=True)
    pos, valid = sdar_moe.probe_positions(batch["masked"], traffic["probe"])
    at = lambda r: np.asarray(r["logits"])[0][pos[0]][valid[0]]
    if args.program_float32:
        with jax.default_matmul_precision("highest"):
            got, own = builder.system_gradients(model, params, names, batch,
                                                bf16=False)
        print(json.dumps({
            "program": "float32, highest", "seed": args.seed,
            "weights": weights, "gradients": reference.compare_gradients(
                got, reference.grads(cfg, params, batch, own, wrt=names,
                                     remat=True))}), flush=True)
    for dtype in filter(None, args.dtypes.split(",")):
        low = {**cfg, "operand_dtype": dtype}
        got = reference.forward(low, params, batch, routing)
        out = reference.compare(float(got["loss"]), at(got),
                                float(ref["loss"]), at(ref))
        # what this precision's own router would have picked, against
        # the float32 probabilities: the reading NEAR_TIE lies under
        k = cfg["num_experts_per_tok"]
        out["routing_max_gap"] = max(
            reference.routing_agreement(
                np.argpartition(-np.asarray(p), k - 1, axis=1)[:, :k],
                e, q)["max_gap"]
            for p, e, q in zip(got["probs"], ref["experts"], ref["probs"]))
        out["gradients"] = reference.compare_gradients(
            reference.grads(low, params, batch, routing, wrt=names,
                            remat=True), want)
        print(json.dumps({"operand_dtype": dtype, "seed": args.seed,
                          "weights": weights, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
