#!/usr/bin/env python3
"""How the seeded weights of `laguna_xs2` route, layer by layer: the
reading `assumed.seeded_weights` of the configuration file is checked
by (PERF.md §6, PR 38; the method of routing_balance_joyai.py).  By
hand, on the CPU (a minute a seed at 2,048 rows; the cell has 16,384):

    JAX_PLATFORMS=cpu python3 benchmark/tests/routing_balance_laguna.py
        [--seed N] [--seq S] [--initializer-only]

One sequence of the first pool batch of `--seed` through the float32
reference's layers, on the weights the builder seeds (`build_model`)
or, with `--initializer-only`, on the initializer's draws as they are.
Prints one JSON line an expert layer: the share of the routed visits
that land on the held experts (a uniform load gives 16/256), the
fullest held
expert over the held mean, the fullest of the 256 router outputs over
their mean, and the share of the rows that pick the one expert most of
them pick (8/256 when rows choose independently)."""

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
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--initializer-only", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as harness
    from benchmark.reference import laguna as reference
    from paddle_tpu.jit import functional_state

    base = os.path.join(ROOT, "benchmark")
    builder = harness.load_module(os.path.join(base, "configs",
                                               "laguna.py"))
    config = harness.load_json(os.path.join(base, "configs",
                                            "laguna_xs2.json"))
    traffic = harness.load_json(os.path.join(base, "traffic",
                                             "ar_s16384.json"))
    if args.initializer_only:
        config = {**config, "assumed": {
            k: v for k, v in config["assumed"].items()
            if k != "seeded_weights"}}
    params = {k: jnp.asarray(v, jnp.float32) for k, v in functional_state(
        builder.build_model(config, args.seed)).items()}
    seq = args.seq or traffic["seq"]
    batch = builder.make_batch(config, 1, seq,
                               np.random.default_rng([args.seed, 0]))
    cfg = builder.reference_config(config)
    out = reference.forward(cfg, params, batch)
    first, count = config["experts_held"]
    names = [f"model.layers.{i}" for i in range(config["num_hidden_layers"])
             if config["mlp_layer_types"][i] == "sparse"]
    for name, experts in zip(names, out["experts"]):
        e = np.asarray(experts)
        load = np.bincount(e.reshape(-1), minlength=config["router_width"])
        held = load[first:first + count]
        print(json.dumps({
            "layer": name, "seed": args.seed, "rows": len(e),
            "seeded_weights": not args.initializer_only,
            "held_visit_share": float(held.sum() / load.sum()),
            "held_max_over_mean": float(held.max() / max(held.mean(), 1)),
            "load_max_over_mean": float(load.max() / load.mean()),
            "rows_on_their_top_expert": float(load.max() / len(e))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
