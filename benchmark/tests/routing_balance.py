#!/usr/bin/env python3
"""How the seeded weights of `sdar_30b_a3b` route, layer by layer: the
reading `assumed.seeded_weights` of the configuration file was chosen
by (PERF.md §6, PR 28).  By hand, on the CPU (two minutes a seed):

    JAX_PLATFORMS=cpu python3 benchmark/tests/routing_balance.py
        [--seed N] [--initializer-only]

One sequence of the first pool batch of `--seed` through the float32
reference's layers, on the weights the builder seeds (`build_model`)
or, with `--initializer-only`, on the initializer's draws as they
are.  Prints one JSON line a layer: the share of the routed visits
that land on the held experts (a uniform load gives 1/8), the fullest
of the 128 experts over their mean, and the share of the mask rows /
of the token rows that pick the one expert most of them pick (8/128
when rows choose independently)."""

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
    ap.add_argument("--initializer-only", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as harness
    from benchmark.reference import sdar_moe as reference
    from paddle_tpu.jit import functional_state

    base = os.path.join(ROOT, "benchmark")
    builder = harness.load_module(os.path.join(base, "configs",
                                               "sdar_moe.py"))
    config = harness.load_json(os.path.join(base, "configs",
                                            "sdar_30b_a3b.json"))
    traffic = harness.load_json(os.path.join(base, "traffic",
                                             "blockdiff_s4096.json"))
    if args.initializer_only:
        config = {**config, "assumed": {
            k: v for k, v in config["assumed"].items()
            if k != "seeded_weights"}}
    params = {k: jnp.asarray(v, jnp.float32) for k, v in functional_state(
        builder.build_model(config, args.seed)).items()}
    seq = traffic["seq"]
    batch = builder.make_batch(config, 1, seq,
                               np.random.default_rng([args.seed, 0]))
    cfg = builder.reference_config(config)
    key, held = reference._key(cfg), tuple(config["experts_held"])
    positions = jnp.tile(jnp.arange(seq), 2)
    mask = jnp.asarray(reference.block_diffusion_mask(
        seq, cfg["block_length"]))
    is_mask = np.concatenate([batch["masked"][0], np.zeros(seq, bool)])
    x = params["model.embed_tokens.weight"][jnp.concatenate(
        [batch["noisy_ids"], batch["clean_ids"]], axis=1)]
    top = lambda e: float(np.bincount(e.reshape(-1)).max() / len(e))
    for i in range(cfg["num_hidden_layers"]):
        own = {k: v for k, v in params.items()
               if k.startswith(f"model.layers.{i}.")}
        x, experts, _ = jax.jit(lambda p, x, i=i: reference._layer(
            dict(key), p, i, x, positions, mask, held, None))(own, x)
        e = np.asarray(experts)
        load = np.bincount(e.reshape(-1), minlength=cfg["num_experts"])
        print(json.dumps({
            "layer": i, "seed": args.seed,
            "seeded_weights": not args.initializer_only,
            "held_visit_share": float(
                ((e >= held[0]) & (e < held[0] + held[1])).mean()),
            "load_max_over_mean": float(load.max() / load.mean()),
            "mask_rows_on_their_top_expert": top(e[is_mask]),
            "token_rows_on_their_top_expert": top(e[~is_mask])}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
