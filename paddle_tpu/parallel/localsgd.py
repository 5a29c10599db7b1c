"""LocalSGD over a device mesh — the k_steps>1 case.

Reference: fleet/meta_optimizers/localsgd_optimizer.py +
transpiler LocalSGD (SURVEY §2.9 #9) — each worker updates its own
parameter copy for k steps, then workers average parameters.

TPU-native mechanism: parameters carry a leading shard axis
(n_shards, ...) sharded over the mesh's data axis, so each device owns
a genuinely DIVERGENT copy (the thing the round-2 single-program
replicated-scope model could not express).  One jitted step runs a
shard_map in which every device computes grads on its batch shard and
updates its local copy; every k-th step the copies are psum-averaged
over the axis inside the same computation (`lax.cond` on the carried
step counter).  k_steps=1 degenerates to synchronous data-parallel SGD
exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS


def build_localsgd_step(loss_fn, params, mesh, axis: str = DATA_AXIS,
                        k_steps: int = 4, lr: float = 0.1,
                        momentum: float = 0.0):
    """Build (step_fn, state) for LocalSGD training.

    loss_fn(params, batch) -> scalar loss (pure jax, per shard).
    params: pytree of arrays (the single-copy initial values).
    step_fn(state, batch) -> (state, mean_loss); `batch` leaves must
    have leading dim divisible by the axis size (sharded over it).

    state = {"params": per-shard stacked copies (n, ...), "vel": same,
    "t": step counter}.  `sync(state)` averages the copies and returns
    a single-copy pytree (for eval/checkpoint).
    """
    n = mesh.shape[axis]
    tmap = jax.tree_util.tree_map

    stacked = tmap(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
                   params)
    shard = NamedSharding(mesh, P(axis))
    stacked = jax.device_put(stacked, shard)
    vel = tmap(jnp.zeros_like, stacked)

    from jax import shard_map

    def local(pstack, vstack, t, batch):
        p = tmap(lambda a: a[0], pstack)     # this shard's copy
        v = tmap(lambda a: a[0], vstack)
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        v = tmap(lambda v, g: momentum * v + g, v, g)
        p = tmap(lambda p, v: p - lr * v, p, v)

        def sync(p):
            return tmap(lambda a: jax.lax.psum(a, axis) / n, p)

        p = jax.lax.cond((t + 1) % k_steps == 0, sync, lambda p: p, p)
        mean_loss = jax.lax.psum(loss, axis) / n
        return (tmap(lambda a: a[None], p), tmap(lambda a: a[None], v),
                mean_loss)

    pspec = tmap(lambda _: P(axis), stacked)

    @jax.jit
    def step(state, batch):
        bspec = tmap(lambda _: P(axis), batch)
        new_p, new_v, loss = shard_map(
            functools.partial(local),
            mesh=mesh,
            in_specs=(pspec, pspec, P(), bspec),
            out_specs=(pspec, pspec, P()),
            check_vma=False)(state["params"], state["vel"], state["t"],
                             batch)
        return {"params": new_p, "vel": new_v,
                "t": state["t"] + 1}, loss

    state = {"params": stacked, "vel": vel, "t": jnp.int32(0)}

    def sync(state):
        """Average the per-shard copies into one pytree."""
        return tmap(lambda a: jnp.mean(a, axis=0), state["params"])

    return step, state, sync
