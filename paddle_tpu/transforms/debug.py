"""Transform observability: jaxpr-level layout introspection.

The acceptance contract of the layout pass is stated over the LOWERED
program, not the Program IR: the transformed trunk must carry NHWC conv
dimension numbers and zero interior activation transposes.  These
helpers trace a Program's forward lowering to a jaxpr (shapes only, no
device work) and classify what actually came out — used by
tests/test_transforms.py for the jaxpr assertions.
"""

from __future__ import annotations

from typing import Dict, List

import jax


def _specs_for(program, feed_shapes: Dict[str, tuple]):
    """ShapeDtypeStruct env covering every read-before-entry var of the
    global block: feeds from `feed_shapes` {name: (shape, dtype)},
    everything else (parameters, running stats) from declared var
    shapes.  Dynamic (-1) dims must be pinned by the feed."""
    from ..fluid.executor import _analyze_block
    from ..ops.registry import jdt

    block = program.global_block()
    reads, _writes = _analyze_block(block, set(feed_shapes), scope=None)
    specs = {}
    for name, (shape, dtype) in feed_shapes.items():
        specs[name] = jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                           jdt(dtype))
    for name in reads:
        v = block._var_recursive(name)
        if v.shape is None or any(d == -1 for d in v.shape):
            raise ValueError(
                f"trace_forward: var {name!r} has dynamic shape "
                f"{v.shape}; pass it via feed_shapes")
        specs[name] = jax.ShapeDtypeStruct(tuple(v.shape), jdt(v.dtype))
    return specs


def trace_forward(program, feed_shapes: Dict[str, tuple],
                  fetch_names: List[str]):
    """Abstractly lower the global block -> ClosedJaxpr (no device
    work; the trace is purely shape-driven)."""
    from ..ops import registry

    block = program.global_block()

    def f(env):
        env = dict(env)
        ctx = registry.LowerCtx(jax.random.PRNGKey(0), block=block)
        registry.lower_block(ctx, block, env)
        return [env[n] for n in fetch_names]

    return jax.make_jaxpr(f)(_specs_for(program, feed_shapes))


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = v if isinstance(v, (list, tuple)) else (v,)
            for s in sub:
                inner = getattr(s, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)
                elif hasattr(s, "eqns"):
                    yield from _iter_eqns(s)


def transpose_report(closed_jaxpr) -> dict:
    """Classify every transpose in the traced forward.

    A transpose is a *boundary* artifact when it consumes a program
    input directly (the NCHW feed entering the NHWC trunk) or when its
    operand is layout-degenerate (>= 2 unit dims beyond the batch dim,
    e.g. the (N, 1, 1, C) global-pool result handed back to NCHW-land —
    a bitcast for XLA).  Everything else is an *interior* activation
    transpose: exactly what the layout pass exists to eliminate."""
    top_invars = set(closed_jaxpr.jaxpr.invars)
    entries = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "transpose":
            continue
        operand = eqn.invars[0]
        shape = tuple(getattr(operand.aval, "shape", ()))
        is_input = operand in top_invars
        degenerate = (len(shape) == 4
                      and sum(1 for d in shape[1:] if d == 1) >= 2)
        entries.append({"shape": shape, "is_input": is_input,
                        "degenerate": degenerate})
    interior = [e for e in entries
                if not (e["is_input"] or e["degenerate"])]
    return {"total": len(entries), "interior": len(interior),
            "boundary": len(entries) - len(interior),
            "entries": entries}


def conv_layouts(closed_jaxpr) -> List[str]:
    """Activation layout of every conv_general_dilated in the trace:
    'NHWC' when the feature dim is minor-most (on the TPU lanes),
    'NCHW' otherwise."""
    out = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "conv_general_dilated":
            continue
        dn = eqn.params["dimension_numbers"]
        rank = len(dn.lhs_spec)
        out.append("NHWC" if dn.lhs_spec[1] == rank - 1 else "NCHW")
    return out

