"""GPipe-style pipeline parallelism over a "pipe" mesh axis (optional).

Not needed for the assigned shapes (DP×TP covers them); provided and tested
as the capability a 1000-node deployment would enable for very deep models.
Stage handoff is a ``lax.ppermute`` ring; microbatches fill the pipeline in
the usual (S + n_micro − 1)-tick schedule.

The runner is model-agnostic: ``stage_fn(stage_params, x) → x`` applied by
every stage, stage params stacked on a leading axis sharded over ``axis``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def pipeline_apply(stage_fn, stage_params, x, *, mesh, axis: str = "pipe",
                   n_microbatches: int | None = None):
    """stage_params: pytree, leaves (n_stages, ...); x: (n_micro, mb, ...).

    Returns (n_micro, mb, ...) = stage_{S-1}(…stage_0(x)…) per microbatch.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0] if n_microbatches is None else n_microbatches
    assert x.shape[0] == n_micro
    ticks = n_micro + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_l, xs_l):
        # params_l leaves: (1, ...) — this stage's slice.  xs_l: (n_micro,…)
        # only meaningful on stage 0 (other stages carry garbage, masked).
        p = jax.tree.map(lambda t: t[0], params_l)
        s = jax.lax.axis_index(axis)

        def tick(carry, t):
            act = carry                                    # (mb, ...)
            inject = xs_l[jnp.clip(t, 0, n_micro - 1)]
            act_in = jnp.where(s == 0, inject, act)
            out = stage_fn(p, act_in)
            nxt = jax.lax.ppermute(out, axis, fwd)
            return nxt, out

        act0 = jax.lax.pcast(jnp.zeros_like(xs_l[0]), (axis,), to="varying")
        _, outs = jax.lax.scan(tick, act0, jnp.arange(ticks))
        # stage S−1 emits microbatch t−(S−1) at tick t
        return outs[None, n_stages - 1:]                   # (1, n_micro, …)

    leaf_spec = lambda _: P(axis)
    outs = shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(leaf_spec, stage_params), P()),
        out_specs=P(axis),
    )(stage_params, x)
    return outs[-1]                                        # last stage's view
