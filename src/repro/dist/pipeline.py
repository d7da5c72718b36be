"""GPipe pipeline parallelism (skewed schedule over stacked stage params).

``make_pipeline_fn(stage_fn, mesh, num_microbatches)`` returns
``pipe(stage_params, x)`` == applying the S stages sequentially, executed
as the classic pipeline: all stages run every tick (vmap over the stacked
stage axis == one device per stage under the ``stage`` mesh axis), with
microbatch m entering stage s at tick m + s.  ``bubble_fraction`` is the
idle share (S-1)/(M+S-1) — the quantity the paper's chaining analysis
minimizes, here at mesh scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def bubble_fraction(stages: int, microbatches: int) -> float:
    return (stages - 1) / (microbatches + stages - 1)


def make_pipeline_fn(stage_fn, mesh=None, num_microbatches: int = 8):
    """stage_fn(params_s, x_mb) -> x_mb; stage params stacked on axis 0."""
    M = num_microbatches
    if mesh is not None:
        from repro.launch.mesh import auto_axes
        mesh = auto_axes(mesh)     # the stage constraint needs Auto axes

    def pipe(stage_params, x):
        S = jax.tree.leaves(stage_params)[0].shape[0]
        B = x.shape[0]
        assert B % M == 0, (B, M)
        mbs = x.reshape(M, B // M, *x.shape[1:])
        buf = jnp.zeros((S,) + mbs.shape[1:], x.dtype)
        outs = jnp.zeros_like(mbs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 consumes microbatch t (garbage after the last one —
            # its output never reaches the collect point below)
            feed = mbs[jnp.clip(t, 0, M - 1)]
            inputs = jnp.concatenate([feed[None], buf[:-1]], axis=0)
            new_buf = jax.vmap(stage_fn)(stage_params, inputs)
            if mesh is not None and "stage" in mesh.axis_names:
                spec = P("stage", *([None] * (new_buf.ndim - 1)))
                new_buf = jax.lax.with_sharding_constraint(
                    new_buf, NamedSharding(mesh, spec))
            # the last stage's output at tick t is microbatch t - (S-1)
            m = t - (S - 1)
            valid = (m >= 0) & (m < M)
            idx = jnp.clip(m, 0, M - 1)
            outs = jnp.where(valid, outs.at[idx].set(new_buf[-1]), outs)
            return (new_buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(M + S - 1))
        return outs.reshape(B, *x.shape[1:])

    return pipe
