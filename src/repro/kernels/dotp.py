"""DOTP kernel — s = x . y (the paper's 2:1 bandwidth-to-compute kernel).

Two operands per FMA: at a 1:1 memory ratio the FPU tops out at 50% (paper
§II); the kernel therefore streams *four* half-streams (two per operand) when
``streams=2``.  The scalar partial accumulates in SMEM scratch across grid
steps (shadow-buffer intent) and each tile reduces as a tree on the VPU (G).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.troop import (TroopConfig, block_rows, pallas_interpret,
                              sublane)
from repro.tune.registry import itemsize, troop_kernel


def _example(small: bool = True):
    key = jax.random.PRNGKey(0)
    n = 4096 if small else 1 << 20
    x = jax.random.normal(key, (n,), jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.bfloat16)
    return (x, y), {}


def _kernel_1s(x_ref, y_ref, o_ref, acc):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        acc[0, 0] = 0.0

    acc[0, 0] += jnp.sum(x_ref[...].astype(jnp.float32)
                         * y_ref[...].astype(jnp.float32))

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        o_ref[0, 0] = acc[0, 0]


def _kernel_2s(x0, x1, y0, y1, o_ref, acc):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        acc[0, 0] = 0.0

    p0 = jnp.sum(x0[...].astype(jnp.float32) * y0[...].astype(jnp.float32))
    p1 = jnp.sum(x1[...].astype(jnp.float32) * y1[...].astype(jnp.float32))
    acc[0, 0] += p0 + p1

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        o_ref[0, 0] = acc[0, 0]


@troop_kernel(
    "dotp",
    flops=lambda x, y: 2.0 * x.shape[0],
    bytes=lambda x, y: x.shape[0] * (itemsize(x) + itemsize(y)) + 4,
    streamed=lambda x, y: [x, y, jax.ShapeDtypeStruct((1,), jnp.float32)],
    space={"streams": (1, 2), "unroll": (1, 2, 4),
           "block_k": (256, 512, 1024)},
    ref="dotp", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def dotp(x, y, cfg: TroopConfig = TroopConfig()):
    """x, y (K,) -> scalar fp32."""
    lanes = 128
    x2, y2 = x.reshape(-1, lanes), y.reshape(-1, lanes)
    rows = x2.shape[0]
    sub = max(sublane(x.dtype), sublane(y.dtype))
    # two half-streams need each half to tile by the sublane granule
    streams = cfg.streams if rows % (2 * sub) == 0 else 1
    br = block_rows(rows // streams, cfg.block_k * cfg.unroll // lanes, sub)

    if streams == 1:
        out = pl.pallas_call(
            _kernel_1s,
            grid=(rows // br,),
            in_specs=[pl.BlockSpec((br, lanes), lambda j: (j, 0)),
                      pl.BlockSpec((br, lanes), lambda j: (j, 0))],
            out_specs=pl.BlockSpec((1, 1), lambda j: (0, 0),
                                   memory_space=pltpu.SMEM),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
            interpret=pallas_interpret(),
        )(x2, y2)
        return out[0, 0]

    steps = rows // 2 // br
    out = pl.pallas_call(
        _kernel_2s,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((br, lanes), lambda j: (j, 0)),
            pl.BlockSpec((br, lanes), lambda j, o=steps: (j + o, 0)),
            pl.BlockSpec((br, lanes), lambda j: (j, 0)),
            pl.BlockSpec((br, lanes), lambda j, o=steps: (j + o, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda j: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        interpret=pallas_interpret(),
    )(x2, x2, y2, y2)
    return out[0, 0]
