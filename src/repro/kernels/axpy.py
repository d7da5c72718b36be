"""AXPY kernel — y <- a*x + y (the paper's 3:1 bandwidth-to-compute kernel).

Three memory streams per FMA (read x, read y, write y): on the paper's 2:1
machine the bound is 66% FPU utilization; on TPU the op is pure bandwidth.
``streams=2`` splits x and y into contiguous halves (4 input DMAs in
flight); ``unroll`` mirrors the paper's §IV-F loop unrolling which breaks
the store->compute chaining dependency.
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
    n = 4096 if small else 1 << 20
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.bfloat16)
    return (1.5, x, y), {}


def _kernel_1s(a_ref, x_ref, y_ref, o_ref):
    a = a_ref[0]
    o_ref[...] = (a * x_ref[...].astype(jnp.float32)
                  + y_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _kernel_2s(a_ref, x0, x1, y0, y1, o0, o1):
    a = a_ref[0]
    o0[...] = (a * x0[...].astype(jnp.float32)
               + y0[...].astype(jnp.float32)).astype(o0.dtype)
    o1[...] = (a * x1[...].astype(jnp.float32)
               + y1[...].astype(jnp.float32)).astype(o1.dtype)


@troop_kernel(
    "axpy",
    flops=lambda a, x, y: 2.0 * x.shape[0],
    bytes=lambda a, x, y: x.shape[0] * (itemsize(x) + 2 * itemsize(y)),
    streamed=lambda a, x, y: [x, y, y],      # y read + y-shaped result out
    space={"streams": (1, 2), "unroll": (1, 2),
           "block_k": (256, 512, 1024)},
    ref="axpy", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def axpy(a, x, y, cfg: TroopConfig = TroopConfig()):
    """a scalar, x/y (K,) -> a*x + y (dtype of y)."""
    K = x.shape[0]
    lanes = 128
    a = jnp.asarray(a, jnp.float32).reshape(1)
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
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((br, lanes), lambda j: (j, 0)),
                      pl.BlockSpec((br, lanes), lambda j: (j, 0))],
            out_specs=pl.BlockSpec((br, lanes), lambda j: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, lanes), y.dtype),
            interpret=pallas_interpret(),
        )(a, x2, y2)
        return out.reshape(K)

    half = rows // 2
    steps = half // br
    out0, out1 = pl.pallas_call(
        _kernel_2s,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, lanes), lambda j: (j, 0)),
            pl.BlockSpec((br, lanes), lambda j, o=steps: (j + o, 0)),
            pl.BlockSpec((br, lanes), lambda j: (j, 0)),
            pl.BlockSpec((br, lanes), lambda j, o=steps: (j + o, 0)),
        ],
        out_specs=[pl.BlockSpec((br, lanes), lambda j: (j, 0)),
                   pl.BlockSpec((br, lanes), lambda j: (j, 0))],
        out_shape=[jax.ShapeDtypeStruct((half, lanes), y.dtype),
                   jax.ShapeDtypeStruct((half, lanes), y.dtype)],
        interpret=pallas_interpret(),
    )(a, x2, x2, y2, y2)
    return jnp.concatenate([out0, out1], axis=0).reshape(K)
