"""Fused RMSNorm kernel (DOTP-class: one streaming pass, row-wise tree
reduction on the VPU + immediate scale — vs. the unfused reference which
reads x twice and materializes the square).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.troop import TroopConfig, pallas_interpret
from repro.tune.registry import itemsize, troop_kernel


def _example(small: bool = True):
    T, d = (8, 256) if small else (256, 4096)
    x = jax.random.normal(jax.random.PRNGKey(0), (T, d), jnp.bfloat16)
    s = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)
    return (x, s), {}


def _kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@troop_kernel(
    "rmsnorm",
    flops=lambda x, s, *a: 4.0 * x.shape[0] * x.shape[1],
    bytes=lambda x, s, *a: (2 * x.shape[0] * x.shape[1] * itemsize(x)
                            + x.shape[1] * itemsize(s)),
    streamed=lambda x, s, *a: [x, s, x],     # x in, scale, x-shaped out
    space={"block_n": (64, 128, 256)},
    ref="rmsnorm", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg", "eps"))
def rmsnorm(x, scale, eps: float = 1e-6, cfg: TroopConfig = TroopConfig()):
    """x (T, d), scale (d,) -> normalized x (dtype preserved)."""
    T, d = x.shape
    bt = max(min(cfg.block_n, T), 1)
    while T % bt:
        bt //= 2
    s2 = scale.reshape(1, d)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        interpret=pallas_interpret(),
    )(x, s2)
