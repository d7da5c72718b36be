"""Flash attention (prefill/training fwd) — the GEMM-class control kernel.

The paper's Table II requires that TROOP *not* regress compute-bound
kernels; this tiled causal-attention forward is the high-OI counterpart used
by the benchmark harness to demonstrate parity (its roofline term is compute,
not memory).  Standard online-softmax tiling with (q-tile x kv-tile) MXU
matmuls; per-tile state in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.troop import TroopConfig, pallas_interpret
from repro.tune.registry import itemsize, troop_kernel

_NEG = -1e30


def _example(small: bool = True):
    B, T, H, KV, hd, S = (1, 128, 4, 2, 64, 128) if small \
        else (2, 512, 8, 2, 64, 512)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    return (q, k, v), {"causal": True}


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc, *,
            scale, bq, bs, causal):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[0].astype(jnp.float32)                      # (bq, KV, G, hd)
    bqd, KV, G, hd = q.shape
    k = jnp.moveaxis(k_ref[0], 1, 0).astype(jnp.float32)  # (KV, bs, hd)
    v = jnp.moveaxis(v_ref[0], 1, 0).astype(jnp.float32)
    qr = jnp.moveaxis(q, 1, 0).reshape(KV, bqd * G, hd)
    s = jax.lax.dot_general(qr, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = s.reshape(KV, bqd, G, bs)
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        spos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(spos > qpos, _NEG, s)
    m_new = jnp.maximum(m_s[...], jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(m_s[...] - m_new)
    p = jnp.exp(s - m_new)                                # (KV,bq,G,bs)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(KV, bqd * G, bs), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(KV, bqd, G, hd)
    acc[...] = acc[...] * alpha + pv
    m_s[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        out = acc[...] / jnp.maximum(l_s[...], 1e-30)     # (KV,bq,G,hd)
        o_ref[0] = jnp.moveaxis(out, 0, 1).astype(o_ref.dtype)


@troop_kernel(
    "flash_attention",
    flops=lambda q, k, v: (4.0 * q.shape[0] * q.shape[1] * k.shape[1]
                           * q.shape[2] * q.shape[3]),
    bytes=lambda q, k, v: (
        2 * q.shape[0] * q.shape[1] * q.shape[2] * q.shape[3] * itemsize(q)
        + k.shape[0] * k.shape[1] * k.shape[2] * k.shape[3]
        * (itemsize(k) + itemsize(v))),
    streamed=lambda q, k, v: [q, q, k, v],   # q in + q-shaped out + cache
    space={"unroll": (1, 2), "block_k": (256, 512)},
    ref="flash_attention", example=_example, key_kwargs=("causal",))
@functools.partial(jax.jit, static_argnames=("cfg", "causal"))
def flash_attention(q, k, v, causal: bool = True,
                    cfg: TroopConfig = TroopConfig()):
    """q (B,T,H,hd), k/v (B,S,KV,hd) -> (B,T,H,hd)."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    bq = max(min(128 * cfg.unroll, T), 1)
    while T % bq:
        bq //= 2
    bs = max(min(cfg.block_k // 2, S), 1)
    while S % bs:
        bs //= 2
    qg = q.reshape(B, T, KV, G, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bs=bs, causal=causal),
        grid=(B, T // bq, S // bs),
        in_specs=[
            pl.BlockSpec((1, bq, KV, G, hd), lambda b, i, j: (b, i, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, i, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, KV, G, hd),
                               lambda b, i, j: (b, i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, KV, G, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((KV, bq, G, 1), jnp.float32),
                        pltpu.VMEM((KV, bq, G, 1), jnp.float32),
                        pltpu.VMEM((KV, bq, G, hd), jnp.float32)],
        interpret=pallas_interpret(),
    )(qg, k, v)
    return out.reshape(B, T, H, hd)
