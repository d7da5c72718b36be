"""Chunked-prefill attention over a paged KV cache (block-table gather).

The chunked-prefill engine (serve.scheduler) feeds prompts through the model
as fixed-size token slabs; each slab attends causally over everything the
slot has cached so far — including *shared prefix* pages it never computed
(serve.kvcache.PrefixIndex).  This kernel is the at-the-roofline path for
that step: the KV stream is gathered page by page through the scalar-
prefetched block table (mechanism (E) at HBM granularity, exactly as in
``paged_decode_attention``), the query slab rides along in VMEM, and the
causal mask is applied against the slab's absolute ``q_offset`` — so a
prefix-cache hit enters mid-sequence without recomputing a single shared
row.

Like the decode kernels the per-page contractions are batched MXU
dot_generals with online-softmax state in VMEM scratch; ``streams=2`` walks
the two halves of the slot's logical sequence concurrently (odd page counts
fall back to one stream).  Pages the whole slab cannot see (entirely beyond
``q_offset + C``) still stream — the grid is static — but their scores mask
to -inf and contribute exact zeros, preserving bit-identical online-softmax
results.
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


def _prologue(m_s, l_s, acc):
    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)


def _slab_update(q, k, v, s0, q0, valid, scale, m_s, l_s, acc):
    """One online-softmax update: slab q (C, KV, G, hd) x one cache page
    k/v (page, KV, hd) whose first row sits at absolute position ``s0``."""
    C, KV, G, hd = q.shape
    page = k.shape[0]
    kT = jnp.moveaxis(k, 1, 0).astype(jnp.float32)        # (KV, page, hd)
    vT = jnp.moveaxis(v, 1, 0).astype(jnp.float32)
    qr = jnp.moveaxis(q, 1, 0).astype(jnp.float32)        # (KV, C, G, hd)
    s = jax.lax.dot_general(
        qr.reshape(KV, C * G, hd), kT, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    s = s.reshape(KV, C, G, page)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    spos = s0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    s = jnp.where((spos > qpos) | (spos >= valid), _NEG, s)
    m_new = jnp.maximum(m_s[...], jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(m_s[...] - m_new)
    p = jnp.exp(s - m_new)                                # (KV, C, G, page)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(KV, C * G, page), vT, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(KV, C, G, hd)
    acc[...] = acc[...] * alpha + pv
    m_s[...] = m_new


def _epilogue(o_ref, l_s, acc, dtype):
    out = acc[...] / jnp.maximum(l_s[...], 1e-30)         # (KV, C, G, hd)
    o_ref[0] = jnp.moveaxis(out, 0, 1).astype(dtype)


def _kernel_1s(bt_ref, off_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
               m_s, l_s, acc, *, scale, page):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    _slab_update(q_ref[0], k_ref[0], v_ref[0], j * page, off_ref[b],
                 len_ref[b], scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue(o_ref, l_s, acc, o_ref.dtype))


def _kernel_2s(bt_ref, off_ref, len_ref, q_ref, k0, v0, k1, v1, o_ref,
               m_s, l_s, acc, *, scale, page, half):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    q = q_ref[0]                                          # (C, KV, G, hd)
    q0, valid = off_ref[b], len_ref[b]
    _slab_update(q, k0[0], v0[0], j * page, q0, valid, scale, m_s, l_s, acc)
    _slab_update(q, k1[0], v1[0], (half + j) * page, q0, valid, scale,
                 m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue(o_ref, l_s, acc, o_ref.dtype))


def _example(small: bool = True):
    import numpy as np
    B, C, H, KV, hd, page, nblk = (2, 16, 4, 2, 128, 16, 4) if small \
        else (4, 64, 16, 8, 128, 16, 16)
    P = 1 + B * nblk
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, C, H, hd), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), jnp.bfloat16)
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)
    # slab b starts mid-sequence (a prefix-cache hit) and fills to length
    q_offset = jnp.asarray([7 * b for b in range(B)], jnp.int32)
    length = q_offset + C
    return (q, k_pool, v_pool, bt, q_offset, length), {}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_attention_paged(q, k_pool, v_pool, block_tables, q_offset,
                             length, cfg: TroopConfig = TroopConfig()):
    B, C, H, hd = q.shape
    page, KV = k_pool.shape[1], k_pool.shape[2]
    nblk = block_tables.shape[1]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, C, KV, G, hd)
    streams = cfg.streams if nblk % 2 == 0 else 1
    half = nblk // streams

    scratch = [pltpu.VMEM((KV, C, G, 1), jnp.float32),
               pltpu.VMEM((KV, C, G, 1), jnp.float32),
               pltpu.VMEM((KV, C, G, hd), jnp.float32)]
    q_spec = pl.BlockSpec((1, C, KV, G, hd),
                          lambda b, j, bt, off, ln: (b, 0, 0, 0, 0))
    out_spec = pl.BlockSpec((1, C, KV, G, hd),
                            lambda b, j, bt, off, ln: (b, 0, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, C, KV, G, hd), q.dtype)
    lo = pl.BlockSpec((1, page, KV, hd),
                      lambda b, j, bt, off, ln: (bt[b, j], 0, 0, 0))
    hi = pl.BlockSpec((1, page, KV, hd),
                      lambda b, j, bt, off, ln, o=half: (bt[b, o + j], 0, 0, 0))

    if streams == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nblk),
            in_specs=[q_spec, lo, lo], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            functools.partial(_kernel_1s, scale=scale, page=page),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=pallas_interpret(),
        )(block_tables, q_offset, length, qg, k_pool, v_pool)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, half),
            in_specs=[q_spec, lo, lo, hi, hi], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            functools.partial(_kernel_2s, scale=scale, page=page, half=half),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=pallas_interpret(),
        )(block_tables, q_offset, length, qg, k_pool, v_pool, k_pool, v_pool)
    return out.reshape(B, C, H, hd)


def _streamed(q, kp, vp, bt, off, ln):
    """Per-slot page traffic + the slab in/out + the table.  Shared prefix
    pages are counted by their block-table entries here (this kernel really
    does stream them per slot); the *residency* dedup — each physical page
    once — is the serve layer's accounting (kvcache.kv_page_bytes)."""
    view = (q.shape[0], bt.shape[1] * kp.shape[1], kp.shape[2], kp.shape[3])
    return [jax.ShapeDtypeStruct(view, kp.dtype),
            jax.ShapeDtypeStruct(view, vp.dtype), q, q, bt]


@troop_kernel(
    "prefill_attention_paged",
    flops=lambda q, kp, vp, bt, off, ln: (
        4.0 * q.shape[0] * q.shape[1] * q.shape[2] * q.shape[3]
        * bt.shape[1] * kp.shape[1]),
    bytes=lambda q, kp, vp, bt, off, ln: (
        q.shape[0] * bt.shape[1] * kp.shape[1] * kp.shape[2] * kp.shape[3]
        * (itemsize(kp) + itemsize(vp))
        + 2 * q.shape[0] * q.shape[1] * q.shape[2] * q.shape[3] * itemsize(q)
        + bt.shape[0] * bt.shape[1] * itemsize(bt)),
    streamed=_streamed,
    space={"streams": (1, 2)},
    ref="prefill_attention_paged", example=_example)
def prefill_attention_paged(q, k_pool, v_pool, block_tables, q_offset,
                            length, cfg: TroopConfig = TroopConfig()):
    """Causal chunk attention over a paged KV cache.

    q (B,C,H,hd) — a prefill slab whose row 0 sits at absolute position
    ``q_offset`` (B,); k_pool/v_pool (P,page,KV,hd); block_tables (B,nblk);
    ``length`` (B,) = q_offset + valid rows (positions >= length are
    masked).  Returns (B,C,H,hd) in q.dtype; rows past the valid count are
    garbage (their positions exceed ``length``) and must be discarded by
    the caller, exactly as the bucketed prefill discards pad rows.
    """
    return _prefill_attention_paged(q, k_pool, v_pool, block_tables,
                                    q_offset, length, cfg)
