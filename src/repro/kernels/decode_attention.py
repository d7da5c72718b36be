"""Flash-decode kernel — the paper's motivating workload (LLM decode phase).

One query token attends over a KV cache: every score/value contraction is a
GEMV on cache lines read exactly once (OI ~= 1 FLOP/byte).  Reaching the HBM
roofline requires exactly the paper's medicine:

  (A) streams=2   — the cache is streamed as two disjoint contiguous
                    S-halves via independent BlockSpecs (two DMAs in flight
                    per grid step, touching disjoint HBM regions — the
                    scrambling guarantee (E) comes for free from the split).
  (B) pipeline    — online-softmax state (m, l, acc) lives in VMEM scratch;
                    compute on block j overlaps the fetch of block j+1.
  (C) shadow acc  — the output commits once at the last S-block; no per-step
                    output DMA backpressure on the VPU/MXU.
  (G) log2 reduce — per-block max/sum are VPU tree reductions; the
                    cross-block combine is the associative online-softmax
                    update (reused cross-device for split-S decode, ops.py).

GQA: q heads grouped over KV heads; per-KV-head contractions run as batched
MXU dot_generals.  The kernel emits UNNORMALIZED (acc, m, l) so the same
code serves full decode (normalize in the wrapper) and split-S partials
(LSE-combined across shards by ``ops.lse_combine``).
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


def _block_update(q, k, v, s0, valid, scale, m_s, l_s, acc):
    """One online-softmax update for a (bs, KV, hd) cache block."""
    KV, G, hd = q.shape
    bs = k.shape[0]
    kT = jnp.moveaxis(k, 1, 0).astype(jnp.float32)       # (KV, bs, hd)
    vT = jnp.moveaxis(v, 1, 0).astype(jnp.float32)
    scores = jax.lax.dot_general(
        q.astype(jnp.float32), kT,
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale      # (KV, G, bs)
    pos = s0 + jax.lax.broadcasted_iota(jnp.int32, (KV, G, bs), 2)
    scores = jnp.where(pos < valid, scores, _NEG)
    m_new = jnp.maximum(m_s[...], jnp.max(scores, -1, keepdims=True))
    alpha = jnp.exp(m_s[...] - m_new)
    p = jnp.exp(scores - m_new)                          # (KV, G, bs)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general(
        p, vT, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (KV, G, hd)
    acc[...] = acc[...] * alpha + pv
    m_s[...] = m_new


def _prologue(m_s, l_s, acc):
    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)


def _epilogue(o_ref, m_ref, l_ref, m_s, l_s, acc):
    o_ref[0] = acc[...]
    m_ref[0] = m_s[...]
    l_ref[0] = l_s[...]


def _kernel_1s(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
               m_s, l_s, acc, *, scale, bs):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    _block_update(q_ref[0], k_ref[0], v_ref[0], j * bs, len_ref[b],
                  scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue(o_ref, m_ref, l_ref, m_s, l_s, acc))


def _kernel_2s(len_ref, q_ref, k0, v0, k1, v1, o_ref, m_ref, l_ref,
               m_s, l_s, acc, *, scale, bs, half):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    q, valid = q_ref[0], len_ref[b]
    _block_update(q, k0[0], v0[0], j * bs, valid, scale, m_s, l_s, acc)
    _block_update(q, k1[0], v1[0], half + j * bs, valid, scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue(o_ref, m_ref, l_ref, m_s, l_s, acc))


@functools.partial(jax.jit, static_argnames=("cfg", "s_offset"))
def decode_attention_stats(q, k, v, length, cfg: TroopConfig = TroopConfig(),
                           s_offset: int = 0):
    """Unnormalized partials: (acc (B,KV,G,hd) f32, m (B,KV,G,1), l (B,KV,G,1))."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    half = S // cfg.streams
    bs = max(min(cfg.block_k // 2 * cfg.unroll, half), 1)
    while half % bs:
        bs //= 2
    steps = half // bs
    qg = q.reshape(B, KV, G, hd)
    length = jnp.maximum(length - s_offset, 0)

    scratch = [pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, hd), jnp.float32)]
    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0))
    out_specs = [pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0)),
                 pl.BlockSpec((1, KV, G, 1), lambda b, j: (b, 0, 0, 0)),
                 pl.BlockSpec((1, KV, G, 1), lambda b, j: (b, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32)]
    lo = pl.BlockSpec((1, bs, KV, hd), lambda b, j: (b, j, 0, 0))
    hi = pl.BlockSpec((1, bs, KV, hd), lambda b, j, o=steps: (b, j + o, 0, 0))

    if cfg.streams == 1:
        acc, m, l = pl.pallas_call(
            functools.partial(_kernel_1s, scale=scale, bs=bs),
            grid=(B, steps),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, lo, lo],
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=pallas_interpret(),
        )(length, qg, k, v)
    else:
        acc, m, l = pl.pallas_call(
            functools.partial(_kernel_2s, scale=scale, bs=bs, half=half),
            grid=(B, steps),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec,
                      lo, lo, hi, hi],
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=pallas_interpret(),
        )(length, qg, k, v, k, v)
    return acc, m, l


def _example(small: bool = True):
    B, H, KV, hd, S = (2, 4, 2, 128, 512) if small else (4, 16, 8, 128, 4096)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    length = jnp.full((B,), S, jnp.int32)
    return (q, k, v, length), {}


@troop_kernel(
    "decode_attention",
    flops=lambda q, k, v, ln: (4.0 * q.shape[0] * q.shape[1]
                               * k.shape[1] * k.shape[3]),
    bytes=lambda q, k, v, ln: (
        k.shape[0] * k.shape[1] * k.shape[2] * k.shape[3]
        * (itemsize(k) + itemsize(v))
        + q.shape[0] * q.shape[1] * q.shape[2] * 2 * itemsize(q)),
    streamed=lambda q, k, v, ln: [k, v, q, q],   # cache + q in + q-like out
    space={"streams": (1, 2), "unroll": (1, 2), "block_k": (256, 512)},
    ref="decode_attention", example=_example)
def decode_attention(q, k, v, length, cfg: TroopConfig = TroopConfig()):
    """q (B,H,hd); k,v (B,S,KV,hd); length (B,) valid prefix. -> (B,H,hd)."""
    B, H, hd = q.shape
    acc, m, l = decode_attention_stats(q, k, v, length, cfg)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# Paged variant: block-table gather feeding the same two-stream pipeline
# --------------------------------------------------------------------------
def _epilogue_norm(o_ref, l_s, acc):
    o_ref[0] = acc[...] / jnp.maximum(l_s[...], 1e-30)


def _kernel_paged_1s(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                     m_s, l_s, acc, *, scale, page):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    _block_update(q_ref[0], k_ref[0], v_ref[0], j * page, len_ref[b],
                  scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue_norm(o_ref, l_s, acc))


def _kernel_paged_2s(bt_ref, len_ref, q_ref, k0, v0, k1, v1, o_ref,
                     m_s, l_s, acc, *, scale, page, half):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    q, valid = q_ref[0], len_ref[b]
    _block_update(q, k0[0], v0[0], j * page, valid, scale, m_s, l_s, acc)
    _block_update(q, k1[0], v1[0], (half + j) * page, valid, scale,
                  m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue_norm(o_ref, l_s, acc))


def _paged_example(small: bool = True):
    import numpy as np
    B, H, KV, hd, page, nblk = (2, 4, 2, 128, 16, 4) if small \
        else (4, 16, 8, 128, 16, 16)
    P = 1 + B * nblk
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), jnp.bfloat16)
    # permuted tables: physically scattered pages, logically contiguous
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)
    length = jnp.asarray([max(1, nblk * page - 5 * i) for i in range(B)],
                         jnp.int32)
    return (q, k_pool, v_pool, bt, length), {}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _paged_decode_attention(q, k_pool, v_pool, block_tables, length,
                            cfg: TroopConfig = TroopConfig()):
    B, H, hd = q.shape
    page, KV = k_pool.shape[1], k_pool.shape[2]
    nblk = block_tables.shape[1]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    streams = cfg.streams if nblk % 2 == 0 else 1
    half = nblk // streams

    scratch = [pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, hd), jnp.float32)]
    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0))
    out_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32)
    # the block-table gather: the page index for grid step (b, j) is read
    # from the scalar-prefetched table, so the DMA engine streams physically
    # scattered pages back-to-back — mechanism (E) at HBM granularity
    lo = pl.BlockSpec((1, page, KV, hd),
                      lambda b, j, bt, ln: (bt[b, j], 0, 0, 0))
    hi = pl.BlockSpec((1, page, KV, hd),
                      lambda b, j, bt, ln, o=half: (bt[b, o + j], 0, 0, 0))

    if streams == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, nblk),
            in_specs=[q_spec, lo, lo], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            functools.partial(_kernel_paged_1s, scale=scale, page=page),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=pallas_interpret(),
        )(block_tables, length, qg, k_pool, v_pool)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, half),
            in_specs=[q_spec, lo, lo, hi, hi], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            functools.partial(_kernel_paged_2s, scale=scale, page=page,
                              half=half),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=pallas_interpret(),
        )(block_tables, length, qg, k_pool, v_pool, k_pool, v_pool)
    return out.reshape(B, H, hd).astype(q.dtype)


def _paged_streamed(q, kp, vp, bt, ln):
    """Per-slot page traffic (nblk pages each for k and v), not pool size."""
    view = (q.shape[0], bt.shape[1] * kp.shape[1], kp.shape[2], kp.shape[3])
    return [jax.ShapeDtypeStruct(view, kp.dtype),
            jax.ShapeDtypeStruct(view, vp.dtype), q, q, bt]


@troop_kernel(
    "paged_decode_attention",
    flops=lambda q, kp, vp, bt, ln: (4.0 * q.shape[0] * q.shape[1]
                                     * bt.shape[1] * kp.shape[1]
                                     * q.shape[2]),
    bytes=lambda q, kp, vp, bt, ln: (
        q.shape[0] * bt.shape[1] * kp.shape[1] * kp.shape[2] * kp.shape[3]
        * (itemsize(kp) + itemsize(vp))
        + q.shape[0] * q.shape[1] * q.shape[2] * 2 * itemsize(q)
        + bt.shape[0] * bt.shape[1] * itemsize(bt)),
    streamed=_paged_streamed,
    space={"streams": (1, 2)},
    ref="paged_decode_attention", example=_paged_example)
def paged_decode_attention(q, k_pool, v_pool, block_tables, length,
                           cfg: TroopConfig = TroopConfig()):
    """Flash-decode over a paged KV cache (serve.kvcache layout).

    q (B,H,hd); k_pool/v_pool (P,page,KV,hd); block_tables (B,nblk) int32
    mapping logical block -> physical page; length (B,) valid prefix.
    Returns (B,H,hd) in q.dtype.

    Same two-stream online-softmax pipeline as ``decode_attention``, but the
    KV stream is gathered through the scalar-prefetched block table — pages
    are disjoint by construction (the allocator never hands a page to two
    slots), so the decoupled streams read conflict-free regions no matter
    how fragmented the pool is.  ``streams=2`` walks the two halves of the
    slot's logical sequence concurrently (falls back to one stream when the
    table length is odd).
    """
    return _paged_decode_attention(q, k_pool, v_pool, block_tables, length,
                                   cfg)


def _block_update_q8(q, k8, ks, v8, vs, s0, valid, scale, m_s, l_s, acc):
    """Online-softmax update reading an int8 cache block: dequantization
    happens in VMEM after the (halved) HBM stream — mechanism (A)+(E) with
    the §Perf A4 quantized layout."""
    k = k8.astype(jnp.float32) * ks.astype(jnp.float32)
    v = v8.astype(jnp.float32) * vs.astype(jnp.float32)
    _block_update(q, k, v, s0, valid, scale, m_s, l_s, acc)


def _kernel_q8(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
               o_ref, m_ref, l_ref, m_s, l_s, acc, *, scale, bs):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    _block_update_q8(q_ref[0], k_ref[0], ks_ref[0], v_ref[0], vs_ref[0],
                     j * bs, len_ref[b], scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue(o_ref, m_ref, l_ref, m_s, l_s, acc))


def _int8_example(small: bool = True):
    from repro.quant.tensor import quantize_kv
    (q, k, v, length), _ = _example(small)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    return (q, k8, ks, v8, vs, length), {}


@troop_kernel(
    "decode_attention_int8",
    flops=lambda q, k8, ks, v8, vs, ln: (4.0 * q.shape[0] * q.shape[1]
                                         * k8.shape[1] * k8.shape[3]),
    # §Perf A4 audit: the scale tensors ARE streamed (one row per cache
    # row) — a bytes model that ignores them overstates the roofline win
    # by hd/(hd+2) and mis-scores fraction-of-roofline in repro.tune
    bytes=lambda q, k8, ks, v8, vs, ln: (
        k8.shape[0] * k8.shape[1] * k8.shape[2] * k8.shape[3]
        * (itemsize(k8) + itemsize(v8))
        + k8.shape[0] * k8.shape[1] * k8.shape[2]
        * (itemsize(ks) + itemsize(vs))
        + q.shape[0] * q.shape[1] * q.shape[2] * 2 * itemsize(q)),
    streamed=lambda q, k8, ks, v8, vs, ln: [k8, v8, ks, vs, q, q],
    space={"streams": (1,), "unroll": (1, 2), "block_k": (256, 512)},
    default=TroopConfig(streams=1),
    ref="decode_attention_int8", example=_int8_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def decode_attention_int8(q, k8, k_scale, v8, v_scale, length,
                          cfg: TroopConfig = TroopConfig()):
    """Quantized-cache flash-decode: k8/v8 (B,S,KV,hd) int8 with
    per-(token, head) scales (B,S,KV,1). Returns (B,H,hd) in q.dtype.

    HBM traffic is ~0.5x the bf16 kernel (int8 values + tiny scales); the
    dequant multiply runs on the VPU between the DMA and the MXU."""
    B, H, hd = q.shape
    S, KV = k8.shape[1], k8.shape[2]
    G = H // KV
    scale = hd ** -0.5
    bs = max(min(cfg.block_k // 2 * cfg.unroll, S), 1)
    while S % bs:
        bs //= 2
    steps = S // bs
    qg = q.reshape(B, KV, G, hd)

    scratch = [pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, hd), jnp.float32)]
    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0))
    out_specs = [pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0)),
                 pl.BlockSpec((1, KV, G, 1), lambda b, j: (b, 0, 0, 0)),
                 pl.BlockSpec((1, KV, G, 1), lambda b, j: (b, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32)]
    kv_spec = pl.BlockSpec((1, bs, KV, hd), lambda b, j: (b, j, 0, 0))
    sc_spec = pl.BlockSpec((1, bs, KV, 1), lambda b, j: (b, j, 0, 0))

    acc, m, l = pl.pallas_call(
        functools.partial(_kernel_q8, scale=scale, bs=bs),
        grid=(B, steps),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec,
                  kv_spec, sc_spec, kv_spec, sc_spec],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=pallas_interpret(),
    )(length, qg, k8, k_scale, v8, v_scale)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# Quantized paged variant: int8 page pools + scale pages, same block-table
# gather feeding the fused-dequant online-softmax pipeline
# --------------------------------------------------------------------------
def _kernel_paged_q8_1s(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                        o_ref, m_s, l_s, acc, *, scale, page):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    _block_update_q8(q_ref[0], k_ref[0], ks_ref[0], v_ref[0], vs_ref[0],
                     j * page, len_ref[b], scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue_norm(o_ref, l_s, acc))


def _kernel_paged_q8_2s(bt_ref, len_ref, q_ref, k0, ks0, v0, vs0,
                        k1, ks1, v1, vs1, o_ref, m_s, l_s, acc,
                        *, scale, page, half):
    b, j = pl.program_id(0), pl.program_id(1)
    pl.when(j == 0)(lambda: _prologue(m_s, l_s, acc))
    q, valid = q_ref[0], len_ref[b]
    _block_update_q8(q, k0[0], ks0[0], v0[0], vs0[0], j * page, valid,
                     scale, m_s, l_s, acc)
    _block_update_q8(q, k1[0], ks1[0], v1[0], vs1[0], (half + j) * page,
                     valid, scale, m_s, l_s, acc)
    pl.when(j == pl.num_programs(1) - 1)(
        lambda: _epilogue_norm(o_ref, l_s, acc))


def _paged_int8_example(small: bool = True):
    from repro.quant.tensor import quantize_kv
    (q, k_pool, v_pool, bt, length), _ = _paged_example(small)
    k8, ks = quantize_kv(k_pool)
    v8, vs = quantize_kv(v_pool)
    return (q, k8, ks, v8, vs, bt, length), {}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _paged_decode_attention_int8(q, k_pool, k_scales, v_pool, v_scales,
                                 block_tables, length,
                                 cfg: TroopConfig = TroopConfig()):
    B, H, hd = q.shape
    page, KV = k_pool.shape[1], k_pool.shape[2]
    nblk = block_tables.shape[1]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    streams = cfg.streams if nblk % 2 == 0 else 1
    half = nblk // streams

    scratch = [pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, 1), jnp.float32),
               pltpu.VMEM((KV, G, hd), jnp.float32)]
    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0))
    out_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32)
    # value pages and their scale pages ride the SAME table entry: one
    # allocator, one gather — the scale page is just a second (tiny) DMA
    lo = pl.BlockSpec((1, page, KV, hd),
                      lambda b, j, bt, ln: (bt[b, j], 0, 0, 0))
    lo_s = pl.BlockSpec((1, page, KV, 1),
                        lambda b, j, bt, ln: (bt[b, j], 0, 0, 0))
    hi = pl.BlockSpec((1, page, KV, hd),
                      lambda b, j, bt, ln, o=half: (bt[b, o + j], 0, 0, 0))
    hi_s = pl.BlockSpec((1, page, KV, 1),
                        lambda b, j, bt, ln, o=half: (bt[b, o + j], 0, 0, 0))

    if streams == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, nblk),
            in_specs=[q_spec, lo, lo_s, lo, lo_s], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            functools.partial(_kernel_paged_q8_1s, scale=scale, page=page),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=pallas_interpret(),
        )(block_tables, length, qg, k_pool, k_scales, v_pool, v_scales)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, half),
            in_specs=[q_spec, lo, lo_s, lo, lo_s, hi, hi_s, hi, hi_s],
            out_specs=out_spec, scratch_shapes=scratch)
        out = pl.pallas_call(
            functools.partial(_kernel_paged_q8_2s, scale=scale, page=page,
                              half=half),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=pallas_interpret(),
        )(block_tables, length, qg, k_pool, k_scales, v_pool, v_scales,
          k_pool, k_scales, v_pool, v_scales)
    return out.reshape(B, H, hd).astype(q.dtype)


def _paged_int8_streamed(q, kp, ks, vp, vs, bt, ln):
    B, nblk, page, KV, hd = (q.shape[0], bt.shape[1], kp.shape[1],
                             kp.shape[2], kp.shape[3])
    view = (B, nblk * page, KV, hd)
    sview = (B, nblk * page, KV, 1)
    return [jax.ShapeDtypeStruct(view, kp.dtype),
            jax.ShapeDtypeStruct(view, vp.dtype),
            jax.ShapeDtypeStruct(sview, ks.dtype),
            jax.ShapeDtypeStruct(sview, vs.dtype), q, q, bt]


@troop_kernel(
    "paged_decode_attention_int8",
    flops=lambda q, kp, ks, vp, vs, bt, ln: (
        4.0 * q.shape[0] * q.shape[1] * bt.shape[1] * kp.shape[1]
        * q.shape[2]),
    # per-slot page traffic at quantized width + scale pages + q io + table
    bytes=lambda q, kp, ks, vp, vs, bt, ln: (
        q.shape[0] * bt.shape[1] * kp.shape[1] * kp.shape[2] * kp.shape[3]
        * (itemsize(kp) + itemsize(vp))
        + q.shape[0] * bt.shape[1] * kp.shape[1] * kp.shape[2]
        * (itemsize(ks) + itemsize(vs))
        + q.shape[0] * q.shape[1] * q.shape[2] * 2 * itemsize(q)
        + bt.shape[0] * bt.shape[1] * itemsize(bt)),
    streamed=_paged_int8_streamed,
    space={"streams": (1, 2)},
    ref="paged_decode_attention_int8", example=_paged_int8_example)
def paged_decode_attention_int8(q, k_pool, k_scales, v_pool, v_scales,
                                block_tables, length,
                                cfg: TroopConfig = TroopConfig()):
    """Flash-decode over int8 page pools with per-(token, head) scale pages.

    q (B,H,hd); k_pool/v_pool (P,page,KV,hd) int8; k_scales/v_scales
    (P,page,KV,1); block_tables (B,nblk) int32; length (B,).  Returns
    (B,H,hd) in q.dtype.

    Identical pipeline to ``paged_decode_attention`` — scalar-prefetched
    block-table gather, two-stream walk of the logical sequence (odd-nblk
    tables fall back to one stream) — but the cache stream is int8 + scale
    pages, ~0.53x the bf16 bytes at hd=128, and the dequant multiply runs
    in-register between the page DMA and the MXU (DESIGN.md §5).
    """
    return _paged_decode_attention_int8(q, k_pool, k_scales, v_pool,
                                        v_scales, block_tables, length, cfg)
