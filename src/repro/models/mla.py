"""Multi-head Latent Attention (DeepSeek-V2), with absorbed decode.

Decode caches the *compressed* latent (kv_lora + rope dims) — exactly the
paper's low-operational-intensity GEMV workload: per decoded token the score
and value contractions stream the latent cache once with O(1) reuse.

Two decode modes:
  * ``expand``  — up-project all cached latents each step (naive).
  * ``absorb``  — fold W_UK into the query and W_UV into the output
    projection so the per-step work is a GEMV against the latent cache
    (production mode; also the §Perf hillclimb subject).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import partitioning as PT
from repro.models import modules as M


class MLACache(NamedTuple):
    c_kv: jax.Array    # (B, S, kv_lora)
    k_pe: jax.Array    # (B, S, rope_dim)


def mla_init(key, cfg):
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": M.dense_init(ks[0], d, H * qd, ("embed", "qkv_out")),
        "wdkv": M.dense_init(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim,
                             ("embed", None)),
        "kv_norm": M.norm_init("rmsnorm", m.kv_lora_rank, (None,)),
        "wuk": M.dense_init(ks[2], m.kv_lora_rank, H * m.qk_nope_head_dim,
                            ("kv_lora", "qkv_out")),
        "wuv": M.dense_init(ks[3], m.kv_lora_rank, H * m.v_head_dim,
                            ("kv_lora", "qkv_out")),
        "wo": M.dense_init(ks[4], H * m.v_head_dim, d, ("qkv_out", "embed")),
    }


def _queries(p, cfg, x, positions, dtype):
    m, H = cfg.mla, cfg.num_heads
    B, T = x.shape[:2]
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = M.apply_dense(p["wq"], x, dtype).reshape(B, T, H, qd)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = M.apply_rope(q_pe, positions, cfg.rope_theta)
    hax = ("batch", None, "heads", None)
    return PT.constrain(q_nope, hax), PT.constrain(q_pe, hax)


def _latent(p, cfg, x, positions, dtype):
    m = cfg.mla
    ckv = M.apply_dense(p["wdkv"], x, dtype)
    c_kv, k_pe = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c_kv = M.apply_norm(p["kv_norm"], c_kv, "rmsnorm", cfg.norm_eps)
    k_pe = M.apply_rope(k_pe[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_pe


@jax.named_scope("attention")
def apply_mla(p, cfg, x, *, positions, dtype):
    """Full-sequence (train / prefill): expand latents to per-head K/V."""
    m, H = cfg.mla, cfg.num_heads
    B, T = x.shape[:2]
    q_nope, q_pe = _queries(p, cfg, x, positions, dtype)
    c_kv, k_pe = _latent(p, cfg, x, positions, dtype)
    hax = ("batch", None, "heads", None)
    k_nope = PT.constrain(M.apply_dense(p["wuk"], c_kv, dtype).reshape(
        B, T, H, m.qk_nope_head_dim), hax)
    v = PT.constrain(M.apply_dense(p["wuv"], c_kv, dtype)
                     .reshape(B, T, H, m.v_head_dim), hax)

    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("bthi,bshi->bhts", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthi,bsi->bhts", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    scores = PT.constrain(scores, ("batch", "heads", None, None))
    tpos = jnp.arange(T)
    mask = tpos[None, None, :, None] < tpos[None, None, None, :]
    scores = jnp.where(mask, jnp.finfo(jnp.float32).min, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    # §Perf C3: pin probs/out shardings (same GSPMD involuntary-remat class
    # of failure that B2 fixed in the GQA path).
    probs = PT.constrain(probs, ("batch", "heads", None, None))
    out = jnp.einsum("bhts,bshi->bthi", probs, v)
    out = PT.constrain(out, ("batch", None, "heads", None)).reshape(B, T, -1)
    return M.apply_dense(p["wo"], out, dtype)


@jax.named_scope("attention")
def apply_mla_decode(p, cfg, x, cache: MLACache, pos, dtype, mode="absorb"):
    m, H = cfg.mla, cfg.num_heads
    B = x.shape[0]
    S = cache.c_kv.shape[1]
    q_nope, q_pe = _queries(p, cfg, x, pos[:, None], dtype)
    c_new, kpe_new = _latent(p, cfg, x, pos[:, None], dtype)
    from repro.models.attention import update_cache
    c_kv = update_cache(cache.c_kv, c_new, pos)      # O(1)-byte scatter (A1)
    k_pe = update_cache(cache.k_pe, kpe_new, pos)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    c_kv = PT.constrain(c_kv, ("batch", "cache_seq", None))
    k_pe = PT.constrain(k_pe, ("batch", "cache_seq", None))
    if mode == "absorb":
        # q' = q_nope @ W_UK^T : (B,1,H,kv_lora) — scores are a GEMV on the
        # compressed cache; the attention output stays in latent space and is
        # up-projected once (W_UV) for the single query token.
        wuk = p["wuk"]["w"].astype(dtype).reshape(
            m.kv_lora_rank, H, m.qk_nope_head_dim)
        q_lat = jnp.einsum("bthi,chi->bthc", q_nope, wuk,
                           preferred_element_type=jnp.float32).astype(dtype)
        # A3: contract the latent cache in its own dtype (no fp32 copies of
        # the cache); fp32 is only the accumulator (MXU semantics) and the
        # small scores for the softmax.
        scores = (jnp.einsum("bthc,bsc->bhts", q_lat.astype(c_kv.dtype),
                             c_kv, preferred_element_type=jnp.float32)
                  + jnp.einsum("bthi,bsi->bhts", q_pe.astype(k_pe.dtype),
                               k_pe,
                               preferred_element_type=jnp.float32)) * scale
        scores = PT.constrain(scores,
                              ("batch", None, None, "attn_kv_seq"))
    else:
        k_nope = M.apply_dense(p["wuk"], c_kv, dtype).reshape(
            B, S, H, m.qk_nope_head_dim)
        scores = (jnp.einsum("bthi,bshi->bhts", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bthi,bsi->bhts", q_pe, k_pe,
                               preferred_element_type=jnp.float32)) * scale

    smask = jnp.arange(S)[None, None, None, :] > pos[:, None, None, None]
    scores = jnp.where(smask, jnp.finfo(jnp.float32).min, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)

    if mode == "absorb":
        # contract to (b, h, t, c) and move the head axis after: the CPU
        # runtime has no bf16 x bf16 -> f32 kernel for the (b, t, h, c)
        # output layout of this dot
        out_lat = jnp.swapaxes(
            jnp.einsum("bhts,bsc->bhtc", probs, c_kv,
                       preferred_element_type=jnp.float32), 1, 2)
        wuv = p["wuv"]["w"].astype(dtype).reshape(
            m.kv_lora_rank, H, m.v_head_dim)
        out = jnp.einsum("bthc,chi->bthi", out_lat.astype(dtype), wuv,
                         preferred_element_type=jnp.float32).astype(dtype)
    else:
        v = M.apply_dense(p["wuv"], c_kv, dtype).reshape(
            B, S, H, m.v_head_dim)
        out = jnp.einsum("bhts,bshi->bthi", probs, v)
    out = M.apply_dense(p["wo"], out.reshape(B, 1, -1), dtype)
    return out, MLACache(c_kv, k_pe)


def init_mla_cache(cfg, B: int, S: int, dtype) -> MLACache:
    m = cfg.mla
    return MLACache(jnp.zeros((B, S, m.kv_lora_rank), dtype),
                    jnp.zeros((B, S, m.qk_rope_head_dim), dtype))
