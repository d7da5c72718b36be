"""Plain reference of the dense decoder, and everything else about that
architecture that the harness needs.

A configuration file names its architecture's module (``"reference":
"dense_decoder"``); ``run.py`` reads it by that name and names none
itself.  A module provides:

* ``shapes(m)`` and ``make(m, seed, layout=None, shardings=None)``: every
  weight's shape and kind, and the seeded maker (``weights.draw``);
* ``program_tree(w)``: the flat weights laid out as the program takes
  them;
* ``frozen(m)`` and ``logits(w, frozen, tokens, rows, mode)``: the plain
  reference and, with ``mode="fp8"``, its control;
* ``decode_step(m, batch, dtype)``: the (FLOPs, bytes) a decode step needs;
* ``program_fields(cfg)``: the program's values of the file's ``model``
  keys that the program has, compared before a run;
* ``TINY``: a configuration at toy widths (registry id, overrides,
  ``model`` block, engine fields) for the contract test on the CPU.

The dense decoder's weights and counts live in ``weights.py`` and
``counts.py``; this module gives them to the harness.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no batching.  One sequence at a time, layer by layer,
attention in blocks of query rows so that a long context fits.

Per layer, as the configuration file's ``model`` block describes it:

    h = rmsnorm(x) * norm1                       (eps from the file)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv    (biases if qkv_bias)
    q, k = rope(q), rope(k)                      (``rope_dims`` leading
                                                  dims of each head, split
                                                  in halves, theta from file)
    a = softmax(q k^T / sqrt(hd), causal) v      (GQA: n_heads / n_kv_heads
                                                  query heads per KV head)
    x = x + a Wo
    h = rmsnorm(x) * norm2
    x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = rmsnorm(x) * final_norm @ lm_head   (embedding^T if tied)

``mode="fp8"`` is the control, computed one precision step below the
configuration's bfloat16: both operands of every matrix product
(projections, MLP, LM head, QK^T and PV) rounded to fp8 (e4m3) in blocks of
32 along the contracted axis with a power-of-two scale per block,
accumulated in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from counts import decode_step  # noqa: F401  (the module's contract)
from weights import make, program_tree, shapes  # noqa: F401

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512
FP8_MAX = 448.0
FP8_EMAX = 8
MX_BLOCK = 32


def fp8_round(x, axis: int = -2):
    """``x`` rounded to fp8 (e4m3) in blocks of 32 along ``axis`` (the
    contraction axis of the product it feeds), each block scaled by
    2^(floor(log2 amax) - 8) and clipped to +-448 (the OCP MX fp8 format
    that the program's own fp8 weights use)."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    blk = math.gcd(MX_BLOCK, n)
    b = x.reshape(*x.shape[:-1], n // blk, blk)
    amax = jnp.max(jnp.abs(b), axis=-1, keepdims=True)
    scale = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(amax, 1e-30)))
                     - FP8_EMAX)
    q = jnp.clip(b / scale, -FP8_MAX, FP8_MAX)
    q = q.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return jnp.moveaxis(q.reshape(x.shape), -1, axis)


def _dot(spec, a, b, mode, a_axis, b_axis):
    """``einsum(spec, a, b)``: float32 at HIGHEST for the reference; for
    the fp8 control both operands rounded to fp8 along the contracted
    axes first (their products are exact in float32)."""
    if mode == "fp8":
        a = fp8_round(a, a_axis).astype(jnp.bfloat16)
        b = fp8_round(b, b_axis).astype(jnp.bfloat16)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _mm(x, w, mode):
    return _dot("...k,kn->...n", x, w, mode, -1, 0)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, m):
    """Rotate the first ``rope_dims`` dims of each head (split halves)."""
    r = m["rope_dims"]
    xr, rest = x[..., :r], x[..., r:]
    freqs = 1.0 / (m["rope_theta"] ** (jnp.arange(0, r, 2, jnp.float32) / r))
    ang = pos[:, None, None].astype(jnp.float32) * freqs      # (T, 1, r/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = xr[..., :r // 2], xr[..., r // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot, rest], -1)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _layer(x, lw, m, mode):
    T = x.shape[0]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    pos = jnp.arange(T)
    h = _rmsnorm(x, lw["norm1"], eps)
    q = _mm(h, lw["wq"], mode)
    k = _mm(h, lw["wk"], mode)
    v = _mm(h, lw["wv"], mode)
    if "bq" in lw:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = _rope(q.reshape(T, H, hd), pos, m)
    k = _rope(k.reshape(T, KV, hd), pos, m)
    v = v.reshape(T, KV, hd)
    G = H // KV
    q = q.reshape(T, KV, G, hd) * hd ** -0.5
    outs = []
    for s in range(0, T, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        sc = _dot("tkgh,skh->kgts", qb, k, mode, -1, -1)
        qpos = s + jnp.arange(qb.shape[0])
        sc = jnp.where(pos[None, None, None, :] > qpos[None, None, :, None],
                       -jnp.inf, sc)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_dot("kgts,skh->tkgh", p, v, mode, -1, 0))
    a = jnp.concatenate(outs, 0).reshape(T, H * hd)
    x = x + _mm(a, lw["wo"], mode)
    h = _rmsnorm(x, lw["norm2"], eps)
    f = jax.nn.silu(_mm(h, lw["w_gate"], mode)) * _mm(h, lw["w_up"], mode)
    return x + _mm(f, lw["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _head(x, rows, final_norm, head, m, mode):
    h = _rmsnorm(x[rows], final_norm, m["norm_eps"])
    return _mm(h, head, mode)


LAYER_KEYS = ("norm1", "wq", "wk", "wv", "bq", "bk", "bv", "wo", "norm2",
              "w_gate", "w_up", "w_down")


def logits(w: dict, m, tokens, rows, mode: str = "ref"):
    """Logits (len(rows), vocab) at positions ``rows`` of the sequence
    ``tokens`` (T,).  ``m`` is the hashable model block (``frozen``)."""
    x = w["embed"][tokens]
    for layer in range(m["n_layers"]):
        lw = {k: w[k][layer] for k in LAYER_KEYS if k in w}
        x = _layer(x, lw, m, mode)
    head = w["embed"].T if m["tie_embeddings"] else w["lm_head"]
    return _head(x, rows, w["final_norm"], head, m, mode)


class Frozen(dict):
    """A read-only dict that hashes, for use as a static jit argument."""
    def __hash__(self):
        return hash(tuple(sorted(self.items())))

    def __setitem__(self, key, value):
        raise TypeError("frozen")


def frozen(m: dict) -> Frozen:
    return Frozen((k, v) for k, v in m.items()
                  if isinstance(v, (int, float, bool, str)))


def program_fields(cfg) -> dict:
    """The registry configuration's value of each ``model`` key."""
    return {"d_model": cfg.d_model, "n_layers": cfg.num_layers,
            "n_heads": cfg.num_heads, "n_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "qkv_bias": cfg.qkv_bias,
            "tie_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "compute_dtype": cfg.dtype}


# qwen1.5-0.5b cut to toy widths: GQA (4 query heads over 2 KV heads, as
# GLM-4's 32 over 2 at tp=4), QKV bias, an untied head
TINY = {
    "registry": "qwen1.5-0.5b",
    "registry_overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                           "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                           "vocab_size": 512},
    "model": {"d_model": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 512, "qkv_bias": True,
              "tie_embeddings": False, "rope_theta": 1000000.0,
              "rope_dims": 16, "norm_eps": 1e-06,
              "compute_dtype": "bfloat16"},
    "engine": {"slots": 4, "cache_len": 128, "backend": "paged",
               "page_size": 16, "chunked_prefill": True, "chunk_size": 32,
               "prefix_cache": True},
}
