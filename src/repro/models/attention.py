"""GQA attention (RoPE, optional qkv-bias / qk-norm), KV-cache aware.

Pure-jnp reference path — GSPMD-shardable, used by the multi-pod dry-run and
as the oracle for the Pallas flash/decode kernels.

Every attention entry the model calls (``apply_attention``,
``apply_attention_decode``, ``apply_attention_chunk_paged``, cross
attention) runs under the ``jax.named_scope`` ``attention``, its steps under
``qkv``, ``kv_write``, ``kv_gather``, ``attend`` and ``out_proj``: the
scopes reach each device op's metadata, so a profile splits a step's device
time by them.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from repro.core import partitioning as PT
from repro.models import modules as M
from repro.serve.kvcache import (ChunkStage, NULL_PAGE,  # noqa: F401
                                 PagedKVCache, PageSpec)


class KVCache(NamedTuple):
    """KV cache; optionally int8-quantized (k/v int8 + per-(token, head)
    bf16 scales — §Perf A4: halves the decode memory-roofline floor)."""
    k: jax.Array       # (B, S, KV, hd) bf16 | int8
    v: jax.Array       # (B, S, KV, hd)
    k_scale: Optional[jax.Array] = None   # (B, S, KV, 1) bf16 when int8
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


# Thin views over the repro.quant primitives (kept under their historical
# names — §Perf A4 predates the quant subsystem; one absmax implementation
# now serves KV caches, page pools and gradient compression alike).
from repro.quant.tensor import dequantize_kv, quantize_kv  # noqa: E402,F401


def attention_init(key, cfg, *, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": M.dense_init(ks[0], d, H * hd, ("embed", "qkv_out"),
                           bias=cfg.qkv_bias),
        "wk": M.dense_init(ks[1], d, KV * hd, ("embed", "kv_out"),
                           bias=cfg.qkv_bias),
        "wv": M.dense_init(ks[2], d, KV * hd, ("embed", "kv_out"),
                           bias=cfg.qkv_bias),
        "wo": M.dense_init(ks[3], H * hd, d, ("qkv_out", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = M.norm_init("rmsnorm", hd, (None,))
        p["k_norm"] = M.norm_init("rmsnorm", hd, (None,))
    return p


@jax.named_scope("attend")
def attend(q, k, v, *, causal: bool, q_offset=0, length: Optional[jax.Array] = None,
           decode: bool = False):
    """q: (B,T,H,hd) k/v: (B,S,KV,hd). GQA via head grouping. fp32 softmax.

    ``q_offset``: absolute position of q[0] (causal masking w/ cache).
    ``length``: valid prefix length of k/v (decode with pre-allocated cache).

    The ``model``-axis strategy (shard KV heads / GQA groups / KV sequence)
    is picked per shape by ``PT.attn_strategy`` — see core.partitioning.
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    q = q.reshape(B, T, KV, G, hd)
    strat = PT.attn_strategy(KV, G, decode)
    if strat in ("kv", "kv_uneven"):
        q = PT.constrain(q, ("batch", None, "heads", None, None),
                         allow_uneven=strat == "kv_uneven")
        k = PT.constrain(k, ("batch", None, "heads", None),
                         allow_uneven=strat == "kv_uneven")
        v = PT.constrain(v, ("batch", None, "heads", None),
                         allow_uneven=strat == "kv_uneven")
        score_axes = ("batch", "heads", None, None, None)
        out_axes = ("batch", None, "heads", None, None)
    elif strat == "group":
        q = PT.constrain(q, ("batch", None, None, "heads", None))
        k = PT.constrain(k, ("batch", None, None, None))
        v = PT.constrain(v, ("batch", None, None, None))
        score_axes = ("batch", None, "heads", None, None)
        out_axes = ("batch", None, None, "heads", None)
    elif strat == "seq":
        q = PT.constrain(q, ("batch", None, None, None, None))
        k = PT.constrain(k, ("batch", "attn_kv_seq", None, None))
        v = PT.constrain(v, ("batch", "attn_kv_seq", None, None))
        score_axes = ("batch", None, None, None, "attn_kv_seq")
        out_axes = ("batch", None, None, None, None)
    else:
        score_axes = out_axes = None
    # §Perf A3: in decode the QK/PV contractions stay in the cache dtype —
    # a f32-preferred einsum makes XLA materialize fp32 copies of the WHOLE
    # cache (2 extra O(S) passes/layer; the MXU accumulates in fp32 anyway).
    # Only the small scores tensor is upcast for the fp32 softmax.  Gated on
    # the distributed context: local/CPU paths keep full-fp32 scores.
    qk_dtype = None if (decode and PT.active()) else jnp.float32
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k,
                        preferred_element_type=qk_dtype)
    scores = scores.astype(jnp.float32)
    if score_axes is not None:
        scores = PT.constrain(scores, score_axes,
                              allow_uneven=strat == "kv_uneven")
    scores = scores * (hd ** -0.5)
    spos = jnp.arange(S)[None, None, None, None, :]
    mask = jnp.zeros((), jnp.bool_)
    neg = jnp.finfo(jnp.float32).min
    if causal:
        qpos = q_offset + jnp.arange(T)[None, None, None, :, None]
        mask = spos > qpos
    if length is not None:
        mask = mask | (spos >= length[:, None, None, None, None])
    scores = jnp.where(mask, neg, scores)
    probs = jax.nn.softmax(scores, axis=-1)
    # §Perf B2: pin probs + output shardings. Without these, GSPMD resolves
    # the PV contraction with an "involuntary full rematerialization" of the
    # (B,KV,G,T,S) probs tensor — ~29 all-gathers of 1.07 GB per layer in
    # glm4-9b train_4k (measured; see EXPERIMENTS.md §Perf).
    if score_axes is not None:
        probs = PT.constrain(probs.astype(v.dtype), score_axes,
                             allow_uneven=strat == "kv_uneven")
    else:
        probs = probs.astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    if out_axes is not None:
        out = PT.constrain(out, out_axes,
                           allow_uneven=strat == "kv_uneven")
    return out.reshape(B, T, H, hd)


def _bf16_cache_einsum(spec, a, b):
    """Contraction over a cache operand without upcasting it (A3)."""
    return jnp.einsum(spec, a.astype(b.dtype), b)


def _tp_ctx():
    from repro.dist import tp as _tp
    return _tp.current()


def _tp_merge_heads(out):
    """Exact-TP merge: re-concatenate the per-device head shards (tiled
    all_gather, bitwise) ahead of the replicated output projection.  A
    no-op outside a TP context and in overlap mode (where ``wo`` is
    row-parallel and consumes the local shard directly)."""
    ctx = _tp_ctx()
    if ctx is not None and ctx.mode == "exact":
        from repro.dist import tp as _tp
        return _tp.gather_cols(out)
    return out


def _tp_attend_kv(k, v, cfg):
    """GQA fallback (``kv_shards == 1``): the cache holds every KV head on
    every device — slice the one head this device's query block reads, so
    ``attend``'s shape-derived grouping sees (KV=1, G=local heads)."""
    ctx = _tp_ctx()
    if ctx is not None and ctx.kv_replicated:
        from repro.dist import tp as _tp
        k = _tp.local_kv_head(k, cfg.num_heads, cfg.num_kv_heads)
        v = _tp.local_kv_head(v, cfg.num_heads, cfg.num_kv_heads)
    return k, v


@jax.named_scope("qkv")
def _project_qkv(p, cfg, x, x_kv, positions, kv_positions, dtype):
    B, T = x.shape[:2]
    hd = cfg.head_dim
    # head counts come from the projection widths, not cfg: under TP the
    # sharded wq/wk/wv emit this device's heads only (wk/wv stay full when
    # the plan replicates KV — fewer KV heads than devices)
    q = M.apply_dense(p["wq"], x, dtype, tp="col").reshape(B, T, -1, hd)
    k = M.apply_dense(p["wk"], x_kv, dtype,
                      tp="col").reshape(B, x_kv.shape[1], -1, hd)
    v = M.apply_dense(p["wv"], x_kv, dtype,
                      tp="col").reshape(B, x_kv.shape[1], -1, hd)
    if cfg.qk_norm:
        q = M.apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        k = M.apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    if cfg.pos_emb == "rope":
        q = M.apply_rope(q, positions, cfg.rope_theta)
        k = M.apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("out_proj")
def _out_proj(p, out, dtype):
    """Merge the heads of ``out`` (B, T, heads*hd) and project back to
    the model width."""
    return M.apply_dense(p["wo"], _tp_merge_heads(out), dtype, tp="row")


@jax.named_scope("attention")
def apply_attention(p, cfg, x, *, positions, dtype, causal=True,
                    return_kv=False):
    """Full-sequence (train / prefill) self-attention."""
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, dtype)
    ka, va = _tp_attend_kv(k, v, cfg)
    out = attend(q, ka, va, causal=causal)
    B, T = x.shape[:2]
    out = _out_proj(p, out.reshape(B, T, -1), dtype)
    # §Perf B3: reduce the TP partial sum HERE, in bf16 — otherwise XLA
    # defers the all-reduce past the next norm's fp32 upcast (2x bytes).
    # §Perf B4: name the post-psum tensor so the remat policy can SAVE it —
    # checkpoint_dots saves the (pre-psum) dot output, so the backward pass
    # re-runs every TP all-reduce otherwise.
    out = PT.constrain(out, ("batch", None, None))
    out = _checkpoint_name(out, "tp_out")
    if return_kv:
        return out, KVCache(k, v)   # k is roped: matches the decode cache
    return out


def update_cache(cache_arr, new, pos):
    """O(1)-byte cache update: scatter the new token row at ``pos``.

    §Perf iterations A1/A2: the naive ``jnp.where(iota == pos, ...)`` reads
    and rewrites the WHOLE cache every step (2 extra O(S) passes/layer).  A
    *global* scatter is worse under GSPMD (it gathers the sharded cache —
    measured, see EXPERIMENTS.md).  The winning form is a shard_map-local
    scatter: each (batch, seq)-shard writes its own rows, indices offset by
    the shard's sequence origin, out-of-range rows dropped — no collectives,
    O(tokens) bytes.
    """
    B, S = cache_arr.shape[:2]
    row = new[:, 0].astype(cache_arr.dtype)

    def local(c, n, p):
        s_local = c.shape[1]
        if PT.active():
            seq_ax = PT.resolve("cache_seq")
            off = jax.lax.axis_index(seq_ax) * s_local if seq_ax else 0
        else:
            off = 0
        idx = p - off
        return c.at[jnp.arange(c.shape[0]), idx].set(n, mode="drop")

    if not PT.active():
        return local(cache_arr, row, pos)

    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = PT._CTX.mesh
    b_ax = PT.resolve("batch")
    bsz = PT.mesh_size(b_ax)
    if bsz <= 1 or B % bsz:
        b_ax = None
    s_ax = PT.resolve("cache_seq")
    if s_ax is not None and (PT.mesh_size(s_ax) <= 1
                             or S % PT.mesh_size(s_ax) or S < 1024):
        s_ax = None
    trail = (None,) * (cache_arr.ndim - 2)
    cspec = P(b_ax, s_ax, *trail)
    nspec = P(b_ax, *trail)
    pspec = P(b_ax)
    return shard_map(local, mesh=mesh, in_specs=(cspec, nspec, pspec),
                     out_specs=cspec, check_rep=False)(cache_arr, row, pos)


def _set_rows(pool, layer, pid, row, rows):
    """Scatter ``rows`` at (page ``pid``, row ``row``) of the pool, or of
    its ``layer`` index when the pool is a stacked group's (L, P, page, W)
    carry: the write lands in the one carried buffer, in place."""
    idx = (pid, row) if layer is None else (layer, pid, row)
    return pool.at[idx].set(rows, mode="drop")


def _rows(new, pool):
    """(B, T, KV, hd|1) new rows as the pool's lane-dense (B, T, W) rows."""
    return new.reshape(new.shape[:2] + (-1,)).astype(pool.dtype)


def update_paged_cache(pool, new, pos, block_tables, layer=None):
    """Paged cache write: route the new token row through the block table.

    pool (P, page, W), or (L, P, page, W) written at index ``layer``; W is
    KV*hd (value pools) or KV (scale pools); new (B, 1, KV, hd|1); pos
    (B,); block_tables (B, nblk).  Token ``pos`` of slot ``b`` lives at
    page ``block_tables[b, pos // page]`` row ``pos % page`` — O(tokens)
    bytes, no full-cache rewrite, and (unlike the dense scatter) the write
    lands in a page that is physically disjoint from every other slot's
    pages.
    """
    page = pool.shape[-2]
    row = _rows(new, pool)[:, 0]
    pid = jnp.take_along_axis(block_tables, (pos // page)[:, None],
                              axis=1)[:, 0]
    return _set_rows(pool, layer, pid, pos % page, row)


def update_paged_cache_chunk(pool, new, offset, valid, block_tables,
                             layer=None):
    """Chunked-prefill cache write: scatter a slab of token rows through the
    block table.

    pool (P, page, W) or (L, P, page, W) as in ``update_paged_cache``; new
    (B, C, KV, hd|1); offset (B,) absolute position of row 0; valid (B,)
    rows of the slab that are real tokens; block_tables (B, nblk).  Row r
    of slot b lands at page ``block_tables[b, (offset+r) // page]`` row
    ``(offset+r) % page``; pad rows (r >= valid) are redirected to the
    never-read NULL page, so a partially filled final chunk cannot clobber
    live pages — in particular never a *shared* prefix page, which by the
    COW invariant is only ever mapped at positions < offset.
    """
    page = pool.shape[-2]
    B, C = new.shape[:2]
    pos = offset[:, None] + jnp.arange(C)[None, :]             # (B, C)
    blk = jnp.clip(pos // page, 0, block_tables.shape[1] - 1)
    pid = jnp.take_along_axis(block_tables, blk, axis=1)       # (B, C)
    pid = jnp.where(jnp.arange(C)[None, :] < valid[:, None], pid, NULL_PAGE)
    rows = _rows(new, pool).reshape(B * C, -1)
    return _set_rows(pool, layer, pid.reshape(-1),
                     (pos % page).reshape(-1), rows)


def _write_paged(cache: PagedKVCache, write, k_new, v_new) -> PagedKVCache:
    """Write new K/V rows into every pool of ``cache`` with ``write(pool,
    rows)``; int8 pools quantize the rows and write value and scale pages
    through the same table entries (§Perf A4 at page granularity)."""
    if not cache.quantized:
        return PagedKVCache(write(cache.k_pool, k_new),
                            write(cache.v_pool, v_new))
    k8, ks = quantize_kv(k_new)
    v8, vs = quantize_kv(v_new)
    return PagedKVCache(write(cache.k_pool, k8), write(cache.v_pool, v8),
                        write(cache.k_scale_pool, ks),
                        write(cache.v_scale_pool, vs))


def _kernel_pool(pool, layer, hd):
    """One layer's pool as the (P, page, KV, hd|1) array the Pallas kernels
    take (a per-layer copy when ``pool`` is a stacked carry)."""
    if layer is not None:
        pool = pool[layer]
    return pool.reshape(pool.shape[:2] + (-1, hd))


@jax.named_scope("kv_gather")
def gather_paged_kv(cache: PagedKVCache, block_tables, head_dim: int,
                    dtype=jnp.bfloat16, layer=None):
    """Dense logical view of a paged cache: (B, nblk*page, KV, hd).

    One gather per pool at ``block_tables`` (at ``(layer, block_tables)``
    of a stacked carry).  Pure-jnp reference path (the oracle for the
    Pallas ``paged_decode_attention`` kernels, which stream pages directly
    from the pool without materializing this view).  int8 pools are
    dequantized through their gathered scale pages (to ``dtype``).
    """
    B, nblk = block_tables.shape
    idx = block_tables if layer is None else (layer, block_tables)

    def view(pool, hd):
        return pool[idx].reshape(B, nblk * pool.shape[-2], -1, hd)

    k, v = view(cache.k_pool, head_dim), view(cache.v_pool, head_dim)
    if cache.quantized:
        ks, vs = view(cache.k_scale_pool, 1), view(cache.v_scale_pool, 1)
        return dequantize_kv(k, ks, dtype), dequantize_kv(v, vs, dtype)
    return k, v


def apply_attention_decode_paged(p, cfg, x, cache: PagedKVCache, pos,
                                 dtype, block_tables, use_kernel=False,
                                 layer=None):
    """Single-token decode against a paged cache (see serve.kvcache).

    ``layer``: this sublayer's index when ``cache`` holds a stacked group's
    pools (the scan carry); None for an unstacked leaf.

    ``use_kernel``: attend through the tuned Pallas
    ``kernels.paged_decode_attention`` (block-table gather inside the
    kernel — the pool is streamed page by page, no dense copy).  Default is
    the jnp reference path, which materializes the gathered logical view
    (full-capacity traffic: fine as oracle / GSPMD path, not the
    at-the-roofline stream — see DESIGN.md §4).
    """
    assert block_tables is not None, \
        "paged caches need batch['block_tables'] in the decode batch"
    B = x.shape[0]
    hd = cfg.head_dim
    q, k_new, v_new = _project_qkv(
        p, cfg, x, x, pos[:, None], pos[:, None], dtype)
    with jax.named_scope("kv_write"):
        new_cache = _write_paged(
            cache, lambda pool, new: update_paged_cache(
                pool, new, pos, block_tables, layer), k_new, v_new)
    if use_kernel:
        from repro.kernels import ops as KO   # lazy: keeps models jnp-only
        kp = _kernel_pool(new_cache.k_pool, layer, hd)
        vp = _kernel_pool(new_cache.v_pool, layer, hd)
        with jax.named_scope("attend"):
            if cache.quantized:
                out = KO.paged_decode_attention_int8(  # dispatches via tune
                    q[:, 0], kp, _kernel_pool(new_cache.k_scale_pool, layer,
                                              1),
                    vp, _kernel_pool(new_cache.v_scale_pool, layer, 1),
                    block_tables, pos + 1)[:, None]
            else:
                out = KO.paged_decode_attention(       # dispatches via tune
                    q[:, 0], kp, vp, block_tables, pos + 1)[:, None]
    else:
        k, v = gather_paged_kv(new_cache, block_tables, hd, dtype, layer)
        k, v = _tp_attend_kv(k, v, cfg)
        out = attend(q, k, v, causal=False, length=pos + 1, decode=True)
    return _out_proj(p, out.reshape(B, 1, -1), dtype), new_cache


@jax.named_scope("attention")
def apply_attention_chunk_paged(p, cfg, x, cache: PagedKVCache, offset,
                                valid, stage_base, dtype, block_tables,
                                stage: Optional[ChunkStage] = None,
                                use_kernel=False, layer=None):
    """Chunked-prefill attention against a paged cache.

    ``x`` (B, C, d) is one fixed-size slab of prompt tokens starting at
    absolute position ``offset`` (B,), of which the first ``valid`` (B,)
    rows are real; the slab's KV is written through the block table, then
    the slab attends causally over positions [0, offset + valid) — shared
    prefix pages included, so a prefix-cache hit starts mid-prompt with
    ``offset`` > 0 and never recomputes the shared rows.  ``layer`` as in
    ``apply_attention_decode_paged``.

    ``stage`` (int8 pools only) keeps this request's own prefill rows in
    bf16 so later chunks do not re-read their predecessors through the
    quantized pages — the chunked engine stays token-identical to the
    bucketed one (see ``kvcache.ChunkStage``).  Rows below ``stage_base``
    (a shared prefix) predate this request and are read from the pages.

    Returns (out (B, C, d), new_cache, new_stage_or_None).
    """
    assert block_tables is not None, \
        "chunked prefill needs batch['block_tables']"
    B, C = x.shape[:2]
    hd = cfg.head_dim
    positions = offset[:, None] + jnp.arange(C)[None, :]
    q, k_new, v_new = _project_qkv(p, cfg, x, x, positions, positions, dtype)
    with jax.named_scope("kv_write"):
        new_cache = _write_paged(
            cache, lambda pool, new: update_paged_cache_chunk(
                pool, new, offset, valid, block_tables, layer),
            k_new, v_new)
    length = offset + valid
    new_stage = None
    if use_kernel and not cache.quantized:
        from repro.kernels import ops as KO   # lazy: keeps models jnp-only
        with jax.named_scope("attend"):
            out = KO.prefill_attention_paged(
                q, _kernel_pool(new_cache.k_pool, layer, hd),
                _kernel_pool(new_cache.v_pool, layer, hd), block_tables,
                offset, length)
    else:
        k, v = gather_paged_kv(new_cache, block_tables, hd, dtype, layer)
        if stage is not None:
            # overlay this request's own bf16 rows (positions in
            # [stage_base, offset + valid)) on the dequantized view
            new_stage = ChunkStage(
                jax.lax.dynamic_update_slice(
                    stage.k, k_new.astype(stage.k.dtype),
                    (0, offset[0], 0, 0)),
                jax.lax.dynamic_update_slice(
                    stage.v, v_new.astype(stage.v.dtype),
                    (0, offset[0], 0, 0)))
            S = k.shape[1]
            spos = jnp.arange(S)[None, :]
            use = ((spos >= stage_base[:, None])
                   & (spos < length[:, None]))[:, :, None, None]
            k = jnp.where(use, new_stage.k[:, :S].astype(k.dtype), k)
            v = jnp.where(use, new_stage.v[:, :S].astype(v.dtype), v)
        k, v = _tp_attend_kv(k, v, cfg)
        out = attend(q, k, v, causal=True,
                     q_offset=offset[:, None, None, None, None],
                     length=length)
    out = _out_proj(p, out.reshape(B, C, -1), dtype)
    if stage is not None and new_stage is None:   # kernel path keeps stage
        new_stage = stage
    return out, new_cache, new_stage


@jax.named_scope("attention")
def apply_attention_decode(p, cfg, x, cache, pos, dtype, block_tables=None,
                           use_kernel=False, layer=None):
    """Single-token decode. ``pos``: (B,) current position; cache has fixed S.
    ``layer`` indexes a paged cache's stacked pools (dense caches arrive
    per layer and ignore it)."""
    if isinstance(cache, PagedKVCache):
        return apply_attention_decode_paged(p, cfg, x, cache, pos, dtype,
                                            block_tables, use_kernel, layer)
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(
        p, cfg, x, x, pos[:, None], pos[:, None], dtype)
    cs = ("batch", "cache_seq", None, None)
    with jax.named_scope("kv_write"):
        if cache.quantized:
            kq, ks = quantize_kv(k_new)
            vq, vs = quantize_kv(v_new)
            new_cache = KVCache(
                PT.constrain(update_cache(cache.k, kq, pos), cs),
                PT.constrain(update_cache(cache.v, vq, pos), cs),
                update_cache(cache.k_scale, ks, pos),
                update_cache(cache.v_scale, vs, pos))
        else:
            new_cache = KVCache(
                PT.constrain(update_cache(cache.k, k_new, pos), cs),
                PT.constrain(update_cache(cache.v, v_new, pos), cs))
    if cache.quantized:
        with jax.named_scope("kv_gather"):
            k = dequantize_kv(new_cache.k, new_cache.k_scale, dtype)
            v = dequantize_kv(new_cache.v, new_cache.v_scale, dtype)
    else:
        k, v = new_cache.k, new_cache.v
    k, v = _tp_attend_kv(k, v, cfg)
    out = attend(q, k, v, causal=False, length=pos + 1, decode=True)
    return _out_proj(p, out.reshape(B, 1, -1), dtype), new_cache


@jax.named_scope("attention")
def apply_cross_attention(p, cfg, x, enc_kv, dtype):
    """Cross-attention over precomputed encoder K/V (whisper decoder)."""
    B, T = x.shape[:2]
    H, hd = cfg.num_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q = M.apply_dense(p["wq"], x, dtype).reshape(B, T, H, hd)
    out = attend(q, enc_kv.k, enc_kv.v, causal=False)
    with jax.named_scope("out_proj"):
        return M.apply_dense(p["wo"], out.reshape(B, T, -1), dtype)


@jax.named_scope("attention")
def cross_kv(p, cfg, enc_out, dtype) -> KVCache:
    B, S = enc_out.shape[:2]
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = M.apply_dense(p["wk"], enc_out, dtype).reshape(B, S, KV, hd)
    v = M.apply_dense(p["wv"], enc_out, dtype).reshape(B, S, KV, hd)
    return KVCache(k, v)


def init_paged_cache(cfg, spec: PageSpec, dtype) -> PagedKVCache:
    """Zeroed page pools for one attention sublayer (shared across slots).

    Lane-dense pages, (P, page, KV*hd) (see ``PagedKVCache``).
    ``spec.kv_dtype == "int8"`` allocates int8 value pools plus bf16 scale
    pages, (P, page, KV) (same page indices — the allocator is oblivious
    to them)."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (spec.num_pages, spec.page_size, KV * hd)
    if jnp.dtype(spec.kv_dtype) == jnp.dtype(jnp.int8):
        sshape = shape[:-1] + (KV,)
        return PagedKVCache(jnp.zeros(shape, jnp.int8),
                            jnp.zeros(shape, jnp.int8),
                            jnp.ones(sshape, jnp.bfloat16),
                            jnp.ones(sshape, jnp.bfloat16))
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def init_cache(cfg, B: int, S: int, dtype, quantized: bool = False) -> KVCache:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (B, S, KV, hd)
    if quantized:
        return KVCache(jnp.zeros(shape, jnp.int8),
                       jnp.zeros(shape, jnp.int8),
                       jnp.ones((B, S, KV, 1), jnp.bfloat16),
                       jnp.ones((B, S, KV, 1), jnp.bfloat16))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
