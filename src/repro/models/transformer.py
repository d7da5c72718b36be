"""Decoder-LM assembly: layer groups + scan-over-layers + caches.

Layers are grouped into maximal runs that tile a fixed (mixer, ffn) pattern;
parameters of a group are stacked over its repeats and the group is executed
with ``lax.scan`` (small HLO, fast multi-pod compiles).  Heterogeneous stacks
(Jamba's 8-layer period, DeepSeek's leading dense layer) become multiple
groups / multi-sublayer patterns.

Three entry points: ``train_logits`` / ``prefill`` / ``decode_step``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import partitioning as PT
from repro.models import attention as A
from repro.models import mamba as MB
from repro.models import mla as ML
from repro.models import moe as MOE
from repro.models import modules as M
from repro.models import rwkv as RW


@dataclass(frozen=True)
class RuntimeConfig:
    """Static runtime switches (jit static arg)."""
    remat: str = "dots"            # none | dots | full
    moe_groups: int = 1            # routing groups (align with data shards)
    mla_decode: str = "absorb"     # absorb | expand
    cache_dtype: str = "bfloat16"  # bf16 | int8 (quantized KV, §Perf)
    scan_layers: bool = True
    loss_chunk: int = 0            # 0 = unchunked softmax xent
    paged_kernel_decode: bool = False  # paged decode via the tuned Pallas
    #   kernel (no gathered dense view); default off: the jnp path is the
    #   GSPMD-shardable reference (interpret-mode Pallas is slow on CPU)
    # ---- repro.quant (DESIGN.md §5): a quantized engine is one flag ----
    quantize_weights: str = "none"  # none|int8|int4|mx4|fp8: matmul-weight
    #   quantization policy tag; the launcher applies
    #   repro.quant.quantize_params and apply_dense dequantizes on the fly
    kv_cache_dtype: str = ""       # "" -> cache_dtype. "int8" under the
    #   paged backend stores int8 page pools + scale pages (dense backends
    #   fall back to the per-slot int8 layout, same as cache_dtype="int8")

    def kv_dtype(self) -> str:
        """Resolved KV-cache storage dtype (serving alias wins)."""
        return self.kv_cache_dtype or self.cache_dtype


@dataclass(frozen=True)
class LayerGroup:
    pattern: Tuple[Tuple[str, str], ...]   # ((mixer, ffn), ...) per repeat
    repeats: int


def plan_groups(cfg) -> List[LayerGroup]:
    kinds = cfg.layer_kinds()
    f = cfg.first_dense_layers
    groups = [LayerGroup((kinds[i],), 1) for i in range(f)]
    rest = kinds[f:]
    if not rest:
        return groups
    import math
    P = abs(len(cfg.pattern()) * cfg.moe_period) // math.gcd(
        len(cfg.pattern()), cfg.moe_period) if cfg.moe_period else len(cfg.pattern())
    P = max(P, 1)
    if len(rest) % P:
        P = len(rest)               # fallback: one big unrolled group
    pat = tuple(rest[:P])
    for a in range(len(rest) // P):
        assert tuple(rest[a * P:(a + 1) * P]) == pat, "non-periodic layer kinds"
    groups.append(LayerGroup(pat, len(rest) // P))
    return groups


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def _init_sublayer(key, cfg, mixer: str, ffn: str):
    ks = jax.random.split(key, 4)
    p = {"norm1": M.norm_init(cfg.norm, cfg.d_model)}
    if mixer == "attn":
        p["mixer"] = (ML.mla_init(ks[0], cfg) if cfg.attention == "mla"
                      else A.attention_init(ks[0], cfg))
        if cfg.encoder_decoder:
            p["xattn"] = A.attention_init(ks[3], cfg, cross=True)
            p["norm_x"] = M.norm_init(cfg.norm, cfg.d_model)
    elif mixer == "mamba":
        p["mixer"] = MB.mamba_init(ks[0], cfg)
    elif mixer == "rwkv":
        p["mixer"] = RW.rwkv_time_mix_init(ks[0], cfg)
    p["norm2"] = M.norm_init(cfg.norm, cfg.d_model)
    if ffn == "mlp":
        p["ffn"] = M.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act)
    elif ffn == "moe":
        p["ffn"] = MOE.moe_init(ks[1], cfg)
    elif ffn == "rwkv_cm":
        p["ffn"] = RW.rwkv_channel_mix_init(ks[1], cfg)
    return p


def _init_repeat(key, cfg, pattern):
    ks = jax.random.split(key, len(pattern))
    return [_init_sublayer(k, cfg, m, f) for k, (m, f) in zip(ks, pattern)]


def _stack_layer_axis(tree):
    return jax.tree.map(lambda p: M.Param(p.value, ("layers",) + p.axes),
                        tree, is_leaf=M.is_param)


def init_group(key, cfg, g: LayerGroup):
    if g.repeats == 1:
        return _init_repeat(key, cfg, g.pattern)
    ks = jax.random.split(key, g.repeats)
    stacked = jax.vmap(lambda k: _init_repeat(k, cfg, g.pattern))(ks)
    return _stack_layer_axis(stacked)


def init_lm(key, cfg):
    groups = plan_groups(cfg)
    ks = jax.random.split(key, len(groups) + 4)
    p = {
        "embed": M.embed_init(ks[0], cfg.vocab_size, cfg.d_model),
        "groups": [init_group(ks[2 + i], cfg, g) for i, g in enumerate(groups)],
        "final_norm": M.norm_init(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = M.dense_init(ks[1], cfg.d_model, cfg.vocab_size,
                                    ("embed", "vocab"))
    if cfg.pos_emb == "learned":
        p["pos_table"] = M.Param(
            0.01 * jax.random.normal(
                ks[-1], (cfg.max_position_embeddings, cfg.d_model),
                jnp.float32), (None, "embed"))
    return p


# --------------------------------------------------------------------------
# Sublayer application
# --------------------------------------------------------------------------
def _zero_state(cfg, mixer, B, dtype):
    if mixer == "mamba":
        return {"mixer": MB.init_mamba_state(cfg, B, jnp.float32)}
    if mixer == "rwkv":
        return {"mixer": RW.init_rwkv_state(cfg, B, dtype)}
    return {}


def _apply_sublayer(p, cfg, rt, x, *, mixer, ffn, positions, state, dtype,
                    decode=False, pos=None, return_cache=False, enc_kv=None,
                    pages=None, chunk=None, layer=None):
    """Returns (x, new_state_or_cache, aux).

    ``layer``: the index of this layer in a paged leaf's stacked pools
    (``_run_groups`` hands paged leaves over whole); None when they are
    unstacked.

    ``chunk`` ({offset, valid, stage_base} arrays) selects chunked-prefill
    mode: a slab of tokens is written through the paged cache's block table
    and attends with a query offset — attention-only archs (a recurrent
    mixer scans through state and cannot resume mid-prompt from pages).
    """
    aux = jnp.zeros((), jnp.float32)
    out_state = {}
    x = PT.constrain(x, ("batch", None, None))
    h = M.apply_norm(p["norm1"], x, cfg.norm, cfg.norm_eps)
    if chunk is not None:
        if mixer != "attn" or cfg.attention == "mla" or "xattn" in p:
            raise ValueError(
                "chunked prefill supports causal-attention archs only "
                f"(got mixer={mixer!r}, attention={cfg.attention!r})")
        # verify slabs (speculative decoding) bypass the bf16 chunk stage:
        # they quantize-then-gather through the pools like plain decode,
        # which is exactly what keeps verify bit-identical to decode
        stage = None if chunk.get("no_stage") else state.get("stage")
        o, c, stg = A.apply_attention_chunk_paged(
            p["mixer"], cfg, h, state["mixer"], chunk["offset"],
            chunk["valid"], chunk["stage_base"], dtype, block_tables=pages,
            stage=stage,
            use_kernel=rt.paged_kernel_decode or M.kernel_routed(),
            layer=layer)
        out_state["mixer"] = c
        if stg is not None:
            out_state["stage"] = stg
        elif "stage" in state:
            # keep the cache tree structure stable (jit donation) when the
            # stage buffer exists but this pass bypassed it
            out_state["stage"] = state["stage"]
        x = x + o
        h = M.apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
        if ffn == "mlp":
            o = M.apply_mlp(p["ffn"], h, cfg.act, dtype)
        elif ffn == "moe":
            o, aux = MOE.apply_moe(p["ffn"], cfg, h, dtype=dtype,
                                   num_groups=rt.moe_groups)
        else:
            raise ValueError(f"chunked prefill: unsupported ffn {ffn!r}")
        return x + o, out_state, aux
    if mixer == "attn":
        if decode:
            if cfg.attention == "mla":
                o, c = ML.apply_mla_decode(p["mixer"], cfg, h, state["mixer"],
                                           pos, dtype, rt.mla_decode)
            else:
                o, c = A.apply_attention_decode(
                    p["mixer"], cfg, h, state["mixer"], pos, dtype,
                    block_tables=pages,
                    use_kernel=rt.paged_kernel_decode or
                    M.kernel_routed(), layer=layer)
            out_state["mixer"] = c
        else:
            if cfg.attention == "mla":
                o = ML.apply_mla(p["mixer"], cfg, h, positions=positions,
                                 dtype=dtype)
                if return_cache:
                    c_kv, k_pe = ML._latent(p["mixer"], cfg, h, positions,
                                            dtype)
                    out_state["mixer"] = ML.MLACache(c_kv, k_pe)
            else:
                causal = enc_kv != "encoder"    # encoder stack: bidirectional
                o = A.apply_attention(p["mixer"], cfg, h, positions=positions,
                                      dtype=dtype, causal=causal,
                                      return_kv=return_cache)
                if return_cache:
                    o, kv = o
                    if rt.kv_dtype() == "int8":     # §Perf A4
                        qk, ks = A.quantize_kv(kv.k)
                        qv, vs = A.quantize_kv(kv.v)
                        kv = A.KVCache(qk, qv, ks, vs)
                    out_state["mixer"] = kv
    elif mixer == "mamba":
        o, st = MB.apply_mamba(p["mixer"], cfg, h, state["mixer"], dtype)
        out_state["mixer"] = st
    elif mixer == "rwkv":
        o, st = RW.apply_time_mix(p["mixer"], cfg, h, state["mixer"], dtype)
        out_state["mixer"] = st
    else:
        raise KeyError(mixer)
    x = x + o

    # cross-attention (whisper decoder). ``enc_kv`` is the encoder output
    # during prefill (per-layer K/V computed + cached here); during decode the
    # per-layer K/V ride along in the cache ("xkv").
    if "xattn" in p and (decode or (enc_kv is not None
                                    and not isinstance(enc_kv, str))):
        if decode:
            xkv = state["xkv"]
        else:
            xkv = A.cross_kv(p["xattn"], cfg, enc_kv.astype(dtype), dtype)
        h = M.apply_norm(p["norm_x"], x, cfg.norm, cfg.norm_eps)
        x = x + A.apply_cross_attention(p["xattn"], cfg, h, xkv, dtype)
        out_state["xkv"] = xkv

    h = M.apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
    if ffn == "mlp":
        o = M.apply_mlp(p["ffn"], h, cfg.act, dtype)
    elif ffn == "moe":
        o, aux = MOE.apply_moe(p["ffn"], cfg, h, dtype=dtype,
                               num_groups=rt.moe_groups)
    elif ffn == "rwkv_cm":
        st = out_state.get("mixer", state.get("mixer"))
        o, st = RW.apply_channel_mix(p["ffn"], cfg, h, st, dtype)
        out_state["mixer"] = st
    x = x + o
    # the chunk-stage buffer (chunked prefill over int8 pools) rides the
    # cache tree through decode steps untouched
    if "stage" in state and "stage" not in out_state:
        out_state["stage"] = state["stage"]
    return x, out_state, aux


def _apply_repeat(ps, cfg, rt, x, *, pattern, positions, states, dtype,
                  decode=False, pos=None, return_cache=False, enc_kv=None,
                  pages=None, chunk=None, layer=None):
    new_states, aux = [], jnp.zeros((), jnp.float32)
    for p, (mixer, ffn), st in zip(ps, pattern, states):
        x, ns, a = _apply_sublayer(
            p, cfg, rt, x, mixer=mixer, ffn=ffn, positions=positions,
            state=st, dtype=dtype, decode=decode, pos=pos,
            return_cache=return_cache, enc_kv=enc_kv, pages=pages,
            chunk=chunk, layer=layer)
        new_states.append(ns)
        aux = aux + a
    return x, new_states, aux


def _split_paged(tree):
    """(paged leaves, other leaves, join) of a state tree: ``join(paged,
    other)`` rebuilds a tree of the same structure."""
    leaves, tdef = jax.tree.flatten(tree, is_leaf=_is_paged)
    is_pg = [_is_paged(v) for v in leaves]

    def join(paged, other):
        it_p, it_o = iter(paged), iter(other)
        return tdef.unflatten([next(it_p) if pg else next(it_o)
                               for pg in is_pg])

    return ([v for v, pg in zip(leaves, is_pg) if pg],
            [v for v, pg in zip(leaves, is_pg) if not pg], join)


def _is_paged(x) -> bool:
    return isinstance(x, A.PagedKVCache)


def _run_groups(params_groups, groups, cfg, rt, x, *, positions, states,
                dtype, decode=False, pos=None, return_cache=False,
                enc_kv=None, pages=None, chunk=None):
    """states: list (per group) of stacked per-repeat state lists.

    Paged KV leaves are shared page pools: a step changes one row per slot
    in them.  In a stacked group they ride whole in the layer loop's carry
    and each layer writes and reads them at its own layer index, so no
    layer's pool is sliced out and written back (a whole-pool copy per
    layer).  Per-slot state (dense KV, MLA, recurrent state, the chunk
    stage) goes through the loop sliced per layer, as ``xs``/``ys``.
    """
    out_states = []
    aux_total = jnp.zeros((), jnp.float32)

    for gi, g in enumerate(groups):
        ps, sts = params_groups[gi], states[gi]

        def body(x, p_rep, st_rep, layer):
            return _apply_repeat(p_rep, cfg, rt, x, pattern=g.pattern,
                                 positions=positions, states=st_rep,
                                 dtype=dtype, decode=decode, pos=pos,
                                 return_cache=return_cache, enc_kv=enc_kv,
                                 pages=pages, chunk=chunk, layer=layer)

        if rt.remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)
        elif rt.remat == "dots_tp":
            # B4: also save post-all-reduce activations ("tp_out") so the
            # backward pass never re-runs TP collectives.
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.checkpoint_dots,
                    jax.checkpoint_policies.save_only_these_names("tp_out")))
        elif rt.remat == "full":
            body = jax.checkpoint(body)

        if g.repeats == 1:
            x, ns, a = body(x, ps, sts, None)
            out_states.append(ns)
            aux_total = aux_total + a
            continue
        # the loop's output may differ in structure from its input (a
        # prefill returns caches for empty states): rebuild it with the
        # join of the last layer's output
        pools, rest, join = _split_paged(sts)
        if not rt.scan_layers:
            rest_list = []
            for r in range(g.repeats):
                p_r = jax.tree.map(lambda v: v[r], ps)
                x, ns, a = body(x, p_r, join(pools, [v[r] for v in rest]), r)
                pools, r_new, out_join = _split_paged(ns)
                rest_list.append(r_new)
                aux_total = aux_total + a
            out_states.append(out_join(
                pools, [jnp.stack(vs) for vs in zip(*rest_list)]))
        else:
            joins = []

            def scan_f(carry, xs):
                x, aux, pools = carry
                p_rep, r_rep, layer = xs
                x, ns, a = body(x, p_rep, join(pools, r_rep), layer)
                pools, r_new, out_join = _split_paged(ns)
                joins.append(out_join)
                return (x, aux + a, pools), r_new

            (x, aux_total, pools), rest = jax.lax.scan(
                scan_f, (x, aux_total, pools),
                (ps, rest, jnp.arange(g.repeats)))
            out_states.append(joins[-1](pools, rest))
    return x, out_states, aux_total


# --------------------------------------------------------------------------
# Input embedding (+ modality frontend stubs)
# --------------------------------------------------------------------------
@jax.named_scope("embed")
def embed_inputs(p, cfg, batch, dtype, offset=0):
    x = M.apply_embed(p["embed"], batch["tokens"], dtype)
    if cfg.frontend == "vision" and "frontend" in batch:
        x = jnp.concatenate([batch["frontend"].astype(dtype), x], axis=1)
    if cfg.pos_emb == "learned":
        T = x.shape[1]
        pos_tab = jax.lax.dynamic_slice_in_dim(
            p["pos_table"], offset, T, axis=0) if isinstance(offset, int) \
            else jnp.take(p["pos_table"], offset[:, None] + jnp.arange(T), axis=0)
        x = x + pos_tab.astype(dtype)
    elif cfg.pos_emb == "sinusoidal":
        x = x + M.sinusoidal_pos(x.shape[1], cfg.d_model).astype(dtype)
    return x


@jax.named_scope("readout")
def readout(p, cfg, x, dtype):
    """Final norm and LM head."""
    x = M.apply_norm(p["final_norm"], x, cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = M.apply_unembed(p["embed"], x, dtype)
    else:
        logits = M.apply_dense(p["lm_head"], x, dtype)
    return PT.constrain(logits, ("batch", None, "vocab"))


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------
def _zero_states(cfg, groups, B, dtype, stacked=True):
    out = []
    for g in groups:
        per_rep = [_zero_state(cfg, m, B, dtype) for (m, f) in g.pattern]
        if g.repeats > 1 and stacked:
            per_rep = jax.tree.map(
                lambda v: jnp.broadcast_to(v, (g.repeats,) + v.shape),
                per_rep)
        out.append(per_rep)
    return out


def train_logits(params, cfg, rt, batch):
    """batch: tokens (B,T) [+ frontend embeds]. Returns (logits, aux)."""
    dtype = jnp.dtype(cfg.dtype)
    groups = plan_groups(cfg)
    x = embed_inputs(params, cfg, batch, dtype)
    B, T = x.shape[:2]
    positions = jnp.arange(T)[None, :]
    states = _zero_states(cfg, groups, B, dtype)
    x, _, aux = _run_groups(params["groups"], groups, cfg, rt, x,
                            positions=positions, states=states, dtype=dtype)
    return readout(params, cfg, x, dtype), aux


def prefill(params, cfg, rt, batch):
    """Full-sequence forward that also returns decode caches."""
    dtype = jnp.dtype(cfg.dtype)
    cache_dtype = jnp.dtype(rt.kv_dtype()) if rt.kv_dtype() != "int8" \
        else dtype
    groups = plan_groups(cfg)
    x = embed_inputs(params, cfg, batch, dtype)
    B, T = x.shape[:2]
    positions = jnp.arange(T)[None, :]
    states = _zero_states(cfg, groups, B, dtype)
    x, caches, aux = _run_groups(params["groups"], groups, cfg, rt, x,
                                 positions=positions, states=states,
                                 dtype=dtype, return_cache=True)
    return readout(params, cfg, x, dtype), caches


def init_caches(cfg, rt, B, S, dtype, page_spec=None, chunk_stage: int = 0):
    """Pre-allocated decode caches for every group/sublayer.

    With ``page_spec`` (a ``serve.kvcache.PageSpec``) plain attention KV
    leaves become shared ``PagedKVCache`` page pools addressed by the
    engine's block table — int8 pools with scale pages when the spec says
    ``kv_dtype="int8"`` (DESIGN.md §5); MLA, dense-int8
    (``cache_dtype="int8"`` without an int8 page spec) and cross-attention
    caches keep the dense per-slot layout (documented fallback, §4).

    ``chunk_stage`` (a chunk size, > 0 under the chunked-prefill engine)
    adds a one-slot bf16 ``ChunkStage`` buffer next to *quantized* paged
    leaves so chunked prefill never re-reads its own rows through int8
    pages (DESIGN.md §6); bf16 pools need no stage.
    """
    groups = plan_groups(cfg)
    paged_int8 = page_spec is not None and \
        jnp.dtype(page_spec.kv_dtype) == jnp.dtype(jnp.int8)
    out = []
    for g in groups:
        per_rep = []
        for (m, f) in g.pattern:
            if m == "attn":
                quant = rt.kv_dtype() == "int8" and cfg.attention != "mla"
                if cfg.attention == "mla":
                    c = ML.init_mla_cache(cfg, B, S, dtype)
                elif page_spec is not None and (not quant or paged_int8):
                    c = A.init_paged_cache(cfg, page_spec, dtype)
                else:
                    c = A.init_cache(cfg, B, S, dtype, quantized=quant)
                entry = {"mixer": c}
                if chunk_stage > 0 and paged_int8 and cfg.attention != "mla":
                    # cover the gathered view plus a full pad chunk so the
                    # staging write never clamps at the sequence end
                    ps = page_spec.page_size
                    S_stage = max(-(-S // ps) * ps, S + chunk_stage)
                    KV, hd = cfg.num_kv_heads, cfg.head_dim
                    entry["stage"] = A.ChunkStage(
                        jnp.zeros((1, S_stage, KV, hd), jnp.bfloat16),
                        jnp.zeros((1, S_stage, KV, hd), jnp.bfloat16))
                if cfg.encoder_decoder:
                    entry["xkv"] = A.init_cache(
                        cfg, B, cfg.cross_attention_len, dtype)
                per_rep.append(entry)
            else:
                per_rep.append(_zero_state(cfg, m, B, dtype))
        if g.repeats > 1:
            per_rep = jax.tree.map(
                lambda v: jnp.broadcast_to(
                    v, (g.repeats,) + v.shape).astype(v.dtype), per_rep)
        out.append(per_rep)
    return out


def chunk_prefill_step(params, cfg, rt, batch, caches):
    """One chunked-prefill slab against the shared paged caches.

    batch: tokens (B, C) right-padded; offset (B,) absolute position of
    token 0; valid (B,) real rows; stage_base (B,) first position owned by
    this request (== the shared-prefix length); block_tables (B, nblk).
    Returns (last-valid-row logits (B, V), new caches) — the logits row
    only matters on a prompt's final chunk, where its argmax is the
    request's first generated token (same greedy readout as the bucketed
    ``prefill_step``).
    """
    dtype = jnp.dtype(cfg.dtype)
    groups = plan_groups(cfg)
    offset, valid = batch["offset"], batch["valid"]
    x = embed_inputs(params, cfg, batch, dtype, offset=offset)
    C = x.shape[1]
    positions = offset[:, None] + jnp.arange(C)[None, :]
    chunk = {"offset": offset, "valid": valid,
             "stage_base": batch.get("stage_base", jnp.zeros_like(offset))}
    x, new_caches, _ = _run_groups(
        params["groups"], groups, cfg, rt, x, positions=positions,
        states=caches, dtype=dtype, chunk=chunk,
        pages=batch.get("block_tables"))
    # gather each row's last valid position BEFORE the O(V) readout (the
    # same trick as the bucketed prefill: never unembed discarded rows)
    last = jnp.take_along_axis(x, (valid - 1)[:, None, None], axis=1)
    logits = readout(params, cfg, last, dtype)          # (B, 1, V)
    return logits[:, 0], new_caches


def verify_step(params, cfg, rt, batch, caches):
    """Score a speculative window: a chunked slab keeping ALL row logits.

    batch: tokens (B, W) = [last emitted token, d_1..d_k] right-padded;
    offset (B,) the last emitted token's position; valid (B,) = k_eff + 1
    real rows (0 disables a row); block_tables (B, nblk).  Returns
    (logits (B, W, V), new caches): row i conditions on everything up to
    and including the first i draft tokens, i.e. row i scores position
    offset + i + 1.  KV rows offset..offset+valid-1 are written through
    the block table exactly like chunked prefill — int8 pools get the
    same quantize-then-gather treatment as decode, so verify logits match
    decode logits bit-for-bit — but the bf16 chunk stage is bypassed
    (``no_stage``): rejected rows are rewritten by the next verify pass
    (whose offset lands exactly on the first rejected row) before anyone
    can attend over them.
    """
    dtype = jnp.dtype(cfg.dtype)
    groups = plan_groups(cfg)
    offset, valid = batch["offset"], batch["valid"]
    x = embed_inputs(params, cfg, batch, dtype, offset=offset)
    C = x.shape[1]
    positions = offset[:, None] + jnp.arange(C)[None, :]
    chunk = {"offset": offset, "valid": valid,
             "stage_base": jnp.zeros_like(offset), "no_stage": True}
    x, new_caches, _ = _run_groups(
        params["groups"], groups, cfg, rt, x, positions=positions,
        states=caches, dtype=dtype, chunk=chunk,
        pages=batch.get("block_tables"))
    return readout(params, cfg, x, dtype), new_caches


def decode_step(params, cfg, rt, batch, caches):
    """batch: tokens (B,1), pos (B,) [+ block_tables (B,nblk) when the cache
    is paged]. Returns (logits (B,1,V), new caches)."""
    dtype = jnp.dtype(cfg.dtype)
    groups = plan_groups(cfg)
    pos = batch["pos"]
    x = embed_inputs(params, cfg, batch, dtype, offset=pos)
    x, new_caches, _ = _run_groups(
        params["groups"], groups, cfg, rt, x, positions=pos[:, None],
        states=caches, dtype=dtype, decode=True, pos=pos,
        enc_kv=batch.get("enc_kv"), pages=batch.get("block_tables"))
    return readout(params, cfg, x, dtype), new_caches
