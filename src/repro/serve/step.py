"""Serving steps: prefill_step / serve_step (single-token decode).

serve_step is the paper's workload: one new token against a KV cache — every
matmul a GEMV-class memory-bound op.  Greedy sampling keeps the step a pure
function (temperature sampling derives a per-(slot, position) key so samples
are independent across the batch).

prefill_step is *bucketed*: it takes a fixed-size batch of right-padded
prompts plus their valid lengths and reads each row's next token at
``length - 1`` — so the engine compiles one prefill executable per length
bucket instead of one per distinct prompt length.

``tuned_kernel_configs`` resolves the best-known TroopConfigs for the decode
hot kernels at the serving shapes (from the persistent tune cache, heuristic
defaults when untuned) so the serving layer and kernel-backed model paths
read tuned configs from one place.

The token choice of every step (argmax or sampling) runs under the
``jax.named_scope`` ``sample``, beside the model's ``embed``,
``attention``, ``mlp`` and ``readout`` scopes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tuned_kernel_configs(model_cfg, batch_size: int, max_seq: int,
                         dtype=jnp.bfloat16, page_size: int = 16,
                         num_pages=None, chunk_size: int = 32):
    """TroopConfigs for the decode-path kernels at the serving shapes.

    Pure shape-level lookup (ShapeDtypeStruct placeholders — nothing is
    allocated or traced): decode attention over the KV cache (dense and
    paged layouts) and the GEMV-class readout projection.  The paged pool
    geometry comes from ``PageSpec.for_engine`` — the same formula the
    engine allocates with — so the tuned-config key always matches the
    pool the engine will actually run (pass ``num_pages`` when
    overcommitting).
    """
    import repro.kernels  # noqa: F401  (populates the tune registry)
    from repro.quant.tensor import granule
    from repro.serve.kvcache import PageSpec
    from repro.tune import get_tuned

    sds = jax.ShapeDtypeStruct
    B, S = batch_size, max_seq
    KV, hd, H = (model_cfg.num_kv_heads, model_cfg.head_dim,
                 model_cfg.num_heads)
    d, V = model_cfg.d_model, model_cfg.vocab_size
    spec = PageSpec.for_engine(B, S, page_size, num_pages, jnp.dtype(dtype))
    P, nblk = spec.num_pages, spec.blocks_per_slot
    # int8 pages obey the coarser int8 layout granule (32 rows); the scale
    # group of the quantized readout GEMV likewise (mechanism D, DESIGN §5)
    p8 = -(-page_size // granule()) * granule()
    spec8 = PageSpec.for_engine(B, S, p8, num_pages, "int8")
    P8, nblk8 = spec8.num_pages, spec8.blocks_per_slot
    g = 128 if d % 128 == 0 else d
    return {
        "decode_attention": get_tuned(
            "decode_attention",
            sds((B, H, hd), dtype), sds((B, S, KV, hd), dtype),
            sds((B, S, KV, hd), dtype), sds((B,), jnp.int32)),
        "decode_attention_int8": get_tuned(
            "decode_attention_int8",
            sds((B, H, hd), dtype),
            sds((B, S, KV, hd), jnp.int8), sds((B, S, KV, 1), jnp.bfloat16),
            sds((B, S, KV, hd), jnp.int8), sds((B, S, KV, 1), jnp.bfloat16),
            sds((B,), jnp.int32)),
        "paged_decode_attention": get_tuned(
            "paged_decode_attention",
            sds((B, H, hd), dtype),
            sds((P, page_size, KV, hd), dtype),
            sds((P, page_size, KV, hd), dtype),
            sds((B, nblk), jnp.int32), sds((B,), jnp.int32)),
        "paged_decode_attention_int8": get_tuned(
            "paged_decode_attention_int8",
            sds((B, H, hd), dtype),
            sds((P8, p8, KV, hd), jnp.int8),
            sds((P8, p8, KV, 1), jnp.bfloat16),
            sds((P8, p8, KV, hd), jnp.int8),
            sds((P8, p8, KV, 1), jnp.bfloat16),
            sds((B, nblk8), jnp.int32), sds((B,), jnp.int32)),
        "prefill_attention_paged": get_tuned(
            "prefill_attention_paged",
            sds((1, chunk_size, H, hd), dtype),
            sds((P, page_size, KV, hd), dtype),
            sds((P, page_size, KV, hd), dtype),
            sds((1, nblk), jnp.int32), sds((1,), jnp.int32),
            sds((1,), jnp.int32)),
        "gemv": get_tuned("gemv", sds((V, d), dtype), sds((d,), dtype)),
        "qgemv": get_tuned(
            "qgemv", sds((V, d), jnp.int8), sds((V, d // g), jnp.float32),
            sds((d,), dtype)),
        "rmsnorm": get_tuned("rmsnorm", sds((B, d), dtype),
                             sds((d,), jnp.float32)),
    }


def make_chunk_step(model):
    """Chunked prefill: one fixed-size token slab against the shared paged
    caches.  batch = {tokens (1, C) right-padded, offset (1,), valid (1,),
    stage_base (1,), block_tables (1, nblk)} -> (next_tok (1,), caches).
    The returned token is the greedy argmax of the last valid row's logits
    — only meaningful on a prompt's final slab (identical readout to the
    bucketed ``make_prefill_step``, so the two engines emit the same first
    token)."""
    def chunk_step(params, batch, caches):
        logits, caches = model.chunk_step(params, batch, caches)  # (B, V)
        with jax.named_scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches
    return chunk_step


def make_draft_step(model):
    """Speculative draft forward: a chunked slab against the draft model's
    own paged caches, returning the full fp32 logits row of each slot's
    last valid position (the scheduler samples/argmaxes on the host so one
    compiled function serves greedy and temperature drafting).  batch =
    {tokens (B, W) right-padded, offset (B,), valid (B,), stage_base (B,),
    block_tables (B, nblk)} -> (logits (B, V), caches)."""
    def draft_step(params, batch, caches):
        logits, caches = model.chunk_step(params, batch, caches)  # (B, V)
        return logits.astype(jnp.float32), caches
    return draft_step


def make_verify_step(model):
    """Speculative verify forward: score all W rows of the slab in one
    target weight pass (the TROOP lever — (k+1)x tokens per byte of
    weights/KV streamed).  batch = {tokens (B, W), offset (B,),
    valid (B,), block_tables (B, nblk)} -> (logits (B, W, V) fp32,
    caches); row i scores position offset + i + 1."""
    def verify_step(params, batch, caches):
        logits, caches = model.verify_step(params, batch, caches)
        return logits.astype(jnp.float32), caches
    return verify_step


def make_prefill_step(model):
    """Bucketed batched prefill: batch = {tokens (Bp, L) right-padded,
    length (Bp,) valid rows incl. any frontend prefix} -> (next_tok (Bp,),
    caches).  Without ``length`` the last position is read (B=1 compat)."""
    def prefill_step(params, batch):
        length = batch.get("length")
        feed = {k: v for k, v in batch.items() if k != "length"}
        logits, caches = model.prefill(params, feed)
        with jax.named_scope("sample"):
            if length is None:
                next_tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
                return next_tok.astype(jnp.int32), caches
            # gather each row's last valid position first: O(Bp*V) argmax
            # instead of O(Bp*L*V) over positions that are then discarded
            last = jnp.take_along_axis(
                logits, (length - 1)[:, None, None], axis=1)[:, 0]  # (Bp, V)
            next_tok = jnp.argmax(last, axis=-1)
            return next_tok.astype(jnp.int32), caches
    return prefill_step


def sample_keys(pos, batch_size: int, seed: int = 0, nonce=None):
    """Per-(request, slot, position) sampling keys: fold the slot index,
    the row's position, and a per-admission ``nonce`` into one base key, so
    no two slots, no two steps of one slot, and no two requests reusing a
    slot ever share a key (the seed engine folded only ``pos[0]``, giving
    every slot the same key each step: correlated samples — and without
    the nonce, a request re-admitted to the same slot would replay its
    predecessor's randomness position for position)."""
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        base, jnp.arange(batch_size))
    keys = jax.vmap(jax.random.fold_in)(keys, pos)
    if nonce is not None:
        keys = jax.vmap(jax.random.fold_in)(keys, nonce)
    return keys


def make_serve_step(model, *, temperature: float = 0.0, seed: int = 0,
                    troop_configs=None):
    """``troop_configs`` (from ``tuned_kernel_configs``) is attached to the
    returned step for kernel-backed decode paths and introspection."""
    def serve_step(params, batch, caches):
        logits, caches = model.decode_step(params, batch, caches)
        with jax.named_scope("sample"):
            if temperature > 0:
                keys = sample_keys(batch["pos"], batch["pos"].shape[0], seed,
                                   nonce=batch.get("sample_nonce"))
                next_tok = jax.vmap(
                    lambda k, row: jax.random.categorical(k,
                                                          row / temperature)
                )(keys, logits[:, -1, :])[:, None].astype(jnp.int32)
            else:
                next_tok = jnp.argmax(logits[:, -1:, :],
                                      axis=-1).astype(jnp.int32)
        return next_tok, caches
    serve_step.troop_configs = troop_configs
    return serve_step
