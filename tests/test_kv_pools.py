"""Paged KV pools carried whole through the layer loop.

A stacked layer group hands its page pools to every layer whole, and each
layer writes and reads them at its own layer index (``_run_groups``).  Each
case fills a dense per-slot cache with a random history, lays the same rows
into lane-dense pages through a shuffled block table, and runs one step on
both: the paged step's logits (and so its tokens) must equal the dense-cache
step's exactly, and the step must change no pool row but the ones it wrote,
which must hold the dense step's rows of every layer exactly.

A chunk reads out one gathered row where decode reads out its only row, and
XLA may fuse the final norm differently there: its logits may differ from
decode's by one bf16 rounding (the same numbers as before the pools were
carried), so for it the tokens are compared, and the logits to that rounding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import RuntimeConfig, build_model
from repro.models import attention as A
from repro.models import modules as M
from repro.serve.kvcache import NULL_PAGE, PagedKVCache, PageSpec

B, S, W = 2, 64, 3
HIST = np.array([20, 37], np.int32)          # rows already in each slot
VALID = np.array([3, 2], np.int32)           # verify rows per slot
# layer group: (layers, scan_layers) — a stacked group scanned, the same
# group unrolled, and a single-repeat group whose pools have no layer axis
LAYOUTS = {"scan": (3, True), "unrolled": (3, False), "single": (1, True)}
KV = {"bf16": ("", 16), "int8": ("int8", 32)}   # kv_cache_dtype, page


def _is_kv(x):
    return isinstance(x, (A.KVCache, PagedKVCache))


def _setup(kv, layout):
    layers, scan = LAYOUTS[layout]
    cfg = reduced(get_config("qwen1.5-0.5b"),
                  num_layers=layers, d_model=64, d_ff=128, vocab_size=128,
                  num_heads=2, num_kv_heads=2, head_dim=32)
    model = build_model(cfg, RuntimeConfig(
        remat="none", scan_layers=scan, kv_cache_dtype=KV[kv][0]))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    return model, params


def _history(model, rng):
    """Dense per-slot caches (int8 with scales under an int8 runtime)
    holding random rows at every position."""
    def fill(c):
        def rand(a):
            return jnp.asarray(rng.normal(size=a.shape), jnp.bfloat16)
        if not c.quantized:
            return A.KVCache(rand(c.k), rand(c.v))
        k8, ks = A.quantize_kv(rand(c.k))
        v8, vs = A.quantize_kv(rand(c.v))
        return A.KVCache(k8, v8, ks, vs)
    return jax.tree.map(fill, model.init_caches(B, S), is_leaf=_is_kv)


def _dequantized(dense):
    def deq(c):
        if not c.quantized:
            return c
        return A.KVCache(A.dequantize_kv(c.k, c.k_scale, jnp.bfloat16),
                         A.dequantize_kv(c.v, c.v_scale, jnp.bfloat16))
    return jax.tree.map(deq, dense, is_leaf=_is_kv)


def _lane_dense(a, spec, bt):
    """Dense rows (*L, B, S, KV, X) as pages (*L, P, page, KV*X)."""
    a = np.asarray(a)
    lead = a.shape[:-4]
    out = np.zeros(lead + (spec.num_pages, spec.page_size,
                           a.shape[-2] * a.shape[-1]), a.dtype)
    for b in range(bt.shape[0]):
        for t in range(S):
            out[..., bt[b, t // spec.page_size], t % spec.page_size, :] = \
                a[..., b, t, :, :].reshape(lead + (-1,))
    return out


def _paged(model, dense, spec, bt, chunk_stage):
    """The engine's paged cache tree, its pools holding ``dense``'s rows."""
    rows = iter(jax.tree.leaves(dense, is_leaf=_is_kv))

    def fill(leaf):
        if not isinstance(leaf, PagedKVCache):
            return leaf
        d = next(rows)
        return PagedKVCache(*(None if a is None else
                              jnp.asarray(_lane_dense(a, spec, bt))
                              for a in d))
    tree = model.init_caches(B, S, page_spec=spec, chunk_stage=chunk_stage)
    return jax.tree.map(fill, tree, is_leaf=_is_kv)


def _dense_decode(model, params, caches, toks, n):
    """``n`` greedy-free decode steps, teacher-forced on ``toks``: the
    logits of each, and the caches after the last."""
    step = jax.jit(model.decode_step)
    out = []
    for i in range(n):
        lg, caches = step(params, {"tokens": jnp.asarray(toks[:, i:i + 1]),
                                   "pos": jnp.asarray(HIST + i)}, caches)
        out.append(np.asarray(lg[:, 0], np.float32))
    return out, caches


@pytest.mark.parametrize("step", ["decode", "chunk", "verify"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("kv", list(KV))
def test_carried_pools_match_dense_and_write_sparsely(kv, layout, step):
    model, params = _setup(kv, layout)
    rng = np.random.default_rng(7)
    spec = PageSpec.for_engine(B, S, KV[kv][1],
                               dtype=KV[kv][0] or "bfloat16")
    nblk = spec.blocks_per_slot
    bt = (rng.permutation(spec.num_pages - 1)[:B * nblk] + 1).reshape(
        B, nblk).astype(np.int32)
    toks = rng.integers(1, 128, (B, W)).astype(np.int32)
    dense = _history(model, rng)
    # int8 chunks read the slab's own rows from the bf16 stage, not the
    # quantized pages: their dense twin holds the dequantized history and
    # stores new rows in bf16
    stage = step == "chunk" and kv == "int8"
    caches = _paged(model, dense, spec, bt, chunk_stage=4 if stage else 0)
    want, dense_after = _dense_decode(
        model, params, _dequantized(dense) if stage else dense, toks,
        1 if step == "decode" else W)

    tables = jnp.asarray(bt)
    if step == "decode":
        fn = jax.jit(model.decode_step)
        got, new = fn(params, {"tokens": jnp.asarray(toks[:, :1]),
                               "pos": jnp.asarray(HIST),
                               "block_tables": tables}, caches)
        np.testing.assert_array_equal(np.asarray(got[:, 0], np.float32),
                                      want[0])
        written = [(b, HIST[b]) for b in range(B)]
    elif step == "verify":
        fn = jax.jit(model.verify_step)
        got, new = fn(params, {"tokens": jnp.asarray(toks),
                               "offset": jnp.asarray(HIST),
                               "valid": jnp.asarray(VALID),
                               "block_tables": tables}, caches)
        got = np.asarray(got, np.float32)
        for b in range(B):
            for i in range(VALID[b]):
                np.testing.assert_array_equal(got[b, i], want[i][b])
        written = [(b, HIST[b] + i) for b in range(B)
                   for i in range(VALID[b])]
    else:
        # one slab of 4 rows for slot 0, 3 of them real: the pad row goes
        # to the NULL page
        fn = jax.jit(model.chunk_step)
        slab = np.concatenate([toks[:1], [[0]]], axis=1).astype(np.int32)
        got, new = fn(params, {"tokens": jnp.asarray(slab),
                               "offset": jnp.asarray(HIST[:1]),
                               "valid": jnp.asarray([W], np.int32),
                               "stage_base": jnp.asarray(HIST[:1]),
                               "block_tables": tables[:1]}, caches)
        got, ref = np.asarray(got[0], np.float32), want[W - 1][0]
        assert np.argmax(got) == np.argmax(ref)
        ulp = 2.0 ** (np.floor(np.log2(np.abs(ref).max())) - 7)
        np.testing.assert_allclose(got, ref, rtol=0, atol=ulp)
        written = [(0, HIST[0] + i) for i in range(W)]

    page = spec.page_size
    olds = jax.tree.leaves(caches, is_leaf=_is_kv)
    news = jax.tree.leaves(new, is_leaf=_is_kv)
    refs = jax.tree.leaves(dense_after, is_leaf=_is_kv)
    for old, nw, ref in zip(olds, news, refs):
        if not isinstance(old, PagedKVCache):
            continue
        assert nw.k_pool.shape == old.k_pool.shape
        if stage:       # the dense twin stored bf16 rows: quantize them
            k8, ks = A.quantize_kv(ref.k)
            v8, vs = A.quantize_kv(ref.v)
            ref = A.KVCache(k8, v8, ks, vs)
        for o, n, r in zip(old, nw, ref):
            if o is None:
                continue
            o, n, r = np.asarray(o), np.asarray(n), np.asarray(r)
            keep = np.ones(o.shape[:-1], bool)
            keep[..., NULL_PAGE, :] = False     # pad rows land here
            for b, t in written:
                pid, row = bt[b, t // page], t % page
                keep[..., pid, row] = False
                np.testing.assert_array_equal(
                    n[..., pid, row, :],
                    r[..., b, t, :, :].reshape(r.shape[:-4] + (-1,)))
            np.testing.assert_array_equal(n[keep], o[keep])
