"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.troop import BASELINE, TROOP, TroopConfig
from repro.kernels import ops as K
from repro.kernels import ref as R

CFGS = [BASELINE, TROOP,
        TroopConfig(streams=2, unroll=1, block_n=128, block_k=256)]


def tol(dt):
    return dict(rtol=3e-2, atol=3e-2) if dt == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("N,K_", [(256, 1024), (512, 4096), (128, 512)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_gemv(N, K_, dt):
    w = jax.random.normal(jax.random.PRNGKey(0), (N, K_), dt)
    x = jax.random.normal(jax.random.PRNGKey(1), (K_,), dt)
    want = R.gemv(w, x)
    for cfg in CFGS:
        np.testing.assert_allclose(K.gemv(w, x, cfg), want, **tol(dt))


@pytest.mark.parametrize("K_", [4096, 32768])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_dotp(K_, dt):
    x = jax.random.normal(jax.random.PRNGKey(0), (K_,), dt)
    y = jax.random.normal(jax.random.PRNGKey(1), (K_,), dt)
    want = R.dotp(x, y)
    for cfg in CFGS:
        np.testing.assert_allclose(K.dotp(x, y, cfg), want,
                                   rtol=5e-2 if dt == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("K_", [4096, 65536])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_axpy(K_, dt):
    x = jax.random.normal(jax.random.PRNGKey(0), (K_,), dt)
    y = jax.random.normal(jax.random.PRNGKey(1), (K_,), dt)
    want = np.asarray(R.axpy(1.7, x, y), np.float32)
    for cfg in CFGS:
        got = np.asarray(K.axpy(1.7, x, y, cfg), np.float32)
        np.testing.assert_allclose(got, want, **tol(dt))


@pytest.mark.parametrize("T,d", [(64, 512), (128, 1024), (8, 256)])
def test_rmsnorm(T, d):
    x = jax.random.normal(jax.random.PRNGKey(0), (T, d), jnp.bfloat16)
    s = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)
    got = np.asarray(K.rmsnorm(x, s), np.float32)
    want = np.asarray(R.rmsnorm(x, s), np.float32)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("n", [1000, 4096, 131072])
def test_fused_adamw(n):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p = jax.random.normal(ks[0], (n,))
    g = jax.random.normal(ks[1], (n,))
    mu = 0.1 * jax.random.normal(ks[2], (n,))
    nu = jnp.abs(0.1 * jax.random.normal(ks[3], (n,)))
    hp = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, bc1=0.2, bc2=0.1)
    want = R.fused_adamw(p, g, mu, nu, **hp)
    for cfg in (BASELINE, TROOP):
        got = K.fused_adamw(p, g, mu, nu, **hp, cfg=cfg)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B,H,KV,hd,S", [
    (2, 8, 8, 64, 1024), (2, 8, 2, 64, 2048), (1, 16, 4, 128, 512),
    (4, 4, 4, 32, 256),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, KV, hd, S, dt):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dt)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dt)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dt)
    length = jnp.asarray([(S // 2 + 17 * b) % S + 1 for b in range(B)],
                         jnp.int32)
    want = np.asarray(R.decode_attention(q, k, v, length), np.float32)
    for cfg in (BASELINE, TROOP):
        got = np.asarray(K.decode_attention(q, k, v, length, cfg), np.float32)
        np.testing.assert_allclose(got, want, **tol(dt))


@pytest.mark.parametrize("B,H,KV,hd,page,nblk", [
    (2, 8, 8, 64, 16, 8), (2, 8, 2, 64, 32, 4), (1, 16, 4, 128, 16, 3),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, H, KV, hd, page, nblk, dt):
    """Block-table gather == dense flash-decode (incl. odd-nblk fallback)."""
    P = 1 + B * nblk
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dt)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), dt)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), dt)
    # physically scattered, logically contiguous tables + ragged lengths
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)
    S = nblk * page
    length = jnp.asarray([(S // 2 + 17 * b) % S + 1 for b in range(B)],
                         jnp.int32)
    want = np.asarray(R.paged_decode_attention(q, k_pool, v_pool, bt, length),
                      np.float32)
    for cfg in (BASELINE, TROOP):
        got = np.asarray(
            K.paged_decode_attention(q, k_pool, v_pool, bt, length, cfg),
            np.float32)
        np.testing.assert_allclose(got, want, **tol(dt))
    # paged result == dense kernel over the gathered logical view
    k_d = k_pool[bt].reshape(B, S, KV, hd)
    v_d = v_pool[bt].reshape(B, S, KV, hd)
    dense = np.asarray(K.decode_attention(q, k_d, v_d, length, TROOP),
                       np.float32)
    np.testing.assert_allclose(dense, want, **tol(dt))


@pytest.mark.parametrize("B,C,H,KV,hd,page,nblk", [
    (2, 16, 8, 8, 64, 16, 8), (2, 32, 8, 2, 64, 32, 4),
    (1, 16, 16, 4, 128, 16, 3),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_prefill_attention_paged(B, C, H, KV, hd, page, nblk, dt):
    """Chunked-prefill slab over scattered pages == causal oracle with a
    query offset (incl. odd-nblk one-stream fallback and pad rows)."""
    P = 1 + B * nblk
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, C, H, hd), dt)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), dt)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), dt)
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)
    # slab b enters mid-sequence (prefix-cache hit) with a ragged tail
    q_offset = jnp.asarray([5 * b for b in range(B)], jnp.int32)
    valid = jnp.asarray([C - 3 * b for b in range(B)], jnp.int32)
    length = q_offset + valid
    want = np.asarray(
        R.prefill_attention_paged(q, k_pool, v_pool, bt, q_offset, length),
        np.float32)
    for cfg in (BASELINE, TROOP):
        got = np.asarray(
            K.prefill_attention_paged(q, k_pool, v_pool, bt, q_offset,
                                      length, cfg), np.float32)
        for b in range(B):                 # pad rows are garbage by contract
            v = int(valid[b])
            np.testing.assert_allclose(got[b, :v], want[b, :v], **tol(dt))


@pytest.mark.parametrize("B,T,H,KV,hd,S", [
    (2, 256, 8, 8, 64, 256), (1, 512, 8, 2, 64, 512),
])
def test_flash_attention(B, T, H, KV, hd, S):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    want = R.flash_attention(q, k, v, causal=True)
    for cfg in (BASELINE, TROOP):
        got = K.flash_attention(q, k, v, True, cfg)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,T,H,hd", [(2, 64, 4, 32), (1, 128, 2, 64)])
def test_wkv6(B, T, H, hd):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))))
    u = 0.5 * jnp.ones((H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    want_y, want_s = R.wkv6(r, k, v, w, u, s0)
    for cfg in (BASELINE, TROOP):
        y, s = K.wkv6(r, k, v, w, u, s0, cfg)
        np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_wkv6_with_carried_state():
    """Nonzero initial state folds in exactly (decode chaining path)."""
    B, T, H, hd = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))))
    u = 0.3 * jnp.ones((H, hd))
    s0 = jax.random.normal(ks[4], (B, H, hd, hd))
    want_y, want_s = R.wkv6(r, k, v, w, u, s0)
    y, s = K.wkv6_with_state(r, k, v, w, u, s0)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_decode_stats_lse_combine_split_s():
    """Split-S partials combine to the full result (SP decode invariant)."""
    B, H, KV, hd, S = 2, 8, 4, 64, 1024
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    length = jnp.asarray([700, 1024], jnp.int32)
    want = np.asarray(R.decode_attention(q, k, v, length), np.float32)
    n_shards = 4
    Sl = S // n_shards
    partials = []
    for i in range(n_shards):
        partials.append(K.decode_attention_stats(
            q, k[:, i * Sl:(i + 1) * Sl], v[:, i * Sl:(i + 1) * Sl],
            length, TROOP, s_offset=i * Sl))
    got = np.asarray(K.lse_combine(partials), np.float32).reshape(B, H, hd)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,T,di,ds", [(1, 64, 128, 16), (2, 32, 64, 8)])
def test_mamba_scan(b, T, di, ds):
    from repro.kernels.mamba_scan import mamba_scan
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, T, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, di)))
    Bm = jax.random.normal(ks[2], (b, T, ds))
    Cm = jax.random.normal(ks[3], (b, T, ds))
    A = -jnp.exp(jax.random.normal(ks[4], (di, ds)))
    D = jnp.ones((di,))
    s0 = jnp.zeros((b, di, ds))
    want_y, want_s = R.mamba_scan(x, dt, Bm, Cm, A, D, s0)
    for cfg in (BASELINE, TROOP):
        y, s = mamba_scan(x, dt, Bm, Cm, A, D, s0, cfg)
        np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,KV,hd,S", [(2, 8, 4, 64, 1024),
                                         (1, 16, 8, 128, 512)])
def test_decode_attention_int8(B, H, KV, hd, S):
    """Quantized flash-decode tracks the fp oracle (§Perf A4 kernel)."""
    from repro.kernels.decode_attention import decode_attention_int8
    from repro.models.attention import dequantize_kv, quantize_kv
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    length = jnp.asarray([S // 2, S][:B], jnp.int32)
    k8, ksc = quantize_kv(k)
    v8, vsc = quantize_kv(v)
    got = decode_attention_int8(q, k8, ksc, v8, vsc, length, TROOP)
    # exact vs the oracle on the dequantized cache (isolates kernel error)
    want = R.decode_attention(q, dequantize_kv(k8, ksc, jnp.float32),
                              dequantize_kv(v8, vsc, jnp.float32), length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # within quantization noise of the unquantized oracle
    full = R.decode_attention(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=0.1, atol=0.05)


def test_gemv_ragged_last_row_tile():
    """N % block_n != 0 (a 151936-row vocabulary at block_n=256 leaves 128
    rows): the ragged last tile writes every row."""
    w = jax.random.normal(jax.random.PRNGKey(0), (384, 512), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (512,), jnp.bfloat16)
    want = np.asarray(R.gemv(w, x))
    for cfg in CFGS:
        got = np.asarray(K.gemv(w, x, cfg))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, **tol(jnp.bfloat16))


@pytest.mark.parametrize("block_k,unroll", [(512, 1), (256, 2), (256, 1)])
@pytest.mark.parametrize("streams", [1, 2])
def test_dotp_axpy_sub_granule_block(block_k, unroll, streams):
    """Configs whose block was fewer rows than the bf16 sublane granule of
    16 (4 rows at the default block_k=512, unroll=1) round up to it."""
    cfg = TroopConfig(streams=streams, unroll=unroll, block_k=block_k)
    x = jax.random.normal(jax.random.PRNGKey(0), (8192,), jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(1), (8192,), jnp.bfloat16)
    np.testing.assert_allclose(K.dotp(x, y, cfg), R.dotp(x, y), rtol=5e-2)
    np.testing.assert_allclose(
        np.asarray(K.axpy(1.7, x, y, cfg), np.float32),
        np.asarray(R.axpy(1.7, x, y), np.float32), **tol(jnp.bfloat16))


def test_block_rows_granule():
    from repro.core.troop import block_rows
    assert block_rows(1 << 16, 4, 16) == 16       # rounded up to the granule
    assert block_rows(1 << 16, 40, 16) == 32      # down to a dividing multiple
    assert block_rows(48, 64, 16) == 48
    assert block_rows(40, 64, 16) == 40           # no multiple divides: whole


def test_pallas_interpret_follows_backend(monkeypatch):
    from repro.core.troop import pallas_interpret
    assert pallas_interpret() is True              # tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pallas_interpret()
