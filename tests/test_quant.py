"""repro.quant: QuantizedTensor roundtrips, int4 packing, the policy pass
over model params, fused-dequant kernels vs their oracles, int8 page pools
(paged vs dense vs bf16 engine parity + allocator accounting), and the
dedup of the historical int8 helpers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro.configs import get_config, reduced
from repro.core.troop import BASELINE, TROOP
from repro.kernels import ref as R
from repro.models import RuntimeConfig, build_model
from repro.models import modules as M
from repro.quant import (QuantizedTensor, dequantize, pack_int4,
                         quantize, quantize_params, quantized_stats,
                         unpack_int4)
from repro.serve import EngineConfig
from repro.serve.kvcache import PagedBackend
from repro.serve.scheduler import Request, ServingEngine
from repro.serve.step import make_prefill_step, make_serve_step


# --------------------------------------------------------------------------
# tensor layer
# --------------------------------------------------------------------------
@pytest.mark.parametrize("bits,rtol", [(8, 1e-2), (4, 2e-1)])
@pytest.mark.parametrize("shape,axis", [((64, 256), -1), ((256, 64), -2),
                                        ((3, 64, 256), -1)])
def test_quantize_dequantize_roundtrip(bits, rtol, shape, axis):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    qt = quantize(x, bits=bits, group_size=128, axis=axis)
    assert qt.values.dtype == jnp.int8
    assert qt.shape == shape
    y = dequantize(qt, jnp.float32)
    assert float(jnp.max(jnp.abs(y - x))) <= rtol * float(jnp.max(jnp.abs(x)))


def test_quantize_per_tensor_scalar_scale():
    x = jax.random.normal(jax.random.PRNGKey(1), (333,), jnp.float32)
    qt = quantize(x, bits=8, axis=None)
    assert qt.scales.shape == ()
    y = dequantize(qt)
    assert float(jnp.max(jnp.abs(y - x))) <= 1.5e-2 * float(jnp.max(jnp.abs(x)))


def test_int4_pack_unpack_exact():
    q = jnp.asarray(np.random.default_rng(0).integers(-7, 8, (16, 64)),
                    jnp.int8)
    for axis in (-1, 0):
        assert np.array_equal(np.asarray(unpack_int4(pack_int4(q, axis),
                                                     axis)), np.asarray(q))


def test_quantized_tensor_is_a_pytree_and_scan_slices():
    """Stacked (L, in, out) weights slice through tree ops exactly like a
    scanned layer group: the negative grouped axis survives."""
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 128, 64), jnp.float32)
    qt = quantize(w, bits=8, group_size=128, axis=-2)
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    assert len(leaves) == 2
    assert jax.tree_util.tree_unflatten(treedef, leaves) == qt
    sliced = jax.tree.map(lambda v: v[1], qt)
    np.testing.assert_allclose(np.asarray(dequantize(sliced)),
                               np.asarray(dequantize(qt))[1], rtol=1e-6)


def test_group_size_must_align_with_granule():
    params = {"wq": {"w": jnp.ones((64, 64), jnp.float32)}}
    with pytest.raises(AssertionError, match="granule"):
        quantize_params(params, group_size=48)


def test_scale_blocks_align_with_kernel_tiles():
    """Mechanism-D alignment: the scale group divides every block_k the
    qgemv space can pick, so no scale block straddles a tile edge."""
    from repro.tune import REGISTRY
    for bk in REGISTRY["qgemv"].space["block_k"]:
        assert bk % 128 == 0


# --------------------------------------------------------------------------
# quantize_params policy
# --------------------------------------------------------------------------
def _leaf_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)
        out[keys] = leaf
    return out


def test_quantize_params_policy_moe_arch():
    """MLP/attention projections quantize; embeddings, norms, router and
    the raw-einsum MoE expert stacks stay untouched."""
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg, RuntimeConfig(remat="none", moe_groups=1))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    qp = quantize_params(params, bits=8)
    for keys, leaf in _leaf_paths(qp).items():
        q = isinstance(leaf, QuantizedTensor) or (
            hasattr(leaf, "dtype") and leaf.dtype == jnp.int8)
        if "embed" in keys or "router" in keys or "norm1" in keys \
                or "final_norm" in keys:
            assert not q, keys
        if keys[-2:] == ("wq", "w"):
            assert q, keys
    stats = quantized_stats(qp)
    assert stats["quantized_leaves"] > 0
    # MoE expert stacks (sibling of the router) stay raw
    raw = _leaf_paths(params)
    for keys, leaf in raw.items():
        if "router" in keys:
            prefix = keys[:keys.index("router")]
            for k2, l2 in _leaf_paths(qp).items():
                if k2[:len(prefix)] == prefix and "wi_up" in k2 \
                        and "shared" not in k2:
                    assert not isinstance(l2, QuantizedTensor), k2


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("bits,rtol", [(8, 0.05)])
def test_quantized_forward_tracks_fp(arch, bits, rtol):
    cfg = reduced(get_config(arch))
    model = build_model(cfg, RuntimeConfig(remat="none", moe_groups=1))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    qp = quantize_params(params, bits=bits)
    toks = jnp.arange(2 * 8).reshape(2, 8) % 7 + 1
    lf, _ = model.train_logits(params, {"tokens": toks})
    lq, _ = model.train_logits(qp, {"tokens": toks})
    rel = float(jnp.max(jnp.abs(lq - lf)) / jnp.max(jnp.abs(lf)))
    assert rel < rtol, rel


# --------------------------------------------------------------------------
# fused-dequant kernels
# --------------------------------------------------------------------------
@pytest.mark.parametrize("N,K_", [(256, 1024), (128, 512)])
@pytest.mark.parametrize("bits", [8, 4])
def test_qgemv(N, K_, bits):
    w = jax.random.normal(jax.random.PRNGKey(0), (N, K_), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (K_,), jnp.bfloat16)
    qt = quantize(w, bits=bits, group_size=128, axis=-1)
    # bits is carried explicitly (no shape heuristic): a (N, K/2) int8
    # buffer could equally be a narrow 8-bit weight
    want = np.asarray(R.qgemv(qt.values, qt.scales, x, bits=bits))
    for cfg in (BASELINE, TROOP):
        got = np.asarray(K.qgemv(qt.values, qt.scales, x, cfg, bits=bits))
        # exact vs the dequantized oracle (isolates kernel error)
        np.testing.assert_allclose(got, want, rtol=3e-3, atol=3e-3)
    # within quantization noise of the fp32 oracle
    full = np.asarray(R.gemv(w, x.astype(jnp.float32)))
    tol = 2e-2 if bits == 8 else 2e-1
    assert np.max(np.abs(want - full)) <= tol * np.max(np.abs(full))


@pytest.mark.parametrize("B", [1, 4])
def test_batched_qgemv(B):
    N, K_ = 128, 512
    w = jax.random.normal(jax.random.PRNGKey(0), (N, K_), jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (B, K_), jnp.bfloat16)
    qt = quantize(w, bits=8, group_size=128, axis=-1)
    want = np.asarray(R.batched_qgemv(qt.values, qt.scales, xs))
    for cfg in (BASELINE, TROOP):
        got = np.asarray(K.batched_qgemv(qt.values, qt.scales, xs, cfg))
        np.testing.assert_allclose(got, want, rtol=3e-3, atol=3e-3)


def test_qgemv_bytes_under_point6_of_bf16():
    """The acceptance bound: modeled qgemv bytes <= 0.6x bf16 gemv bytes
    at the same logical shape (int8 + scale traffic vs bf16)."""
    from repro.tune import REGISTRY
    sds = jax.ShapeDtypeStruct
    N, K_ = 2048, 4096
    bf = REGISTRY["gemv"].bytes(sds((N, K_), jnp.bfloat16),
                                sds((K_,), jnp.bfloat16))
    q8 = REGISTRY["qgemv"].bytes(sds((N, K_), jnp.int8),
                                 sds((N, K_ // 128), jnp.float32),
                                 sds((K_,), jnp.bfloat16))
    q4 = REGISTRY["qgemv"].bytes(sds((N, K_ // 2), jnp.int8),
                                 sds((N, K_ // 128), jnp.float32),
                                 sds((K_,), jnp.bfloat16))
    assert q8 <= 0.6 * bf
    assert q4 <= 0.35 * bf


@pytest.mark.parametrize("B,H,KV,hd,page,nblk", [
    (2, 8, 8, 64, 32, 8), (2, 8, 2, 64, 32, 3), (1, 16, 4, 128, 32, 4),
])
def test_paged_decode_attention_int8(B, H, KV, hd, page, nblk):
    """int8 pools + scale pages through the block-table gather == the
    dequantized oracle (incl. odd-nblk one-stream fallback)."""
    from repro.quant import quantize_kv
    P = 1 + B * nblk
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (P, page, KV, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (P, page, KV, hd), jnp.float32)
    k8, ksc = quantize_kv(k_pool)
    v8, vsc = quantize_kv(v_pool)
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    bt = jnp.asarray(perm[:B * nblk].reshape(B, nblk), jnp.int32)
    S = nblk * page
    length = jnp.asarray([(S // 2 + 17 * b) % S + 1 for b in range(B)],
                         jnp.int32)
    want = np.asarray(
        R.paged_decode_attention_int8(q, k8, ksc, v8, vsc, bt, length),
        np.float32)
    for cfg in (BASELINE, TROOP):
        got = np.asarray(
            K.paged_decode_attention_int8(q, k8, ksc, v8, vsc, bt, length,
                                          cfg), np.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


# --------------------------------------------------------------------------
# int8 paged engine: parity + allocator accounting (two archs)
# --------------------------------------------------------------------------
def _engine(model, params, backend, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 64)
    kw.setdefault("min_bucket", 4)
    name = backend if isinstance(backend, str) else backend.name
    return ServingEngine(
        model, prefill_step=make_prefill_step(model),
        serve_step=make_serve_step(model), params=params, backend=backend,
        config=EngineConfig(backend=name, **kw))


def _serve(model, params, backend):
    eng = _engine(model, params, backend)
    reqs = [Request(rid=i, prompt=np.arange(1, 5 + 2 * i) % 63 + 1,
                    max_new_tokens=6) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_drained()
    assert len(finished) == len(reqs)
    return {r.rid: r.out for r in reqs}, eng


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "glm4-9b"])
def test_paged_int8_matches_dense_int8_and_tracks_bf16(arch):
    """Token-identical greedy outputs: paged-int8 == dense-int8 (same
    quantization, different layout); and the int8 decode logits stay
    within quantization tolerance of the bf16 engine's."""
    cfg = reduced(get_config(arch),
                  num_layers=2, d_model=64, d_ff=128, vocab_size=128,
                  num_heads=2, num_kv_heads=2, head_dim=32)
    rt8 = RuntimeConfig(remat="none", kv_cache_dtype="int8")
    model8 = build_model(cfg, rt8)
    model_bf = build_model(cfg, RuntimeConfig(remat="none"))
    params = M.unbox(model_bf.init(jax.random.PRNGKey(0)))

    out_d8, _ = _serve(model8, params, "dense")
    out_p8, eng8 = _serve(model8, params,
                          PagedBackend(page_size=32, kv_dtype="int8"))
    assert out_p8 == out_d8

    # int8 vs bf16: compare one decode step's logits (greedy tokens can
    # legitimately flip near ties under quantization noise)
    eng_bf = _engine(model_bf, params, "paged")
    eng8b = _engine(model8, params, PagedBackend(page_size=32,
                                                 kv_dtype="int8"))
    prompt = np.asarray([3, 14, 15, 9], np.int32)
    for eng, model in ((eng_bf, model_bf), (eng8b, model8)):
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
        eng.step()
    batch = {"tokens": jnp.asarray(eng_bf.last_tok[:, None]),
             "pos": jnp.asarray(eng_bf.pos)}
    l_bf, _ = model_bf.decode_step(
        params, dict(batch, **eng_bf.backend.batch_extras()), eng_bf.caches)
    l_q8, _ = model8.decode_step(
        params, dict(batch, **eng8b.backend.batch_extras()), eng8b.caches)
    np.testing.assert_allclose(
        np.asarray(l_q8[0], np.float32), np.asarray(l_bf[0], np.float32),
        rtol=0.15, atol=0.15)


def test_paged_int8_allocator_accounting_no_leaked_pages():
    """Scale pages ride the value pages' table entries: the allocator is
    unchanged, and a drained engine returns every page."""
    cfg = reduced(get_config("qwen1.5-0.5b"),
                  num_layers=2, d_model=64, d_ff=128, vocab_size=128,
                  num_heads=2, num_kv_heads=2, head_dim=32)
    model = build_model(cfg, RuntimeConfig(remat="none",
                                           kv_cache_dtype="int8"))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    backend = PagedBackend(page_size=32, kv_dtype="int8")
    _, eng = _serve(model, params, backend)
    assert backend.allocator.num_free == backend.spec.num_pages - 1
    assert backend.spec.kv_dtype == "int8"
    leaf = eng.caches[0][0]["mixer"]
    assert leaf.quantized and leaf.k_pool.dtype == jnp.int8
    assert leaf.k_scale_pool.shape == leaf.k_pool.shape[:-1] + (
        cfg.num_kv_heads,)
    # int8 pages obey the coarser 32-row granule (mechanism D)
    with pytest.raises(AssertionError, match="granule"):
        PagedBackend(page_size=16, kv_dtype="int8").init_caches(
            model, 2, 64)


def test_paged_int8_kernel_decode_matches_jnp_path():
    """paged_kernel_decode=True routes a quantized paged cache through the
    fused-dequant Pallas kernel; logits match the jnp gather path."""
    cfg = reduced(get_config("qwen1.5-0.5b"),
                  num_layers=2, d_model=64, d_ff=128, vocab_size=128,
                  num_heads=2, num_kv_heads=2, head_dim=32)
    rt = RuntimeConfig(remat="none", kv_cache_dtype="int8")
    model = build_model(cfg, rt)
    kmodel = build_model(cfg, RuntimeConfig(
        remat="none", kv_cache_dtype="int8", paged_kernel_decode=True))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    eng = _engine(model, params, PagedBackend(page_size=32, kv_dtype="int8"),
                  slots=2)
    eng.submit(Request(rid=0, prompt=np.asarray([3, 14, 15, 9], np.int32),
                       max_new_tokens=2))
    eng.step()
    batch = {"tokens": jnp.asarray(eng.last_tok[:, None]),
             "pos": jnp.asarray(eng.pos)}
    batch.update(eng.backend.batch_extras())
    l_jnp, _ = model.decode_step(params, batch, eng.caches)
    l_ker, _ = kmodel.decode_step(params, batch, eng.caches)
    np.testing.assert_allclose(
        np.asarray(l_ker[0], np.float32), np.asarray(l_jnp[0], np.float32),
        rtol=3e-2, atol=3e-2)


def test_quantized_weights_serve_end_to_end():
    """--quantize-weights in engine form: quantized params decode greedily
    and the byte accounting shows the shrink."""
    cfg = reduced(get_config("qwen1.5-0.5b"),
                  num_layers=2, d_model=64, d_ff=128, vocab_size=128,
                  num_heads=2, num_kv_heads=2, head_dim=32)
    model = build_model(cfg, RuntimeConfig(remat="none",
                                           quantize_weights="int8"))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    qp = quantize_params(params, bits=8)
    stats = quantized_stats(qp)
    assert stats["quantized_leaves"] >= 8
    out, _ = _serve(model, qp, "paged")
    assert all(len(v) == 6 for v in out.values())


# --------------------------------------------------------------------------
# dedup of the historical helpers
# --------------------------------------------------------------------------
def test_attention_quantize_kv_matches_historical_formula():
    from repro.models.attention import dequantize_kv, quantize_kv
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
    assert s.shape == (2, 16, 4, 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    want_s = jnp.maximum(amax / 127.0, 1e-8).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(s, np.float32),
                                  np.asarray(want_s, np.float32))
    y = dequantize_kv(q, s, jnp.float32)
    assert float(jnp.max(jnp.abs(y - x))) < 2e-2 * float(jnp.max(jnp.abs(x)))


def test_dist_compression_wrappers_roundtrip():
    from repro.dist.compression import dequantize_int8, quantize_int8
    g = jax.random.normal(jax.random.PRNGKey(0), (257,), jnp.float32)
    q, s = quantize_int8(g)
    assert q.dtype == jnp.int8 and s.shape == ()
    deq = dequantize_int8(q, s)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(s) * 0.5 + 1e-6


# --------------------------------------------------------------------------
# MX microscaling (mx4 / fp8, DESIGN.md §11)
# --------------------------------------------------------------------------
from repro.quant import (e8m0_decode, fp4_decode, fp4_encode,  # noqa: E402
                         pack_fp4, quantize_mx, unpack_fp4)
from repro.quant.tensor import FP8_DTYPE, granule  # noqa: E402


def test_fp4_code_roundtrip_exact():
    """Every representable e2m1 value encodes to itself."""
    vals = [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0]
    xs = jnp.asarray([v * s for v in vals for s in (1.0, -1.0)], jnp.float32)
    codes = fp4_encode(xs)
    np.testing.assert_array_equal(np.asarray(fp4_decode(codes), np.float32),
                                  np.asarray(xs))
    # and the nibble pack/unpack is lossless along the leading axis
    c2 = codes.reshape(4, 4)
    np.testing.assert_array_equal(np.asarray(unpack_fp4(pack_fp4(c2))),
                                  np.asarray(c2))


def test_fp4_encode_rounds_to_nearest():
    # midpoints resolve to a neighbouring representable magnitude
    xs = jnp.asarray([0.2, 0.8, 1.2, 2.4, 5.5, -3.4], jnp.float32)
    got = np.asarray(fp4_decode(fp4_encode(xs)), np.float32)
    grid = np.asarray([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
    for x, g in zip(np.asarray(xs), got):
        best = grid[np.argmin(np.abs(grid - abs(x)))] * np.sign(x)
        assert g == best, (x, g, best)


@pytest.mark.parametrize("elem,max_rel", [("fp4", 0.30), ("fp8", 0.10)])
def test_quantize_mx_roundtrip(elem, max_rel):
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 64), jnp.float32)
    qt = quantize_mx(x, elem=elem)
    assert qt.fmt == "mx" and qt.axis == -2
    assert qt.group_size == granule()
    assert qt.scales.dtype == jnp.uint8          # E8M0 shared exponents
    assert qt.shape == x.shape
    if elem == "fp4":
        assert qt.values.dtype == jnp.uint8 and qt.bits == 4
        assert qt.values.shape == (128, 64)      # two codes per byte
    else:
        assert qt.values.dtype == FP8_DTYPE and qt.bits == 8
    y = np.asarray(dequantize(qt, jnp.float32))
    err = np.max(np.abs(y - np.asarray(x)))
    # block-relative: each 32-block scales to its own amax
    assert err <= max_rel * float(jnp.max(jnp.abs(x)))


def test_mx_error_monotone_fp8_beats_fp4():
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 32), jnp.float32)
    e4 = float(jnp.mean(jnp.abs(
        dequantize(quantize_mx(x, elem="fp4"), jnp.float32) - x)))
    e8 = float(jnp.mean(jnp.abs(
        dequantize(quantize_mx(x, elem="fp8"), jnp.float32) - x)))
    assert e8 <= e4


def test_mx_bytes_ratios():
    """The headline roofline move: mx4 <= 0.28x and fp8 <= 0.55x of the
    bf16 weight bytes at a serving shape (values + E8M0 traffic)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4096, 2048), jnp.float32)
    bf16 = x.size * 2
    assert quantize_mx(x, elem="fp4").nbytes <= 0.28 * bf16
    assert quantize_mx(x, elem="fp8").nbytes <= 0.55 * bf16


def test_quantize_mx_odd_k_falls_back_to_fp8():
    x = jax.random.normal(jax.random.PRNGKey(2), (33, 8), jnp.float32)
    qt = quantize_mx(x, elem="fp4")
    assert qt.values.dtype == FP8_DTYPE and qt.bits == 8
    y = np.asarray(dequantize(qt, jnp.float32))
    assert np.max(np.abs(y - np.asarray(x))) <= 0.1 * float(
        jnp.max(jnp.abs(x)))


def test_quantize_params_mx_policy_flips_expert_stacks():
    """Under MX the MoE expert stacks DO quantize (grouped_expert_qgemv
    consumes them); router/norms/embeds stay raw, exactly as under int8."""
    def qt_paths(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        return {tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path): leaf for path, leaf in flat}

    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg, RuntimeConfig(remat="none", moe_groups=1))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    qp = quantize_params(params, fmt="mx4")
    paths = qt_paths(qp)
    expert_q = [k for k, v in paths.items()
                if isinstance(v, QuantizedTensor) and v.fmt == "mx"
                and "wi_up" in k and "shared" not in k]
    assert expert_q, "MX must quantize the routed expert stacks"
    for keys, leaf in paths.items():
        if "embed" in keys or "router" in keys or "norm1" in keys \
                or "final_norm" in keys:
            assert not isinstance(leaf, QuantizedTensor), keys
    # and fp8 follows the same policy with 8-bit elements
    qp8 = qt_paths(quantize_params(params, fmt="fp8"))
    for k in expert_q:
        assert qp8[k].bits == 8 and qp8[k].fmt == "mx", k


def test_quantize_params_mx4_rejects_tp():
    params = {"wq": {"w": jnp.ones((64, 64), jnp.float32)}}
    with pytest.raises(AssertionError):
        quantize_params(params, fmt="mx4", tp=2)


@pytest.mark.parametrize("elem", ["fp4", "fp8"])
def test_mx_qgemv_matches_oracle(elem):
    N, K_ = 128, 512
    w = jax.random.normal(jax.random.PRNGKey(0), (K_, N), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (K_,), jnp.float32)
    qt = quantize_mx(w, elem=elem)
    want = np.asarray(R.mx_qgemv(qt.values, qt.scales, x))
    for cfg in (BASELINE, TROOP):
        got = np.asarray(K.mx_qgemv(qt.values, qt.scales, x, cfg))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # within quantization noise of the fp32 oracle
    full = np.asarray(R.gemv(w.T, x))
    tol = 0.35 if elem == "fp4" else 0.1
    assert np.max(np.abs(want - full)) <= tol * np.max(np.abs(full))


@pytest.mark.parametrize("B", [1, 4])
def test_batched_mx_qgemv_matches_oracle(B):
    N, K_ = 128, 256
    w = jax.random.normal(jax.random.PRNGKey(0), (K_, N), jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (B, K_), jnp.float32)
    qt = quantize_mx(w, elem="fp4")
    want = np.asarray(R.batched_mx_qgemv(qt.values, qt.scales, xs))
    for cfg in (BASELINE, TROOP):
        got = np.asarray(K.batched_mx_qgemv(qt.values, qt.scales, xs, cfg))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("elem", ["fp4", "fp8"])
def test_mx_qgemv_swiglu_matches_oracle(elem):
    d, f = 256, 128
    kg, ku, kx = jax.random.split(jax.random.PRNGKey(0), 3)
    qg = quantize_mx(jax.random.normal(kg, (d, f), jnp.float32), elem=elem)
    qu = quantize_mx(jax.random.normal(ku, (d, f), jnp.float32), elem=elem)
    x = jax.random.normal(kx, (d,), jnp.float32)
    want = np.asarray(R.mx_qgemv_swiglu(qg.values, qg.scales,
                                        qu.values, qu.scales, x))
    got = np.asarray(K.mx_qgemv_swiglu(qg.values, qg.scales,
                                       qu.values, qu.scales, x))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("elem", ["fp4", "fp8"])
def test_grouped_expert_qgemv_token_identical_to_gather(elem):
    """The routed expert dispatch == dequantize-then-einsum over the
    gathered stacks, for every expert-id pattern."""
    E, K_, N, topk = 4, 128, 64, 2
    w = jax.random.normal(jax.random.PRNGKey(0), (E, K_, N), jnp.float32)
    qt = quantize_mx(w, elem=elem)
    xs = jax.random.normal(jax.random.PRNGKey(1), (topk, K_), jnp.float32)
    for ids in ([0, 0], [1, 3], [3, 2]):
        ids_a = jnp.asarray(ids, jnp.int32)
        want = np.asarray(R.grouped_expert_qgemv(qt.values, qt.scales,
                                                 xs, ids_a))
        got = np.asarray(K.grouped_expert_qgemv(qt.values, qt.scales,
                                                xs, ids_a))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_mx_engine_end_to_end_within_int4_tolerance():
    """mx4-quantized MoE engine: decodes greedily end-to-end, and its
    prefill logits stay within the int4 error envelope of the fp oracle."""
    from repro.models.transformer import prefill
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg, RuntimeConfig(remat="none"))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    batch = {"tokens": jnp.arange(1, 5)[None, :],
             "positions": jnp.arange(4)[None, :]}
    ref, _ = prefill(params, cfg, model.rt, batch)
    ref = np.asarray(ref, np.float32)
    scale = np.max(np.abs(ref)) + 1e-9

    def err(qp):
        lg, _ = prefill(qp, cfg, model.rt, batch)
        return np.max(np.abs(np.asarray(lg, np.float32) - ref)) / scale

    e_mx4 = err(quantize_params(params, fmt="mx4"))
    e_int4 = err(quantize_params(params, bits=4))
    e_fp8 = err(quantize_params(params, fmt="fp8"))
    assert e_mx4 <= max(e_int4, 0.30) * 1.25, (e_mx4, e_int4)
    assert e_fp8 <= e_mx4

    # and the engine drains under mx4 (the --quantize-weights mx4 path)
    qp = quantize_params(params, fmt="mx4")
    out, _ = _serve(model, qp, "paged")
    assert all(len(v) == 6 for v in out.values())


def test_mx_routed_decode_matches_gather_path():
    """kernel_routing ON routes mx_qgemv / mx_qgemv_swiglu /
    grouped_expert_qgemv; the step output tracks the in-graph dequant
    path to accumulation precision."""
    from repro.models.transformer import decode_step, init_caches
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg, RuntimeConfig(remat="none"))
    params = M.unbox(model.init(jax.random.PRNGKey(0)))
    qp = quantize_params(params, fmt="mx4")
    db = {"tokens": jnp.array([[7]]), "pos": jnp.array([0])}
    caches = init_caches(cfg, model.rt, 1, 64, jnp.float32)
    a, _ = decode_step(qp, cfg, model.rt, db, caches)
    caches = init_caches(cfg, model.rt, 1, 64, jnp.float32)
    with M.kernel_routing():
        b, _ = decode_step(qp, cfg, model.rt, db, caches)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("fmt", ["mx4", "fp8"])
def test_audit_decode_step_mx_exact(fmt):
    """The acceptance bar: a quantized-MoE decode step audits byte-exact
    (kernel multiset AND modeled bytes) against decode_step_account."""
    from repro import obs
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg, RuntimeConfig(remat="none",
                                           quantize_weights=fmt))
    a = obs.audit_decode_step(model, cache_len=64, page_size=16)
    assert a.ok, a.report()
    assert a.dispatches == sum(a.expected.values())
    assert a.measured_bytes == a.expected_bytes > 0


def test_engine_config_mx_validation():
    assert EngineConfig(quantize_weights="mx4").validate()
    assert EngineConfig(quantize_weights="fp8", tp=1).validate()
    with pytest.raises(ValueError, match="mx4"):
        EngineConfig(quantize_weights="mx4", tp=2).validate()
    with pytest.raises(ValueError):
        EngineConfig(quantize_weights="mx5").validate()
