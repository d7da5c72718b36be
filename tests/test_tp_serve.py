"""Tensor-parallel serving tests (4 fake CPU devices via a subprocess).

The TP engine (``repro.dist.tp`` + ``ServingEngine(tp=N)``) must be
*token-identical* to single-device serving in exact mode: column-parallel
projections compute a bitwise column subset and ``gather_cols`` is a tiled
all-gather, so nothing reassociates.  Overlap mode (ring collective
matmuls) is tolerance-equal only and is tested against einsum references.

Heavy tests run inside ``run_with_devices`` subprocesses (the fake-device
XLA flag must be set before jax imports); plan/quantize validation runs
in-process.
"""
import numpy as np
import pytest

from test_dist import run_with_devices

# Shared preamble: tiny 2-layer attention arch (H=4, KV=4, hd=32) with a
# mixed-length trace whose last prompt shares a prefix with an earlier one
# (exercises radix reuse under chunked prefill).
PRELUDE = """
    import jax, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import RuntimeConfig, build_model
    from repro.models import modules as M
    from repro.serve import EngineConfig
    from repro.serve.kvcache import PagedBackend
    from repro.serve.scheduler import Request, ServingEngine
    from repro.serve.step import make_prefill_step, make_serve_step

    PROMPTS = [np.arange(1, 4 + 7 * i) % 63 + 1 for i in range(4)]
    PROMPTS += [np.concatenate([PROMPTS[2][:12], np.asarray([9, 9, 9])])]

    def build(KV=4, moe=False, **rt_kw):
        name = "qwen2-moe-a2.7b" if moe else "qwen1.5-0.5b"
        cfg = reduced(get_config(name), num_layers=2, d_model=64,
                      d_ff=128, vocab_size=128, num_heads=4,
                      num_kv_heads=KV, head_dim=32)
        model = build_model(cfg, RuntimeConfig(remat="none", **rt_kw))
        params = M.unbox(model.init(jax.random.PRNGKey(0)))
        return model, params

    def run(model, params, tp, backend=None, chunked=True,
            tp_mode="exact", tracer=None):
        be = backend if backend is not None else (
            PagedBackend(page_size=16) if chunked else "dense")
        eng = ServingEngine(
            model, prefill_step=make_prefill_step(model),
            serve_step=make_serve_step(model), params=params,
            backend=be, tracer=tracer,
            config=EngineConfig(
                slots=3, cache_len=64,
                backend=be if isinstance(be, str) else be.name,
                chunked_prefill=chunked, chunk_size=8,
                prefix_cache=chunked, tp=tp, tp_mode=tp_mode))
        reqs = [Request(rid=i, prompt=np.asarray(p, np.int32),
                        max_new_tokens=6)
                for i, p in enumerate(PROMPTS)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert all(r.done for r in reqs)
        return [r.out for r in reqs], eng
"""


def test_tp4_token_identity_bf16_and_telemetry():
    """tp=4 chunked+prefix == tp=1 bitwise; per-device streamed bytes are
    exactly 1/4; async dispatch overlaps and emits its span pair."""
    run_with_devices(PRELUDE + """
        from repro.obs import Tracer
        model, params = build()
        o1, e1 = run(model, params, 1)
        tr = Tracer()
        o4, e4 = run(model, params, 4, tracer=tr)
        assert o1 == o4, (o1, o4)
        m1, m4 = e1.metrics(), e4.metrics()
        assert m4["kv_shards"] == 4
        assert m4["kv_bytes_streamed"] == m1["kv_bytes_streamed"]
        assert m4["kv_bytes_streamed_per_device"] * 4 == \\
            m4["kv_bytes_streamed"]
        assert m4["dispatch_overlap_fraction"] > 0
        assert tr.events("device_submit") and tr.events("stream_out")
        # submit precedes the matching stream-out: spans interleave
        t_sub = tr.events("device_submit")[0][0]
        t_out = tr.events("stream_out")[0][0]
        assert t_sub <= t_out
        print("OK")
    """, n=4)


def test_tp4_token_identity_int8_kv():
    run_with_devices(PRELUDE + """
        model, params = build()
        be = lambda: PagedBackend(page_size=32, kv_dtype="int8")
        o1, e1 = run(model, params, 1, backend=be())
        o4, e4 = run(model, params, 4, backend=be())
        assert o1 == o4, (o1, o4)
        m1, m4 = e1.metrics(), e4.metrics()
        assert m4["kv_bytes_streamed"] == m1["kv_bytes_streamed"]
        assert m4["kv_bytes_streamed_per_device"] * 4 == \\
            m4["kv_bytes_streamed"]
        print("OK")
    """, n=4)


def test_tp4_gqa_fallback_and_bucketed_backends():
    """KV=2 < tp=4 replicates KV (kv_shards=1) yet stays token-identical;
    the non-chunked dense and paged bucketed paths shard too."""
    run_with_devices(PRELUDE + """
        model2, params2 = build(KV=2)
        o1, _ = run(model2, params2, 1)
        o4, e4 = run(model2, params2, 4)
        assert o1 == o4, (o1, o4)
        m4 = e4.metrics()
        assert m4["kv_shards"] == 1
        assert m4["kv_bytes_streamed_per_device"] == m4["kv_bytes_streamed"]

        model, params = build()
        o1, _ = run(model, params, 1, chunked=False)
        o4, _ = run(model, params, 4, chunked=False)
        assert o1 == o4, (o1, o4)
        o1, _ = run(model, params, 1,
                    backend=PagedBackend(page_size=16), chunked=False)
        o4, _ = run(model, params, 4,
                    backend=PagedBackend(page_size=16), chunked=False)
        assert o1 == o4, (o1, o4)
        print("OK")
    """, n=4)


def test_tp4_moe_expert_parallel_identity():
    run_with_devices(PRELUDE + """
        model, params = build(moe=True)
        o1, _ = run(model, params, 1)
        o4, _ = run(model, params, 4)
        assert o1 == o4, (o1, o4)
        print("OK")
    """, n=4)


def test_overlap_collectives_match_einsum():
    """Ring collective matmuls (3-D activations, incl. int8-quantized
    weights) match the plain einsum within fp32 tolerance, and the
    overlap-mode engine drains every request."""
    run_with_devices(PRELUDE + """
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.dist.collective_matmul import (allgather_matmul,
                                                  reduce_scatter_matmul)
        from repro.quant.tensor import quantize, dequantize
        mesh = jax.make_mesh((4,), ("tp",))
        B, T, K, N = 2, 6, 64, 96
        x = jax.random.normal(jax.random.PRNGKey(0), (B, T, K), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
        ref = jnp.einsum("btk,kn->btn", x, w)
        ag = shard_map(lambda xl, wf: allgather_matmul(xl, wf, "tp"),
                       mesh=mesh, in_specs=(P(None, None, "tp"), P()),
                       out_specs=P(), check_rep=False)
        np.testing.assert_allclose(ag(x, w), ref, rtol=1e-4, atol=1e-4)
        rs = shard_map(lambda xl, wl: reduce_scatter_matmul(xl, wl, "tp"),
                       mesh=mesh,
                       in_specs=(P(None, None, "tp"), P("tp", None)),
                       out_specs=P(None, None, "tp"), check_rep=False)
        np.testing.assert_allclose(rs(x, w), ref, rtol=1e-4, atol=1e-4)
        # int8 weights: dequantized reference through the same ring
        qw = quantize(w, bits=8, group_size=32)
        wd = dequantize(qw).astype(jnp.float32)
        np.testing.assert_allclose(ag(x, wd),
                                   jnp.einsum("btk,kn->btn", x, wd),
                                   rtol=1e-4, atol=1e-4)

        model, params = build()
        oo, eo = run(model, params, 4, tp_mode="overlap")
        assert eo.metrics()["requests_finished"] == len(PROMPTS)
        print("OK")
    """, n=4)


def test_tp4_kv_page_bytes_invariant_mid_run():
    """sum(per-device resident page bytes) == logical resident bytes while
    requests are live (post-drain everything is freed and reads zero)."""
    run_with_devices(PRELUDE + """
        model, params = build()
        eng = ServingEngine(
            model, prefill_step=make_prefill_step(model),
            serve_step=make_serve_step(model), params=params,
            backend=PagedBackend(page_size=16),
            config=EngineConfig(slots=3, cache_len=64, backend="paged",
                                chunked_prefill=True, chunk_size=8,
                                prefix_cache=True, tp=4))
        for i, p in enumerate(PROMPTS):
            eng.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                               max_new_tokens=6))
        checked = 0
        for _ in range(200):
            if eng.step() is None and not eng.queue:
                break
            kb = eng.backend.kv_page_bytes()
            if kb["kv_page_bytes_resident"] > 0:
                per = kb["kv_page_bytes_per_device"]
                assert kb["kv_shards"] == 4 and len(per) == 4
                assert sum(per) == kb["kv_page_bytes_resident"]
                # never tp x the real footprint
                assert per[0] < kb["kv_page_bytes_logical"]
                checked += 1
        assert checked > 0
        print("OK")
    """, n=4)


# ---- plan / quantize validation ----------------------------------------

def test_plan_rejects_bad_configs():
    # the device-count check precedes the shape checks, so the shape
    # rejections also need the fake 4-device mesh
    run_with_devices(PRELUDE + """
        from repro.dist.tp import plan
        def raises(fn, frag):
            try:
                fn()
            except ValueError as e:
                assert frag in str(e), (frag, e)
            else:
                raise AssertionError(f"no error containing {frag!r}")
        model, _ = build()
        raises(lambda: plan(model, 2, mode="bogus"), "tp_mode")
        raises(lambda: plan(model, 1), "tp >= 2")
        raises(lambda: plan(model, 64), "devices visible")
        model3, _ = build(KV=3)
        raises(lambda: plan(model3, 2), "num_kv_heads")   # 2 % 3 != 0
        raises(lambda: plan(model3, 2, mode="overlap"), "num_kv_heads")
        model5, _ = build(paged_kernel_decode=True)
        raises(lambda: plan(model5, 2), "paged_kernel_decode")
        print("OK")
    """, n=4)


def _tiny_params(KV=4):
    import jax
    from repro.configs import get_config, reduced
    from repro.models import RuntimeConfig, build_model
    from repro.models import modules as M
    cfg = reduced(get_config("qwen1.5-0.5b"), num_layers=2, d_model=64,
                  d_ff=128, vocab_size=128, num_heads=4, num_kv_heads=KV,
                  head_dim=32)
    model = build_model(cfg, RuntimeConfig(remat="none"))
    return M.unbox(model.init(jax.random.PRNGKey(0)))


def test_quantize_tp_alignment():
    """Quantize-time shard contract: int4 row pairs and scale groups must
    not straddle the tensor-parallel shard boundary."""
    from repro.quant import quantize_params
    params = _tiny_params()
    with pytest.raises(AssertionError, match="int4"):
        quantize_params(params, bits=4, tp=2)
    with pytest.raises(AssertionError, match="scale groups"):
        # wo contraction extent 128 -> 32 rows per tp=4 shard, which
        # cannot hold a whole 64-row scale group
        quantize_params(params, bits=8, group_size=64, tp=4)
    q = quantize_params(params, bits=8, group_size=32, tp=4)
    assert q is not None


def test_exact_mode_compiler_options_follow_backend(monkeypatch):
    """Exact mode turns XLA's excess precision off on TPU only, for every
    engine in that mode (tp=1 included); the CPU programs agree without."""
    import jax
    from repro.dist.tp import compiler_options
    assert compiler_options("exact") is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compiler_options("exact") == {"xla_allow_excess_precision": False}
    assert compiler_options("overlap") is None
