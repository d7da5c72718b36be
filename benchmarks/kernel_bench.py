"""TPU-kernel benchmark: structural roofline terms per Pallas kernel,
baseline vs TROOP variant, plus interpret-mode wall time (correctness
exercise only — CPU interpret timing is NOT TPU performance).

Structural terms (exact from shapes): bytes streamed from HBM, FLOPs, OI,
and the v5e roofline-bound time; the TROOP-vs-baseline delta shows the
mechanism value (e.g. fused_adamw: 1 pass vs the ~4 passes of the unfused
reference chain)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.roofline import HBM_BW, PEAK_FLOPS
from repro.core.troop import BASELINE, TROOP
from repro.kernels import ops as K
from repro.tune import get_tuned


def _time(fn, *args, iters=3):
    fn(*args).block_until_ready() if hasattr(fn(*args), "block_until_ready") \
        else fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(csv=print):
    key = jax.random.PRNGKey(0)

    # GEMV: N x K bf16 weights streamed once
    N, Kd = 2048, 4096
    w = jax.random.normal(key, (N, Kd), jnp.bfloat16)
    x = jax.random.normal(key, (Kd,), jnp.bfloat16)
    bytes_ = N * Kd * 2 + Kd * 2 + N * 4
    flops = 2 * N * Kd
    bound_us = max(bytes_ / HBM_BW, flops / PEAK_FLOPS) * 1e6
    # "tuned" rows consume the persistent tune cache (heuristic on a miss)
    for cfg, tag in ((BASELINE, "baseline"), (TROOP, "troop"),
                     (get_tuned("gemv", w, x), "tuned")):
        us = _time(lambda: K.gemv(w, x, cfg))
        csv(f"kernel/gemv/{tag},{us:.0f},interp_us OI={flops / bytes_:.2f} "
            f"v5e_bound_us={bound_us:.1f}")

    # quantized GEMV (repro.quant): bf16 / int8 / int4 side by side — the
    # bytes ratio IS the roofline move (values + scale traffic, DESIGN §5)
    from repro.quant import quantize
    from repro.tune import REGISTRY
    wf = jax.random.normal(key, (N, Kd), jnp.float32)
    for bits in (8, 4):
        qt = quantize(wf, bits=bits, group_size=128, axis=-1)
        q_bytes = REGISTRY["qgemv"].bytes(qt.values, qt.scales, x)
        us = _time(lambda: K.qgemv(qt.values, qt.scales, x, TROOP,
                                   bits=bits))
        csv(f"kernel/qgemv/int{bits},{us:.0f},interp_us "
            f"bytes_ratio_vs_bf16={q_bytes / bytes_:.2f} "
            f"v5e_bound_us={q_bytes / HBM_BW * 1e6:.1f}")

    # DOTP
    n = 1 << 20
    a = jax.random.normal(key, (n,), jnp.bfloat16)
    b = jax.random.normal(key, (n,), jnp.bfloat16)
    bytes_ = 2 * n * 2
    bound_us = bytes_ / HBM_BW * 1e6
    for cfg, tag in ((BASELINE, "baseline"), (TROOP, "troop"),
                     (get_tuned("dotp", a, b), "tuned")):
        us = _time(lambda: K.dotp(a, b, cfg))
        csv(f"kernel/dotp/{tag},{us:.0f},interp_us OI=0.5 "
            f"v5e_bound_us={bound_us:.1f}")

    # AXPY
    for cfg, tag in ((BASELINE, "baseline"), (TROOP, "troop"),
                     (get_tuned("axpy", 1.5, a, b), "tuned")):
        us = _time(lambda: K.axpy(1.5, a, b, cfg))
        csv(f"kernel/axpy/{tag},{us:.0f},interp_us OI=0.33 "
            f"v5e_bound_us={3 * n * 2 / HBM_BW * 1e6:.1f}")

    # decode attention: the paper's LLM-decode GEMV
    B, H, KV, hd, S = 4, 16, 8, 128, 4096
    q = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    kc = jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
    vc = jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
    length = jnp.full((B,), S, jnp.int32)
    cache_bytes = 2 * B * S * KV * hd * 2
    flops = 4 * B * H * S * hd
    bound_us = cache_bytes / HBM_BW * 1e6
    for cfg, tag in ((BASELINE, "baseline"), (TROOP, "troop"),
                     (get_tuned("decode_attention", q, kc, vc, length),
                      "tuned")):
        us = _time(lambda: K.decode_attention(q, kc, vc, length, cfg))
        csv(f"kernel/decode_attn/{tag},{us:.0f},interp_us "
            f"OI={flops / cache_bytes:.2f} v5e_bound_us={bound_us:.1f}")

    # int8 quantized flash-decode (§Perf A4): half the cache stream — the
    # bytes come from the registered (audited) cost model, scales included
    from repro.models.attention import quantize_kv
    k8, ksc = quantize_kv(kc)
    v8, vsc = quantize_kv(vc)
    q8_bytes = REGISTRY["decode_attention_int8"].bytes(
        q, k8, ksc, v8, vsc, length)
    us = _time(lambda: K.decode_attention_int8(q, k8, ksc, v8, vsc,
                                               length,
                                               get_tuned("decode_attention_int8",
                                                         q, k8, ksc, v8, vsc,
                                                         length)))
    csv(f"kernel/decode_attn_int8/tuned,{us:.0f},interp_us "
        f"bytes_ratio_vs_bf16={q8_bytes / cache_bytes:.2f} "
        f"v5e_bound_us={q8_bytes / HBM_BW * 1e6:.1f}")

    # fused adamw: 1-pass (7 streams) vs unfused reference (~10 HLO passes)
    n = 1 << 20
    p = jax.random.normal(key, (n,))
    g = jax.random.normal(key, (n,))
    mu = jnp.zeros((n,))
    nu = jnp.zeros((n,))
    hp = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, bc1=0.1, bc2=0.1)
    fused_bytes = n * (4 + 4 + 4 + 4 + 4 + 4 + 4)
    unfused_bytes = fused_bytes * 2.4        # measured HLO pass count ratio
    csv(f"kernel/fused_adamw/bytes,{fused_bytes},"
        f"one_pass vs unfused~{unfused_bytes:.0f} "
        f"v5e_bound_us={fused_bytes / HBM_BW * 1e6:.1f}")
    us = _time(lambda: K.fused_adamw(p, g, mu, nu, **hp, cfg=TROOP))
    csv(f"kernel/fused_adamw/troop,{us:.0f},interp_us")

    # wkv6: chunked MXU form vs T-step scan oracle
    Bw, T, Hw, hdw = 1, 256, 4, 64
    r = jax.random.normal(key, (Bw, T, Hw, hdw))
    kk = jax.random.normal(key, (Bw, T, Hw, hdw))
    vv = jax.random.normal(key, (Bw, T, Hw, hdw))
    ww = jnp.exp(-jnp.exp(jax.random.normal(key, (Bw, T, Hw, hdw))))
    u = 0.5 * jnp.ones((Hw, hdw))
    s0 = jnp.zeros((Bw, Hw, hdw, hdw))
    us = _time(lambda: K.wkv6(r, kk, vv, ww, u, s0, TROOP))
    from repro.kernels import ref as R
    us_ref = _time(lambda: R.wkv6(r, kk, vv, ww, u, s0))
    csv(f"kernel/wkv6/troop,{us:.0f},interp_us scan_ref={us_ref:.0f}us "
        f"chunked_matmul_form=True")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
