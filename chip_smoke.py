#!/usr/bin/env python3
"""Smoke run of the serving engine on TPU chips, through its normal entry
points, with qwen1.5-0.5b at its published widths and seeded random weights.

    python chip_smoke.py          # one chip: device, kernels, engine
    python chip_smoke.py --tp4    # four chips: the tp=4 engine against tp=1

Everything runs in this one process (a process that has touched JAX holds
the chip).  Each phase prints one line.  Only when every phase passed is the
last line of standard output the record

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU the script exits non-zero before any phase: there is no CPU
fallback.  Times printed here are the wall times of one smoke run, compile
included where it says so; they are not benchmark numbers.

Phases (one chip):
  device   JAX/jaxlib/libtpu versions and the devices JAX reports.
  kernel   each main-path Pallas kernel at the engine's sizes, compiled to a
           Mosaic kernel and checked against its fp32 oracle (kernels/ref.py).
  engine   16 seeded requests (a shared 128-token system prefix, 64-512 more
           prompt tokens, 32 new tokens each) through ``build_engine`` with
           the Pallas decode/prefill kernels, then through the jnp decode
           path; first tokens and the first decode step's logits must agree.

Phase (four chips, ``--tp4`` only):
  tp4      the same requests through the engine at tp=1 and at tp=4 in
           exact mode, on the jnp decode path; the mesh must span four
           devices and every token must be identical.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
SEED = 0                       # requests and kernel inputs
SLOTS, CACHE_LEN, PAGE, CHUNK = 8, 1024, 16, 32
REQUESTS, PREFIX, EXTRA, MAX_NEW = 16, 128, (64, 512), 32
VEC = 1 << 24                  # dotp / axpy elements
# max|err| / max|ref| bounds against the fp32 oracles (axpy: 1 bf16 ulp)
TOL = {"gemv": 1e-3, "dotp": 1e-3, "rmsnorm": 1e-3, "attention": 2e-2}
LOGIT_TOL = 2e-2


def report(phase: str, ok: bool, **fields) -> bool:
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {'ok' if ok else 'FAIL'} {items}", flush=True)
    return ok


def version(pkg: str) -> str:
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not-installed"


# ------------------------------------------------------------------ device
def device_phase(need: int):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    kinds = sorted({d.device_kind for d in devs})
    ok = platform == "tpu" and len(devs) >= need
    report("device", ok, jax=jax.__version__, jaxlib=version("jaxlib"),
           libtpu=version("libtpu"), platform=platform,
           kind=repr(",".join(kinds)), count=len(devs))
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX's devices are {platform!r}; "
                 f"this smoke run needs a TPU and has no CPU fallback")
    if len(devs) < need:
        sys.exit(f"chip_smoke: {need} TPU devices needed, {len(devs)} found")
    return devs


# ----------------------------------------------------------------- kernels
def compiled_call(fn, *args):
    """Compile ``fn`` at ``args``, run it, and say whether it lowered to a
    Mosaic kernel (not the Pallas interpreter)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    return jax.block_until_ready(compiled(*args)), mosaic


def oracle(fn, *args):
    import jax
    with jax.default_matmul_precision("float32"):
        return jax.block_until_ready(jax.jit(fn)(*args))


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def bf16_ulps(got, want) -> float:
    """Largest |got - want| in units of the bf16 spacing at ``want``."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    mag = np.maximum(np.abs(want), np.finfo(np.float32).tiny)
    ulp = np.exp2(np.floor(np.log2(mag)) - 7)         # 8-bit significand
    return float(np.max(np.abs(got - want) / ulp))


def kernel_phase() -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops as K
    from repro.kernels import ref as R

    cfg = get_config(ARCH)
    d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))
    bf16 = jnp.bfloat16

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, dtype)

    ok = True

    def check(name, shape, mosaic, err, tol, unit="max_rel_err"):
        nonlocal ok
        good = mosaic and bool(np.isfinite(err)) and err <= tol
        ok &= report("kernel", good, name=name, shape=shape,
                     lowered="mosaic" if mosaic else "NOT-mosaic",
                     **{unit: f"{err:.3e}"}, tol=tol)

    for N, Kd, what in ((d, ff, "down"), (ff, d, "up"), (V, d, "lm_head")):
        w, x = normal((N, Kd)), normal((Kd,))
        got, mosaic = compiled_call(K.gemv, w, x)
        check("gemv", f"{N}x{Kd}({what})", mosaic,
              rel_err(got, oracle(R.gemv, w, x)), TOL["gemv"])

    n = VEC
    x, y = normal((n,)), normal((n,))
    got, mosaic = compiled_call(K.dotp, x, y)
    check("dotp", f"{n}xbf16", mosaic,
          rel_err(got, oracle(R.dotp, x, y)), TOL["dotp"])
    got, mosaic = compiled_call(lambda a, b: K.axpy(1.5, a, b), x, y)
    check("axpy", f"{n}xbf16", mosaic,
          bf16_ulps(got, oracle(lambda a, b: R.axpy(1.5, a, b), x, y)),
          1.0, unit="max_bf16_ulps")

    # fp32 activations: a bf16 output alone rounds by up to 2^-9 relative
    xs, scale = normal((SLOTS, d), jnp.float32), normal((d,), jnp.float32)
    got, mosaic = compiled_call(K.rmsnorm, xs, scale)
    check("rmsnorm", f"{SLOTS}x{d}xf32", mosaic,
          rel_err(got, oracle(R.rmsnorm, xs, scale)), TOL["rmsnorm"])

    # the engine's paged pool: SLOTS x CACHE_LEN rows in pages of PAGE
    nblk = CACHE_LEN // PAGE
    pool = SLOTS * nblk + 1
    rng = np.random.default_rng(SEED)
    kp, vp = normal((pool, PAGE, KV, hd)), normal((pool, PAGE, KV, hd))
    tables = jnp.asarray((rng.permutation(pool - 1) + 1).reshape(SLOTS, nblk),
                         jnp.int32)
    q = normal((SLOTS, H, hd))
    length = jnp.asarray(rng.integers(1, CACHE_LEN + 1, SLOTS), jnp.int32)
    got, mosaic = compiled_call(K.paged_decode_attention, q, kp, vp, tables,
                                length)
    check("paged_decode_attention", f"B{SLOTS}xH{H}xhd{hd}xS{CACHE_LEN}",
          mosaic, rel_err(got, oracle(R.paged_decode_attention, q, kp, vp,
                                      tables, length)), TOL["attention"])

    # one slab entering mid-prompt with a ragged tail, as the engine feeds it
    valid = CHUNK - 5
    qs = normal((1, CHUNK, H, hd))
    off = jnp.asarray([CACHE_LEN // 2 // CHUNK * CHUNK], jnp.int32)
    args = (qs, kp, vp, tables[:1], off, off + valid)
    got, mosaic = compiled_call(K.prefill_attention_paged, *args)
    want = oracle(R.prefill_attention_paged, *args)
    check("prefill_attention_paged", f"C{CHUNK}xH{H}xhd{hd}xS{CACHE_LEN}",
          mosaic, rel_err(got[:, :valid], want[:, :valid]),
          TOL["attention"])
    return ok


# ------------------------------------------------------------------ engine
def make_requests(vocab: int):
    import numpy as np

    from repro.serve.scheduler import Request
    rng = np.random.default_rng(SEED)
    prefix = rng.integers(1, vocab, PREFIX)
    reqs = []
    for rid in range(REQUESTS):
        tail = rng.integers(1, vocab, int(rng.integers(EXTRA[0],
                                                       EXTRA[1] + 1)))
        reqs.append(Request(rid=rid, max_new_tokens=MAX_NEW,
                            prompt=np.concatenate([prefix, tail])
                            .astype(np.int32)))
    return reqs


def serve(engine):
    """Serve the seeded requests; returns ({rid: tokens}, wall seconds)."""
    t0 = time.perf_counter()
    reqs = make_requests(engine.model.cfg.vocab_size)
    for r in reqs:
        engine.submit(r)
    done = engine.run_until_drained()
    wall = time.perf_counter() - t0
    return {r.rid: list(r.out) for r in done}, wall


def engine_config(**over):
    from repro.serve import EngineConfig
    return EngineConfig(slots=SLOTS, cache_len=CACHE_LEN, backend="paged",
                        page_size=PAGE, chunked_prefill=True,
                        chunk_size=CHUNK, prefix_cache=True, **over)


def path_logits(params, models, options=None):
    """Per path (``models`` maps a name to the Model facade of one attention
    path), the logits of the seeded requests at two points, with the steps
    compiled under the XLA ``options`` the engine uses:

    ``prompt``  the last prompt slab's row, which picks the first token;
                each path prefills its own paged caches slab by slab.
    ``decode``  the first decode step; every path decodes the first path's
                first token from the first path's caches, so only the
                decode step differs.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.kvcache import PagedBackend
    names = list(models)
    chunk = {n: jax.jit(m.chunk_step, donate_argnums=(2,),
                        compiler_options=options)
             for n, m in models.items()}
    decode = {n: jax.jit(m.decode_step, compiler_options=options)
              for n, m in models.items()}
    model = models[names[0]]
    reqs = make_requests(model.cfg.vocab_size)
    out = {(n, at): [] for n in names for at in ("prompt", "decode")}
    for start in range(0, len(reqs), SLOTS):
        group = reqs[start:start + SLOTS]
        backend = PagedBackend(page_size=PAGE)
        zeros = backend.init_caches(model, SLOTS, CACHE_LEN)
        caches = {n: jax.tree.map(jnp.copy, zeros) for n in names}
        pos = np.zeros((SLOTS,), np.int32)
        for slot, req in enumerate(group):
            L = req.prompt_len
            assert backend.reserve(slot, L + 1)
            for off in range(0, L, CHUNK):
                valid = min(CHUNK, L - off)
                tokens = np.zeros((1, CHUNK), np.int32)
                tokens[0, :valid] = req.prompt[off:off + valid]
                batch = {"tokens": jnp.asarray(tokens),
                         "offset": jnp.asarray([off], jnp.int32),
                         "valid": jnp.asarray([valid], jnp.int32),
                         "stage_base": jnp.zeros((1,), jnp.int32),
                         "block_tables": jnp.asarray(
                             backend.block_tables[slot:slot + 1])}
                for n in names:
                    logits, caches[n] = chunk[n](params, batch, caches[n])
                    if off + CHUNK >= L:
                        out[n, "prompt"].append(
                            np.asarray(logits[0], np.float32))
            pos[slot] = L
        first = np.zeros((SLOTS, 1), np.int32)
        first[:len(group), 0] = np.argmax(out[names[0], "prompt"][start:],
                                          axis=-1)
        batch = {"tokens": jnp.asarray(first), "pos": jnp.asarray(pos),
                 "block_tables": jnp.asarray(backend.block_tables)}
        for n in names:
            logits, _ = decode[n](params, batch, caches[names[0]])
            out[n, "decode"].append(
                np.asarray(logits[:len(group), 0], np.float32))
    return {key: np.stack(v) if key[1] == "prompt" else np.concatenate(v)
            for key, v in out.items()}


def top2_margin(logits):
    """Per row: the largest logit minus the second largest."""
    import numpy as np
    top = np.sort(logits, axis=-1)[:, -2:]
    return top[:, 1] - top[:, 0]


def smoke_metrics(engine, wall: float) -> dict:
    m = engine.metrics()
    keys = ("tokens_generated", "engine_cycles", "decode_steps",
            "chunk_calls", "prefill_traces", "prefill_s", "decode_s",
            "tokens_per_s", "ttft_s_p50", "ttft_s_p95", "prefix_hit_rate")
    out = {k: m[k] for k in keys if k in m}
    out["wall_s_incl_compile"] = round(wall, 3)
    return out


def engine_phase() -> bool:
    import numpy as np

    from repro.dist.tp import compiler_options
    from repro.serve import build_engine

    eng_k = build_engine(ARCH, engine_config(kernel_decode=True))
    toks_k, wall_k = serve(eng_k)
    print("[engine] smoke-run metrics, not a benchmark (pallas path): "
          + json.dumps(smoke_metrics(eng_k, wall_k)), flush=True)
    eng_j = build_engine(ARCH, engine_config(kernel_decode=False),
                         params=eng_k.params)
    toks_j, wall_j = serve(eng_j)
    print("[engine] smoke-run metrics, not a benchmark (jnp path): "
          + json.dumps(smoke_metrics(eng_j, wall_j)), flush=True)

    finished = (len(toks_k) == REQUESTS and len(toks_j) == REQUESTS
                and all(len(t) == MAX_NEW for t in toks_k.values())
                and all(len(t) == MAX_NEW for t in toks_j.values()))
    ok = report("engine", finished, step="serve",
                finished=f"{len(toks_k)}+{len(toks_j)}/{2 * REQUESTS}",
                tokens_each=MAX_NEW)
    if not finished:
        return False
    logits = path_logits(eng_k.params, {"pallas": eng_k.model,
                                        "jnp": eng_j.model},
                         compiler_options(eng_k.tp_mode))
    finite = all(bool(np.isfinite(v).all()) for v in logits.values())
    prompt_k, prompt_j = logits["pallas", "prompt"], logits["jnp", "prompt"]
    err = rel_err(prompt_k, prompt_j)
    ok &= report("engine", finite and err <= LOGIT_TOL,
                 step="first_token_logits", requests=REQUESTS,
                 finite=finite, max_rel_err=f"{err:.3e}", tol=LOGIT_TOL,
                 min_top2_margin=f"{top2_margin(prompt_j).min():.3e}(info)")

    # Every request's first token must match between the two engines, but
    # for a near-tie.  Random weights leave the top logits bf16 steps
    # apart, and the two paths' logits differ by rounding noise (bf16
    # activations rounded in different places, amplified layer by layer).
    # A mismatch passes only when, on both paths, both tokens lie within
    # the request's own disagreement (max |logit_pallas - logit_jnp|) of
    # the top logit; a token from the wrong slot or position falls short
    # by whole logits.
    rows = np.arange(REQUESTS)
    first_k = np.array([toks_k[r][0] for r in rows])
    first_j = np.array([toks_j[r][0] for r in rows])
    noise = np.abs(prompt_k - prompt_j).max(-1)
    short = np.max([lg.max(-1) - lg[rows, tok] for lg in (prompt_k, prompt_j)
                    for tok in (first_k, first_j)], axis=0)
    match = first_k == first_j
    near_tie = ~match & (short <= noise)
    agree = np.mean([a == b for r in toks_k
                     for a, b in zip(toks_k[r], toks_j[r])])
    ok &= report("engine", bool(np.all(match | near_tie)),
                 step="first_tokens", match=f"{match.sum()}/{REQUESTS}",
                 near_ties=int(near_tie.sum()),
                 mismatch_shortfall_vs_noise=[
                     f"{short[r]:.3e}<={noise[r]:.3e}"
                     for r in rows[~match]],
                 sequence_token_agreement=f"{agree:.4f}(info)")

    dec_k, dec_j = logits["pallas", "decode"], logits["jnp", "decode"]
    err = rel_err(dec_k, dec_j)
    ok &= report("engine", err <= LOGIT_TOL, step="first_decode_logits",
                 requests=REQUESTS, max_rel_err=f"{err:.3e}", tol=LOGIT_TOL,
                 argmax_match=f"{int(np.sum(dec_k.argmax(-1) == dec_j.argmax(-1)))}"
                              f"/{REQUESTS}(info)")
    return ok


def tp4_phase() -> bool:
    import jax

    from repro.serve import build_engine
    eng_1 = build_engine(ARCH, engine_config(kernel_decode=False))
    toks_1, wall_1 = serve(eng_1)
    print("[tp4] smoke-run metrics, not a benchmark (tp=1): "
          + json.dumps(smoke_metrics(eng_1, wall_1)), flush=True)
    eng_4 = build_engine(ARCH, engine_config(kernel_decode=False, tp=4,
                                             tp_mode="exact"),
                         params=eng_1.params)
    mesh_devs = {d.id for d in eng_4._tpx.mesh.devices.flat}
    leaves = jax.tree.leaves(eng_4.params)
    split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
    ok = report("tp4", len(mesh_devs) == 4 and split > 0, step="mesh",
                mesh_devices=sorted(mesh_devs),
                split_param_leaves=f"{split}/{len(leaves)}")
    toks_4, wall_4 = serve(eng_4)
    print("[tp4] smoke-run metrics, not a benchmark (tp=4 exact): "
          + json.dumps(smoke_metrics(eng_4, wall_4)), flush=True)
    same = sum(toks_4.get(r) == t for r, t in toks_1.items())
    # info: where each request's tokens first differ (MAX_NEW: never)
    diverge = [next((i for i, (a, b) in enumerate(zip(t, toks_4.get(r, [])))
                     if a != b), MAX_NEW) for r, t in toks_1.items()]
    ok &= report("tp4", len(toks_1) == REQUESTS and same == REQUESTS,
                 step="tokens", identical_requests=f"{same}/{REQUESTS}",
                 tokens_each=MAX_NEW,
                 first_divergence=f"{sorted(diverge)}(info)")
    return ok


def run_phase(fn) -> bool:
    """A phase that raises is reported as failed; the next phase still
    runs, so one run on the chip shows every fault."""
    try:
        return fn()
    except Exception:
        traceback.print_exc()
        return report(fn.__name__.replace("_phase", ""), False,
                      error="exception(see stderr)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp4", action="store_true",
                    help="run only the tensor-parallel phase: the engine at "
                         "tp=4 (exact mode) against tp=1, on four chips")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = device_phase(4 if args.tp4 else 1)
    print(f"[cache] compilation cache at {cache_dir}", flush=True)
    if args.tp4:
        phases = [tp4_phase]
    else:
        phases = [kernel_phase, engine_phase]
    ok = all([run_phase(fn) for fn in phases])
    print(f"[total] {'ok' if ok else 'FAIL'} wall_s={time.perf_counter() - t0:.1f}"
          f" (smoke run, compile included)", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
