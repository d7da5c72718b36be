"""Trace-driven load harness -> table + BENCH_load.json.

Replays a seeded workload trace (``repro.obs.workload``) through the
serving engine in three modes — bucketed paged, chunked prefill, chunked +
prefix cache — under the deterministic step clock (``repro.obs.replay``)
and reports per-request latency percentiles in *engine cycles*
(``ttft_steps_p50/p95/p99``, ``tpot_steps_*``, ``wait_steps_p95``),
queue-depth / pool-occupancy timelines and defer/eviction counts.  The
step-clock percentiles are bit-identical run over run for a given
``(dist, seed)`` — ``benchmarks/ci_gate.py`` puts SLO bands on them, while
wall-clock (``*_s``) metrics stay info-only.

A second section joins the tune registry's byte models, the Spatz cycle
model and the Table-II energy constants (``repro.obs.energy``) into
modeled energy rows per engine config — bytes/token, joules/token,
tokens/s/W, fraction-of-roofline — for bf16 and int8 KV+weights.

Two profiler sections (``repro.obs.profiler``, DESIGN.md §9): ``profile``
re-runs the chunked+prefix replay under a ``DispatchProfiler`` and reports
per-phase dispatch counts + modeled bytes (deterministic, exact-gated) and
wall-derived roofline fractions (info); ``audit`` runs the decode-step
dispatch audit (measured kernel multiset == ``decode_step_account``) for
bf16 and int8 KV, gated as exact booleans.

    PYTHONPATH=src python benchmarks/load_bench.py --fast
    PYTHONPATH=src python benchmarks/load_bench.py --requests 64 \
        --trace-out BENCH_load_trace.json      # open in ui.perfetto.dev

Interpret-mode wall times on CPU are NOT TPU performance (DESIGN.md §3);
the step-clock latencies and modeled energy are hardware-independent.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

MODES = ("paged", "chunked", "chunked+prefix")


def build_engine(arch: str, mode: str, *, slots, cache_len, page_size,
                 chunk_size, tracer=None, profiler=None, tp=1,
                 speculate_k=0):
    """(arch, mode) -> (model cfg, engine) through ``repro.serve``'s one
    factory.  ``speculate_k`` > 0 adds a same-arch draft (seed-0 params on
    both sides -> 100% greedy acceptance, so the speculative metrics are
    deterministic and gateable)."""
    from repro.configs import get_config, reduced
    from repro.serve import EngineConfig
    from repro.serve import build_engine as _factory

    cfg = reduced(get_config(arch))
    base = mode.split("/")[0]
    engine_cfg = EngineConfig(
        slots=slots, cache_len=cache_len, backend="paged",
        page_size=page_size,
        chunked_prefill=base.startswith("chunked") or speculate_k > 0,
        chunk_size=chunk_size, prefix_cache=(base == "chunked+prefix"),
        speculate_k=speculate_k, tp=tp)
    draft = reduced(get_config(arch)) if speculate_k else None
    eng = _factory(cfg, engine_cfg, draft=draft, tracer=tracer,
                   profiler=profiler)
    return cfg, eng


def replay_mode(arch: str, mode: str, trace, *, slots, cache_len,
                page_size, chunk_size, prefix_len, tracer=None):
    from repro.obs import Replayer

    cfg, eng = build_engine(arch, mode, slots=slots, cache_len=cache_len,
                            page_size=page_size, chunk_size=chunk_size,
                            tracer=tracer)
    rep = Replayer(eng, prefix_len=prefix_len).run(
        trace, vocab_size=cfg.vocab_size)
    row = {"arch": cfg.name, "mode": mode, "dist": trace.meta.get("dist"),
           "seed": trace.meta.get("seed"), **rep.row()}
    return row, rep


def profile_rows(arch: str, trace, *, slots, cache_len, page_size,
                 chunk_size, prefix_len):
    """Profiled chunked+prefix replay: per-phase dispatch counts and
    modeled bytes (deterministic — exact CI gates) plus wall-derived
    roofline fractions (info)."""
    from repro.configs import get_config, reduced
    from repro.obs import (DispatchProfiler, Replayer, decode_step_account)

    cfg = reduced(get_config(arch))
    prof = DispatchProfiler()
    prof.seed_phase("decode", decode_step_account(
        cfg, slots=slots, cache_len=cache_len, page_size=page_size))
    _, eng = build_engine(arch, "chunked+prefix", slots=slots,
                          cache_len=cache_len, page_size=page_size,
                          chunk_size=chunk_size, profiler=prof)
    prof.install()
    try:
        Replayer(eng, prefix_len=prefix_len).run(
            trace, vocab_size=cfg.vocab_size)
    finally:
        prof.uninstall()
    return prof.phase_rows()


def audit_rows(arch: str, *, cache_len, page_size):
    """Dispatch audit (exact-match booleans + byte totals) for bf16 and
    int8 KV — the measured-vs-modeled invariant, gated exactly."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models import RuntimeConfig, build_model
    from repro.obs import audit_decode_step

    rows = []
    for kv_dtype in ("bfloat16", "int8"):
        cfg = reduced(get_config(arch))
        model = build_model(cfg, RuntimeConfig(
            remat="none", kv_cache_dtype="int8" if kv_dtype == "int8"
            else ""))
        a = audit_decode_step(model, cache_len=cache_len,
                              page_size=page_size)
        rows.append({"arch": cfg.name, "kv_dtype": kv_dtype,
                     "match": bool(a.ok),
                     "dispatches": a.dispatches,
                     "modeled_bytes_measured": int(a.measured_bytes),
                     "modeled_bytes_expected": int(a.expected_bytes)})
    return rows


def energy_rows(arch: str, *, slots, cache_len, page_size):
    from repro.configs import get_config, reduced
    from repro.obs import engine_energy_row

    cfg = reduced(get_config(arch))
    rows = []
    for kv_dtype, weights in (("bfloat16", "bfloat16"), ("int8", "int8")):
        rows.append(engine_energy_row(
            cfg, slots=slots, cache_len=cache_len, page_size=page_size,
            kv_dtype=kv_dtype, weights=weights))
    # the TROOP lever as a bytes/token ratio: same target stream amortized
    # over slots * (1 + k * acceptance) tokens per verify pass
    rows.append(engine_energy_row(
        cfg, slots=slots, cache_len=cache_len, page_size=page_size,
        kv_dtype="bfloat16", weights="bfloat16", speculate_k=3,
        acceptance=1.0))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--dist", default="heavy_tail")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fast", action="store_true",
                    help="16-request smoke (CI); default is a 64-request "
                         "soak")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk-size", type=int, default=16)
    ap.add_argument("--prefix-len", type=int, default=24)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the chunked+prefix run's Chrome trace "
                         "(open in ui.perfetto.dev)")
    ap.add_argument("--workload-out", default=None, metavar="PATH",
                    help="also persist the workload trace as JSON-lines")
    ap.add_argument("--out", default="BENCH_load.json")
    args = ap.parse_args(argv)

    import jax
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    requests = args.requests or (16 if args.fast else 64)
    trace = obs.generate(args.dist, requests=requests, seed=args.seed,
                         prompt_len=(4, min(48, args.cache_len - 18)),
                         max_new=(2, 16))
    if args.workload_out:
        trace.to_jsonl(args.workload_out)
        print(f"wrote {args.workload_out}")

    rows = []
    for mode in MODES:
        tracer = obs.Tracer() if mode == "chunked+prefix" else None
        row, _ = replay_mode(
            args.arch, mode, trace, slots=args.slots,
            cache_len=args.cache_len, page_size=args.page_size,
            chunk_size=args.chunk_size, prefix_len=args.prefix_len,
            tracer=tracer)
        rows.append(row)
        print(f"{mode:<15} ttft_steps p50/p95/p99 "
              f"{row['ttft_steps_p50']:.1f}/{row['ttft_steps_p95']:.1f}/"
              f"{row['ttft_steps_p99']:.1f}  "
              f"tpot_steps p95 {row['tpot_steps_p95']:.2f}  "
              f"queue max {row['queue_depth_max']}  "
              f"defers {row['deferrals']}  "
              f"drained={row['all_finished']}")
        if tracer is not None and args.trace_out:
            tracer.to_chrome(args.trace_out)
            print(f"wrote {args.trace_out} ({len(tracer.events())} events, "
                  f"{tracer.dropped} dropped)")

    energy = energy_rows(args.arch, slots=args.slots,
                         cache_len=args.cache_len,
                         page_size=args.page_size)
    for e in energy:
        if e.get("speculate_k"):
            print(f"energy bf16/spec-k{e['speculate_k']} "
                  f"{e['bytes_per_token']:>8} B/tok  "
                  f"{e['joules_per_token']*1e6:>8.3f} uJ/tok  "
                  f"{e['tokens_per_s_per_w']:>10.0f} tok/s/W  "
                  f"roofline frac {e['fraction_of_roofline']:.3f}")
            continue
        print(f"energy {e['kv_dtype']:<9} {e['bytes_per_token']:>8} B/tok  "
              f"{e['joules_per_token']*1e6:>8.3f} uJ/tok  "
              f"{e['tokens_per_s_per_w']:>10.0f} tok/s/W  "
              f"roofline frac {e['fraction_of_roofline']:.3f}")

    profile = profile_rows(args.arch, trace, slots=args.slots,
                           cache_len=args.cache_len,
                           page_size=args.page_size,
                           chunk_size=args.chunk_size,
                           prefix_len=args.prefix_len)
    for p in profile:
        print(f"profile {p['phase']:<16} {p['occurrences']:>4} occ  "
              f"{p['dispatches']:>6} dispatches  "
              f"{p['modeled_bytes']:>12,} B modeled")
    audit = audit_rows(args.arch, cache_len=args.cache_len,
                       page_size=args.page_size)
    for a in audit:
        print(f"audit  kv={a['kv_dtype']:<9} match={a['match']}  "
              f"{a['dispatches']} dispatches  "
              f"{a['modeled_bytes_measured']:,} B")

    from repro.core.troop import pallas_interpret
    payload = {
        "backend": jax.default_backend(),
        "interpret_mode": pallas_interpret(),
        "workload": trace.meta,
        "rows": rows,
        "energy": energy,
        "profile": profile,
        "audit": audit,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"wrote {args.out}")
    return payload


if __name__ == "__main__":
    main()
