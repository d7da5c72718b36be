"""Per-kernel roofline-tuning report -> table + BENCH_tune.json.

Runs the full tune subsystem end to end for each registered kernel:
enumerate the TroopConfig space, prune analytically, time the survivors
(interpret mode on CPU — wall times are NOT TPU performance, but the
tune -> cache -> dispatch loop is exercised for real), and report each
kernel's best config with its fraction-of-roofline score.  A second
invocation resolves every kernel from the persistent cache without
re-timing (the acceptance check in tests/test_tune.py).

    PYTHONPATH=src python benchmarks/tune_report.py --fast

``--fast`` uses the registry's small example shapes, 2 survivors and 1
timing iteration per candidate (CI smoke).  Set REPRO_TUNE_BW to a
measured host bandwidth to make interpret-mode fractions meaningful;
the default denominator is the TPU v5e HBM roofline.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable as `python benchmarks/tune_report.py` without PYTHONPATH=src
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


FAST_KERNELS = ("gemv", "dotp", "axpy", "rmsnorm")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small shapes, keep=2, iters=1 (CI smoke)")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated subset (default: fast four / all)")
    ap.add_argument("--keep", type=int, default=None,
                    help="survivors of the analytic prune per kernel")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations per survivor")
    ap.add_argument("--force", action="store_true",
                    help="retune even when cached")
    ap.add_argument("--out", default="BENCH_tune.json")
    args = ap.parse_args(argv)

    import repro.kernels  # noqa: F401  (populates the registry)
    from repro import tune
    from repro.core.roofline import PEAK_FLOPS
    from repro.tune.search import roofline_bw
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    keep = args.keep if args.keep is not None else (2 if args.fast else 4)
    iters = args.iters if args.iters is not None else (1 if args.fast else 3)
    if args.kernels:
        names = tuple(k.strip() for k in args.kernels.split(",") if k.strip())
    else:
        names = FAST_KERNELS if args.fast else tune.names()

    cache = tune.default_cache()
    rows = []
    for name in names:
        if name not in tune.REGISTRY:
            print(f"-- unknown kernel {name!r}; registered: "
                  f"{', '.join(tune.names())}", file=sys.stderr)
            continue
        spec = tune.REGISTRY[name]
        if spec.example is None:
            print(f"-- {name}: no example factory, skipped", file=sys.stderr)
            continue
        kargs, kkw = spec.example(small=args.fast)
        t0 = time.time()
        res = tune.tune(name, *kargs, kernel_kwargs=kkw, keep=keep,
                        iters=iters, cache=cache, force=args.force)
        b = res.best
        rows.append({
            "kernel": name,
            "key": res.key,
            "config": tune.config_to_dict(b),
            "fraction_of_roofline": res.fraction,
            "predicted_fraction": res.predicted,
            "measured_us": (res.measured_s or 0.0) * 1e6,
            "roofline_us": res.roofline_s * 1e6,
            "from_cache": res.from_cache,
            "timings_run": res.timings_run,
            "tune_wall_s": time.time() - t0,
        })

    hdr = (f"{'kernel':<18}{'best config':<26}{'frac-roofline':>14}"
           f"{'predicted':>10}{'meas_us':>10}{'roof_us':>10}{'cached':>8}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        c = r["config"]
        cfg_s = (f"s{c['streams']}/u{c['unroll']}/"
                 f"n{c['block_n']}/k{c['block_k']}")
        print(f"{r['kernel']:<18}{cfg_s:<26}"
              f"{r['fraction_of_roofline']:>14.3e}"
              f"{r['predicted_fraction']:>10.3f}"
              f"{r['measured_us']:>10.1f}{r['roofline_us']:>10.3f}"
              f"{str(r['from_cache']):>8}")

    from repro.core.troop import pallas_interpret
    payload = {
        "backend": jax.default_backend(),
        "interpret_mode": pallas_interpret(),
        "roofline_bytes_per_s": roofline_bw(),
        "peak_flops": PEAK_FLOPS,
        "cache_path": cache.path,
        "kernels": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"\nwrote {args.out} ({len(rows)} kernels; cache: {cache.path})")
    return rows


if __name__ == "__main__":
    main()
