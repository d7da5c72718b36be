#!/usr/bin/env python3
"""Benchmark of the serving engine: one cell, one run, one result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/limits/<cell>.json``.  Per-layer
metrics are read by ``bench/metrics/<metric>.py``.  Everything about an
architecture (its weights' shapes and maker, the program's parameter
layout, the plain reference, the count of a decode step's work, the fields
compared with the program) comes from ``bench/reference/<name>.py``, the
module the configuration file names under ``"reference"``; see
``reference/dense_decoder.py`` for the contract.  Nothing here names a
cell, a configuration, a mix or an architecture.

A cell's chips are its engine's chips: the mix's engine ``tp`` (1 when
absent) has to equal the cell's ``chips``, or the run exits with no result.

A run, in one process (the process that touches JAX holds the chip):

1. set-up: weights made from ``--seed`` in one jitted call, on the chips
   that serve them: on one chip, or sharded as the engine's own
   tensor-parallel plan shards them (so the engine moves none); the engine
   built by ``repro.serve.build_engine`` from the configuration's registry
   id and the mix's engine fields, its programs warmed (a chunk, a decode
   step, a copy-on-write page copy), then the lead-in the traffic needs.
   ``setup_s`` runs from process start to the window's opening.
2. the window: ``--seconds`` of traffic from ``traffic/generate.py``,
   submitted when due (open loop) or when a request finishes (closed
   loop), the engine stepped in between.  Token times are what the host
   sees after each ``engine.step()``; every latency runs from due time.
3. with ``--trace 1``, a profiler trace of a slice of the window, reduced by
   ``trace_reduce.py`` (device time is the mean over the cell's chips);
   the line then carries the per-layer metrics.  A roofline share or an
   MFU sets a step's need against the peaks of all the cell's chips.
4. the check, after the window: a seeded sample of the requests the window
   served (finished in it or still decoding at its close; the one with the
   most tokens among them), run through the plain reference on weights
   made again from the seed, each sharded over the cell's chips on its
   largest axis that they divide, once the engine is freed.  ``correct``
   holds when every served token lies within the cell's limit of the
   reference's best logit, and every finished request has all its
   tokens.  ``--control 1`` puts the fp8 control (the reference one
   precision step down) in the program's place: its tokens are the ones
   judged, and the run has to come out not correct.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  ``--platform cpu`` rehearses the harness on
the CPU: its line says ``cpu`` and carries no device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from traffic.generate import generate  # noqa: E402

MIN_ITL_SPAN_S = 0.25   # a request's in-window token span read for ITL
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


# ------------------------------------------------------------------ spec
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """Everything a run of ``workload`` needs, found by name."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(metric):
        return workload in metric.get("workloads", [workload])
    return {
        "cell": cell,
        "config": load_json(root / config["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def metric_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    if str(BENCH / "metrics") not in sys.path:
        sys.path.insert(0, str(BENCH / "metrics"))
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(config: dict):
    """The architecture's module, ``bench/reference/<name>.py``, by the
    name the configuration file gives; a name with no module ends the run
    without a result."""
    name = str(config.get("reference", ""))
    path = BENCH / "reference" / f"{name}.py"
    if not name.isidentifier() or not path.is_file():
        raise SystemExit(f"bench: the configuration names the reference "
                         f"{name!r}, and bench/reference/ has no such "
                         f"module")
    return importlib.import_module(f"reference.{name}")


def read_per_layer(per_layer, run_info) -> dict:
    """Every per-layer metric of the cell, read from the run's records.
    One whose source is missing from the run (say, a jitted program that
    no longer matches ``programs.json``) ends the run without a result."""
    out = {}
    for mdef in per_layer:
        value = metric_reader(mdef["name"])(run_info)
        if value is None:
            raise SystemExit(f"bench: metric {mdef['name']} found nothing "
                             f"to read in this run; its source is missing")
        out[mdef["name"]] = {"value": value, "unit": mdef["unit"]}
    return out


def log(*parts):
    print("[bench]", *parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- devices
def devices_or_exit(platform: str, chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"bench: JAX's devices are {devs[0].platform!r}, "
                         f"this run needs {platform!r}; no fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    """The devices as JAX reports them; ``memory_peak_bytes`` is the
    fullest chip's peak, ``memory_peak_bytes_per_chip`` each chip's."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks),
            "memory_peak_bytes_per_chip": peaks}


# ----------------------------------------------------------------- engine
def model_config(config: dict, R):
    """The program's registry configuration, with the file's overrides,
    checked against the file's ``model`` block on the fields that the
    architecture's module ``R`` names."""
    import dataclasses

    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config.get("registry_overrides", {}))
    m = config["model"]
    differ = {k: (v, m.get(k)) for k, v in R.program_fields(cfg).items()
              if m.get(k) != v}
    if differ:
        raise SystemExit(f"bench: the program's {config['registry']!r} "
                         f"differs from the configuration file: {differ}")
    return cfg


def engine_config(fields: dict):
    """The mix's engine fields as an ``EngineConfig``.  A field the program
    no longer has is reported and left out, so that deleting an option
    from the program needs no edit here."""
    import dataclasses

    from repro.serve import EngineConfig
    known = {f.name for f in dataclasses.fields(EngineConfig)}
    for k in sorted(set(fields) - known):
        log(f"engine field {k}={fields[k]!r} is not an EngineConfig field "
            f"of the program; left out")
    return EngineConfig(**{k: v for k, v in fields.items() if k in known})


def engine_shardings(model, ecfg):
    """Where the engine keeps its weights: None (the default device) for
    ``tp`` 1, else, given the abstract parameter tree, the sharding of
    each leaf under the engine's own plan (``repro.dist.tp.plan``'s mesh
    and ``param_specs``), which ``shard_params`` then leaves in place."""
    if ecfg.tp == 1:
        return None
    import jax
    from jax.sharding import NamedSharding

    from repro.dist import tp as TP
    plan = TP.plan(model, ecfg.tp, ecfg.tp_mode)

    def place(params):
        specs = TP.param_specs(model, params, plan)
        return jax.tree.map(lambda _, s: NamedSharding(plan.mesh, s),
                            params, specs)
    return place


def spread_over(devs):
    """Where the check keeps its weights: None (the default device) on one
    chip, else each leaf sharded over ``devs`` on its largest axis that
    they divide (replicated where none does)."""
    if len(devs) == 1:
        return None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(devs), ("chips",))
    n = len(devs)

    def one(leaf):
        axes = [i for i in range(leaf.ndim) if leaf.shape[i] % n == 0]
        spec = [None] * leaf.ndim
        if axes:
            spec[max(axes, key=lambda i: leaf.shape[i])] = "chips"
        return NamedSharding(mesh, PartitionSpec(*spec))
    return lambda w: jax.tree.map(one, w)


def build(spec: dict, seed: int, R):
    import jax

    from repro.models import RuntimeConfig, build_model
    from repro.models import modules as M
    from repro.serve import build_engine
    cfg = model_config(spec["config"], R)
    ecfg = engine_config(spec["traffic"]["engine"])
    m = spec["config"]["model"]
    model = build_model(cfg, RuntimeConfig(remat="none"))
    want = jax.eval_shape(lambda: M.unbox(model.init(jax.random.PRNGKey(0))))
    got = jax.eval_shape(lambda: R.make(m, seed, layout=R.program_tree))
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise SystemExit(f"bench: the program's parameter layout differs "
                         f"from {R.__name__}.program_tree")
    params = R.make(m, seed, layout=R.program_tree,
                    shardings=engine_shardings(model, ecfg))
    jax.block_until_ready(params)
    log(f"set-up: weights made at {time.perf_counter() - T_START:.3f}s")
    return build_engine(cfg, ecfg, params=params)


def warm_up(engine, traffic: dict):
    """Compile every program the window drives: a chunk (two, the second
    mid-prompt), a decode step and, where prefixes are shared, the page
    copy of a copy-on-write (a hit that ends inside a page)."""
    from repro.serve.scheduler import Request
    ecfg = traffic["engine"]
    C, P = ecfg["chunk_size"], ecfg["page_size"]
    rng = np.random.default_rng(0)
    V = engine.model.cfg.vocab_size
    base = rng.integers(1, V, C + 2 * P).astype(np.int32)
    engine.submit(Request(rid=-1, prompt=base, max_new_tokens=3))
    engine.run_until_drained()
    if ecfg.get("prefix_cache"):
        tail = rng.integers(1, V, 9).astype(np.int32)
        engine.submit(Request(rid=-2, prompt=np.concatenate(
            [base[:C + P + 5], tail]), max_new_tokens=3))
        engine.run_until_drained()


# ---------------------------------------------------------------- driving
class Track:
    """The host's view of one request."""
    __slots__ = ("spec", "req", "due", "submit_t", "times", "seen",
                 "finish_t", "in_window")
    # ``req`` is None for a request due in the window that was never
    # submitted before the window closed

    def __init__(self, spec, req, due, now, ws):
        self.spec, self.req, self.due = spec, req, due
        self.submit_t = now
        self.times = []
        self.seen = 0
        self.finish_t = None
        self.in_window = due >= ws


@contextlib.contextmanager
def span(name: str, on: bool):
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Driver:
    """Submits the mix's requests and steps the engine; records the host
    time of every token."""

    def __init__(self, engine, specs, traffic, annotate, on_step=None):
        self.engine = engine
        self.pending = deque(specs)
        self.traffic = traffic
        self.annotate = annotate
        self.on_step = on_step
        self.live = {}
        self.tracks = []
        self.failed = 0
        self.ws = float("inf")

    def submit(self, spec, due, now):
        from repro.serve.scheduler import Request
        req = Request(rid=spec.rid, prompt=spec.prompt,
                      max_new_tokens=spec.max_new)
        try:
            with span("bench.submit", self.annotate):
                self.engine.submit(req)
        except ValueError as e:
            self.failed += due >= self.ws
            log(f"request {spec.rid} refused: {e}")
            return
        tr = Track(spec, req, due, now, self.ws)
        self.live[spec.rid] = tr
        self.tracks.append(tr)

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(r is not None for r in e.active.values())

    def step(self):
        with span("bench.step", self.annotate):
            self.engine.step()
        now = time.perf_counter()
        done = []
        for rid, tr in self.live.items():
            n = len(tr.req.out)
            if n > tr.seen:
                tr.times.extend([now] * (n - tr.seen))
                tr.seen = n
            if tr.req.done:
                tr.finish_t = now
                done.append(rid)
        for rid in done:
            del self.live[rid]
        if self.on_step is not None:
            self.on_step(self, now)

    def open_loop(self, t0, until):
        """Submit every request due by now, step; sleep when idle."""
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            while self.pending and t0 + self.pending[0].due_s <= now:
                spec = self.pending.popleft()
                self.submit(spec, t0 + spec.due_s, now)
            if self.busy():
                self.step()
            else:
                nxt = t0 + self.pending[0].due_s if self.pending else until
                with span("bench.idle", self.annotate):
                    time.sleep(max(0.0, min(nxt, until) - now))

    def closed_loop(self, until, opened=None):
        """Keep ``concurrency`` requests in flight; a finished one is
        replaced at once.  With ``opened`` set, stop as soon as it says the
        window may open."""
        conc = int(self.traffic["concurrency"])
        while True:
            now = time.perf_counter()
            if now >= until or (opened is not None and opened(self)):
                return now
            if not self.pending and not self.busy():
                raise RuntimeError("closed loop ran out of requests")
            while len(self.live) < conc and self.pending:
                self.submit(self.pending.popleft(), now, now)
            self.step()


def all_decoding(driver) -> bool:
    conc = int(driver.traffic["concurrency"])
    live = list(driver.live.values())
    return len(live) == conc and all(tr.seen >= 1 for tr in live)


def request_count(traffic: dict, seconds: float) -> int:
    if traffic["loop"] == "open":
        rate = traffic.get("rate_per_s") or \
            traffic["session_rate_per_s"] * np.mean(
                traffic["documents"]["asks"])
        horizon = traffic.get("lead_in_s", 0.0) + seconds
        return int(rate * horizon * 1.5) + 64
    return int(traffic["concurrency"]) * 4 + int(seconds * 100) + 64


# ---------------------------------------------------------------- metrics
def pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(driver, ws, we, setup_s, diag):
    tokens = sum(sum(ws <= t <= we for t in tr.times)
                 for tr in driver.tracks)
    due = [tr for tr in driver.tracks if tr.in_window]
    ttft = [(tr.times[0] if tr.times and tr.times[0] <= we else we) - tr.due
            for tr in due]
    itl, short = [], 0
    for tr in driver.tracks:
        ts = [t for t in tr.times if ws <= t <= we]
        if len(ts) < 2:
            continue
        if ts[-1] - ts[0] < MIN_ITL_SPAN_S:
            short += 1
            continue
        itl.append((ts[-1] - ts[0]) / (len(ts) - 1))
    lateness = [tr.submit_t - tr.due for tr in due]
    third = (we - ws) / 3
    for part, lo in (("first", ws), ("last", we - third)):
        part_ttft = [t for t, tr in zip(ttft, due) if lo <= tr.due < lo + third]
        if part_ttft:
            diag[f"ttft_p50_{part}_third"] = pct(part_ttft, 50)
    diag["offered_per_s"] = len(due) / (we - ws)
    log(f"window {we - ws:.3f}s: {len(due)} requests due, {tokens} tokens, "
        f"{len(itl)} requests read for ITL ({short} spans under "
        f"{MIN_ITL_SPAN_S}s left out)")
    if lateness:
        log(f"generator lateness p99 {pct(lateness, 99):.6f}s "
            f"max {max(lateness):.6f}s")
    if ttft:
        log(f"ttft p50 {pct(ttft, 50):.6f}s p95 {pct(ttft, 95):.6f}s "
            f"(n={len(ttft)})")
    out = {"setup_s": (setup_s, "s"),
           "output_tok_per_s": (tokens / (we - ws), "tokens/s")}
    if ttft:
        out["ttft_p50_s"] = (pct(ttft, 50), "s")
        out["ttft_p95_s"] = (pct(ttft, 95), "s")
    if itl:
        out["itl_p50_s"] = (pct(itl, 50), "s")
        out["itl_p95_s"] = (pct(itl, 95), "s")
    return out


class DecodeNeed:
    """Sums the architecture's ``decode_step`` count over the decode steps
    submitted while the trace runs (after each ``engine.step()``, the
    requests that have a first token and are not finished are the ones its
    decode step feeds)."""

    def __init__(self, R, m):
        self.R, self.m = R, m
        self.on = False
        self.flops = self.bytes = 0.0
        self.steps = 0

    def __call__(self, driver, now):
        if not self.on:
            return
        batch = [(tr.spec.prompt.size + tr.seen, tr.spec.head_id,
                  tr.spec.head_len)
                 for tr in driver.live.values() if tr.seen >= 1]
        if not batch:
            return
        f, b = self.R.decode_step(self.m, batch, self.m["compute_dtype"])
        self.flops += f
        self.bytes += b
        self.steps += 1


# ------------------------------------------------------------------ check
def check(spec, seed, sample, R, devs, control: bool):
    """Run the reference ``R`` over the sampled requests, its weights
    spread over the cell's chips ``devs``: the largest gap by which a
    served token's reference logit lies below the reference's best.  With
    ``control``, the fp8 control stands in the program's place at the same
    positions, and the gap of its own top token is returned as well."""
    import jax
    import jax.numpy as jnp

    m = spec["config"]["model"]
    frozen = R.frozen(m)
    T = int(spec["traffic"]["engine"]["cache_len"])
    n_rows = int(spec["traffic"]["output"]["max"])
    w = R.make(m, seed, shardings=spread_over(devs))
    gap = ctl = 0.0
    served = 0
    for req in sample:
        seq = np.concatenate([req.prompt, np.asarray(req.out[:-1],
                                                     np.int32)])
        toks = np.zeros(T, np.int32)
        toks[:seq.size] = seq
        n = len(req.out)
        rows = np.zeros(n_rows, np.int32)
        rows[:n] = req.prompt.size - 1 + np.arange(n)
        ref = np.asarray(R.logits(w, frozen, jnp.asarray(toks),
                                  jnp.asarray(rows)))[:n]
        best = ref.max(-1)
        got = ref[np.arange(n), np.asarray(req.out, np.int64)]
        gap = max(gap, float((best - got).max()))
        served += n
        if control:
            low = np.asarray(R.logits(w, frozen, jnp.asarray(toks),
                                      jnp.asarray(rows), mode="fp8"))[:n]
            alt = ref[np.arange(n), low.argmax(-1)]
            ctl = max(ctl, float((best - alt).max()))
    del w
    jax.clear_caches()
    return gap, ctl, served


def sample_served(driver, ws, we, k, seed):
    """A seeded sample of ``k`` requests the window served: finished in it,
    or still decoding at its close (their tokens so far), with the one
    that has the most tokens always in it."""
    served = [tr for tr in driver.tracks if tr.req is not None and (
        ws <= (tr.finish_t or we) <= we and tr.times and tr.times[-1] >= ws)]
    if not served:
        return []
    longest = max(served, key=lambda tr: len(tr.req.out))
    rest = [tr for tr in served if tr is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest.req] + [rest[i].req for i in sorted(pick)]


# ------------------------------------------------------------------- main
def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--platform", default="tpu",
                   help="tpu (default); cpu only rehearses the harness")
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="judge the fp8 control in the program's place "
                        "(a check of the check, never a benchmark run)")
    return p.parse_args(argv)


def run(args, spec, hooks=None, diag=None) -> dict:
    """One run; returns the result record (``main`` prints it).  ``hooks``
    (tests) may wrap the engine after it is built; ``diag``, a dict, gets
    the backlog and TTFT-by-thirds readings that ``sweep.py`` judges."""
    diag = {} if diag is None else diag
    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell, traffic = spec["cell"], spec["traffic"]
    tp = int(traffic["engine"].get("tp", 1))
    if tp != int(cell["chips"]):
        raise SystemExit(f"bench: the cell asks for {cell['chips']} chips "
                         f"and its engine for tp={tp}; they have to agree")
    R = reference(spec["config"])
    devs = devices_or_exit(args.platform, int(cell["chips"]))
    on_chip = args.platform == "tpu"
    tracing = bool(args.trace) and on_chip
    log(f"set-up: devices at {time.perf_counter() - T_START:.3f}s")

    engine = build(spec, args.seed, R)
    if hooks and "engine" in hooks:
        hooks["engine"](engine)
    log(f"set-up: weights and engine at {time.perf_counter() - T_START:.3f}s")
    warm_up(engine, traffic)
    log(f"set-up: warm-up done at {time.perf_counter() - T_START:.3f}s")

    compiles = [0]
    counting = [False]

    def on_event(name, *_a, **_k):
        if counting[0] and name in COMPILE_EVENTS:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    need = DecodeNeed(R, spec["config"]["model"]) if tracing else None
    specs = generate(traffic, args.seed,
                     request_count(traffic, args.seconds),
                     engine.model.cfg.vocab_size)
    driver = Driver(engine, specs, traffic, annotate=tracing, on_step=need)
    trace_dir = None
    if tracing:
        # started before the traffic: starting the profiler stalls the
        # host for seconds, which must not land in the window
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if traffic["loop"] == "open":
        t0 = time.perf_counter()
        ws = t0 + float(traffic.get("lead_in_s", 0.0))
        driver.ws = ws
        driver.open_loop(t0, ws)
    else:
        ws = driver.closed_loop(float("inf"), opened=all_decoding)
        driver.ws = ws
    we = ws + args.seconds
    setup_s = ws - T_START
    diag["queued_open"] = len(engine.queue)
    log(f"backlog at the window's opening: {len(engine.queue)} queued, "
        f"{sum(r is not None for r in engine.active.values())} in slots")
    counting[0] = True
    counters0 = (engine.shared_tokens, engine.prefill_tokens)
    with span("bench.window", tracing):
        if need is not None:
            need.on = True
        if traffic["loop"] == "open":
            driver.open_loop(t0, we)
        else:
            driver.closed_loop(we)
        if tracing:
            jax.block_until_ready(engine.caches)
    if tracing:
        need.on = False
        jax.profiler.stop_trace()
    counting[0] = False
    if traffic["loop"] == "open":
        # requests due before the close that a long step kept the loop from
        # submitting count as waiting since their due time
        for sp in driver.pending:
            if t0 + sp.due_s >= we:
                break
            driver.tracks.append(Track(sp, None, t0 + sp.due_s, we, ws))
    diag["queued_close"] = len(engine.queue)
    log(f"backlog at the window's close: {len(engine.queue)} queued, "
        f"{sum(r is not None for r in engine.active.values())} in slots")
    counters1 = (engine.shared_tokens, engine.prefill_tokens)
    log(f"compilations inside the window: {compiles[0]}")
    log(f"set-up {setup_s:.3f}s")
    device = device_record(devs)
    e2e = end_to_end(driver, ws, we, setup_s, diag)

    k = int(traffic.get("check_requests", 6))
    sample = sample_served(driver, ws, we, k, args.seed)
    finished = [tr for tr in driver.tracks
                if tr.finish_t is not None and ws <= tr.finish_t <= we]
    # a request ends at its max_new tokens, or where its rows reach the
    # engine's cache_len - 1 (the capacity a slot holds)
    cap = int(traffic["engine"]["cache_len"])
    short = sum(len(tr.req.out) != min(tr.spec.max_new,
                                       cap - tr.spec.prompt.size)
                for tr in finished)
    # the requests the window worked on: due in it, or given tokens in it
    attempted = sum(tr.in_window or any(ws <= t <= we for t in tr.times)
                    for tr in driver.tracks) + driver.failed
    failed = driver.failed

    record = {"trace": None}
    if tracing:
        import trace_reduce
        from peaks import peaks
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        red = trace_reduce.reduce_trace(files[-1])
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        record["peaks"] = peaks(device["kind"])
        record["chips"] = len(devs)
        record["need"] = {"flops": need.flops, "bytes": need.bytes,
                          "steps": need.steps}

    # free the engine before the reference runs on the same chip
    run_info = {
        "requests": [{"due": tr.due,
                      "admit_t": tr.req.admit_t or None if tr.req else None,
                      "times": tr.times, "in_window": tr.in_window}
                     for tr in driver.tracks],
        "window": (ws, we),
        "counters": {"shared_tokens": counters1[0] - counters0[0],
                     "prefill_tokens": counters1[1] - counters0[1]},
        **record,
    }
    del engine, driver
    gc.collect()

    t_check = time.perf_counter()
    gap, ctl, served = check(spec, args.seed, sample, R, devs,
                             bool(args.control)) \
        if sample else (float("inf"), 0.0, 0)
    log(f"check took {time.perf_counter() - t_check:.3f}s, "
        f"{time.perf_counter() - we:.3f}s after the window's close")
    limits = spec["limits"]
    log(f"check: {len(sample)} requests, {served} served tokens compared "
        f"against the reference, of {len(finished)} finished in the window")
    if args.control:
        # the control stands in the program's place: its gap is judged
        log(f"program max_logit_gap {gap!r}; judged: the fp8 control's")
        gap = ctl
    numbers = {"max_logit_gap": (gap, limits["max_logit_gap"]),
               "short_answers": (short, 0)}
    correct = bool(sample) and all(v <= lim for v, lim in numbers.values())

    metrics = {}
    if args.trace:
        if on_chip:
            metrics = read_per_layer(spec["per_layer"], run_info)
    else:
        for mdef in spec["end_to_end"]:
            if mdef["name"] in e2e:
                value, unit = e2e[mdef["name"]]
                metrics[mdef["name"]] = {"value": value, "unit": unit}
    result = {"correct": correct, "attempted": attempted,
              "failed": failed,
              "metrics": metrics, "device": device}
    if record["trace"] is not None:
        result["breakdown"] = {
            "device_ops": record["trace"]["top_ops"],
            "idle_gaps": record["trace"]["idle_gaps"]}
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"check {k} = {v!r} limit {lim!r}")
    return result


def main(argv=None, hooks=None) -> int:
    args = parse(argv)
    spec = load_spec(args.workload)
    result = run(args, spec, hooks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
