"""repro.obs — observability: lifecycle tracing, load harness, telemetry.

Four layers over the serving stack (DESIGN.md §7, §9):

  tracer    — ring-buffer ``Tracer``: per-request spans + allocator events
              + counters, exported as JSON-lines or Chrome trace-event
              format (opens in Perfetto, one track per engine slot;
              ``launch/serve.py --trace-out`` writes it); ``tracer.Phase``,
              the engine's one phase mechanism, also opens a ``jax.profiler``
              annotation ``engine.<phase>`` per cycle phase, so a run under
              ``jax.profiler.trace`` shows the phases beside the device ops
  workload  — seeded replayable traces (bursty / diurnal / heavy-tail
              arrival + length distributions) and ``Replayer``, which
              drives any engine config against the arrival schedule and
              reports TTFT/TPOT percentiles, queue/occupancy timelines and
              defer/eviction counts — deterministic under the step clock
  energy    — ``decode_step_account`` + ``EnergyModel``: joins the tune
              registry's byte/FLOP models, the Spatz machine point and the
              Table-II energy constants into modeled joules/token,
              tokens/s/W and fraction-of-roofline per engine row
  profiler  — ``DispatchProfiler`` on the registry dispatch seam: per-
              (kernel, phase, signature) dispatch counts, modeled bytes,
              achieved bytes/s vs the Spatz roofline, Perfetto kernel
              spans + streamed-bytes counters, and the measured-vs-modeled
              ``audit_decode_step`` invariant (DESIGN.md §9)

Quickstart::

    from repro import obs
    tracer = obs.Tracer()
    eng = ServingEngine(..., tracer=tracer)
    trace = obs.generate("heavy_tail", requests=64, seed=0)
    report = obs.Replayer(eng).run(trace, vocab_size=cfg.vocab_size)
    tracer.to_chrome("soak.trace.json")      # open in ui.perfetto.dev
    print(report.row())                      # ttft_steps_p99, ...
"""
from repro.obs.energy import (AccountEntry, E_BEAT, E_FMA, EnergyModel,
                              P_STATIC, StepReport, account_totals,
                              decode_step_account, engine_energy_row)
from repro.obs.profiler import (AuditResult, DispatchProfiler,
                                DispatchRecord, audit_decode_step,
                                modeled_time_s, roofline_bytes_per_s)
from repro.obs.replay import Replayer, ReplayReport, percentiles
from repro.obs.tracer import Tracer, span_pairs
from repro.obs.workload import (DISTRIBUTIONS, TraceEntry, WorkloadTrace,
                                generate)

__all__ = [
    "Tracer", "span_pairs",
    "DISTRIBUTIONS", "TraceEntry", "WorkloadTrace", "generate",
    "Replayer", "ReplayReport", "percentiles",
    "AccountEntry", "EnergyModel", "StepReport", "account_totals",
    "decode_step_account", "engine_energy_row",
    "P_STATIC", "E_BEAT", "E_FMA",
    "DispatchProfiler", "DispatchRecord", "AuditResult",
    "audit_decode_step", "modeled_time_s", "roofline_bytes_per_s",
]
