"""A cell small enough for a test run on the CPU: the qwen1.5-0.5b
registry entry cut to toy widths, driven by the real harness."""
from __future__ import annotations

import argparse
import copy

from reference.dense_decoder import TINY

MODEL = TINY["model"]
OVERRIDES = TINY["registry_overrides"]
ENGINE = TINY["engine"]

OPEN = {"loop": "open", "rate_per_s": 12.0, "lead_in_s": 0.5,
        "shape_seed": 5,
        "prefixes": {"length": 24, "popularity": [0.6, 0.4], "share": 0.8},
        "prompt": {"dist": "pareto", "alpha": 1.8, "min": 4, "max": 40},
        "output": {"dist": "pareto", "alpha": 1.8, "min": 4, "max": 12},
        "engine": ENGINE, "check_requests": 4}
CLOSED = {"loop": "closed", "concurrency": 4, "shape_seed": 6,
          "prompt": {"dist": "loguniform", "min": 20, "max": 60},
          "output": {"dist": "uniform", "min": 8, "max": 24},
          "engine": ENGINE, "check_requests": 6}


def spec(traffic=OPEN, limit=0.05, chips=1):
    """The toy cell; on ``chips`` > 1 its engine is tensor-parallel over
    them (``tp`` = chips, exact mode)."""
    traffic = copy.deepcopy(traffic)
    if chips > 1:
        traffic["engine"] = dict(traffic["engine"], tp=chips,
                                 tp_mode="exact")
    return {"cell": {"name": "tiny", "config": "tiny", "traffic": "tiny",
                     "chips": chips},
            "config": {"registry": TINY["registry"],
                       "registry_overrides": dict(OVERRIDES),
                       "reference": "dense_decoder",
                       "model": dict(MODEL)},
            "traffic": traffic,
            "limits": {"max_logit_gap": limit},
            "end_to_end": [{"name": n} for n in
                           ("setup_s", "ttft_p50_s", "ttft_p95_s", "itl_p50_s",
                            "itl_p95_s", "output_tok_per_s")],
            "per_layer": []}


def args(seed=12345678901, seconds=2.0, control=0):
    return argparse.Namespace(workload="tiny", seed=seed, seconds=seconds,
                              trace=0, platform="cpu", control=control)
