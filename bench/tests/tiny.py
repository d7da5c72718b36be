"""A cell small enough for a test run on the CPU: the qwen1.5-0.5b
registry entry cut to toy widths, driven by the real harness."""
from __future__ import annotations

import argparse
import copy

MODEL = {"d_model": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 512, "qkv_bias": True,
         "tie_embeddings": False, "rope_theta": 1000000.0, "rope_dims": 16,
         "norm_eps": 1e-06, "compute_dtype": "bfloat16"}
OVERRIDES = {"num_layers": 2, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
             "vocab_size": 512}
ENGINE = {"slots": 4, "cache_len": 128, "backend": "paged", "page_size": 16,
          "chunked_prefill": True, "chunk_size": 32, "prefix_cache": True}

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


def spec(traffic=OPEN, limit=0.05):
    return {"cell": {"name": "tiny", "config": "tiny", "traffic": "tiny",
                     "chips": 1},
            "config": {"registry": "qwen1.5-0.5b",
                       "registry_overrides": dict(OVERRIDES),
                       "model": dict(MODEL)},
            "traffic": copy.deepcopy(traffic),
            "limits": {"max_logit_gap": limit},
            "end_to_end": [{"name": n} for n in
                           ("setup_s", "ttft_p50_s", "ttft_p95_s", "itl_p50_s",
                            "itl_p95_s", "output_tok_per_s")],
            "per_layer": []}


def args(seed=12345678901, seconds=2.0, control=0):
    return argparse.Namespace(workload="tiny", seed=seed, seconds=seconds,
                              trace=0, platform="cpu", control=control)
