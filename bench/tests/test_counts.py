"""counts.py against bytes and FLOPs worked out by hand."""
import json
from pathlib import Path

import pytest

import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_qwen_layer_and_weight_bytes():
    m = model("qwen1.5-0.5b")
    # q, k, v, o: 4 x 1024^2; biases 3 x 1024; MLP 3 x 1024 x 2816; norms
    assert counts.layer_params(m) == 4 * 1024 ** 2 + 3 * 1024 \
        + 3 * 1024 * 2816 + 2 * 1024 == 12_850_176
    # 24 layers + final norm + the head 1024 x 151936 (the tied table, read
    # whole once as the head)
    weights = 24 * 12_850_176 + 1024 + 1024 * 151936
    assert counts.streamed_weight_params(m) == weights
    assert 2 * weights == pytest.approx(0.928e9, rel=1e-3)      # 0.93 GB
    assert counts.kv_row_bytes(m, "bfloat16") == 96 * 1024     # 96 KiB


def test_glm_layer_and_weight_bytes():
    m = model("glm4-9b-1chip")
    layer = (4096 * 4096 * 2 + 2 * 4096 * 256 + (4096 + 512)
             + 3 * 4096 * 13696 + 2 * 4096)
    assert counts.layer_params(m) == layer == 203_960_832
    weights = 6 * layer + 4096 + 4096 * 151552
    assert 2 * weights == pytest.approx(3.69e9, rel=1e-3)      # 3.69 GB
    assert counts.kv_row_bytes(m, "bfloat16") == 6 * 1024      # 6 KiB


def test_decode_step_by_hand():
    m = model("qwen1.5-0.5b")
    # two requests: 100 live rows (the 100th written this step) and 50
    flops, nbytes = counts.decode_step(m, [(100, -1, 0), (50, -1, 0)],
                                       "bfloat16")
    per_tok = 24 * (4 * 1024 ** 2 + 3 * 1024 * 2816) + 1024 * 151936
    assert flops == 2 * 2 * per_tok + 4 * 24 * 16 * 64 * 150
    row = 96 * 1024
    assert nbytes == (2 * counts.streamed_weight_params(m) + 2 * 2 * 1024
                      + (99 + 49) * row + 2 * row)


def test_a_shared_head_counts_once():
    m = model("glm4-9b-1chip")
    row = counts.kv_row_bytes(m, "bfloat16")
    one = counts.decode_step(m, [(3001, 7, 3000)], "bfloat16")[1]
    # four requests over one 3000-token document, 20 own rows each
    four = counts.decode_step(m, [(3021, 7, 3000)] * 4, "bfloat16")[1]
    base = 2 * counts.streamed_weight_params(m)
    assert one - base == 2 * 4096 + 3000 * row + row
    assert four - base == 4 * 2 * 4096 + (3000 + 4 * 20) * row + 4 * row
    # the same rows unshared are read once per request
    apart = counts.decode_step(m, [(3021, -1, 0)] * 4, "bfloat16")[1]
    assert apart - base == 4 * 2 * 4096 + 4 * 3020 * row + 4 * row
