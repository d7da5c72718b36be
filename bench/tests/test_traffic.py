"""The generator repeats exactly for one seed, and what it draws keeps to
each traffic file's lengths and sharing."""
import json
from pathlib import Path

import numpy as np
import pytest

from traffic.generate import generate, load_mix

MIXES = sorted(p.stem for p in (Path(__file__).resolve().parents[1]
                                / "traffic").glob("*.json"))
VOCAB = 151552
N = 300


def as_bytes(specs):
    return b"".join(np.asarray([s.rid, -1 if s.due_s is None else
                                round(s.due_s * 1e9), s.max_new, s.head_id,
                                s.head_len], np.int64).tobytes()
                    + s.prompt.tobytes() for s in specs)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_bytes(mix):
    m = load_mix(mix)
    seed = 2 ** 33 + 17
    assert as_bytes(generate(m, seed, N, VOCAB)) == \
        as_bytes(generate(m, seed, N, VOCAB))
    assert as_bytes(generate(m, seed, N, VOCAB)) != \
        as_bytes(generate(m, seed + 1, N, VOCAB))


@pytest.mark.parametrize("mix", MIXES)
def test_seed_permutes_a_fixed_shape(mix):
    """Two seeds serve the same multiset of sizes and the same arrivals."""
    m = load_mix(mix)
    a, b = generate(m, 1, N, VOCAB), generate(m, 2, N, VOCAB)

    def shape(specs):
        return sorted((s.prompt.size, s.max_new, s.head_len) for s in specs)
    assert shape(a) == shape(b)
    assert [s.due_s for s in a] == [s.due_s for s in b]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_and_sharing_match_the_file(mix):
    m = load_mix(mix)
    specs = generate(m, 99, N, VOCAB)
    body = np.array([s.prompt.size - s.head_len for s in specs])
    out = np.array([s.max_new for s in specs])
    assert body.min() >= m["prompt"]["min"]
    assert body.max() <= m["prompt"]["max"]
    assert out.min() >= m["output"]["min"]
    assert out.max() <= m["output"]["max"]
    for s in specs:
        assert s.prompt.dtype == np.int32
        assert 1 <= s.prompt.min() and s.prompt.max() < VOCAB
    if m["loop"] == "closed":
        assert all(s.due_s is None for s in specs)
        assert all(s.head_id == -1 for s in specs)
        return
    due = np.array([s.due_s for s in specs])
    assert np.all(np.diff(due) >= 0)
    heads = {}
    for s in specs:
        if s.head_id >= 0:
            head = s.prompt[:s.head_len]
            if s.head_id in heads:
                assert np.array_equal(heads[s.head_id], head)
            heads[s.head_id] = head
    if "prefixes" in m:
        pre = m["prefixes"]
        shared = np.mean([s.head_id >= 0 for s in specs])
        assert abs(shared - pre["share"]) < 0.06
        assert {s.head_len for s in specs if s.head_id >= 0} == \
            {pre["length"]}
        assert len(heads) == len(pre["popularity"])
        rate = (len(specs) - 1) / (due[-1] - due[0])
        assert abs(rate / m["rate_per_s"] - 1) < 0.15
    if "documents" in m:
        doc = m["documents"]
        lens = {s.head_id: s.head_len for s in specs}
        assert min(lens.values()) >= doc["min"]
        assert max(lens.values()) <= doc["max"]
        asks = np.bincount([s.head_id for s in specs])
        assert asks[:-1].min() >= doc["asks"][0]
        assert asks.max() <= doc["asks"][1]


def test_files_are_json_objects():
    for mix in MIXES:
        path = Path(__file__).resolve().parents[1] / "traffic" / f"{mix}.json"
        assert isinstance(json.loads(path.read_text()), dict)
