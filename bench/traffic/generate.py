"""Seeded request streams for the benchmark, read from a traffic file.

One general generator for every mix in ``bench/traffic/*.json``: a mix is
data (loop kind, rate or concurrency, length distributions, prefix and
document sharing), never code.  Adapted from the program's own seeded
generator (``repro.obs.workload``), copied rather than imported so that the
yardstick cannot move with the program, and widened to real lengths and
token ids over the whole vocabulary.

Two streams make a run:

* the *shape* of the traffic (every length, gap and prefix choice) comes
  from the mix's own ``shape_seed``, so every run of a cell serves the same
  multiset of sizes and arrivals;
* the run's ``--seed`` permutes that multiset within consecutive blocks
  (which request comes when) and draws every token id, so the check sees
  new prompts and new weights on every seed while the work per run stays
  nearly fixed.

Open-loop mixes give every request a due time; closed-loop mixes give none
(the driver submits the next request when one finishes).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent
PERMUTE_BLOCK = 16


@dataclasses.dataclass
class Spec:
    """One request as the generator hands it to the driver."""
    rid: int
    due_s: Optional[float]     # seconds after the schedule starts; None: closed loop
    prompt: np.ndarray         # (T,) int32 token ids
    max_new: int
    head_id: int               # -1: no shared head, else the shared head's id
    head_len: int              # leading tokens shared with others of head_id


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    with open(directory / f"{name}.json") as f:
        return json.load(f)


def draw_lengths(rng, dist: dict, n: int) -> np.ndarray:
    """``n`` integer lengths from a length distribution of a mix."""
    kind, lo, hi = dist["dist"], int(dist["min"]), int(dist["max"])
    if kind == "pareto":
        raw = lo * (1.0 + rng.pareto(float(dist["alpha"]), n))
    elif kind == "loguniform":
        raw = lo * np.exp(rng.uniform(0.0, np.log(hi / lo), n))
    elif kind == "uniform":
        raw = rng.integers(lo, hi + 1, n).astype(np.float64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(raw), lo, hi).astype(np.int64)


def _shape(mix: dict, n: int):
    """The seed-independent shape of ``n`` requests: per request a due
    time (or None), a head id, a head length, a body length and an output
    length.  Drawn in a fixed order from ``shape_seed``."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    loop = mix["loop"]
    body = draw_lengths(rng, mix["prompt"], n)
    out = draw_lengths(rng, mix["output"], n)
    heads = np.full(n, -1, np.int64)
    head_len = np.zeros(n, np.int64)
    due = [None] * n
    if "documents" in mix:
        # sessions over documents: each session asks about one document
        # several times; asks follow each other after exponential gaps
        doc = mix["documents"]
        a_lo, a_hi = doc["asks"]
        t, i, d = 0.0, 0, 0
        doc_len = []
        while i < n:
            L = int(draw_lengths(rng, doc, 1)[0])
            doc_len.append(L)
            t += rng.exponential(1.0 / float(mix["session_rate_per_s"]))
            ask_t = t
            for _ in range(int(rng.integers(a_lo, a_hi + 1))):
                if i == n:
                    break
                heads[i], head_len[i], due[i] = d, L, ask_t
                ask_t += rng.exponential(float(doc["ask_gap_s"]))
                i += 1
            d += 1
        order = np.argsort(np.asarray(due), kind="stable")
        due = [float(due[j]) for j in order]
        heads, head_len = heads[order], head_len[order]
        body, out = body[order], out[order]
        return due, heads, head_len, body, out, doc_len
    if "prefixes" in mix:
        pre = mix["prefixes"]
        shared = rng.random(n) < float(pre["share"])
        pick = rng.choice(len(pre["popularity"]), size=n,
                          p=np.asarray(pre["popularity"], np.float64))
        heads = np.where(shared, pick, -1)
        head_len = np.where(shared, int(pre["length"]), 0)
    if loop == "open":
        gaps = rng.exponential(1.0 / float(mix["rate_per_s"]), n)
        due = list(np.cumsum(gaps))
    elif loop != "closed":
        raise ValueError(f"unknown loop {loop!r}")
    return due, heads, head_len, body, out, None


def generate(mix: dict, seed: int, n: int, vocab: int) -> List[Spec]:
    """``n`` requests of ``mix`` for run seed ``seed``: the shape from the
    mix's ``shape_seed``, permuted and filled with token ids in [1, vocab)
    drawn from ``seed``.  Identical arguments give identical streams."""
    due, heads, head_len, body, out, doc_len = _shape(mix, n)
    rng = np.random.default_rng(int(seed))
    if doc_len is None:
        # the seed permutes which request comes when, within consecutive
        # blocks, so that any stretch of the stream (the window, the first
        # requests of a closed loop) serves nearly the same sizes on every
        # seed; due times stay put
        block = int(mix.get("concurrency", PERMUTE_BLOCK))
        perm = np.concatenate([s + rng.permutation(min(block, n - s))
                               for s in range(0, n, block)])
        heads, head_len, body, out = (heads[perm], head_len[perm],
                                      body[perm], out[perm])
        lens = [int(mix["prefixes"]["length"])] * \
            len(mix["prefixes"]["popularity"]) if "prefixes" in mix else []
    else:
        # sessions keep their order (a follow-up comes after its first
        # ask); the seed chooses the tokens only
        lens = doc_len
    head_toks = [rng.integers(1, vocab, L, dtype=np.int64).astype(np.int32)
                 for L in lens]
    specs = []
    for i in range(n):
        tail = rng.integers(1, vocab, int(body[i]),
                            dtype=np.int64).astype(np.int32)
        h = int(heads[i])
        prompt = tail if h < 0 else np.concatenate([head_toks[h], tail])
        specs.append(Spec(rid=i, due_s=None if due[i] is None
                          else float(due[i]), prompt=prompt,
                          max_new=int(out[i]), head_id=h,
                          head_len=int(head_len[i]) if h >= 0 else 0))
    return specs
