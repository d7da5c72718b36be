"""What one decode step of the dense decoder *needs*: FLOPs and HBM bytes
from shapes alone.  ``reference/dense_decoder.py`` gives ``decode_step`` to
the harness; another architecture's module counts its own step.

Counted from the configuration's ``model`` block and the requests in the
decode batch, at the configuration's compute dtype, as the least that any
implementation of the step has to do:

* every weight is read once, at the compute dtype (a program that holds
  float32 masters and reads them reads more than this);
* the embedding table contributes only the gathered rows;
* each live KV row is read once, however many requests share it: rows of a
  shared head (a system prompt, a document) count once per step, since a
  kernel may read a shared page once for every slot that maps it;
* the new KV row of every request is written once.

FLOPs are 2 per multiply-add of every matrix product (projections, MLP,
LM head) plus the attention products QK^T and PV over the live rows.
Norms, rotary and softmax are left out: they only lower the bound.

Nothing here comes from the program's own accounting or from
``cost_analysis()``.
"""
from __future__ import annotations

from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(m: dict) -> int:
    """Weights of one decoder layer (projections, biases, MLP, norms)."""
    d, H, KV, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    bias = (H * hd + 2 * KV * hd) if m["qkv_bias"] else 0
    return attn + bias + 3 * d * ff + 2 * d


def matmul_params(m: dict) -> int:
    """Weights that one token multiplies through: every layer's matrices
    and the LM head (no norms, no biases)."""
    d, H, KV, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return m["n_layers"] * per_layer + d * m["vocab"]


def streamed_weight_params(m: dict) -> int:
    """Weights a decode step reads whole: the layers, the final norm and
    the LM head (the embedding table only through gathered rows)."""
    return m["n_layers"] * layer_params(m) + m["d_model"] + \
        m["d_model"] * m["vocab"]


def kv_row_bytes(m: dict, dtype: str) -> int:
    """K and V of one token over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * \
        DTYPE_BYTES[dtype]


def unique_rows(batch: Iterable[Tuple[int, int, int]]) -> int:
    """Live KV rows of a decode batch, each shared head counted once.

    ``batch``: per request ``(rows, head_id, head_len)`` where ``rows`` is
    its live length including the row this step writes, and its first
    ``head_len`` rows are the head ``head_id`` shares (-1: none)."""
    heads = {}
    own = 0
    for rows, head_id, head_len in batch:
        if head_id >= 0:
            heads[head_id] = head_len
            own += rows - head_len
        else:
            own += rows
    return own + sum(heads.values())


def decode_step(m: dict, batch, dtype: str) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for ``batch`` (see
    ``unique_rows``) at compute dtype ``dtype``."""
    batch = list(batch)
    B = len(batch)
    w = DTYPE_BYTES[dtype]
    live = sum(rows for rows, _, _ in batch)
    flops = 2.0 * B * matmul_params(m) + \
        4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * live
    nbytes = (streamed_weight_params(m) * w          # weights once
              + B * m["d_model"] * w                 # gathered embeddings
              + unique_rows((r - 1, h, hl) for r, h, hl in batch)
              * kv_row_bytes(m, dtype)               # cached KV read
              + B * kv_row_bytes(m, dtype))          # new rows written
    return flops, float(nbytes)
