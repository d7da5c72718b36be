"""GEMV kernel — the paper's flagship memory-bound workload.

y = W @ x with W (N,K) streamed from HBM exactly once (operational intensity
~= 1 FLOP/byte at bf16: deep under the v5e ridge of 240, so runtime ==
bytes/BW iff every optimization below holds — the paper's "at-the-roofline"
condition).

TROOP mechanisms:
  (A) streams=2: W and x fetched as two disjoint contiguous half-streams of
      the K dimension (independent BlockSpecs -> two in-flight DMAs/step).
  (B) grid pipeline overlaps block DMA with the MXU tile matmul.
  (C) fp32 accumulator lives in VMEM scratch; y commits once per row-tile
      (no per-step output DMA: the shadow-buffer intent).
  (F) unroll=2: two K-tiles per stream per grid step.
  (G) the K-reduction is tree-shaped inside the tile (jnp.dot) + sequential
      scratch accumulation across tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.troop import TroopConfig, pallas_interpret
from repro.tune.registry import itemsize, troop_kernel


def _example(small: bool = True):
    key = jax.random.PRNGKey(0)
    N, K = (128, 512) if small else (2048, 4096)
    w = jax.random.normal(key, (N, K), jnp.bfloat16)
    x = jax.random.normal(key, (K,), jnp.bfloat16)
    return (w, x), {}


def _kernel_1s(w_ref, x_ref, o_ref, acc):
    """Baseline: single interface."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(w_ref[...].astype(jnp.float32),
                        x_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _kernel_2s(w0_ref, w1_ref, x0_ref, x1_ref, o_ref, acc):
    """TROOP: two decoupled interfaces (contiguous K halves)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    a = jnp.dot(w0_ref[...].astype(jnp.float32),
                x0_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    b = jnp.dot(w1_ref[...].astype(jnp.float32),
                x1_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    acc[...] += a + b          # two accumulation chains folded per step

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@troop_kernel(
    "gemv",
    flops=lambda w, x: 2.0 * w.shape[0] * w.shape[1],
    bytes=lambda w, x: (w.shape[0] * w.shape[1] * itemsize(w)
                        + w.shape[1] * itemsize(x) + w.shape[0] * 4),
    streamed=lambda w, x: [
        w, x, jax.ShapeDtypeStruct((w.shape[0],), jnp.float32)],
    space={"streams": (1, 2), "unroll": (1, 2),
           "block_n": (128, 256), "block_k": (256, 512)},
    ref="gemv", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def gemv(w, x, cfg: TroopConfig = TroopConfig()):
    """w (N,K), x (K,) -> y (N,) fp32.

    When ``block_n`` does not divide N the last row tile is ragged: its
    out-of-range rows read padding and their results are never written, so
    every one of the N outputs (e.g. a 151936-row vocabulary) comes out."""
    N, K = w.shape
    bn = min(cfg.block_n, N)
    bk = min(cfg.block_k * cfg.unroll, K)
    x2 = x.reshape(K, 1)

    if cfg.streams == 1:
        while K % bk:
            bk //= 2
        grid = (pl.cdiv(N, bn), K // bk)
        return pl.pallas_call(
            _kernel_1s,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
                pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
            scratch_shapes=[_scratch(bn)],
            interpret=pallas_interpret(),
        )(w, x2).reshape(N)

    # streams == 2: stream0 = first K half, stream1 = second K half
    Kh = K // 2
    while Kh % bk:
        bk //= 2
    steps = Kh // bk
    grid = (pl.cdiv(N, bn), steps)
    off = steps  # block offset of the second half

    return pl.pallas_call(
        _kernel_2s,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bk), lambda i, j, o=off: (i, j + o)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda i, j, o=off: (j + o, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        scratch_shapes=[_scratch(bn)],
        interpret=pallas_interpret(),
    )(w, w, x2, x2).reshape(N)


def _scratch(bn):
    from jax.experimental import pallas as pl
    import jax.experimental.pallas.tpu as pltpu
    return pltpu.VMEM((bn, 1), jnp.float32)
