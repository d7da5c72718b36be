"""TROOP as a composable feature: configuration + the four mechanisms.

Paper -> TPU mapping (see DESIGN.md §2):
  (A) decoupled VLSU interfaces  -> ``streams=2``: every streamed operand is
      fetched as two disjoint contiguous half-streams with independent
      BlockSpecs, so two DMAs are in flight per grid step.
  (B) improved chaining          -> the Pallas grid pipeline (compute on
      block i overlaps the fetch of block i+1); ``unroll`` widens the
      per-step work to keep the faster unit saturated (paper §IV-F).
  (C) shadow buffers             -> accumulation in VMEM/SMEM scratch;
      results commit to HBM once per tile, so compute never stalls on the
      output path.
  (D/E) layout / scrambling      -> hardware-aligned tile shapes
      (multiples of the (8..32, 128) layout granule) + pre-tiled weight
      layout so each stream reads disjoint contiguous HBM regions
      (``core.layout``).
  (G) log2 reductions            -> intra-tile tree reductions + cross-tile
      scratch accumulation (and the cross-device LSE-combine for split-K
      decode in ``kernels.ops``).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class TroopConfig:
    streams: int = 2          # decoupled memory interfaces (1 = baseline)
    unroll: int = 1           # per-step block multiplier (software pipelining)
    block_n: int = 256        # output-tile rows
    block_k: int = 512        # reduction-tile depth
    scrambled_layout: bool = True   # pre-tiled weights (E)

    def validate(self):
        assert self.streams in (1, 2), "paper evaluates 1 or 2 interfaces"
        assert self.unroll in (1, 2, 4)
        return self


BASELINE = TroopConfig(streams=1, unroll=1, scrambled_layout=False)
TROOP = TroopConfig(streams=2, unroll=2, scrambled_layout=True)


def pallas_interpret() -> bool:
    """Whether ``pallas_call`` runs in the interpreter, read from the default
    backend at trace time: interpreted on ``cpu`` (tests), compiled to a
    Mosaic kernel on ``tpu``.  Any other backend raises — a kernel never
    falls back to the interpreter or to its jnp oracle on an accelerator."""
    import jax
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'cpu' (interpreted) or 'tpu' "
                       f"(compiled); the default backend is {backend!r}")


def sublane(dtype) -> int:
    """Minor-to-major second dim granule for a dtype on TPU."""
    import jax.numpy as jnp
    return {2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def block_rows(rows: int, want: int, granule: int) -> int:
    """Rows per block of a (rows, 128) stream: the largest multiple of the
    sublane ``granule`` that divides ``rows`` and is at most
    ``max(want, granule)`` — or ``rows`` itself (one whole-array block) when
    no multiple of the granule divides it."""
    br = max(want // granule, 1) * granule
    while br >= granule:
        if rows % br == 0:
            return br
        br -= granule
    return rows


def aligned(dim: int, dtype, lane: bool = False) -> bool:
    return dim % (128 if lane else sublane(dtype)) == 0
