"""Mamba selective-scan kernel — AXPY-class recurrence, one HBM pass.

h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t * x_t ;  y_t = C_t . h_t + D * x_t

The XLA reference is a T-step ``lax.scan``: every step re-touches HBM-level
buffers.  The kernel streams (x, dt, B, C) tiles once ((A)/(B): pipelined
dual-purpose fetches), keeps the (d_block, d_state) state in VMEM scratch
across the whole sequence ((C): shadow-state, committed only as y tiles),
and runs the recurrence on-chip.  Channels are independent, so the grid
parallelizes (batch x channel-block) like the paper's per-lane FPUs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.troop import TroopConfig, pallas_interpret
from repro.tune.registry import itemsize, numel, troop_kernel


def _example(small: bool = True):
    b, T, di, ds = (1, 64, 128, 16) if small else (2, 512, 512, 16)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, T, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, di)))
    B = jax.random.normal(ks[2], (b, T, ds))
    C = jax.random.normal(ks[3], (b, T, ds))
    A = -jnp.exp(jax.random.normal(ks[4], (di, ds)))
    D = jnp.ones((di,))
    s0 = jnp.zeros((b, di, ds))
    return (x, dt, B, C, A, D, s0), {}


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, o_ref, so_ref,
            state, *, bt):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0].astype(jnp.float32)          # (bt, dc)
    dt = dt_ref[0].astype(jnp.float32)        # (bt, dc)
    B = b_ref[0].astype(jnp.float32)          # (bt, ds)
    C = c_ref[0].astype(jnp.float32)          # (bt, ds)
    A = a_ref[0].astype(jnp.float32)          # (dc, ds), A < 0
    D = d_ref[0].astype(jnp.float32)          # (1, dc)

    def step(t, carry):
        h, ys = carry
        a_t = jnp.exp(dt[t][:, None] * A)                 # exp(<=0): safe
        h = a_t * h + (dt[t] * x[t])[:, None] * B[t][None, :]
        y_t = jnp.sum(h * C[t][None, :], axis=-1)         # (dc,)
        ys = jax.lax.dynamic_update_index_in_dim(ys, y_t, t, 0)
        return h, ys

    h0 = state[...]
    ys = jnp.zeros((bt, x.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, bt, step, (h0, ys))
    state[...] = h
    o_ref[0] = (ys + x * D).astype(o_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        so_ref[0] = state[...]


@troop_kernel(
    "mamba_scan",
    # per (t, channel): state decay + update + output contraction over ds
    flops=lambda x, dt, B, C, A, D, s0: (6.0 * numel(x) * A.shape[1]),
    bytes=lambda x, dt, B, C, A, D, s0: (
        (2 * numel(x) + numel(B) + numel(C)) * itemsize(x)
        + numel(x) * itemsize(x)            # y out
        + (numel(A) + numel(D) + numel(s0)) * 4),
    streamed=lambda x, dt, B, C, A, D, s0: [
        x, jax.ShapeDtypeStruct(dt.shape, x.dtype),      # x, dt in
        jax.ShapeDtypeStruct(B.shape, x.dtype),
        jax.ShapeDtypeStruct(C.shape, x.dtype),
        x,                                               # y out (x-shaped)
        jax.ShapeDtypeStruct(A.shape, jnp.float32),
        jax.ShapeDtypeStruct(D.shape, jnp.float32),
        jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
    space={"block_n": (64, 128, 256)},
    ref="mamba_scan", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def mamba_scan(x, dt, B, C, A, D, state0, cfg: TroopConfig = TroopConfig()):
    """x, dt: (b, T, di); B, C: (b, T, ds); A: (di, ds) (<0); D: (di,);
    state0: (b, di, ds) fp32 (must be zeros — prefill form).

    Returns (y (b, T, di) f32, state (b, di, ds) f32)."""
    b, T, di = x.shape
    ds = B.shape[-1]
    dc = min(256, di)
    while di % dc:
        dc //= 2
    bt = max(min(cfg.block_n // 8, T), 1)
    while T % bt:
        bt //= 2
    nch = di // dc

    # fold channel blocks into the outer grid dim alongside batch
    def fold(t):   # (b, T, di) -> (b * nch, T, dc)
        return (t.reshape(b, T, nch, dc).transpose(0, 2, 1, 3)
                .reshape(b * nch, T, dc))
    xf, dtf = fold(x), fold(dt)
    Bf = jnp.repeat(B, nch, axis=0) if nch > 1 else B
    Cf = jnp.repeat(C, nch, axis=0) if nch > 1 else C
    Af = A.reshape(nch, dc, ds)
    Df = D.reshape(nch, 1, dc)

    y, state = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(b * nch, T // bt),
        in_specs=[
            pl.BlockSpec((1, bt, dc), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bt, dc), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bt, ds), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bt, ds), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, dc, ds), lambda g, j, n=nch: (g % n, 0, 0)),
            pl.BlockSpec((1, 1, dc), lambda g, j, n=nch: (g % n, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bt, dc), lambda g, j: (g, j, 0)),
                   pl.BlockSpec((1, dc, ds), lambda g, j: (g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * nch, T, dc), jnp.float32),
                   jax.ShapeDtypeStruct((b * nch, dc, ds), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dc, ds), jnp.float32)],
        interpret=pallas_interpret(),
    )(xf, dtf, Bf, Cf, Af.reshape(nch, dc, ds), Df)

    y = (y.reshape(b, nch, T, dc).transpose(0, 2, 1, 3).reshape(b, T, di))
    state = state.reshape(b, nch, dc, ds).reshape(b, di, ds)
    return y, state
