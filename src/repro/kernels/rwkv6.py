"""Chunked WKV6 kernel (RWKV-6 recurrence) — TPU-native adaptation.

The recurrence S_t = diag(w_t) S_{t-1} + k_t (x) v_t is AXPY-class: O(hd^2)
state updated by streaming (r,k,v,w) once.  The reference evaluates it as a
T-step scan (T sequential VPU steps — hopeless on the MXU).  This kernel
uses the chunked linear-attention form with TROOP structure:

  * state (hd x hd) fp32 lives in VMEM scratch across the whole grid row
    (shadow-buffer (C): never written to HBM until the final chunk);
  * per chunk, (r,k,v,w) tiles stream in once ((A)/(B): pipelined fetches);
  * within a chunk the math is re-associated into three MXU matmuls
    (inter-chunk, intra-chunk, state update) — the log2-reduction idea (G)
    applied to a recurrence;
  * all exponentials take non-positive arguments (cumulative log-decays are
    monotone non-increasing), so the chunked form is overflow-safe at any
    decay strength — this is what makes the re-association valid in fp32,
    where a naive exp(+cumsum) separable form overflows.
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
    B, T, H, hd = (1, 64, 2, 32) if small else (1, 256, 4, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))))
    u = 0.5 * jnp.ones((H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    return (r, k, v, w, u, s0), {}


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, so_ref, state, *, bt):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0].astype(jnp.float32)          # (bt, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)          # decay in (0, 1]
    u = u_ref[0].astype(jnp.float32)          # (1, hd) bonus

    lw = jnp.log(jnp.maximum(w, 1e-30))       # <= 0
    cum = jnp.cumsum(lw, axis=0)              # inclusive, non-increasing
    cum_x = cum - lw                          # exclusive

    # inter-chunk: r_t decayed to the chunk start, applied to carried state
    r_dec = r * jnp.exp(cum_x)                            # exp(<=0)
    y = jnp.dot(r_dec, state[...], preferred_element_type=jnp.float32)

    # intra-chunk: pairwise decayed scores, strictly lower-triangular
    # A[t,s] = sum_i r[t,i] k[s,i] exp(cum_x[t,i] - cum[s,i])   (s < t)
    e = cum_x[:, None, :] - cum[None, :, :]               # (bt, bt, hd)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (bt, bt, 1), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (bt, bt, 1), 1)
    mask = s_idx < t_idx
    e = jnp.where(mask, e, -jnp.inf)                      # mask BEFORE exp
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(e), axis=-1)
    # current-token bonus (diagonal): (r_t . (u * k_t)) v_t
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)
    y = y + jnp.dot(scores, v, preferred_element_type=jnp.float32) + diag * v
    o_ref[0] = y.astype(o_ref.dtype)

    # state update: S <- diag(prod w) S + (k decayed-to-end)^T v
    decay_all = jnp.exp(cum[-1])                          # (hd,)
    k_dec = k * jnp.exp(cum[-1][None, :] - cum)           # exp(<=0)
    state[...] = decay_all[:, None] * state[...] + jnp.dot(
        k_dec.T, v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        so_ref[0] = state[...]


@troop_kernel(
    "rwkv6",
    # state update + readout: O(hd) per (t, head, channel) element
    flops=lambda r, k, v, w, u, s0: 6.0 * numel(r) * r.shape[3],
    bytes=lambda r, k, v, w, u, s0: (
        4 * numel(r) * itemsize(r)          # r, k, v, w in
        + numel(r) * 4 + numel(s0) * 4      # y + final state out (fp32)
        + numel(u) * itemsize(u)),
    streamed=lambda r, k, v, w, u, s0: [
        r, jax.ShapeDtypeStruct(k.shape, r.dtype),
        jax.ShapeDtypeStruct(v.shape, r.dtype),
        jax.ShapeDtypeStruct(w.shape, r.dtype),
        jax.ShapeDtypeStruct(r.shape, jnp.float32),      # y out
        jax.ShapeDtypeStruct(s0.shape, jnp.float32),     # final state out
        u],
    space={"block_n": (64, 128, 256)},
    ref="wkv6", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def wkv6(r, k, v, w, u, state0, cfg: TroopConfig = TroopConfig()):
    """r,k,v,w (B,T,H,hd); u (H,hd); state0 (B,H,hd,hd) fp32.

    Returns (y (B,T,H,hd) f32, state (B,H,hd,hd) f32).
    NOTE: carried-in state0 must be zero in this kernel variant (prefill);
    nonzero initial state is folded in by the ops.py wrapper.
    """
    B, T, H, hd = r.shape
    bt = max(min(cfg.block_n // 8, T), 1)
    while T % bt:
        bt //= 2
    # layout: fold (B,H) into the outer grid dim, time-major tiles
    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, T, hd)
    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)

    y, state = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(B * H, T // bt),
        in_specs=[
            pl.BlockSpec((1, bt, hd), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bt, hd), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bt, hd), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bt, hd), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, hd), lambda g, j, H=H: (g % H, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bt, hd), lambda g, j: (g, j, 0)),
                   pl.BlockSpec((1, hd, hd), lambda g, j: (g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=pallas_interpret(),
    )(rf, kf, vf, wf, u)
    y = jnp.moveaxis(y.reshape(B, H, T, hd), 1, 2)
    return y, state.reshape(B, H, hd, hd)
