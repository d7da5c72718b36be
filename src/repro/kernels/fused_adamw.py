"""Fused AdamW kernel — the paper's AXPY-class chain as ONE memory pass.

The reference optimizer evaluates ~10 elementwise HLO ops over param-sized
arrays (each a full HBM round-trip when not fused); this kernel streams
(p, g, mu, nu) once and writes (p', mu', nu') once: 7 streams total, the
roofline minimum.  ``streams=2`` splits every operand into contiguous
halves like the paper's decoupled VLSU.
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
    n = 4096 if small else 1 << 20
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    p = jax.random.normal(ks[0], (n,))
    g = jax.random.normal(ks[1], (n,))
    mu = jnp.zeros((n,))
    nu = jnp.zeros((n,))
    hp = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, bc1=0.1, bc2=0.1)
    return (p, g, mu, nu), hp


def _update(h_ref, p, g, mu, nu, po, muo, nuo):
    lr, b1, b2, eps, wd, bc1, bc2 = [h_ref[i] for i in range(7)]
    gf = g[...].astype(jnp.float32)
    m = b1 * mu[...] + (1 - b1) * gf
    n = b2 * nu[...] + (1 - b2) * gf * gf
    upd = (m / bc1) / (jnp.sqrt(n / bc2) + eps)
    pf = p[...].astype(jnp.float32)
    pf = pf - lr * (upd + wd * pf)
    po[...] = pf.astype(po.dtype)
    muo[...] = m
    nuo[...] = n


def _kernel_2s(h_ref, p0, p1, g0, g1, mu0, mu1, nu0, nu1,
               po0, po1, muo0, muo1, nuo0, nuo1):
    _update(h_ref, p0, g0, mu0, nu0, po0, muo0, nuo0)
    _update(h_ref, p1, g1, mu1, nu1, po1, muo1, nuo1)


@troop_kernel(
    "fused_adamw",
    flops=lambda p, g, mu, nu: 12.0 * numel(p),
    # one pass: read (p, g, mu, nu), write (p', mu', nu'); moments fp32
    bytes=lambda p, g, mu, nu: numel(p) * (2 * itemsize(p) + itemsize(g)
                                           + 4 * 4),
    streamed=lambda p, g, mu, nu: [p, p, g] + [
        jax.ShapeDtypeStruct(p.shape, jnp.float32)] * 4,
    #   p in + p' out + g in + (mu, nu) fp32 in/out
    space={"streams": (1, 2), "unroll": (1, 2), "block_k": (256, 512, 1024)},
    ref="fused_adamw", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def fused_adamw(p, g, mu, nu, *, lr, b1, b2, eps, wd, bc1, bc2,
                cfg: TroopConfig = TroopConfig()):
    """Flat-or-shaped arrays; returns (p', mu', nu')."""
    shape, dtype = p.shape, p.dtype
    n = p.size
    lanes = 128
    pad = (-n) % lanes
    def flat(a, dt):
        a = a.reshape(-1).astype(dt)
        if pad:
            a = jnp.concatenate([a, jnp.zeros((pad,), dt)])
        return a.reshape(-1, lanes)
    pf, gf = flat(p, dtype), flat(g, g.dtype)
    muf, nuf = flat(mu, jnp.float32), flat(nu, jnp.float32)
    rows = pf.shape[0]
    h = jnp.stack([jnp.asarray(v, jnp.float32) for v in
                   (lr, b1, b2, eps, wd, bc1, bc2)])

    br = max(min(cfg.block_k * cfg.unroll // lanes, rows // cfg.streams), 1)
    if cfg.streams == 1 or rows < 2:
        while rows % br:
            br //= 2
        blk = lambda: pl.BlockSpec((br, lanes), lambda j: (j, 0))
        outs = pl.pallas_call(
            functools.partial(_update),
            grid=(rows // br,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      blk(), blk(), blk(), blk()],
            out_specs=[blk(), blk(), blk()],
            out_shape=[jax.ShapeDtypeStruct((rows, lanes), dtype),
                       jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
                       jax.ShapeDtypeStruct((rows, lanes), jnp.float32)],
            interpret=pallas_interpret(),
        )(h, pf, gf, muf, nuf)
        po, muo, nuo = outs
    else:
        half = rows // 2
        while half % br:
            br //= 2
        steps = half // br
        lo = lambda: pl.BlockSpec((br, lanes), lambda j: (j, 0))
        hi = lambda: pl.BlockSpec((br, lanes), lambda j, o=steps: (j + o, 0))
        sh = lambda dt: jax.ShapeDtypeStruct((half, lanes), dt)
        outs = pl.pallas_call(
            _kernel_2s,
            grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      lo(), hi(), lo(), hi(), lo(), hi(), lo(), hi()],
            out_specs=[lo(), lo(), lo(), lo(), lo(), lo()],
            out_shape=[sh(dtype), sh(dtype), sh(jnp.float32),
                       sh(jnp.float32), sh(jnp.float32), sh(jnp.float32)],
            interpret=pallas_interpret(),
        )(h, pf, pf, gf, gf, muf, muf, nuf, nuf)
        po = jnp.concatenate([outs[0], outs[1]])
        muo = jnp.concatenate([outs[2], outs[3]])
        nuo = jnp.concatenate([outs[4], outs[5]])

    unflat = lambda a, dt: a.reshape(-1)[:n].reshape(shape).astype(dt)
    return unflat(po, dtype), unflat(muo, jnp.float32), unflat(nuo, jnp.float32)
