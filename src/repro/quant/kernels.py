"""Fused-dequant GEMV kernels — quantization applied AT the roofline.

``qgemv``/``batched_qgemv`` stream int8 (or packed-int4) weights plus their
per-group scales and dequantize *in register*, between the DMA and the MXU:

  (A) streams=2   — the quantized weight, its scale blocks and x are each
                    fetched as two disjoint contiguous K-halves (independent
                    BlockSpecs -> two DMAs in flight per grid step).
  (C) shadow acc  — fp32 accumulator in VMEM scratch; y commits once per
                    row-tile.
  (D) alignment   — the scale group is a multiple of the int8 layout
                    granule and divides block_k, so each (block_n, block_k)
                    weight tile consumes whole scale blocks: the dequant
                    multiply is one reshape-broadcast on the VPU, never a
                    gather across tile edges (DESIGN.md §5).
  (E) layout      — int4 packs two values per byte along K, so a packed
                    block is still one dense contiguous HBM region.

At OI ~= 1 the runtime bound is bytes/BW, so int8 halves and int4 quarters
the attainable decode-GEMV time — the registered ``bytes=`` models count
the quantized widths *and* the scale traffic, which is what ``repro.tune``
scores fraction-of-roofline against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.troop import TroopConfig, pallas_interpret
from repro.quant.tensor import E8M0_BIAS, quantize, quantize_mx
from repro.tune.registry import itemsize, numel, troop_kernel


def _dequant_block(w_ref, s_ref, *, bits: int, g: int):
    """(bn, bk[, packed]) int8 + (bn, bk//g) scales -> (bn, bk) fp32."""
    w8 = w_ref[...]
    if bits == 4:
        lo = jnp.right_shift(jnp.left_shift(w8, 4), 4)   # sign-extend
        hi = jnp.right_shift(w8, 4)
        w8 = jnp.stack([lo, hi], axis=-1).reshape(w8.shape[0], -1)
    bn, bk = w8.shape
    s = s_ref[...].astype(jnp.float32)                   # (bn, bk // g)
    w = w8.astype(jnp.float32).reshape(bn, bk // g, g) * s[:, :, None]
    return w.reshape(bn, bk)


def _kernel_1s(w_ref, s_ref, x_ref, o_ref, acc, *, bits, g):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    w = _dequant_block(w_ref, s_ref, bits=bits, g=g)
    acc[...] += jnp.dot(w, x_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _kernel_2s(w0, s0, x0, w1, s1, x1, o_ref, acc, *, bits, g):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    a = jnp.dot(_dequant_block(w0, s0, bits=bits, g=g),
                x0[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    b = jnp.dot(_dequant_block(w1, s1, bits=bits, g=g),
                x1[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    acc[...] += a + b

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _qgemv_2d(wq, scales, x2, cfg: TroopConfig, bits: int):
    """wq (N, Ks) int8, scales (N, K//g), x2 (K, B) -> (N, B) fp32."""
    N = wq.shape[0]
    K, B = x2.shape
    assert wq.shape[1] == (K // 2 if bits == 4 else K), \
        f"weight K extent {wq.shape[1]} inconsistent with bits={bits}, K={K}"
    g = K // scales.shape[1]
    pack = 2 if bits == 4 else 1

    bn = min(cfg.block_n, N)
    while N % bn:
        bn //= 2
    streams = cfg.streams if (K // g) % 2 == 0 and cfg.streams == 2 else 1
    Kh = K // streams
    bk = max(min(cfg.block_k * cfg.unroll, Kh) // g * g, g)
    while Kh % bk:
        bk -= g
    steps = Kh // bk
    body = functools.partial(
        _kernel_1s if streams == 1 else _kernel_2s, bits=bits, g=g)

    # block index maps share j: the packed weight, its scale blocks and the
    # x slice advance in lockstep along K (bk elements = bk//pack bytes =
    # bk//g scale entries per step)
    w_lo = pl.BlockSpec((bn, bk // pack), lambda i, j: (i, j))
    w_hi = pl.BlockSpec((bn, bk // pack), lambda i, j, o=steps: (i, j + o))
    s_lo = pl.BlockSpec((bn, bk // g), lambda i, j: (i, j))
    s_hi = pl.BlockSpec((bn, bk // g), lambda i, j, o=steps: (i, j + o))
    x_lo = pl.BlockSpec((bk, B), lambda i, j: (j, 0))
    x_hi = pl.BlockSpec((bk, B), lambda i, j, o=steps: (j + o, 0))

    if streams == 1:
        in_specs, ops = [w_lo, s_lo, x_lo], (wq, scales, x2)
    else:
        in_specs = [w_lo, s_lo, x_lo, w_hi, s_hi, x_hi]
        ops = (wq, scales, x2, wq, scales, x2)
    return pl.pallas_call(
        body,
        grid=(N // bn, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, B), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, B), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, B), jnp.float32)],
        interpret=pallas_interpret(),
    )(*ops)


# --------------------------------------------------------------------------
# registration
# --------------------------------------------------------------------------
def _example(small: bool = True, bits: int = 8, batch: int = 0):
    N, K = (128, 512) if small else (2048, 4096)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    w = jax.random.normal(ks[0], (N, K), jnp.float32)
    qt = quantize(w, bits=bits, group_size=128, axis=-1)
    if batch:
        x = jax.random.normal(ks[1], (batch, K), jnp.bfloat16)
    else:
        x = jax.random.normal(ks[1], (K,), jnp.bfloat16)
    return (qt.values, qt.scales, x), {}


def _qgemv_bytes(wq, s, x):
    K = x.shape[-1]
    B = x.shape[0] if len(x.shape) == 2 else 1
    return (numel(wq) * itemsize(wq) + numel(s) * itemsize(s)
            + B * K * itemsize(x) + B * wq.shape[0] * 4)


def _qgemv_streamed(wq, s, x):
    out = (x.shape[0], wq.shape[0]) if len(x.shape) == 2 else (wq.shape[0],)
    return [wq, s, x, jax.ShapeDtypeStruct(out, jnp.float32)]


_QSPACE = {"streams": (1, 2), "unroll": (1, 2),
           "block_n": (128, 256), "block_k": (256, 512)}


@troop_kernel(
    "qgemv",
    flops=lambda wq, s, x: 2.0 * wq.shape[0] * x.shape[0],
    bytes=_qgemv_bytes,
    streamed=_qgemv_streamed,
    space=_QSPACE,
    key_kwargs=("bits",),
    ref="qgemv", example=_example)
@functools.partial(jax.jit, static_argnames=("cfg", "bits"))
def qgemv(wq, scales, x, cfg: TroopConfig = TroopConfig(), *, bits: int = 8):
    """Quantized GEMV: wq (N, K | K//2-packed) int8, scales (N, K//g),
    x (K,) -> y (N,) fp32.  ``bits`` is carried explicitly from the
    ``QuantizedTensor`` aux data (4 = nibble-packed along K)."""
    return _qgemv_2d(wq, scales, x.reshape(-1, 1), cfg, bits).reshape(-1)


@troop_kernel(
    "batched_qgemv",
    flops=lambda wq, s, xs: 2.0 * xs.shape[0] * wq.shape[0] * xs.shape[1],
    bytes=_qgemv_bytes,
    streamed=_qgemv_streamed,
    space=_QSPACE,
    key_kwargs=("bits",),
    ref="batched_qgemv",
    example=functools.partial(_example, batch=4))
@functools.partial(jax.jit, static_argnames=("cfg", "bits"))
def batched_qgemv(wq, scales, xs, cfg: TroopConfig = TroopConfig(), *,
                  bits: int = 8):
    """Small-batch decode projection: xs (B, K) -> (B, N) fp32.  The batch
    rides the lane dim of one kernel invocation — the weight stream (the
    roofline term) is unchanged from ``qgemv``."""
    return _qgemv_2d(wq, scales, xs.T, cfg, bits).T


# --------------------------------------------------------------------------
# MX microscaling kernels — block-exponent dequant in register
# --------------------------------------------------------------------------
# MX weights keep their stored (K, N) = (in_dim, out_dim) layout: the
# shared-exponent blocks run down K (axis -2, one uint8 E8M0 per 32 rows),
# so the kernels walk columns of the stored array directly — dequant is a
# nibble unpack + exp2 multiply between the DMA and the FMA stream, and no
# transpose ever materializes.  fp4 (uint8-packed e2m1) vs fp8
# (float8_e4m3fn) is discriminated statically by ``values.dtype``.

def _mx_bits(wq) -> int:
    return 4 if jnp.dtype(wq.dtype) == jnp.dtype(jnp.uint8) else 8


def _fp4_decode_block(w8):
    """(bkp, bn) uint8 packed e2m1 -> (2*bkp, bn) fp32 (unpack along K)."""
    lo = w8 & jnp.uint8(0x0F)
    hi = jnp.right_shift(w8, 4)
    c = jnp.stack([lo, hi], axis=1).reshape(2 * w8.shape[0], w8.shape[1])
    c = c.astype(jnp.int32)
    sign = 1.0 - 2.0 * (c >> 3).astype(jnp.float32)
    exp = ((c >> 1) & 3).astype(jnp.float32)
    man = (c & 1).astype(jnp.float32)
    mag = jnp.where(exp == 0, 0.5 * man,
                    (1.0 + 0.5 * man) * jnp.exp2(exp - 1.0))
    return sign * mag


def _mx_dequant_block(w_ref, s_ref, *, bits: int, g: int):
    """(bk[, packed], bn) codes + (bk//g, bn) E8M0 -> (bk, bn) fp32."""
    if bits == 4:
        w = _fp4_decode_block(w_ref[...])
    else:
        w = w_ref[...].astype(jnp.float32)
    bk, bn = w.shape
    s = jnp.exp2(s_ref[...].astype(jnp.float32) - E8M0_BIAS)
    return (w.reshape(bk // g, g, bn) * s[:, None, :]).reshape(bk, bn)


def _mx_kernel_1s(w_ref, s_ref, x_ref, o_ref, acc, *, bits, g):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    w = _mx_dequant_block(w_ref, s_ref, bits=bits, g=g)
    acc[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _mx_kernel_2s(w0, s0, x0, w1, s1, x1, o_ref, acc, *, bits, g):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    a = jnp.dot(x0[...].astype(jnp.float32),
                _mx_dequant_block(w0, s0, bits=bits, g=g),
                preferred_element_type=jnp.float32)
    b = jnp.dot(x1[...].astype(jnp.float32),
                _mx_dequant_block(w1, s1, bits=bits, g=g),
                preferred_element_type=jnp.float32)
    acc[...] += a + b

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _mx_tiles(N, K, g, pack, cfg: TroopConfig):
    """Shared tile solve for the MX kernels: (bn, bk, steps, streams)."""
    bn = min(cfg.block_n, N)
    while N % bn:
        bn //= 2
    streams = cfg.streams if (K // g) % 2 == 0 and cfg.streams == 2 else 1
    Kh = K // streams
    bk = max(min(cfg.block_k * cfg.unroll, Kh) // g * g, g)
    while Kh % bk:
        bk -= g
    assert bk % pack == 0, f"MX block_k {bk} not packable (pack={pack})"
    return bn, bk, Kh // bk, streams


def _mx_gemv_2d(wq, scales, x2, cfg: TroopConfig):
    """wq (K | K//2-packed, N), scales (K//g, N), x2 (B, K) -> (B, N)."""
    Ks, N = wq.shape
    B, K = x2.shape
    bits = _mx_bits(wq)
    pack = 2 if bits == 4 else 1
    g = K // scales.shape[0]
    bn, bk, steps, streams = _mx_tiles(N, K, g, pack, cfg)
    body = functools.partial(
        _mx_kernel_1s if streams == 1 else _mx_kernel_2s, bits=bits, g=g)

    w_lo = pl.BlockSpec((bk // pack, bn), lambda i, j: (j, i))
    w_hi = pl.BlockSpec((bk // pack, bn), lambda i, j, o=steps: (j + o, i))
    s_lo = pl.BlockSpec((bk // g, bn), lambda i, j: (j, i))
    s_hi = pl.BlockSpec((bk // g, bn), lambda i, j, o=steps: (j + o, i))
    x_lo = pl.BlockSpec((B, bk), lambda i, j: (0, j))
    x_hi = pl.BlockSpec((B, bk), lambda i, j, o=steps: (0, j + o))

    if streams == 1:
        in_specs, ops = [w_lo, s_lo, x_lo], (wq, scales, x2)
    else:
        in_specs = [w_lo, s_lo, x_lo, w_hi, s_hi, x_hi]
        ops = (wq, scales, x2, wq, scales, x2)
    return pl.pallas_call(
        body,
        grid=(N // bn, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((B, bn), jnp.float32)],
        interpret=pallas_interpret(),
    )(*ops)


def _mx_example(small: bool = True, elem: str = "fp4", batch: int = 0):
    N, K = (128, 512) if small else (2048, 4096)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    qt = quantize_mx(jax.random.normal(ks[0], (K, N), jnp.float32),
                     elem=elem, axis=-2)
    shape = (batch, K) if batch else (K,)
    x = jax.random.normal(ks[1], shape, jnp.bfloat16)
    return (qt.values, qt.scales, x), {}


def _mx_qgemv_bytes(wq, s, x):
    K = x.shape[-1]
    B = x.shape[0] if len(x.shape) == 2 else 1
    return (numel(wq) * itemsize(wq) + numel(s) * itemsize(s)
            + B * K * itemsize(x) + B * wq.shape[-1] * 4)


def _mx_qgemv_streamed(wq, s, x):
    out = (x.shape[0], wq.shape[-1]) if len(x.shape) == 2 else (wq.shape[-1],)
    return [wq, s, x, jax.ShapeDtypeStruct(out, jnp.float32)]


@troop_kernel(
    "mx_qgemv",
    flops=lambda wq, s, x: 2.0 * wq.shape[-1] * x.shape[-1],
    bytes=_mx_qgemv_bytes,
    streamed=_mx_qgemv_streamed,
    space=_QSPACE,
    ref="mx_qgemv", example=_mx_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def mx_qgemv(wq, scales, x, cfg: TroopConfig = TroopConfig()):
    """MX GEMV: wq (K | K//2-packed, N) fp4/fp8 codes, scales (K//g, N)
    E8M0, x (K,) -> y (N,) fp32.  Block-exponent dequant in register."""
    return _mx_gemv_2d(wq, scales, x.reshape(1, -1), cfg).reshape(-1)


@troop_kernel(
    "batched_mx_qgemv",
    flops=lambda wq, s, xs: 2.0 * xs.shape[0] * wq.shape[-1] * xs.shape[-1],
    bytes=_mx_qgemv_bytes,
    streamed=_mx_qgemv_streamed,
    space=_QSPACE,
    ref="batched_mx_qgemv",
    example=functools.partial(_mx_example, batch=4))
@functools.partial(jax.jit, static_argnames=("cfg",))
def batched_mx_qgemv(wq, scales, xs, cfg: TroopConfig = TroopConfig()):
    """Small-batch MX projection: xs (B, K) -> (B, N) fp32.  The batch
    rides the sublane dim; the weight stream is unchanged."""
    return _mx_gemv_2d(wq, scales, xs, cfg)


def _mx_swiglu_kernel(wg_ref, sg_ref, wu_ref, su_ref, x_ref, o_ref,
                      acc_g, acc_u, *, bits, g):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...].astype(jnp.float32)
    acc_g[...] += jnp.dot(x, _mx_dequant_block(wg_ref, sg_ref,
                                               bits=bits, g=g),
                          preferred_element_type=jnp.float32)
    acc_u[...] += jnp.dot(x, _mx_dequant_block(wu_ref, su_ref,
                                               bits=bits, g=g),
                          preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        a = acc_g[...]
        o_ref[...] = (a * jax.nn.sigmoid(a)
                      * acc_u[...]).astype(o_ref.dtype)


def _mx_swiglu_example(small: bool = True, elem: str = "fp4"):
    N, K = (128, 512) if small else (2048, 4096)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    qg = quantize_mx(jax.random.normal(ks[0], (K, N), jnp.float32),
                     elem=elem, axis=-2)
    qu = quantize_mx(jax.random.normal(ks[1], (K, N), jnp.float32),
                     elem=elem, axis=-2)
    x = jax.random.normal(ks[2], (K,), jnp.bfloat16)
    return (qg.values, qg.scales, qu.values, qu.scales, x), {}


def _mx_swiglu_bytes(wg, sg, wu, su, x):
    return (numel(wg) * itemsize(wg) + numel(sg) * itemsize(sg)
            + numel(wu) * itemsize(wu) + numel(su) * itemsize(su)
            + x.shape[-1] * itemsize(x) + wg.shape[-1] * 4)


def _mx_swiglu_streamed(wg, sg, wu, su, x):
    return [wg, sg, wu, su, x,
            jax.ShapeDtypeStruct((wg.shape[-1],), jnp.float32)]


@troop_kernel(
    "mx_qgemv_swiglu",
    flops=lambda wg, sg, wu, su, x: 4.0 * wg.shape[-1] * x.shape[-1],
    bytes=_mx_swiglu_bytes,
    streamed=_mx_swiglu_streamed,
    space={"streams": (1,), "unroll": (1, 2),
           "block_n": (128, 256), "block_k": (256, 512)},
    ref="mx_qgemv_swiglu", example=_mx_swiglu_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def mx_qgemv_swiglu(wg, sg, wu, su, x, cfg: TroopConfig = TroopConfig()):
    """Fused MX swiglu: silu(wg.T @ x) * (wu.T @ x) in one pass — the gate
    and up projections dequant-GEMV against the same resident x block and
    the silu·gate epilogue runs on the committed accumulators, halving the
    activation round-trips of the two-call form."""
    Ks, N = wg.shape
    K = x.shape[-1]
    bits = _mx_bits(wg)
    pack = 2 if bits == 4 else 1
    g = K // sg.shape[0]
    one = TroopConfig(streams=1, unroll=cfg.unroll, block_n=cfg.block_n,
                      block_k=cfg.block_k)
    bn, bk, steps, _ = _mx_tiles(N, K, g, pack, one)
    body = functools.partial(_mx_swiglu_kernel, bits=bits, g=g)
    w_spec = pl.BlockSpec((bk // pack, bn), lambda i, j: (j, i))
    s_spec = pl.BlockSpec((bk // g, bn), lambda i, j: (j, i))
    x_spec = pl.BlockSpec((1, bk), lambda i, j: (0, j))
    out = pl.pallas_call(
        body,
        grid=(N // bn, steps),
        in_specs=[w_spec, s_spec, w_spec, s_spec, x_spec],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32),
                        pltpu.VMEM((1, bn), jnp.float32)],
        interpret=pallas_interpret(),
    )(wg, sg, wu, su, x.reshape(1, -1))
    return out.reshape(-1)


def _grouped_kernel(ids_ref, w_ref, s_ref, x_ref, o_ref, acc, *, bits, g):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    w = _mx_dequant_block(w_ref[0], s_ref[0], bits=bits, g=g)
    acc[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _grouped_example(small: bool = True, elem: str = "fp4"):
    E, topk = 4, 2
    N, K = (128, 512) if small else (1408, 2048)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    qt = quantize_mx(jax.random.normal(ks[0], (E, K, N), jnp.float32),
                     elem=elem, axis=-2)
    xs = jax.random.normal(ks[1], (topk, K), jnp.bfloat16)
    ids = jnp.array([1, 3], jnp.int32)[:topk]
    return (qt.values, qt.scales, xs, ids), {}


def _grouped_bytes(wq, s, xs, ids):
    topk, K = xs.shape
    # gathered traffic: top-k expert slices of the stacked weights/scales,
    # not the whole pool (the scalar-prefetched ids ride in SMEM for free)
    return (topk * wq.shape[1] * wq.shape[2] * itemsize(wq)
            + topk * s.shape[1] * s.shape[2] * itemsize(s)
            + topk * K * itemsize(xs) + topk * wq.shape[-1] * 4)


def _grouped_streamed(wq, s, xs, ids):
    topk = xs.shape[0]
    return [jax.ShapeDtypeStruct((topk,) + tuple(wq.shape[1:]), wq.dtype),
            jax.ShapeDtypeStruct((topk,) + tuple(s.shape[1:]), s.dtype),
            xs, jax.ShapeDtypeStruct((topk, wq.shape[-1]), jnp.float32)]


@troop_kernel(
    "grouped_expert_qgemv",
    flops=lambda wq, s, xs, ids: 2.0 * xs.shape[0] * wq.shape[-1]
    * xs.shape[-1],
    bytes=_grouped_bytes,
    streamed=_grouped_streamed,
    space={"streams": (1,), "unroll": (1, 2),
           "block_n": (128, 256), "block_k": (256, 512)},
    ref="grouped_expert_qgemv", example=_grouped_example)
@functools.partial(jax.jit, static_argnames=("cfg",))
def grouped_expert_qgemv(wq, scales, xs, expert_ids,
                         cfg: TroopConfig = TroopConfig()):
    """Grouped MX expert dispatch: wq (E, K | K//2-packed, N), scales
    (E, K//g, N) E8M0, xs (topk, K), expert_ids (topk,) int32 -> (topk, N).

    The router's selections are scalar-prefetched into SMEM and drive the
    weight BlockSpec index map, so each grid row DMAs exactly its chosen
    expert's tiles out of the stacked pool — no gather ever materializes a
    dequantized expert in HBM (same mechanism as the paged-attention
    block-table walk)."""
    E, Ks, N = wq.shape
    topk, K = xs.shape
    bits = _mx_bits(wq)
    pack = 2 if bits == 4 else 1
    g = K // scales.shape[1]
    one = TroopConfig(streams=1, unroll=cfg.unroll, block_n=cfg.block_n,
                      block_k=cfg.block_k)
    bn, bk, steps, _ = _mx_tiles(N, K, g, pack, one)
    body = functools.partial(_grouped_kernel, bits=bits, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(topk, N // bn, steps),
        in_specs=[
            pl.BlockSpec((1, bk // pack, bn),
                         lambda t, i, j, ids: (ids[t], j, i)),
            pl.BlockSpec((1, bk // g, bn),
                         lambda t, i, j, ids: (ids[t], j, i)),
            pl.BlockSpec((1, bk), lambda t, i, j, ids: (t, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda t, i, j, ids: (t, i)),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
    )
    return pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((topk, N), jnp.float32),
        interpret=pallas_interpret(),
    )(expert_ids.astype(jnp.int32), wq, scales, xs)
