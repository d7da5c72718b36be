"""Parameter system + basic layers (pure functional JAX).

Every parameter is created inside a ``Param`` box that carries its *logical
sharding axes* (t5x-style).  ``unbox``/``axes_of`` split a boxed tree into the
raw array tree used by apply functions and the logical-axes tree used by the
launcher to derive ``NamedSharding``s.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
import numpy as np

from repro.quant.tensor import QuantizedTensor


# --------------------------------------------------------------------------
# Param boxing
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Param:
    value: Any                       # jnp array (or ShapeDtypeStruct)
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)


def is_param(x) -> bool:
    return isinstance(x, Param)


def unbox(tree):
    return jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)


def axes_of(tree):
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)


def box_like(values, boxed):
    """Re-attach axes from ``boxed`` onto a raw value tree."""
    return jax.tree.map(lambda v, p: Param(v, p.axes), values, boxed,
                        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))


# --------------------------------------------------------------------------
# Kernel routing (observability)
# --------------------------------------------------------------------------
# When enabled, single-token projections, rmsnorms and the tied read-out
# dispatch their registry Pallas kernels (repro.kernels.ops) instead of the
# inline jnp expressions — the capture mode behind the dispatch audit
# (obs.profiler.audit_decode_step), which replays a decode step under
# jax.eval_shape and compares the dispatched kernel multiset against
# obs.energy.decode_step_account.  Off by default; the flag is read at
# trace time, so already-jitted steps are unaffected by a later flip.
_KERNEL_ROUTED = False


def kernel_routed() -> bool:
    return _KERNEL_ROUTED


@contextlib.contextmanager
def kernel_routing(enable: bool = True):
    global _KERNEL_ROUTED
    prev = _KERNEL_ROUTED
    _KERNEL_ROUTED = enable
    try:
        yield
    finally:
        _KERNEL_ROUTED = prev


def _no_tp() -> bool:
    from repro.dist import tp as _tp
    return _tp.current() is None


def _gemv_routable(x, w) -> bool:
    """One output row-vector against a raw 2-D weight, outside TP."""
    return (getattr(w, "ndim", 0) == 2 and x.ndim >= 1
            and x.shape[-1] == w.shape[0]
            and int(np.prod(x.shape[:-1], dtype=np.int64)) == 1 and _no_tp())


def _mx_routable(x, w) -> bool:
    """Single-token projection against a 2-D MX-quantized weight whose
    shared-exponent blocks run down the contraction axis — the layout
    ``mx_qgemv`` walks without a transpose."""
    return (isinstance(w, QuantizedTensor) and w.fmt == "mx"
            and len(w.shape) == 2 and w.axis == -2 and x.ndim >= 1
            and x.shape[-1] == w.shape[0]
            and int(np.prod(x.shape[:-1], dtype=np.int64)) == 1 and _no_tp())


def _routed_gemv(w_nk, x, dtype):
    """Dispatch the registry gemv on an (N, K) weight; returns (N,)."""
    from repro.kernels import ops as KO
    return KO.gemv(w_nk, x.reshape(-1)).astype(dtype)


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------
def _normal(key, shape, dtype, scale):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def dense_init(key, in_dim: int, out_dim: int, axes, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None):
    """W: (in_dim, out_dim) with fan-in scaling."""
    scale = (1.0 / np.sqrt(in_dim)) if scale is None else scale
    p = {"w": Param(_normal(key, (in_dim, out_dim), dtype, scale), axes)}
    if bias:
        p["b"] = Param(jnp.zeros((out_dim,), dtype), (axes[1],))
    return p


def apply_dense(p, x, dtype=None, tp=None):
    """``tp``: the projection's tensor-parallel role when serving under a
    TP mesh — ``"col"`` (output columns sharded: qkv/up/gate) or ``"row"``
    (contraction rows sharded: o/down projections).  Outside a TP context
    the flag is inert.  In *exact* TP mode the flag is also inert here:
    column shards are plain local matmuls on the pre-sharded weight and
    row projections see the re-gathered full activation.  In *overlap*
    mode the projection routes through ``repro.dist.collective_matmul``'s
    ring collectives so the gather/scatter hides behind the GEMV."""
    w = p["w"]
    quantized = isinstance(w, QuantizedTensor)
    if _KERNEL_ROUTED and quantized and _mx_routable(x, w):
        # MX weights stream their fp4/fp8 codes + E8M0 scales straight into
        # the fused block-exponent dequant GEMV (DESIGN.md §11)
        from repro.kernels import ops as KO
        if dtype is not None:
            x = x.astype(dtype)
        y = KO.mx_qgemv(w.values, w.scales,
                        x.reshape(-1)).astype(dtype or x.dtype)
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y.reshape(x.shape[:-1] + (w.shape[-1],))
    if quantized:
        # repro.quant weights (DESIGN.md §5): grouped dequant on the fly —
        # the GSPMD-shardable reference of the fused-dequant qgemv kernels
        # (which stream the int8/int4 bytes + scales; repro.quant.kernels)
        w = w.dequantize(dtype or x.dtype)
    elif dtype is not None:
        w = w.astype(dtype)
    if dtype is not None:
        x = x.astype(dtype)
    if _KERNEL_ROUTED and not quantized and _gemv_routable(x, w):
        # W is stored (in_dim, out_dim); the gemv kernel walks (N, K)
        y = _routed_gemv(w.T, x, jnp.result_type(x.dtype, w.dtype))
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y.reshape(x.shape[:-1] + (w.shape[1],))
    if tp is not None:
        from repro.dist import tp as _tp
        ctx = _tp.current()
        if ctx is not None and ctx.mode == "overlap":
            from repro.dist.collective_matmul import (allgather_matmul,
                                                      reduce_scatter_matmul)
            if tp == "col":
                # slice our K-chunk of the replicated activation and walk
                # the ring against the full-K local-column weight
                Kl = x.shape[-1] // ctx.size
                xs = jax.lax.dynamic_slice_in_dim(
                    x, _tp.axis_index() * Kl, Kl, axis=x.ndim - 1)
                y = allgather_matmul(xs, w, ctx.axis)
            else:                                 # "row"
                y = reduce_scatter_matmul(x, w, ctx.axis)
                y = _tp.gather_cols(y)            # re-replicate the tiles
            if "b" in p:
                y = y + p["b"].astype(y.dtype)
            return y
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": Param(_normal(key, (vocab, d), dtype, 1.0),
                           ("vocab", "embed"))}


def apply_embed(p, ids, dtype):
    return jnp.take(p["table"].astype(dtype), ids, axis=0)


def apply_unembed(p, x, dtype):
    """Tied read-out: x @ table.T"""
    t = p["table"].astype(dtype)
    if _KERNEL_ROUTED and x.ndim >= 1 and x.shape[-1] == t.shape[1] \
            and int(np.prod(x.shape[:-1], dtype=np.int64)) == 1 and _no_tp():
        y = _routed_gemv(t, x.astype(dtype), dtype)   # table is (V, d)
        return y.reshape(x.shape[:-1] + (t.shape[0],))
    return x.astype(dtype) @ t.T


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def norm_init(kind: str, d: int, axes=("embed",)):
    p = {"scale": Param(jnp.ones((d,), jnp.float32), axes)}
    if kind == "layernorm":
        p["bias"] = Param(jnp.zeros((d,), jnp.float32), axes)
    return p


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    if _KERNEL_ROUTED and kind == "rmsnorm" and "bias" not in p and _no_tp():
        from repro.kernels import ops as KO
        d = x.shape[-1]
        # eps is a static kernel arg — must stay a kwarg
        return KO.rmsnorm(x.reshape(-1, d), p["scale"],
                          eps=eps).reshape(x.shape)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        x = x - mu
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    x = x * p["scale"]
    if "bias" in p:
        x = x + p["bias"]
    return x.astype(dtype)


# --------------------------------------------------------------------------
# Activations
# --------------------------------------------------------------------------
def activation(name: str):
    if name == "gelu":
        return jax.nn.gelu
    if name == "silu":
        return jax.nn.silu
    if name == "relu_sq":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise KeyError(name)


# --------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# --------------------------------------------------------------------------
def mlp_init(key, d: int, d_ff: int, act: str, *, ff_axis: str = "ffn",
             dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {}
    if act == "swiglu":
        p["wi_gate"] = dense_init(ks[0], d, d_ff, ("embed", ff_axis), dtype=dtype)
        p["wi_up"] = dense_init(ks[1], d, d_ff, ("embed", ff_axis), dtype=dtype)
    else:
        p["wi_up"] = dense_init(ks[1], d, d_ff, ("embed", ff_axis), dtype=dtype)
    p["wo"] = dense_init(ks[2], d_ff, d, (ff_axis, "embed"), dtype=dtype)
    return p


@jax.named_scope("mlp")
def apply_mlp(p, x, act: str, dtype):
    from repro.core.partitioning import constrain
    from repro.dist import tp as _tp
    ffn_axes = ("batch",) + (None,) * (x.ndim - 2) + ("ffn",)
    if "wi_gate" in p and _KERNEL_ROUTED \
            and _mx_routable(x, p["wi_gate"]["w"]) \
            and _mx_routable(x, p["wi_up"]["w"]) \
            and "b" not in p["wi_gate"] and "b" not in p["wi_up"]:
        # fused MX swiglu: gate + up dequant-GEMV and the silu·gate
        # epilogue in ONE kernel pass (DESIGN.md §11)
        from repro.kernels import ops as KO
        wg, wu = p["wi_gate"]["w"], p["wi_up"]["w"]
        xk = x.astype(dtype) if dtype is not None else x
        h = KO.mx_qgemv_swiglu(wg.values, wg.scales, wu.values, wu.scales,
                               xk.reshape(-1)).astype(dtype or x.dtype)
        h = h.reshape(x.shape[:-1] + (wg.shape[-1],))
    elif "wi_gate" in p:
        h = jax.nn.silu(apply_dense(p["wi_gate"], x, dtype, tp="col")) * \
            apply_dense(p["wi_up"], x, dtype, tp="col")
    else:
        h = activation(act)(apply_dense(p["wi_up"], x, dtype, tp="col"))
    h = constrain(h, ffn_axes)
    ctx = _tp.current()
    if ctx is not None and ctx.mode == "exact":
        # exact TP: the silu-gate was elementwise on our ffn columns;
        # re-concatenate the shards (bitwise) for the replicated down-proj
        h = _tp.gather_cols(h)
    out = apply_dense(p["wo"], h, dtype, tp="row")
    # §Perf B3/B4: pin the TP reduction in bf16 + name it for the remat
    # policy (see attention.py)
    out = constrain(out, ("batch",) + (None,) * (x.ndim - 1))
    return _checkpoint_name(out, "tp_out")


# --------------------------------------------------------------------------
# Rotary embeddings
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., T, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(T: int, d: int, offset=0):
    pos = jnp.arange(T, dtype=jnp.float32) + offset
    inv = 1.0 / (10_000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
