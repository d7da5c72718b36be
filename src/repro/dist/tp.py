"""Tensor-parallel serving: shard the jitted engine steps under ``shard_map``.

The paper's decode workload is memory-bound (OI ~= 1); once one device sits
at its roofline the only throughput lever left is more devices each
streaming a *slice* of the bytes — the mesh analogue of Spatz clustering
vector units against a shared L1.  This module makes the serving engine's
step functions (decode / chunked prefill / bucketed prefill) run SPMD over
a 1-D ``tp`` mesh:

  * **attention heads** are column-sharded (``wq``/``wk``/``wv`` output
    dims), GQA-aware: when ``num_kv_heads < tp`` the KV projections and the
    KV page pools stay *replicated* and each device slices the one KV head
    its query block reads (``kv_shards == 1``);
  * **MLP / expert ffn dims** are column-sharded; MoE experts are
    expert-parallel (dim 0 of the stacked expert weights);
  * **KV page pools and scale pages** are sharded on the head axis
    (``kv_shards == tp`` when divisible) so each device streams only its
    slice of the cache — the per-device byte count the engine's streamed-
    bytes model reports;
  * **block tables, the radix prefix index and the BlockAllocator** stay
    host-side and replicated: paging is control flow, not tensor data.

Two execution modes, selected per engine:

  * ``"exact"`` (default): activations stay replicated at layer
    boundaries.  Column-parallel projections compute their local output
    columns (bitwise equal to the corresponding columns of the unsharded
    matmul — XLA's dot is column-separable), attention runs on local
    heads, and the head/ffn shards are re-concatenated with a tiled
    ``all_gather`` before the (replicated) output projections.  Every
    device then holds bitwise-identical logits, which is what makes the
    TP engine *token-identical* to the single-device engine.  On TPU the
    engine's steps compile with XLA's excess precision off
    (``compiler_options``): otherwise the compiler rounds bf16 chains
    where a fusion ends, and the ``all_gather`` ends them in places the
    single-device program does not.
  * ``"overlap"``: the row/column-parallel projections route through
    ``repro.dist.collective_matmul``'s ring collectives
    (``allgather_matmul`` for qkv/up/gate, ``reduce_scatter_matmul`` for
    the o/down projections) so the gather/scatter hides behind the
    GEMV/GEMM.  The ring's split-K fp32 accumulation is tolerance-equal
    (not bitwise) to a single dot, so this mode trades exact token
    identity for communication overlap — the tests pin it to fp32
    tolerance against ``jnp.einsum`` references.

Model code discovers TP through a thread-local context (``current()``),
set only while tracing inside the ``shard_map`` body — the same pattern
as ``core.partitioning.PT``: outside a TP engine every call site costs
one attribute check and nothing else.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "tp"

# weight output axes that column-shard (the head / ffn dims)
_COL_AXES = ("qkv_out", "ffn")
_KV_AXES = ("kv_out",)


@dataclass(frozen=True)
class TPPlan:
    """Static sharding decisions for one engine."""
    size: int                      # mesh extent
    kv_shards: int                 # tp when num_kv_heads % tp == 0, else 1
    mode: str                      # "exact" | "overlap"
    axis: str = AXIS
    mesh: Any = field(default=None, compare=False)

    @property
    def kv_replicated(self) -> bool:
        return self.kv_shards == 1


_STATE = threading.local()


def current() -> Optional[TPPlan]:
    """The active plan while tracing inside a TP ``shard_map`` body; None
    everywhere else (single-device paths pay one attribute check)."""
    return getattr(_STATE, "plan", None)


@contextmanager
def enter(plan: TPPlan):
    prev = getattr(_STATE, "plan", None)
    _STATE.plan = plan
    try:
        yield
    finally:
        _STATE.plan = prev


def compiler_options(mode: str) -> Optional[Dict[str, Any]]:
    """XLA options for every jitted step of an engine in ``mode``, at any
    ``tp`` (the single-device engine is what a TP engine must match).

    Exact mode promises tokens that do not depend on ``tp``.  On TPU, XLA
    by default runs a bf16 elementwise chain in f32 and rounds it only
    where a fusion ends; a tp>1 program's ``all_gather`` ends fusions
    where the tp=1 program does not, so the two round differently and
    near-tied logits flip.  With excess precision off every bf16 op
    rounds, wherever fusions end.  The CPU programs agree without it."""
    if mode == "exact" and jax.default_backend() == "tpu":
        return {"xla_allow_excess_precision": False}
    return None


# ---------------------------------------------------------------- helpers
def axis_index():
    return jax.lax.axis_index(current().axis)


def gather_cols(x):
    """Exact-mode shard merge: tiled ``all_gather`` on the last axis —
    device-order concatenation of column shards, bitwise equal to the
    unsharded operator's output."""
    ctx = current()
    return jax.lax.all_gather(x, ctx.axis, axis=x.ndim - 1, tiled=True)


def local_kv_head(k, num_heads: int, num_kv_heads: int):
    """GQA fallback (``kv_shards == 1``): slice the one replicated KV head
    this device's query block attends to.  ``k`` is (..., KV, hd); the
    plan guarantees the local query heads span exactly one KV head."""
    ctx = current()
    m = ctx.size // num_kv_heads            # devices per KV head
    kv_idx = axis_index() // m
    return jax.lax.dynamic_slice_in_dim(k, kv_idx, 1, axis=k.ndim - 2)


# ------------------------------------------------------------------ plan
def plan(model, tp: int, mode: str = "exact") -> TPPlan:
    """Validate the arch/runtime against TP and freeze the sharding plan.

    Raises with a concrete reason for everything the TP engine does not
    (yet) support — a TP engine must never silently compute wrong tokens.
    """
    if mode not in ("exact", "overlap"):
        raise ValueError(f"tp_mode must be 'exact' or 'overlap': {mode!r}")
    cfg, rt = model.cfg, getattr(model, "rt", None)
    if tp < 2:
        raise ValueError("tp plan needs tp >= 2 (tp=1 is the plain engine)")
    if len(jax.devices()) < tp:
        raise ValueError(
            f"tp={tp} but only {len(jax.devices())} devices visible — on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count")
    if cfg.encoder_decoder or getattr(cfg, "frontend", "none") != "none":
        raise ValueError("TP serving supports decoder-only text archs "
                         f"(not {cfg.name!r})")
    if cfg.attention == "mla":
        raise ValueError("TP serving does not shard MLA's latent "
                         "projections yet — use the single-device engine")
    if any(m != "attn" for (m, f) in cfg.layer_kinds()):
        raise ValueError("TP serving supports attention mixers only "
                         "(recurrent state sharding is not head-sliced)")
    if rt is not None and getattr(rt, "paged_kernel_decode", False):
        raise ValueError("paged_kernel_decode is not supported under "
                         "shard_map — the Pallas kernel reads the full "
                         "pool; use the gathered jnp decode path")
    H, KV = cfg.num_heads, cfg.num_kv_heads
    if H % tp:
        raise ValueError(f"num_heads {H} not divisible by tp={tp}")
    if cfg.d_ff % tp:
        raise ValueError(f"d_ff {cfg.d_ff} not divisible by tp={tp}")
    if KV % tp == 0:
        kv_shards = tp
    else:
        # fewer KV heads than devices: replicate KV (pools included) and
        # give each device a query block within a single KV head
        if tp % KV or (H // KV) % (tp // KV):
            raise ValueError(
                f"GQA fallback needs tp % num_kv_heads == 0 and the query "
                f"group divisible by tp // num_kv_heads (H={H}, KV={KV}, "
                f"tp={tp})")
        kv_shards = 1
    if mode == "overlap":
        if cfg.d_model % tp:
            raise ValueError(f"overlap mode shards the contraction axis: "
                             f"d_model {cfg.d_model} % tp={tp} != 0")
        if kv_shards == 1:
            raise ValueError("overlap mode requires num_kv_heads % tp == 0 "
                             "(ring-sharded KV projections)")
    if cfg.moe is not None and getattr(cfg.moe, "num_experts", 0):
        if cfg.moe.num_experts % tp:
            raise ValueError(f"num_experts {cfg.moe.num_experts} not "
                             f"divisible by tp={tp}")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((tp,), (AXIS,))
    return TPPlan(size=tp, kv_shards=kv_shards, mode=mode, mesh=mesh)


# ------------------------------------------------------------ param specs
def _leaf_spec(axes: Optional[Tuple], ndim: int, plan: TPPlan):
    """PartitionSpec for one weight leaf from its logical axis names."""
    if not axes or ndim == 0:
        return P()
    col = set(_COL_AXES) | (set(_KV_AXES) if plan.kv_shards > 1 else set())
    if "expert" in axes:                       # stacked MoE expert weights
        return P(*[plan.axis if a == "expert" else None for a in axes])
    ent = [None] * ndim
    if ndim == 1:
        if axes[0] in col:                     # column-parallel bias
            ent[0] = plan.axis
    elif axes[-1] in col:                      # column-parallel weight
        ent[-1] = plan.axis
    elif (plan.mode == "overlap" and ndim >= 2 and len(axes) >= 2
          and axes[-2] in _COL_AXES and axes[-1] == "embed"):
        # row-parallel o / down proj: shard the contraction axis (ndim - 2;
        # stacked leaves carry a leading "layers" dim before it)
        ent[ndim - 2] = plan.axis
    return P(*ent)


def param_specs(model, params, plan: TPPlan):
    """Spec tree (a pytree prefix of ``params``: one spec per logical
    weight, covering both children of a ``QuantizedTensor``).  Axis names
    come from the model's ``Param`` boxes via ``eval_shape`` — no
    allocation, and quantized params keep their original dict paths."""
    from repro.models import modules as M
    from repro.quant.tensor import QuantizedTensor

    boxed = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    # axes are tuples — pytrees themselves — so stop flattening at them
    axes_leaves = jax.tree_util.tree_flatten_with_path(
        M.axes_of(boxed),
        is_leaf=lambda x: x is None or isinstance(x, tuple))[0]
    axes_by_path = {_pathkeys(p): a for p, a in axes_leaves}

    def is_logical(x):
        return isinstance(x, QuantizedTensor)

    def visit(path, leaf):
        axes = axes_by_path.get(_pathkeys(path))
        if isinstance(leaf, QuantizedTensor):
            if getattr(leaf, "bits", 8) != 8:
                fmt = "mx4" if getattr(leaf, "fmt", "int") == "mx" else "int4"
                raise ValueError(f"{fmt}-packed weights cannot shard: the "
                                 "packing pairs rows across the shard "
                                 "boundary — use int8 or fp8 under TP")
            ndim = len(leaf.shape)
        else:
            ndim = getattr(leaf, "ndim", 0)
        return _leaf_spec(axes, ndim, plan)

    return jax.tree_util.tree_map_with_path(visit, params,
                                            is_leaf=is_logical)


def _pathkeys(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return tuple(out)


def _arr_spec(leaf, plan: TPPlan):
    """Spec for one cache/state array: every KV-bearing leaf is
    (..., KV, hd) or (..., KV, 1) — shard axis ``ndim - 2`` when the plan
    shards KV, else replicate.  Non-cache leaves (tokens, logits, tables)
    are < 4-D and stay replicated."""
    ndim = getattr(leaf, "ndim", 0)
    if ndim >= 4 and plan.kv_shards > 1:
        ent = [None] * ndim
        ent[ndim - 2] = plan.axis
        return P(*ent)
    return P()


def cache_specs(caches, plan: TPPlan):
    return jax.tree.map(lambda l: _arr_spec(l, plan), caches)


# -------------------------------------------------------------- executor
class TPExecutor:
    """Places params/caches on the mesh and wraps the engine's jitted step
    functions in ``shard_map``.  One instance per ``ServingEngine``."""

    def __init__(self, model, tp: int, mode: str = "exact"):
        self.plan = plan(model, tp, mode)
        self.mesh = self.plan.mesh
        self._pspecs = None
        # optional repro.obs.DispatchProfiler (set by the engine): every
        # sharded step call is bracketed in a "collective" phase tagged
        # with the mesh size
        self.profiler = None

    # ------------------------------------------------------- placement
    def shard_params(self, model, params):
        self._pspecs = param_specs(model, params, self.plan)
        from repro.quant.tensor import QuantizedTensor

        def put(leaf, spec):
            # device_put on a QuantizedTensor applies the spec to both
            # children — values (K, N) and scales (K/g, N) share dims
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        return jax.tree.map(
            put, params, self._pspecs,
            is_leaf=lambda x: isinstance(x, QuantizedTensor))

    def shard_caches(self, caches):
        return jax.tree.map(
            lambda l: jax.device_put(
                l, NamedSharding(self.mesh, _arr_spec(l, self.plan))),
            caches)

    # ---------------------------------------------------------- steps
    def jit_step(self, fn: Callable, *, probe: Optional[Callable] = None,
                 donate: Optional[int] = None):
        """``jax.jit(shard_map(fn))`` with specs derived lazily from the
        first call's arguments.  Positional convention (the engine's):
        arg 0 = params, arg 1 = batch (replicated), arg 2 (optional) =
        caches.  ``probe`` is an effect-free twin of ``fn`` used for the
        one ``eval_shape`` (so trace-time counters count compiles only);
        ``donate`` forwards to ``jax.jit(donate_argnums=...)``."""
        state: Dict[str, Any] = {}
        plan_, mesh = self.plan, self.mesh

        def build(args):
            in_specs = [self._pspecs, P()]
            if len(args) > 2:
                in_specs.append(cache_specs(args[2], plan_))
            out_shape = jax.eval_shape(probe or fn, *args)
            out_specs = jax.tree.map(
                lambda l: _arr_spec(l, plan_), out_shape)

            def body(*a):
                with enter(plan_):
                    return fn(*a)

            sm = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs, check_rep=False)
            return jax.jit(sm, donate_argnums=()
                           if donate is None else (donate,),
                           compiler_options=compiler_options(plan_.mode))

        def call(*args):
            f = state.get("f")
            if f is None:
                f = state["f"] = build(args)
            prof = self.profiler
            if prof is not None:
                with prof.phase("collective", devices=plan_.size):
                    return f(*args)
            return f(*args)

        return call
