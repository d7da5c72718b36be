"""Kernel registry — the tuning subsystem's source of truth.

Every Pallas kernel in ``repro.kernels`` registers itself with
``@troop_kernel(name, flops=..., bytes=...)``, declaring:

  * a roofline cost model (``flops`` / ``bytes`` callables over the call's
    positional arguments — only ``.shape``/``.dtype`` are read, so
    ``jax.ShapeDtypeStruct`` placeholders work),
  * its tunable ``TroopConfig`` space (knob -> candidate values),
  * the name of its pure-jnp oracle in ``repro.kernels.ref`` (resolved
    lazily to avoid import cycles),
  * an example-args factory used by ``benchmarks/tune_report.py`` and the
    test suite.

The decorator returns a *dispatching* wrapper: called with an explicit
``TroopConfig`` (positionally or as ``cfg=``) it behaves exactly like the
raw kernel; called without one it resolves the best-known config through
``repro.tune.cache.get_tuned`` (persistent tuned cache, falling back to the
spec's heuristic default).  The raw kernel stays reachable as
``spec.fn`` so the search engine never recurses through dispatch.

When a ``repro.obs.profiler.DispatchProfiler`` is installed (module global
``PROFILER``, via ``install_profiler``), every dispatch routes through
``profiler.record`` which logs the call (name, arg signature, resolved
config, modeled flops/bytes) before invoking the kernel with the exact
config the plain path would have used.  With no profiler installed the
wrapper pays a single module-attr check — nothing else.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

from repro.core.troop import TroopConfig

# Knob -> candidate values swept when a kernel does not restrict its space.
# (streams x unroll x block_n x block_k x layout — the paper's §IV axes.)
DEFAULT_SPACE: Mapping[str, Tuple] = {
    "streams": (1, 2),
    "unroll": (1, 2),
    "block_n": (128, 256),
    "block_k": (256, 512),
    "scrambled_layout": (False, True),
}


def itemsize(a) -> int:
    """Bytes per element; works on arrays, tracers and ShapeDtypeStructs."""
    import jax.numpy as jnp
    return jnp.dtype(a.dtype).itemsize


def numel(a) -> int:
    """Element count; works on arrays, tracers and ShapeDtypeStructs."""
    import math
    return int(math.prod(a.shape))


def arg_signature(args: Sequence[Any]) -> str:
    """``f32[128,512],bf16[512]`` — shape/dtype key of the array args.
    Non-array positional args (variant flags, scalar coefficients) key by
    ``repr`` so different kernel variants never share a cache entry."""
    import jax.numpy as jnp
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            if a is not None and not isinstance(a, TroopConfig):
                parts.append(repr(a)[:32])
            continue
        name = jnp.dtype(dtype).name.replace("float", "f").replace(
            "int", "i").replace("buint", "bui")
        parts.append(f"{name}[{','.join(str(int(d)) for d in shape)}]")
    return ",".join(parts)


def cache_key(name: str, args: Sequence[Any], backend: Optional[str] = None,
              variant: Optional[Mapping[str, Any]] = None) -> str:
    if backend is None:
        import jax
        backend = jax.default_backend()
    var = "".join(f"|{k}={repr(variant[k])[:32]}"
                  for k in sorted(variant)) if variant else ""
    return f"{name}|{arg_signature(args)}|{backend}{var}"


def operand_bytes(operands) -> float:
    """Total bytes of a streamed-operand list (arrays / ShapeDtypeStructs).

    The audit invariant behind every ``bytes=`` cost model: modeled traffic
    must equal the sum of the operands the kernel actually streams —
    including scale tensors for quantized layouts (tested registry-wide)."""
    return float(sum(numel(o) * itemsize(o) for o in operands))


@dataclass(frozen=True)
class KernelSpec:
    name: str
    fn: Callable                      # raw kernel: fn(*args, cfg=..., **kw)
    flops: Callable                   # (*args) -> float (useful FLOPs)
    bytes: Callable                   # (*args) -> float (min HBM traffic)
    space: Mapping[str, Tuple] = field(default_factory=lambda: DEFAULT_SPACE)
    ref: Optional[str] = None         # oracle name in repro.kernels.ref
    example: Optional[Callable] = None  # (small=True) -> (args, kwargs)
    default: TroopConfig = TroopConfig()
    key_kwargs: Tuple[str, ...] = ()  # kwargs that select a kernel variant
    streamed: Optional[Callable] = None  # (*args) -> streamed-operand list
    #   (each with .shape/.dtype; sum of nbytes must equal bytes(*args) —
    #   scalar/SMEM prefetch args are excluded by convention)

    def reference(self) -> Optional[Callable]:
        if self.ref is None:
            return None
        mod = importlib.import_module("repro.kernels.ref")
        return getattr(mod, self.ref)

    def heuristic(self, *args) -> TroopConfig:
        """Untuned fallback: the spec default (the repo's TROOP preset
        semantics — streams=2, hardware-granule blocks)."""
        return self.default

    def key(self, *args, backend: Optional[str] = None,
            kwargs: Optional[Mapping[str, Any]] = None) -> str:
        variant = {k: kwargs[k] for k in self.key_kwargs
                   if kwargs and k in kwargs}
        return cache_key(self.name, args, backend, variant)


REGISTRY: Dict[str, KernelSpec] = {}

# Installed DispatchProfiler (repro.obs.profiler) or None.  The dispatch
# wrapper below reads this module global once per call — the disabled path
# costs exactly one attr check and nothing else.
PROFILER: Optional[Any] = None


def install_profiler(profiler) -> None:
    """Route every registry dispatch through ``profiler.record``."""
    global PROFILER
    PROFILER = profiler


def uninstall_profiler(profiler=None) -> None:
    """Remove the installed profiler (no-op if ``profiler`` isn't it)."""
    global PROFILER
    if profiler is None or PROFILER is profiler:
        PROFILER = None


def get(name: str) -> KernelSpec:
    if name not in REGISTRY:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(REGISTRY)}"
            " (import repro.kernels to populate the registry)")
    return REGISTRY[name]


def names() -> Tuple[str, ...]:
    return tuple(sorted(REGISTRY))


def troop_kernel(name: str, *, flops: Callable, bytes: Callable,
                 space: Optional[Mapping[str, Tuple]] = None,
                 ref: Optional[str] = None,
                 example: Optional[Callable] = None,
                 default: Optional[TroopConfig] = None,
                 key_kwargs: Tuple[str, ...] = (),
                 streamed: Optional[Callable] = None):
    """Register a kernel and return its registry-dispatching wrapper."""
    def deco(fn: Callable) -> Callable:
        spec = KernelSpec(
            name=name, fn=fn, flops=flops, bytes=bytes,
            space=dict(space) if space is not None else dict(DEFAULT_SPACE),
            ref=ref, example=example,
            default=default if default is not None else TroopConfig(),
            key_kwargs=tuple(key_kwargs), streamed=streamed)
        REGISTRY[name] = spec

        def dispatch(*args, **kwargs):
            prof = PROFILER            # one module-attr load when disabled
            if prof is not None:
                return prof.record(spec, fn, args, kwargs)
            if kwargs.get("cfg") is not None or \
                    any(isinstance(a, TroopConfig) for a in args):
                return fn(*args, **kwargs)
            kwargs.pop("cfg", None)       # cfg=None -> dispatch
            from repro.tune.cache import get_tuned
            return fn(*args, cfg=get_tuned(name, *args, variant_kwargs=kwargs),
                      **kwargs)

        # manual wraps: jitted callables are C objects without a plain
        # __dict__ for functools.wraps to copy
        dispatch.__name__ = getattr(fn, "__name__", name)
        dispatch.__doc__ = getattr(fn, "__doc__", None)
        dispatch.__wrapped__ = fn
        dispatch.spec = spec
        return dispatch
    return deco
