"""Unified kernel dispatch: one registry, one padding/bucketing policy.

Every Pallas kernel package used to ship its own ``ops.py`` wrapper with
a private copy of backend selection (``_on_tpu``), power-of-two bucket
padding (``_bucket``, with floors that had drifted apart: 8 here, 128
there) and interpret-mode plumbing.  This module centralizes all of it:

* :class:`KernelOp` — a declarative description of a kernel: the Pallas
  body, the pure-``jnp`` reference body, which argument axes are
  *elastic* (sized by the irregular workload and therefore padded), the
  pad constants, the bucket floor, and an a-priori cost hint.
* :func:`register_kernel` / :func:`get_kernel` /
  :func:`registered_kernels` — the registry.  Kernel packages register
  at import time; adding a new kernel is one :class:`KernelOp` plus a
  thin public wrapper (see the README recipe).
* :func:`dispatch` — the single entry point that owns

  - **backend resolution**: ``"tpu-pallas"`` (compiled Mosaic),
    ``"interpret"`` (Pallas interpreter — kernel test sweeps), ``"ref"``
    (pure-jnp oracle, the fast path off-TPU), or ``None`` = auto
    (``tpu-pallas`` on TPU, ``ref`` elsewhere); the legacy spelling
    ``"pallas"`` is accepted as an alias of ``"tpu-pallas"``;
  - **bucket padding**: every elastic axis is padded up to the next
    power of two >= the op's floor, so a run whose operand sizes vary
    irregularly (UTS frontiers, Mariani-Silver rectangles) triggers at
    most O(log max_size) jit traces instead of one per distinct size;
  - **jit-cache-bounded recompilation**: one jitted callable per
    (op, backend, static-kwargs) triple, reused across all bucketed
    shapes, with a :func:`compile_log` the tests use to assert the
    O(log) bound;
  - **unpadding**: outputs are sliced back to the caller's true sizes;
  - **accounting**: with the recorder of ``repro.core.telemetry`` on,
    each call runs inside a ``dispatch.<op>`` span and adds the output
    positions the kernel ran on, padding included, to
    ``<op>.launched`` and those the caller keeps to ``<op>.kept``.

:func:`to_device` and :func:`to_host` are ``jnp.asarray`` and
``np.asarray`` across the host/device boundary, counted in
``h2d_bytes`` and ``d2h_bytes``; the copy to the host, which waits for
the device, runs in a ``device.pull`` span.

The three shipped ops — ``uts_hash``, ``mandelbrot``,
``flash_attention_fwd`` — are registered by their packages'
``ops.py`` modules (imported lazily on first lookup).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.telemetry import count, enabled, span

__all__ = [
    "KernelOp", "register_kernel", "get_kernel", "registered_kernels",
    "dispatch", "bucket", "resolve_backend", "on_tpu",
    "compile_log", "reset_compile_log", "estimate_cost",
    "enable_compile_cache", "to_device", "to_host",
]

#: canonical backend names, in resolution-priority order
BACKENDS = ("tpu-pallas", "interpret", "ref")
_ALIASES = {"pallas": "tpu-pallas"}


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: Optional[str]) -> str:
    """Canonical backend name; ``None`` = auto (tpu-pallas on TPU, else ref).

    The auto choice falls back to ``ref`` without a word when no TPU is
    found, which is what the CPU test suite relies on.  A run meant for
    the chip therefore checks :func:`compile_log` afterwards: every entry
    of its kernels must carry the ``tpu-pallas`` backend."""
    if backend is None:
        return "tpu-pallas" if on_tpu() else "ref"
    backend = _ALIASES.get(backend, backend)
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of "
            f"{', '.join(BACKENDS)} (or the alias 'pallas')")
    return backend


def bucket(n: int, floor: int = 128) -> int:
    """Next power-of-two >= max(floor, n).

    The shared bucketing policy: irregular operand sizes collapse onto
    O(log max_size) distinct padded shapes, which bounds jit
    recompilation over a whole run (frontier sizes change every
    generation by construction).
    """
    if floor < 1:
        raise ValueError("bucket floor must be >= 1")
    b = floor
    while b < n:
        b <<= 1
    return b


@dataclass(frozen=True)
class KernelOp:
    """Declarative description of one dispatchable kernel.

    ``arg_dims`` names the *elastic* axes: for each positional array
    argument, a tuple of ``(axis, dim_name)`` pairs.  Axes sharing a
    ``dim_name`` must agree in size and are padded to the same bucket;
    arguments with an empty tuple are passed through untouched (e.g.
    flash attention, whose shapes are already block-aligned by the
    model layer).  ``out_dims`` locates the same named dims on the
    (single) output so :func:`dispatch` can slice the padding back off.
    """

    name: str
    #: Pallas body: ``(*arrays, interpret=..., **static) -> array``
    pallas_body: Callable[..., Any]
    #: pure-jnp oracle with the same array signature: ``(*arrays, **static)``
    reference_body: Callable[..., Any]
    #: per-argument elastic axes: ((axis, dim_name), ...) per positional arg
    arg_dims: Tuple[Tuple[Tuple[int, str], ...], ...] = ()
    #: per-argument pad constant (only used for args with elastic axes)
    pad_values: Tuple[Any, ...] = ()
    #: elastic axes of the output, for unpadding
    out_dims: Tuple[Tuple[int, str], ...] = ()
    #: bucket floor for every elastic dim of this op
    bucket_floor: int = 128
    #: a-priori work estimate from the *unpadded* operands
    cost_hint: Callable[..., float] = field(default=lambda *args: 1.0)

    def __post_init__(self) -> None:
        if self.pad_values and len(self.pad_values) != len(self.arg_dims):
            raise ValueError(
                f"{self.name}: pad_values ({len(self.pad_values)}) and "
                f"arg_dims ({len(self.arg_dims)}) must align")


_REGISTRY: Dict[str, KernelOp] = {}
# (backend, static-kwargs) -> jitted callable, one per op
_JIT_CACHE: Dict[Tuple[str, str, tuple], Callable[..., Any]] = {}
# op name -> set of (backend, static-kwargs, padded arg signatures);
# each entry is one jit trace, so tests can assert the O(log) bound.
# Capped per op: ops without elastic axes (flash attention) see a new
# signature per distinct operand shape, and a long-lived process must
# not grow this diagnostic set forever.
_COMPILE_LOG: Dict[str, Set[tuple]] = {}
_COMPILE_LOG_CAP = 4096
# op name -> (span, launched counter, kept counter), built once per op
_TELEMETRY_NAMES: Dict[str, Tuple[str, str, str]] = {}


def register_kernel(op: KernelOp) -> KernelOp:
    """Add ``op`` to the registry (idempotent on re-import).

    Re-registering a name drops its jitted callables and compile log —
    they close over the previous op's bodies and would otherwise keep
    dispatching the replaced implementation."""
    if op.name in _REGISTRY:
        for key in [k for k in _JIT_CACHE if k[0] == op.name]:
            del _JIT_CACHE[key]
        _COMPILE_LOG.pop(op.name, None)
    _REGISTRY[op.name] = op
    return op


def _ensure_registered() -> None:
    # Kernel packages self-register at import; pull the shipped three in
    # for callers that touch the registry before importing any of them.
    if {"uts_hash", "mandelbrot", "flash_attention_fwd"} \
            <= _REGISTRY.keys():
        return
    from .uts_hash import ops as _u      # noqa: F401
    from .mandelbrot import ops as _m    # noqa: F401
    from .flash_attention import ops as _f  # noqa: F401


def get_kernel(name: str) -> KernelOp:
    if name not in _REGISTRY:
        _ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel {name!r}; registered: "
            f"{', '.join(sorted(_REGISTRY))}") from None


def registered_kernels() -> List[str]:
    _ensure_registered()
    return sorted(_REGISTRY)


def compile_log(name: Optional[str] = None) -> Dict[str, Set[tuple]]:
    """Distinct (backend, static, padded-shape) signatures dispatched so
    far — a one-to-one proxy for jit cache entries.  The bucketing
    policy's whole job is to keep ``len(compile_log()[op])`` at
    O(log max_operand_size) over a run."""
    if name is not None:
        return {name: set(_COMPILE_LOG.get(name, set()))}
    return {k: set(v) for k, v in _COMPILE_LOG.items()}


def reset_compile_log(name: Optional[str] = None) -> None:
    if name is None:
        _COMPILE_LOG.clear()
    else:
        _COMPILE_LOG.pop(name, None)


#: where the persistent compilation cache lives when the environment
#: names no directory: one fixed path inside the checkout
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`, which never depends on a temporary name, a
    process id or the time, so a later run finds what an earlier one
    compiled.  Entry points call this; importing the library does not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def estimate_cost(op: Union[str, KernelOp], *args: Any) -> float:
    """The op's a-priori work estimate for these (unpadded) operands."""
    if isinstance(op, str):
        op = get_kernel(op)
    return float(op.cost_hint(*args))


def _jitted(op: KernelOp, backend: str,
            static: tuple) -> Callable[..., Any]:
    key = (op.name, backend, static)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        skw = dict(static)
        if backend == "ref":
            def call(*arrays: Any) -> Any:
                return op.reference_body(*arrays, **skw)
        else:
            interpret = backend == "interpret"
            def call(*arrays: Any) -> Any:
                return op.pallas_body(*arrays, interpret=interpret, **skw)
        # the jitted program, and so the profiler's host and device
        # events, carry the op's name
        call.__name__ = call.__qualname__ = op.name
        fn = jax.jit(call)
        _JIT_CACHE[key] = fn
    return fn


def _telemetry_names(op: KernelOp) -> Tuple[str, str, str]:
    names = _TELEMETRY_NAMES.get(op.name)
    if names is None:
        names = _TELEMETRY_NAMES[op.name] = (
            f"dispatch.{op.name}", f"{op.name}.launched", f"{op.name}.kept")
    return names


def dispatch(op: Union[str, KernelOp], *args: Any,
             backend: Optional[str] = None, kept: Optional[int] = None,
             **static: Any) -> Any:
    """Run a registered kernel: pad -> jit-dispatch -> unpad.

    ``static`` kwargs (iteration counts, block shapes, masks flags...)
    are forwarded to the op bodies and must be hashable — they are part
    of the jit-cache key alongside the op, the backend, and the
    bucketed operand shapes.  ``kept`` is how many of the output's
    positions the caller uses, where it padded the operands itself
    (default: all it passed).
    """
    if isinstance(op, str):
        op = get_kernel(op)
    span_name, launched_name, kept_name = _telemetry_names(op)

    with span(span_name):
        backend = resolve_backend(backend)
        # -- measure the elastic dims off the unpadded operands -----------
        dims: Dict[str, int] = {}
        for i, (arr, adims) in enumerate(zip(args, op.arg_dims)):
            for axis, dname in adims:
                size = arr.shape[axis]
                if dims.setdefault(dname, size) != size:
                    raise ValueError(
                        f"{op.name}: dim {dname!r} is {dims[dname]} but "
                        f"arg {i} axis {axis} has size {size}")

        buckets = {d: bucket(n, op.bucket_floor) for d, n in dims.items()}

        # -- pad every elastic axis up to its bucket -----------------------
        padded = []
        for i, arr in enumerate(args):
            adims = op.arg_dims[i] if i < len(op.arg_dims) else ()
            widths = [(0, 0)] * getattr(arr, "ndim", 0)
            grew = False
            for axis, dname in adims:
                extra = buckets[dname] - arr.shape[axis]
                if extra:
                    widths[axis] = (0, extra)
                    grew = True
            if grew:
                pv = op.pad_values[i] if i < len(op.pad_values) else 0
                arr = jnp.pad(arr, widths, constant_values=pv)
            padded.append(arr)

        skey = tuple(sorted(static.items()))
        sig = tuple((tuple(a.shape), str(a.dtype))
                    if hasattr(a, "shape") else repr(a) for a in padded)
        log = _COMPILE_LOG.setdefault(op.name, set())
        if len(log) < _COMPILE_LOG_CAP:
            log.add((backend, skey, sig))

        out = _jitted(op, backend, skey)(*padded)

        # -- slice the padding back off -----------------------------------
        if op.out_dims:
            index: List[Any] = [slice(None)] * out.ndim
            for axis, dname in op.out_dims:
                index[axis] = slice(0, dims[dname])
            out = out[tuple(index)]
    if enabled() and op.out_dims:
        count(launched_name,
              math.prod(buckets[d] for _, d in op.out_dims))
        count(kept_name, kept if kept is not None
              else math.prod(dims[d] for _, d in op.out_dims))
    return out


def to_device(x: Any, dtype: Any = None) -> jax.Array:
    """``jnp.asarray(x, dtype)``: a host array onto the default device,
    its bytes counted in ``h2d_bytes``."""
    out = jnp.asarray(x, dtype)
    if enabled():
        count("h2d_bytes", out.nbytes)
    return out


def to_host(x: jax.Array) -> np.ndarray:
    """``np.asarray(x)``: wait for the device, copy the array to the host,
    its bytes counted in ``d2h_bytes``."""
    with span("device.pull"):
        out = np.asarray(x)
    if enabled():
        count("d2h_bytes", out.nbytes)
    return out
