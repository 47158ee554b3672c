"""Persistent, context-keyed compilation caching + the ``xla_runtime``
pseudo-component.

Every fresh process used to pay the full XLA trace+compile bill again — the
dominant startup cost for the bigger ``configs/`` models — and the XLA
runtime flags that gate codegen quality were hardcoded env pokes outside the
tuning loop.  This module closes both gaps (ROADMAP item 3, fronts b/c):

  * :func:`enable_persistent_cache` wires JAX's persistent compilation cache
    (through the :mod:`repro.compat` shim).  Where ``JAX_COMPILATION_CACHE_DIR``
    is set the cache lives exactly there; otherwise at
    ``<checkout>/results/compilecache/<hw>/<sw>``, namespaced by the same
    hardware-fingerprint × software-version coordinates as the ConfigStore,
    so a tuned (config, shape-bucket) pair never recompiles across processes
    — and an entry compiled under different coordinates is never reused.
  * :func:`cached_jit` is the process-local jit registry: compiled callables
    memoized by an explicit key + config-store context signature, with
    hit/miss/compile-seconds counters exported via ``core.telemetry``.  The
    serve decode step, the train step, and kernel-autotune candidates all
    route through it — new jitted hot paths should too, instead of bare
    ``jax.jit`` at call sites.
  * The ``xla_runtime`` pseudo-component (:data:`XLA_RUNTIME_SPACE`) makes
    the host-relevant XLA flag surface a declared tunable space, resolved /
    promoted through the normal ConfigStore + ``stats.compare`` machinery
    under a hardware-fingerprint context.  ``XLA_FLAGS`` is parsed once at
    backend startup, so settings apply to *child processes* via
    :func:`child_env` (launchers re-exec); raw ``os.environ["XLA_FLAGS"]``
    writes outside this module are a lint finding (MLOS008).

No top-level jax import: launchers import the flag helpers *before* the
backend initializes and locks the flag string.
"""
from __future__ import annotations

import hashlib
import os
import re
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, Hashable, List, Mapping, MutableMapping, Optional

from .configstore import WILDCARD, Context, context_for, default_store, \
    hardware_fingerprint, resolve_settings, sw_fingerprint
from .tunable import Bool, Int, TunableSpace

__all__ = [
    "COMPONENT", "XLA_RUNTIME_SPACE",
    "enable_persistent_cache", "persistent_cache_dir", "cache_counters",
    "cached_jit", "clear_jit_registry", "config_signature",
    "xla_flags_string", "merge_xla_flags", "apply_to_env", "child_env",
    "force_host_device_count", "ensure_host_device_count",
    "resolve_xla_settings", "set_xla_override", "promote_xla_settings",
]

COMPONENT = "xla_runtime"
# The checkout this package runs from: the default cache root hangs off it,
# so the cache path is the same from any working directory.
CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_ROOT = CHECKOUT / "results" / "compilecache"
# Read at first use, so benchmark children can flip them without code changes:
ENV_DISABLE = "REPRO_COMPILECACHE"           # "off"/"0"/"false" disables persistence
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"  # JAX's own: when set, the cache lives there


# =============================================================================
# Persistent compilation cache (front b)
# =============================================================================
_SANITIZE = re.compile(r"[^A-Za-z0-9._-]+")


def _sanitize(s: str) -> str:
    """Fingerprint → path component (``cpu:unknown:x8`` → ``cpu-unknown-x8``)."""
    return _SANITIZE.sub("-", s).strip("-") or "unknown"


def persistent_cache_dir() -> Path:
    """Where this process's compiled executables live.

    Exactly ``$JAX_COMPILATION_CACHE_DIR`` when it is set (whoever set it
    owns the layout).  Otherwise ``<checkout>/results/compilecache``,
    namespaced by the ConfigStore's hardware × software coordinates."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return Path(env)
    return CACHE_ROOT / _sanitize(hardware_fingerprint()) / _sanitize(sw_fingerprint())


_CACHE_LOCK = threading.Lock()
_CACHE_DIR: Optional[Path] = None
_CACHE_TRIED = False


def _disabled() -> bool:
    return os.environ.get(ENV_DISABLE, "").strip().lower() in ("off", "0", "false", "no")


def enable_persistent_cache() -> Optional[Path]:
    """Idempotently enable the persistent compilation cache; returns the
    active cache directory, or None when disabled (``REPRO_COMPILECACHE=off``)
    or not writable.  Safe to call from anywhere on the jit path — the first
    caller wins, later calls are a no-op."""
    global _CACHE_DIR, _CACHE_TRIED
    if _disabled():
        return None
    with _CACHE_LOCK:
        if _CACHE_TRIED:
            return _CACHE_DIR
        d: Optional[Path] = persistent_cache_dir()
        from .. import compat  # lazy: compat imports jax

        try:
            d.mkdir(parents=True, exist_ok=True)
        except OSError:
            d = None  # unwritable root: degrade to cold compiles
        else:
            compat.enable_compilation_cache(str(d))
        _CACHE_TRIED = True
        _CACHE_DIR = d
        return d


# =============================================================================
# Process-local jit registry (front b, in-process half)
# =============================================================================
_JIT_LOCK = threading.Lock()
_JIT_REGISTRY: Dict[Any, "_CachedJit"] = {}
_COUNTERS = {"hits": 0, "misses": 0, "compile_seconds": 0.0}


class _CachedJit:
    """A jitted callable that attributes its first-call wall time (trace +
    compile + first execute — the startup cost the persistent cache attacks)
    to the registry's ``compile_seconds`` counter."""

    __slots__ = ("_jitted", "_first", "registry_key")

    def __init__(self, jitted: Any, registry_key: Any):
        self._jitted = jitted
        self._first = True
        self.registry_key = registry_key

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self._first:
            t0 = time.perf_counter()
            out = self._jitted(*args, **kwargs)
            dt = time.perf_counter() - t0
            with _JIT_LOCK:
                _COUNTERS["compile_seconds"] += dt
            self._first = False
            return out
        return self._jitted(*args, **kwargs)

    def __getattr__(self, name: str) -> Any:  # .lower(), .trace(), ...
        return getattr(self._jitted, name)


def config_signature(obj: Any) -> str:
    """Stable short signature of a config object (dataclasses field-hashed,
    everything else by repr) — the cfg-identity part of a cached_jit context.
    Two configs with equal signatures must trace to the same computation."""
    import dataclasses

    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        body = repr(sorted(dataclasses.asdict(obj).items()))
        name = getattr(obj, "name", type(obj).__name__)
    else:
        body, name = repr(obj), type(obj).__name__
    return f"{name}:{hashlib.sha1(body.encode()).hexdigest()[:16]}"


def cached_jit(fn: Callable, *, key: str, context: Hashable = None,
               static_argnums: tuple = (), donate_argnums: tuple = (),
               persistent: bool = True) -> Callable:
    """``jax.jit`` through the process-local registry: the compiled callable
    is memoized by ``(key, context)`` — NOT by ``fn`` identity, since callers
    pass fresh lambdas — so re-constructing the same step (same component,
    same config-store context signature) returns the already-jitted callable
    instead of re-tracing.  ``context`` must fully determine the traced
    computation (closure contents included); input *shapes* need not be part
    of it — jax retraces per shape under one callable as usual.

    The first use also wires the persistent compilation cache, so the miss
    path's XLA compile is itself served from disk on repeat runs.

    ``donate_argnums`` and ``persistent=True`` are mutually exclusive: XLA's
    CPU runtime under an earlier jaxlib mis-handled ``input_output_aliases``
    on a *deserialized* executable — the donated buffer was freed while the
    aliased output was still live, a heap-corrupting use-after-free
    (intermittent SIGSEGV/SIGABRT).  No run has shown the installed JAX free
    of it, so each jit site picks one: donate on hot in-process loops that
    never restart (serve decode), persist on the expensive traces where cold
    restarts hurt (train/prefill steps)."""
    if donate_argnums and persistent:
        raise ValueError(
            f"cached_jit({key!r}): donate_argnums with persistent=True would "
            "deserialize a donating executable into a use-after-free; pass "
            "persistent=False to donate, or drop donation to persist")
    registry_key = (key, context, tuple(static_argnums), tuple(donate_argnums))
    with _JIT_LOCK:
        entry = _JIT_REGISTRY.get(registry_key)
        if entry is not None:
            _COUNTERS["hits"] += 1
            return entry
        _COUNTERS["misses"] += 1
    if persistent:
        enable_persistent_cache()
    import jax  # lazy: keep this module importable pre-backend-init

    jitted = jax.jit(fn, static_argnums=static_argnums or None,
                     donate_argnums=donate_argnums or None)
    entry = _CachedJit(jitted, registry_key)
    with _JIT_LOCK:
        # Two threads may race to compile the same key; first write wins so
        # every caller shares one trace cache.
        entry = _JIT_REGISTRY.setdefault(registry_key, entry)
    return entry


def cache_counters() -> Dict[str, float]:
    """Snapshot of the registry telemetry: hits, misses, compile_seconds and
    the number of live compiled entries (exported via ``core.telemetry``)."""
    with _JIT_LOCK:
        return {**_COUNTERS, "entries": float(len(_JIT_REGISTRY))}


def clear_jit_registry() -> None:
    """Drop memoized callables + zero the counters (tests)."""
    with _JIT_LOCK:
        _JIT_REGISTRY.clear()
        _COUNTERS.update(hits=0, misses=0, compile_seconds=0.0)


# =============================================================================
# xla_runtime pseudo-component (front c)
# =============================================================================
# Declared spec, cast/validated by launch/tuning exactly like a registered
# component's (the `optimizer` pseudo-component pattern).  GPU flags are
# declared so a GPU deployment tunes the same surface, but emit only when
# enabled — XLA accepts them as inert no-ops on CPU.
XLA_RUNTIME_SPACE = TunableSpace([
    Int("host_device_count", 8, 1, 512, log=True,
        description="--xla_force_host_platform_device_count: CPU host devices"),
    Int("intra_op_threads", 0, 0, 64,
        description="intra_op_parallelism_threads: XLA:CPU intra-op pool (0 = default)"),
    Bool("eigen_multithread", True,
         description="--xla_cpu_multi_thread_eigen: multithreaded Eigen contractions"),
    Bool("gpu_triton_gemm_any", False,
         description="--xla_gpu_triton_gemm_any: Triton for all GEMMs (inert on CPU)"),
    Bool("gpu_latency_hiding_scheduler", False,
         description="--xla_gpu_enable_latency_hiding_scheduler (inert on CPU)"),
])

_BOOL = {True: "true", False: "false"}


def xla_flags_string(settings: Optional[Mapping[str, Any]] = None) -> str:
    """Assemble the XLA_FLAGS token string for a (partial) settings dict;
    unset keys fall back to the declared defaults.  Pure string work — no
    jax, callable before any backend exists."""
    known = {k: v for k, v in dict(settings or {}).items() if k in XLA_RUNTIME_SPACE}
    s = XLA_RUNTIME_SPACE.validate(known)  # stale stored keys degrade, not crash
    toks: List[str] = [
        f"--xla_force_host_platform_device_count={s['host_device_count']}",
        f"--xla_cpu_multi_thread_eigen={_BOOL[s['eigen_multithread']]}",
    ]
    if s["intra_op_threads"] > 0:
        # tsl-parsed bare token (no -- prefix), the documented jax CPU idiom.
        toks.append(f"intra_op_parallelism_threads={s['intra_op_threads']}")
    if s["gpu_triton_gemm_any"]:
        toks.append("--xla_gpu_triton_gemm_any=true")
    if s["gpu_latency_hiding_scheduler"]:
        toks.append("--xla_gpu_enable_latency_hiding_scheduler=true")
    return " ".join(toks)


def _parse_flags(flags: Optional[str]) -> Dict[str, str]:
    """Token string → {flag-name: full token}, order-preserving."""
    out: Dict[str, str] = {}
    for tok in (flags or "").split():
        out[tok.split("=", 1)[0]] = tok
    return out


def merge_xla_flags(existing: Optional[str], new: str) -> str:
    """Merge flag strings by flag name: tokens in ``new`` replace same-named
    tokens in ``existing``; every other user-set token survives.  This is the
    ONLY sanctioned way to combine XLA_FLAGS — plain assignment clobbers
    whatever the user (or another component) already pinned."""
    toks = _parse_flags(existing)
    toks.update(_parse_flags(new))
    return " ".join(toks.values())


def apply_to_env(settings: Optional[Mapping[str, Any]] = None,
                 env: Optional[MutableMapping[str, str]] = None) -> str:
    """Merge the settings' flags into ``env`` (default ``os.environ``) and
    return the resulting flag string.  Against ``os.environ`` this only
    matters BEFORE the backend initializes — after that, use :func:`child_env`
    and re-exec."""
    env = os.environ if env is None else env
    flags = merge_xla_flags(env.get("XLA_FLAGS"), xla_flags_string(settings))
    env["XLA_FLAGS"] = flags
    return flags


def child_env(settings: Optional[Mapping[str, Any]] = None,
              base: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """Environment for a child re-exec carrying the tuned (or given)
    ``xla_runtime`` settings — the component's apply path, since XLA_FLAGS is
    only read at process startup."""
    out = dict(os.environ if base is None else base)
    apply_to_env(settings if settings is not None else resolve_xla_settings(), out)
    return out


def force_host_device_count(n: int, env: Optional[MutableMapping[str, str]] = None) -> str:
    """Pin ``--xla_force_host_platform_device_count`` to ``n``, preserving
    every other user-set flag (dryrun needs 512 placeholder devices to build
    production meshes; the merge keeps the rest of the operator's string)."""
    env = os.environ if env is None else env
    flags = merge_xla_flags(env.get("XLA_FLAGS"),
                            f"--xla_force_host_platform_device_count={int(n)}")
    env["XLA_FLAGS"] = flags
    return flags


def ensure_host_device_count(n: int, env: Optional[MutableMapping[str, str]] = None) -> str:
    """Set the host-device-count flag only when absent — setdefault semantics
    for benchmarks that want the test.sh device layout without overriding an
    operator's explicit choice."""
    env = os.environ if env is None else env
    if "--xla_force_host_platform_device_count" in _parse_flags(env.get("XLA_FLAGS")):
        return env.get("XLA_FLAGS", "")
    return force_host_device_count(n, env)


# -- ConfigStore integration ---------------------------------------------------
def resolve_xla_settings() -> Dict[str, Any]:
    """The xla_runtime settings for THIS hardware/software: declared defaults
    overlaid by the stored (promoted) entry and any in-process override —
    the same fallback chain every smart component resolves through.  Keyed
    by hardware fingerprint via the component-wide ``"*"`` workload: flags
    are per-host, not per-shape."""
    return dict(resolve_settings(COMPONENT, WILDCARD,
                                 defaults=XLA_RUNTIME_SPACE.defaults()))


def set_xla_override(kv: Mapping[str, Any]) -> None:
    """In-process override tier for ``xla_runtime.key=value`` CLI sets: lands
    in the store's override tier (outranks promoted entries, never persists).
    Takes effect in children built via :func:`child_env`."""
    default_store().set_override(COMPONENT, WILDCARD, dict(kv))


def promote_xla_settings(settings: Mapping[str, Any], *,
                         baseline: Optional[List[float]] = None,
                         samples: Optional[List[float]] = None,
                         mode: str = "min",
                         provenance: Optional[Dict[str, Any]] = None,
                         store: Any = None,
                         context: Optional[Context] = None) -> bool:
    """Validated write of tuned flags under a hardware-fingerprint context
    (default: this process's): the entry persists only if the
    ``stats.compare`` gate doesn't call it a significant regression vs
    ``baseline`` (the normal ``ConfigStore.promote`` machinery; verdict
    recorded in provenance).  A launcher that measured in children, and
    starts no backend itself, passes the children's ``context``."""
    store = store if store is not None else default_store()
    kv = XLA_RUNTIME_SPACE.validate(dict(settings))
    return store.promote(context or context_for(COMPONENT), kv, baseline=baseline,
                         samples=samples, mode=mode, provenance=dict(provenance or {}))
