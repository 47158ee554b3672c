"""Context-keyed store of optimized configurations — tuned settings that
*survive* the process and are resolved per instance, per workload.

The paper's central complaint about classical SPE is the fragility of
one-size-fits-all tuning; its promise is *continuous, instance-level,
trackable* optimization.  This module is the persistence half of that loop
(Fig. 2: tune → validate → persist → redeploy):

  * A :class:`Context` keys a tuned configuration by its full experimental
    coordinates — ``component × workload signature × hardware fingerprint ×
    software version`` (the Collective-Mind stance: tuned results are only
    meaningful together with the context they were measured in).
  * :class:`ConfigStore` persists one JSON file per component under
    ``results/configstore/`` and resolves lookups through a *fallback chain*:
    exact context → partial match (same workload, relaxed hw/sw; then a
    component-wide ``"*"`` workload) → ``None`` (the caller's global-default
    tier — the legacy singleton ``settings`` dict — takes over).
  * :func:`resolve_settings` is the per-call hot path used by every smart
    component's ``settings_for``: an ``lru_cache`` keyed on (store identity,
    store generation, context) so a kernel dispatching on its call shape pays
    a dict lookup, not a file read — and the same workload signature always
    resolves to the same settings object, so jit tracing stays stable.
  * :meth:`ConfigStore.promote` is the *validated* write path: a config only
    enters the store if it passes its RPI envelope (``rpi.check``), and every
    entry records provenance (run id, budget, best objective, timestamp).

Overrides (``launch/tuning.py``'s ``component@workload.key=value``) live in
an in-process tier that outranks stored entries but never persists — the
operator's hand on the dial for one launch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import json
import math
import os
import re
import sys
import tempfile
import time

try:
    import fcntl
except ImportError:  # non-POSIX: writers fall back to atomic-rename only
    fcntl = None  # type: ignore[assignment]
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "Context", "ConfigStore", "bucket_pow2", "context_for",
    "hardware_fingerprint", "sw_fingerprint", "workload_distance",
    "default_store", "set_default_store", "resolve_settings", "invalidate_cache",
]

WILDCARD = "*"


def bucket_pow2(n: int) -> int:
    """Round up to a power of two (floor 1) — workload-signature bucketing.

    Call shapes bucket so that e.g. ``s=500`` and ``s=512`` share one tuned
    entry while ``s=512`` and ``s=4096`` do not; mirrors the optimizer
    engine's power-of-two history buckets (no per-shape cache explosion).
    """
    return 1 << max(0, (int(n) - 1).bit_length())


@functools.lru_cache(maxsize=1)
def hardware_fingerprint() -> str:
    """Backend + device kind + device count of this process's accelerator.

    Starts the JAX backend.  A failed device lookup raises: a made-up
    fingerprint would file tuned entries under hardware that did not run
    them."""
    import jax

    dev = jax.devices()[0]
    kind = str(dev.device_kind).replace(" ", "_")
    return f"{jax.default_backend()}:{kind}:x{jax.device_count()}"


@functools.lru_cache(maxsize=1)
def sw_fingerprint() -> str:
    """Library + interpreter versions the tuned config was produced under."""
    try:
        import jax

        jv = jax.__version__
    except Exception:  # noqa: BLE001
        jv = "none"
    return f"jax-{jv}/py-{sys.version_info.major}.{sys.version_info.minor}"


@dataclasses.dataclass(frozen=True)
class Context:
    """Full coordinates of one tuned configuration."""

    component: str
    workload: str = WILDCARD
    hardware: str = WILDCARD
    sw: str = WILDCARD

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, str]) -> "Context":
        return cls(**d)


def context_for(component: str, workload: str = WILDCARD) -> Context:
    """A concrete Context for *this* process's hardware/software."""
    return Context(component, workload, hardware_fingerprint(), sw_fingerprint())


def _match_rank(entry_ctx: Dict[str, str], query: Context) -> Optional[Tuple[int, int, int]]:
    """Specificity of an entry for a query, or None if incompatible.

    The workload must match exactly, or the entry must be component-wide
    (``"*"``).  The reverse does NOT hold: a ``"*"`` *query* (a caller with
    no workload information) never picks up a shape-specific entry — that
    would re-apply one workload's tune everywhere, exactly the
    one-size-fits-all failure this store exists to eliminate.  Hardware and
    software matches add rank but never disqualify — a config tuned under an
    older jax on the same workload beats the global default (the
    SPE-in-DevOps cross-release reuse).  Rank orders workload > hardware > sw.
    """
    wl = entry_ctx.get("workload", WILDCARD)
    if wl != query.workload and wl != WILDCARD:
        return None
    return (
        int(wl == query.workload),
        int(entry_ctx.get("hardware", WILDCARD) == query.hardware),
        int(entry_ctx.get("sw", WILDCARD) == query.sw),
    )


_SIG_FIELD = re.compile(r"([a-zA-Z_]+?)(\d+)")
_SIG_SHAPE = re.compile(r"(?:[a-zA-Z_]+\d+)+")


def _sig_fields(workload: str) -> Dict[str, int]:
    """Numeric fields of a bucketed workload signature.

    ``b2q512k512d64`` → ``{b: 2, q: 512, k: 512, d: 64}``;
    ``olmo_c256`` → ``{olmo_c: 256}``.  Only strings that are ENTIRELY
    (name, number) pairs parse: a signature with stray separators (e.g.
    ``olmo-1b_c256``, where the ``1`` is a model size, not a shape bucket)
    parses empty rather than risk reading name digits as shape fields —
    mis-parsing here would let :func:`workload_distance` call two different
    families near neighbors.  Wildcards parse empty too.
    """
    if workload == WILDCARD or _SIG_SHAPE.fullmatch(workload) is None:
        return {}
    return {m.group(1): int(m.group(2)) for m in _SIG_FIELD.finditer(workload)}


def workload_distance(a: str, b: str) -> float:
    """How far apart two workload signatures are, in bucket steps.

    0.0 for identical signatures; for two signatures of the same *family*
    (identical field names, e.g. two flash_attention shape buckets) the
    distance is the summed |log2| gap of their numeric fields — one bucket
    step per unit, mirroring the power-of-two bucketing that produced them.
    Different families (or unparseable signatures) are infinitely far: a
    serve-capacity tune must never warm-start an attention kernel.
    """
    if a == b:
        return 0.0
    fa, fb = _sig_fields(a), _sig_fields(b)
    if not fa or not fb or set(fa) != set(fb):
        return math.inf
    return sum(abs(math.log2(max(fa[k], 1)) - math.log2(max(fb[k], 1))) for k in fa)


_STORE_TOKENS = itertools.count(1)


class ConfigStore:
    """Persistent, context-keyed store of optimized configurations.

    Layout: ``<root>/<component>.json`` holding ``{"component": ...,
    "entries": [{"context": {...}, "settings": {...}, "provenance": {...}}]}``.
    Writes are atomic (tmp file + rename) so a concurrent reader never sees a
    torn file.  ``generation`` bumps on every in-process mutation and is part
    of the resolver cache key; cross-process writes are picked up after
    :meth:`invalidate` (or by a fresh process, whose cache starts cold).
    """

    def __init__(self, root: str = "results/configstore"):
        self.root = Path(root)
        self.token = next(_STORE_TOKENS)  # distinguishes stores in the resolver cache
        self.generation = 0
        self._cache: Dict[str, List[Dict[str, Any]]] = {}
        self._overrides: Dict[Tuple[str, str], Dict[str, Any]] = {}

    # -- file layer -----------------------------------------------------------
    def _path(self, component: str) -> Path:
        return self.root / f"{component}.json"

    def _entries(self, component: str) -> List[Dict[str, Any]]:
        if component not in self._cache:
            p = self._path(component)
            entries: List[Dict[str, Any]] = []
            if p.exists():
                # Fail soft on a corrupted/truncated file: resolution is a
                # best-effort optimization layer — a bad store file must
                # degrade to the global-default tier, not take the host down.
                try:
                    doc = json.loads(p.read_text())
                    entries = doc.get("entries", []) if isinstance(doc, dict) else []
                except (json.JSONDecodeError, OSError) as e:
                    print(f"[configstore] ignoring unreadable {p}: {e}")
            self._cache[component] = entries
        return self._cache[component]

    def _write(self, component: str, entries: List[Dict[str, Any]]) -> None:
        self.root.mkdir(parents=True, exist_ok=True)
        doc = json.dumps({"component": component, "entries": entries}, indent=1)
        fd, tmp = tempfile.mkstemp(dir=self.root, prefix=f".{component}.")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(doc)
            os.replace(tmp, self._path(component))
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise
        self._cache[component] = entries
        self.generation += 1

    def invalidate(self, component: Optional[str] = None) -> None:
        """Drop the in-memory entry cache (picks up other processes' writes)."""
        if component is None:
            self._cache.clear()
        else:
            self._cache.pop(component, None)
        self.generation += 1

    # -- write paths ----------------------------------------------------------
    def put(self, context: Context, settings: Dict[str, Any],
            provenance: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Unconditional write; replaces the entry with the identical context.

        The read-modify-write runs under an exclusive file lock with the
        on-disk entries re-read inside it — two processes promoting into the
        same component file (an agent host and a perf.hillclimb, say) merge
        instead of silently deleting each other's entries.
        """
        prov = dict(provenance or {})
        prov.setdefault("updated", time.time())
        entry = {"context": context.to_dict(), "settings": dict(settings), "provenance": prov}
        ctx_d = context.to_dict()
        self.root.mkdir(parents=True, exist_ok=True)
        with contextlib.ExitStack() as stack:
            if fcntl is not None:
                lf = stack.enter_context(open(self.root / f".{context.component}.lock", "w"))
                fcntl.flock(lf, fcntl.LOCK_EX)
            self._cache.pop(context.component, None)  # re-read disk under the lock
            entries = [e for e in self._entries(context.component) if e["context"] != ctx_d]
            entries.append(entry)
            self._write(context.component, entries)
        return entry

    def promote(self, context: Context, settings: Dict[str, Any], *,
                rpi: Any = None, metrics: Optional[Dict[str, float]] = None,
                baseline: Optional[List[float]] = None,
                samples: Optional[List[float]] = None,
                mode: str = "min", tolerance: float = 0.05, alpha: float = 0.05,
                provenance: Optional[Dict[str, Any]] = None) -> bool:
        """Validated write: the config enters the store only if it passes the
        gates (the paper's tune → VALIDATE → persist loop).  Two gates, both
        optional and composable:

          * ``rpi`` + ``metrics`` — static envelope check (absolute bounds);
          * ``baseline`` + ``samples`` — the noise-aware A/B comparator
            (:func:`repro.core.stats.compare`): rejected only when the new
            config's samples are a *statistically significant* regression
            beyond ``tolerance`` versus the baseline distribution — a raw
            threshold can be tripped by jitter, the comparator cannot.
            Samples too few for the test to reach ``alpha`` (a singleton
            measurement) never reject — pass real distributions to gate.

        Returns True on promotion; on rejection the store is left untouched
        and False is returned for the caller to record.  The comparator
        verdict is recorded in provenance either way a write happens.
        """
        if rpi is not None:
            report = rpi.check(metrics or {})
            if not report:
                return False
        prov = dict(provenance or {})
        if baseline is not None and samples is not None:
            from . import stats  # local: stats imports nothing from here

            cmp = stats.compare(baseline, samples, alpha=alpha,
                                min_effect=tolerance, mode=mode)
            verdict = cmp.verdict
            if verdict != "noise" and cmp.p_value is None:
                verdict = "insufficient_data"  # evidence-free shift: no veto
            elif verdict == "regressed":
                return False
            prov.setdefault("gate", {"verdict": verdict,
                                     "effect": cmp.effect,
                                     "p_value": cmp.p_value})
        self.put(context, settings, prov)
        return True

    # -- read paths -----------------------------------------------------------
    def resolve_entry(self, query: Context) -> Optional[Dict[str, Any]]:
        """Best-matching entry via the fallback chain, or None (global tier)."""
        best: Optional[Dict[str, Any]] = None
        best_key: Tuple = ()
        for e in self._entries(query.component):
            rank = _match_rank(e["context"], query)
            if rank is None:
                continue
            key = (*rank, e.get("provenance", {}).get("updated", 0.0))
            if best is None or key > best_key:
                best, best_key = e, key
        return best

    def resolve(self, query: Context) -> Optional[Dict[str, Any]]:
        e = self.resolve_entry(query)
        return dict(e["settings"]) if e is not None else None

    def nearest_entry(self, query: Context, *,
                      max_distance: float = math.inf,
                      ) -> Optional[Tuple[Dict[str, Any], float]]:
        """Best warm-start source for a context: ``(entry, workload_distance)``.

        The cross-context transfer query (campaigns seed a new cell's
        optimizer from it — see :mod:`repro.core.campaign`).  The normal
        fallback chain runs first: an entry it resolves (exact workload, or
        a component-wide ``"*"``) is *the* answer at distance 0.  Only when
        the chain misses does the workload constraint relax: among all of the
        component's entries, the one whose signature is the fewest bucket
        steps away (:func:`workload_distance`) wins, hardware/software match
        and recency breaking ties.  Different signature families never match,
        so there is no cross-kernel contamination.  Returns None when nothing
        is within ``max_distance`` — the caller cold-starts.
        """
        hit = self.resolve_entry(query)
        if hit is not None:
            return hit, 0.0
        best: Optional[Dict[str, Any]] = None
        best_key: Tuple = ()
        best_dist = math.inf
        for e in self._entries(query.component):
            ctx = e["context"]
            dist = workload_distance(ctx.get("workload", WILDCARD), query.workload)
            if not math.isfinite(dist) or dist > max_distance:
                continue
            key = (-dist,
                   int(ctx.get("hardware", WILDCARD) == query.hardware),
                   int(ctx.get("sw", WILDCARD) == query.sw),
                   e.get("provenance", {}).get("updated", 0.0))
            if best is None or key > best_key:
                best, best_key, best_dist = e, key, dist
        return (best, best_dist) if best is not None else None

    # -- in-process override tier ---------------------------------------------
    def set_override(self, component: str, workload: str, kv: Dict[str, Any]) -> None:
        self._overrides.setdefault((component, workload), {}).update(kv)
        self.generation += 1

    def get_override(self, component: str, workload: str) -> Optional[Dict[str, Any]]:
        ov = self._overrides.get((component, workload))
        return dict(ov) if ov is not None else None

    def clear_override(self, component: str, workload: str) -> None:
        if self._overrides.pop((component, workload), None) is not None:
            self.generation += 1

    def contexts(self) -> List[Tuple[str, str]]:
        """(component, workload) pairs with any stored or overridden state —
        scoped to this hardware/software where stored entries say so."""
        out: List[Tuple[str, str]] = []
        if self.root.exists():
            for p in sorted(self.root.glob("*.json")):
                comp = p.stem
                for e in self._entries(comp):
                    pair = (comp, e["context"].get("workload", WILDCARD))
                    if pair not in out:
                        out.append(pair)
        for pair in self._overrides:
            if pair not in out:
                out.append(pair)
        return out


# -- process-default store + cached resolver (the per-call hot path) ----------
_DEFAULT: Optional[ConfigStore] = None


def default_store() -> ConfigStore:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = ConfigStore()
    return _DEFAULT


def set_default_store(store: Optional[ConfigStore]) -> Optional[ConfigStore]:
    """Swap the process-default store (tests / embedding); returns the old one."""
    global _DEFAULT
    old, _DEFAULT = _DEFAULT, store
    _cached_lookup.cache_clear()
    return old


def invalidate_cache() -> None:
    """Drop resolver + store caches — call after another process wrote."""
    if _DEFAULT is not None:
        _DEFAULT.invalidate()
    _cached_lookup.cache_clear()


@functools.lru_cache(maxsize=4096)
def _cached_lookup(token: int, generation: int, component: str, workload: str,
                   hardware: str, sw: str,
                   ) -> Optional[Tuple[Tuple[Tuple[str, Any], ...], Tuple[Tuple[str, Any], ...]]]:
    """The memoized store lookup: (stored-entry items, override items).
    Keyed on (store token, generation) so any write/override/invalidate
    naturally misses; returns hashable item tuples (never the mutable entry)
    so cache hits can't be corrupted by callers.  The two tiers stay separate
    because explicit global settings slot *between* them (see
    :func:`resolve_settings`)."""
    store = default_store()
    entry = store.resolve(Context(component, workload, hardware, sw))
    override = store.get_override(component, workload)
    if entry is None and override is None:
        return None
    return (tuple((entry or {}).items()), tuple((override or {}).items()))


def resolve_settings(component: str, workload: str = WILDCARD,
                     defaults: Optional[Dict[str, Any]] = None,
                     explicit: Optional[Any] = None,
                     hardware: Optional[str] = None,
                     sw: Optional[str] = None) -> Dict[str, Any]:
    """Resolve the settings for a (component, workload) context — on this
    process's hardware/software unless ``hardware``/``sw`` pin other
    coordinates.  Tiers, strongest first:

      1. in-process context override (``component@workload.key=value``)
      2. keys in ``explicit`` — settings the operator/agent set on the global
         singleton *this process* (constructor kwargs, ``apply_settings``);
         a live human/agent decision outranks persisted tuning
      3. stored entry (fallback chain: exact → partial → component-wide)
      4. ``defaults`` — the caller's live global-singleton settings

    When nothing context-specific exists, ``defaults`` is returned *unmerged
    and uncopied* — the legacy global path stays zero-overhead and fully
    live."""
    store = default_store()
    res = _cached_lookup(store.token, store.generation, component, workload,
                         hardware or hardware_fingerprint(), sw or sw_fingerprint())
    if res is None:
        return defaults if defaults is not None else {}
    entry_items, override_items = res
    merged = dict(defaults) if defaults else {}
    merged.update(entry_items)
    if explicit and defaults:
        for k in explicit:
            if k in defaults:
                merged[k] = defaults[k]
    merged.update(override_items)
    return merged
