"""The invariant rules MLOS001–MLOS008 (see docs/INVARIANTS.md).

Each rule encodes one "rule for future PRs" from the ROADMAP DESIGN notes
as an AST check.  Rules are static approximations by design: they resolve
import aliases, follow one level of local dataflow (variable taint,
module-local call sites), and stop there — anything subtler goes through
the documented escape hatch (``# mloslint: disable=...`` with a
justification) rather than growing the checker into a type system.

Two-phase protocol: ``check(mod, index)`` runs per module and may record
facts on the shared :class:`RepoIndex`; ``finalize(index)`` runs once after
every module has been seen, for cross-module checks (dead tunables).
"""
from __future__ import annotations

import ast
import dataclasses
import re
from collections import Counter
from typing import Any, Dict, List, Optional, Set, Tuple

from .findings import Finding
from .parsing import (
    ParsedModule,
    const_str,
    dotted_name,
    import_map,
    resolve_call_target,
    walk_with_parents,
)

__all__ = ["Rule", "RepoIndex", "ALL_RULES", "RULES_BY_ID"]


# =============================================================================
# Shared repo-wide facts
# =============================================================================
@dataclasses.dataclass
class TunableDecl:
    name: str
    line: int
    # literal params when statically evaluable; None otherwise
    kind: str = ""
    default: Any = None
    low: Any = None
    high: Any = None
    log: Any = None
    choices: Any = None
    evaluable: bool = False


@dataclasses.dataclass
class ComponentDecl:
    name: str
    rel: str
    line: int
    tunables: Dict[str, TunableDecl]


@dataclasses.dataclass
class SettingsRead:
    singleton: str            # variable name the settings dict came from
    key: str
    rel: str
    line: int
    col: int
    snippet: str


class RepoIndex:
    """Facts accumulated across modules for finalize-stage checks."""

    def __init__(self) -> None:
        self.components: Dict[str, ComponentDecl] = {}
        self.singletons: Dict[str, str] = {}        # module-level var -> component
        self.reads: List[SettingsRead] = []
        self.str_counter: Counter = Counter()       # every string constant in the repo
        self.decl_str_counts: Counter = Counter()   # strings inside tunable declarations


class Rule:
    id: str = ""
    name: str = ""

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        return []

    def finalize(self, index: RepoIndex) -> List[Finding]:
        return []

    def _f(self, mod: ParsedModule, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        snippet = mod.lines[line - 1].strip() if 0 < line <= len(mod.lines) else ""
        return Finding(rule=self.id, path=mod.rel, line=line,
                       col=getattr(node, "col_offset", 0), message=message,
                       snippet=snippet)


def _in(rel: str, *prefixes: str) -> bool:
    return any(rel == p or rel.startswith(p.rstrip("/") + "/") for p in prefixes)


# =============================================================================
# MLOS001 — compat-bypass
# =============================================================================
class CompatBypass(Rule):
    """Drifted JAX APIs (shard_map, AbstractMesh, axis_types=) are absorbed by
    repro/compat.py; probing them anywhere else re-creates the per-call-site
    version sniffing the compat layer exists to kill."""

    id = "MLOS001"
    name = "compat-bypass"

    EXEMPT = ("src/repro/compat.py",)
    DRIFTED = ("jax.experimental.shard_map", "jax.sharding.AbstractMesh", "jax.shard_map")

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        if _in(mod.rel, *self.EXEMPT):
            return []
        out: List[Finding] = []
        imports = import_map(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith("jax.experimental.shard_map"):
                        out.append(self._f(mod, node,
                                   f"import of drifted API {a.name!r}: route through repro.compat.shard_map"))
            elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
                for a in node.names:
                    full = f"{node.module}.{a.name}"
                    if node.module.startswith("jax.experimental.shard_map") or full in self.DRIFTED:
                        out.append(self._f(mod, node,
                                   f"import of drifted API {full!r}: route through repro.compat"))
            elif isinstance(node, ast.Attribute):
                full = dotted_name(node)
                if full:
                    resolved = self._resolve(full, imports)
                    if any(resolved == d or resolved.startswith(d + ".") for d in self.DRIFTED):
                        out.append(self._f(mod, node,
                                   f"use of drifted API {resolved!r}: route through repro.compat"))
            elif isinstance(node, ast.Call):
                target = resolve_call_target(node, imports) or ""
                if target.endswith(("make_mesh", "Mesh")) and "compat" not in target:
                    if any(kw.arg == "axis_types" for kw in node.keywords):
                        out.append(self._f(mod, node,
                                   "axis_types= kwarg drifted across JAX versions: "
                                   "build meshes through repro.compat.make_mesh"))
        return out

    @staticmethod
    def _resolve(full: str, imports: Dict[str, str]) -> str:
        head, _, rest = full.partition(".")
        origin = imports.get(head)
        if origin and origin != head:
            return f"{origin}.{rest}" if rest else origin
        return full


# =============================================================================
# MLOS002 — singleton-settings
# =============================================================================
class SingletonSettings(Rule):
    """Per-workload behavior resolves through ``settings_for`` / the config
    store; reaching into another object's live ``.settings`` dict (or adding a
    new module-level mutable config dict) reintroduces the one-size-fits-all
    global tier that PR 3 removed.  ``self.settings`` inside a component is
    the sanctioned hooked-constants surface and stays legal."""

    id = "MLOS002"
    name = "singleton-settings"

    SCOPE = ("src", "benchmarks", "examples")
    EXEMPT = ("src/repro/core/configstore.py", "src/repro/core/registry.py",
              "src/repro/core/config.py")
    _CONFIG_NAME = re.compile(r"(^|_)(settings|config)$")

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        if not _in(mod.rel, *self.SCOPE) or _in(mod.rel, *self.EXEMPT):
            return []
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and node.attr == "settings":
                base = node.value
                if isinstance(base, ast.Name) and base.id in ("self", "cls"):
                    continue
                out.append(self._f(mod, node,
                           "direct read/write of a settings singleton: resolve per-workload "
                           "via .settings_for(workload) (see configstore DESIGN note)"))
        for stmt in mod.tree.body:  # module level only: the singleton tier
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                name = stmt.targets[0].id
                if not self._CONFIG_NAME.search(name) or name.isupper():
                    continue
                v = stmt.value
                is_mut = isinstance(v, (ast.Dict, ast.List, ast.Set)) or (
                    isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                    and v.func.id in ("dict", "list", "set"))
                if is_mut:
                    out.append(self._f(mod, stmt,
                               f"module-level mutable config singleton {name!r}: use a "
                               "@tunable_component + context-keyed settings_for instead"))
        return out


# =============================================================================
# MLOS003 — bare-perf-claim
# =============================================================================
_TIMING_KEY = re.compile(r"time|latency|throughput|duration|wall|(^|_)(us|ns|ms|s)$")
_TIMING_CALLS = {"time.time", "time.perf_counter", "time.monotonic", "time.process_time"}
_AGGREGATORS = {"min", "max", "sorted", "numpy.median", "numpy.argmin", "numpy.argmax",
                "statistics.median", "numpy.min", "numpy.max"}


class BarePerfClaim(Rule):
    """All perf claims go through ``core.stats`` — that is the rule (ROADMAP,
    stats DESIGN note).  A benchmark either registers a ``bench(quick, seed)``
    entry (raw samples; the runner's gate applies the statistics) or applies
    ``core.stats`` itself; outside those, raw wall-clock deltas and bare
    min/median aggregation over timing metrics are unsupported claims."""

    id = "MLOS003"
    name = "bare-perf-claim"

    SCOPE = ("benchmarks", "tests")

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        if not _in(mod.rel, *self.SCOPE):
            return []
        imports = import_map(mod.tree)
        if any(v == "repro.core.stats" or v.startswith("repro.core.stats.")
               for v in imports.values()):
            return []  # stats-routed module: claims assumed gated (spot-checked in review)
        if any(isinstance(n, ast.FunctionDef) and n.name == "bench" for n in mod.tree.body):
            return []  # registered benchmark: raw samples feed the runner's stats gate
        out: List[Finding] = []
        tainted = self._taint(mod.tree, imports)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve_call_target(node, imports) or ""
            if target in _TIMING_CALLS and _in(mod.rel, "benchmarks"):
                out.append(self._f(mod, node,
                           f"raw {target}() timing in a benchmark: sample via "
                           "launch.microbench.time_samples_us and claim via core.stats.compare"))
            elif target in _AGGREGATORS:
                args = list(node.args) + [kw.value for kw in node.keywords]
                if any(self._is_timing_expr(a, tainted, imports) for a in args):
                    out.append(self._f(mod, node,
                               f"bare {target.split('.')[-1]}() over timing samples: aggregate "
                               "with core.stats (median/compare) so the claim carries a verdict"))
        return out

    # -- timing-taint dataflow ------------------------------------------------
    def _is_timing_expr(self, node: ast.AST, tainted: Set[str],
                        imports: Dict[str, str]) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Subscript):
                key = const_str(sub.slice)
                if key and _TIMING_KEY.search(key):
                    return True
            elif isinstance(sub, ast.Call):
                if (resolve_call_target(sub, imports) or "") in _TIMING_CALLS:
                    return True
            elif isinstance(sub, ast.Name) and sub.id in tainted:
                return True
        return False

    def _taint(self, tree: ast.Module, imports: Dict[str, str]) -> Set[str]:
        tainted: Set[str] = set()
        for _ in range(4):  # small fixpoint: taint flows through a few hops
            grew = False
            for node in ast.walk(tree):
                src = None
                dsts: List[str] = []
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    src = node.value
                    targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                    dsts = [t.id for t in targets if isinstance(t, ast.Name)]
                elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("append", "extend", "insert")
                        and isinstance(node.func.value, ast.Name) and node.args):
                    src = node.args[-1]
                    dsts = [node.func.value.id]
                if src is None or not dsts:
                    continue
                if self._is_timing_expr(src, tainted, imports):
                    for d in dsts:
                        if d not in tainted:
                            tainted.add(d)
                            grew = True
            if not grew:
                break
        return tainted


# =============================================================================
# MLOS004 — fork-hazard
# =============================================================================
class ForkHazard(Rule):
    """Any process in this repo may hold a multithreaded JAX runtime;
    ``os.fork`` clones its locks into a latent deadlock.  Subprocesses are
    spawn-only (agent DESIGN note): multiprocessing always goes through
    ``get_context("spawn")``."""

    id = "MLOS004"
    name = "fork-hazard"

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        out: List[Finding] = []
        imports = import_map(mod.tree)
        func_defaults: Dict[Tuple[str, str], Optional[str]] = {}
        for node, parents in walk_with_parents(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve_call_target(node, imports) or ""
            if target == "os.fork":
                out.append(self._f(mod, node,
                           "os.fork() in a repo that holds JAX runtimes: use the spawn "
                           "multiprocessing context instead"))
            elif target in ("multiprocessing.Process", "multiprocessing.Pool"):
                out.append(self._f(mod, node,
                           f"bare {target}(): defaults to fork on Linux — create through "
                           'multiprocessing.get_context("spawn")'))
            elif target.endswith("get_context") and target.startswith("multiprocessing"):
                out.extend(self._check_ctx_arg(mod, node, parents))
            elif target == "multiprocessing.set_start_method":
                lit = const_str(node.args[0]) if node.args else None
                if lit != "spawn":
                    out.append(self._f(mod, node,
                               'set_start_method must pin "spawn" (JAX-runtime fork hazard)'))
        return out

    def _check_ctx_arg(self, mod: ParsedModule, node: ast.Call,
                       parents: List[ast.AST]) -> List[Finding]:
        arg = node.args[0] if node.args else None
        if arg is None:
            return [self._f(mod, node,
                    'get_context() without "spawn": the platform default is fork on Linux')]
        lit = const_str(arg)
        if lit == "spawn":
            return []
        if lit is not None:
            return [self._f(mod, node,
                    f'get_context({lit!r}): only the "spawn" context is fork-safe here')]
        # Variable argument: accept when it is an enclosing-function parameter
        # whose default is the literal "spawn" — the one sanctioned indirection
        # (AgentProcess(mp_context="spawn")).
        if isinstance(arg, ast.Name):
            for p in reversed(parents):
                if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if self._param_default(p, arg.id) == "spawn":
                        return []
                    break
        return [self._f(mod, node,
                "get_context() argument is not statically 'spawn': pin the spawn "
                "context (or parameter-default it to 'spawn')")]

    @staticmethod
    def _param_default(fn: ast.FunctionDef, name: str) -> Optional[str]:
        args = fn.args
        pos = args.posonlyargs + args.args
        defaults = args.defaults
        offset = len(pos) - len(defaults)
        for i, a in enumerate(pos):
            if a.arg == name and i >= offset:
                return const_str(defaults[i - offset])
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if a.arg == name and d is not None:
                return const_str(d)
        return None


# =============================================================================
# MLOS005 — rejit-hazard
# =============================================================================
_ARRAY_CTORS = ("zeros", "ones", "empty", "full")
_X64_CTORS = ("array", "asarray", "zeros", "ones", "full", "eye", "arange", "linspace")


class RejitHazard(Rule):
    """Engine DESIGN rules: (1) history-dependent buffer shapes bucket at
    powers of two (``bucket_of``) — a ``len(history)``-sized array re-jits on
    every observation; (2) engine math is float64 under ``enable_x64``
    (``repro.compat``'s, or a module's own wrapper of it) — device arrays
    built outside the context silently downcast to f32."""

    id = "MLOS005"
    name = "rejit-hazard"

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        imports = import_map(mod.tree)
        uses_jax = any(v == "jax" or v.startswith("jax.") for v in imports.values())
        if not uses_jax:
            return []
        out: List[Finding] = []
        out.extend(self._check_len_shapes(mod, imports))
        if self._uses_x64(mod.tree):
            out.extend(self._check_x64(mod, imports))
        return out

    # -- (1) len()-derived shapes --------------------------------------------
    def _check_len_shapes(self, mod: ParsedModule, imports: Dict[str, str]) -> List[Finding]:
        out = []
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            target = resolve_call_target(node, imports) or ""
            tail = target.rsplit(".", 1)[-1]
            if tail not in _ARRAY_CTORS or not target.startswith(("numpy.", "jax.numpy.")):
                continue
            if self._has_unbucketed_len(node.args[0]):
                out.append(self._f(mod, node,
                           f"{tail}() shape derives from len(): bucket history-dependent "
                           "shapes at powers of two (bucket_of) or every observation re-jits"))
        return out

    @staticmethod
    def _has_unbucketed_len(shape_expr: ast.AST) -> bool:
        for node, parents in walk_with_parents(shape_expr):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id == "len"):
                covered = any(
                    isinstance(p, ast.Call)
                    and (dotted_name(p.func) or "").rsplit(".", 1)[-1] == "bucket_of"
                    for p in parents)
                if not covered:
                    return True
        return False

    # -- (2) x64 guard --------------------------------------------------------
    def _check_x64(self, mod: ParsedModule, imports: Dict[str, str]) -> List[Finding]:
        # jnp-constructor calls not lexically under `with enable_x64():`,
        # grouped by enclosing function; a function is excused when every
        # intra-module call site of it sits under the guard (one-hop check).
        offenders: Dict[Optional[str], List[ast.Call]] = {}
        callsites: Dict[str, List[bool]] = {}
        for node, parents in walk_with_parents(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            under = self._under_x64(parents)
            fname = self._called_name(node)
            if fname:
                callsites.setdefault(fname, []).append(under)
            target = resolve_call_target(node, imports) or ""
            tail = target.rsplit(".", 1)[-1]
            if not (target.startswith("jax.numpy.") and tail in _X64_CTORS):
                continue
            if under:
                continue
            fn = next((p for p in reversed(parents)
                       if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef))), None)
            offenders.setdefault(fn.name if fn else None, []).append(node)
        out = []
        for fname, calls in offenders.items():
            if fname is not None:
                sites = callsites.get(fname, [])
                if sites and all(sites):
                    continue  # only ever invoked under the guard
            for c in calls:
                out.append(self._f(mod, c,
                           "device-array construction outside `with enable_x64():` in an "
                           "x64-engine module: values silently downcast to f32"))
        return out

    @staticmethod
    def _uses_x64(tree: ast.Module) -> bool:
        """The module imports or defines an ``enable_x64`` (import style and
        relative imports aside — the name is the contract)."""
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and any(
                    a.name == "enable_x64" for a in node.names):
                return True
            if isinstance(node, ast.FunctionDef) and node.name == "enable_x64":
                return True
        return False

    @staticmethod
    def _under_x64(parents: List[ast.AST]) -> bool:
        for p in parents:
            if isinstance(p, ast.With):
                for item in p.items:
                    ce = item.context_expr
                    if (isinstance(ce, ast.Call)
                            and (dotted_name(ce.func) or "").rsplit(".", 1)[-1] == "enable_x64"):
                        return True
        return False

    @staticmethod
    def _called_name(node: ast.Call) -> Optional[str]:
        if isinstance(node.func, ast.Name):
            return node.func.id
        if isinstance(node.func, ast.Attribute) and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in ("self", "cls"):
            return node.func.attr
        return None


# =============================================================================
# MLOS006 — tunables-contract
# =============================================================================
_TUNABLE_CTORS = ("Int", "Float", "Categorical", "Bool", "Tunable")
# positional parameter order of each convenience constructor (core/tunable.py)
_CTOR_SIG = {
    "Int": ("name", "default", "low", "high", "log", "description"),
    "Float": ("name", "default", "low", "high", "log", "description"),
    "Categorical": ("name", "default", "choices", "description"),
    "Bool": ("name", "default", "description"),
    "Tunable": ("name", "kind", "default"),
}


class TunablesContract(Rule):
    """The ``@tunable_component`` declaration IS the contract: every settings
    key a component reads must be declared, every declared tunable must be
    consumed somewhere, and literal defaults must sit inside their declared
    domain — an out-of-domain default crashes the first ask."""

    id = "MLOS006"
    name = "tunables-contract"

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        out: List[Finding] = []
        class_to_component: Dict[str, str] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef):
                comp = self._component_decl(mod, node, index, out)
                if comp:
                    class_to_component[node.name] = comp
                    self._collect_self_reads(mod, node, comp, index)
        # module-level singletons: attention_settings = AttentionKernelSettings()
        for stmt in mod.tree.body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Call)
                    and isinstance(stmt.value.func, ast.Name)
                    and stmt.value.func.id in class_to_component):
                index.singletons[stmt.targets[0].id] = class_to_component[stmt.value.func.id]
        self._collect_reads(mod, index)
        for node in ast.walk(mod.tree):
            s = const_str(node)
            if s is not None:
                index.str_counter[s] += 1
        return out

    # -- declaration parsing --------------------------------------------------
    def _component_decl(self, mod: ParsedModule, cls: ast.ClassDef,
                        index: RepoIndex, out: List[Finding]) -> Optional[str]:
        deco = next((d for d in cls.decorator_list
                     if isinstance(d, ast.Call)
                     and (dotted_name(d.func) or "").rsplit(".", 1)[-1] == "tunable_component"),
                    None)
        if deco is None:
            return None
        comp_name = cls.name
        if deco.args and const_str(deco.args[0]):
            comp_name = const_str(deco.args[0])
        for kw in deco.keywords:
            if kw.arg == "name" and const_str(kw.value):
                comp_name = const_str(kw.value)
        tun_node = None
        if len(deco.args) > 1:
            tun_node = deco.args[1]
        for kw in deco.keywords:
            if kw.arg == "tunables":
                tun_node = kw.value
        tunables: Dict[str, TunableDecl] = {}
        if isinstance(tun_node, (ast.Tuple, ast.List)):
            for el in tun_node.elts:
                decl = self._parse_ctor(el)
                if decl is None:
                    continue
                tunables[decl.name] = decl
                for sub in ast.walk(el):
                    s = const_str(sub)
                    if s is not None:
                        index.decl_str_counts[s] += 1
                bad = self._domain_error(decl)
                if bad:
                    out.append(self._f(mod, el, bad))
        index.components[comp_name] = ComponentDecl(
            name=comp_name, rel=mod.rel, line=cls.lineno, tunables=tunables)
        return comp_name

    @staticmethod
    def _parse_ctor(el: ast.AST) -> Optional[TunableDecl]:
        if not isinstance(el, ast.Call):
            return None
        ctor = (dotted_name(el.func) or "").rsplit(".", 1)[-1]
        sig = _CTOR_SIG.get(ctor)
        if sig is None:
            return None
        params: Dict[str, ast.AST] = {}
        for i, a in enumerate(el.args):
            if i < len(sig):
                params[sig[i]] = a
        for kw in el.keywords:
            if kw.arg:
                params[kw.arg] = kw.value
        name = const_str(params.get("name", ast.Constant(value=None)))
        if not name:
            return None
        decl = TunableDecl(name=name, line=el.lineno, kind=ctor.lower())
        evaluable = True
        for field in ("default", "low", "high", "log", "choices"):
            node = params.get(field)
            if node is None:
                continue
            try:
                setattr(decl, field, ast.literal_eval(node))
            except (ValueError, SyntaxError):
                evaluable = False
        decl.evaluable = evaluable
        return decl

    @staticmethod
    def _domain_error(d: TunableDecl) -> Optional[str]:
        if not d.evaluable or d.default is None:
            return None
        if d.kind in ("int", "float") and d.low is not None and d.high is not None:
            if not (d.low <= d.default <= d.high):
                return (f"tunable {d.name!r}: default {d.default!r} outside declared "
                        f"domain [{d.low}, {d.high}]")
            if d.log and d.low <= 0:
                return f"tunable {d.name!r}: log scale requires low > 0 (got {d.low})"
        if d.kind == "categorical" and d.choices is not None:
            if d.default not in tuple(d.choices):
                return (f"tunable {d.name!r}: default {d.default!r} not in declared "
                        f"choices {tuple(d.choices)!r}")
        return None

    # -- read collection ------------------------------------------------------
    def _collect_self_reads(self, mod: ParsedModule, cls: ast.ClassDef,
                            comp: str, index: RepoIndex) -> None:
        for node in ast.walk(cls):
            if isinstance(node, ast.Subscript):
                key = const_str(node.slice)
                v = node.value
                if (key and isinstance(v, ast.Attribute) and v.attr == "settings"
                        and isinstance(v.value, ast.Name) and v.value.id == "self"):
                    index.reads.append(self._read(mod, node, f"@{comp}", key))

    def _collect_reads(self, mod: ParsedModule, index: RepoIndex) -> None:
        # v = <singleton>.settings_for(...) ; later v["key"] / v.get("key")
        var_src: Dict[str, str] = {}
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in ("settings_for",)):
                recv = dotted_name(node.value.func.value)
                if recv:
                    var_src[node.targets[0].id] = recv.rsplit(".", 1)[-1]
        for node in ast.walk(mod.tree):
            key = None
            recv_expr = None
            if isinstance(node, ast.Subscript):
                key = const_str(node.slice)
                recv_expr = node.value
            elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get" and node.args):
                key = const_str(node.args[0])
                recv_expr = node.func.value
            if not key or recv_expr is None:
                continue
            singleton = None
            if isinstance(recv_expr, ast.Name) and recv_expr.id in var_src:
                singleton = var_src[recv_expr.id]
            elif isinstance(recv_expr, ast.Call) and isinstance(recv_expr.func, ast.Attribute) \
                    and recv_expr.func.attr in ("settings_for",):
                recv = dotted_name(recv_expr.func.value)
                singleton = recv.rsplit(".", 1)[-1] if recv else None
            elif isinstance(recv_expr, ast.Attribute) and recv_expr.attr == "settings":
                recv = dotted_name(recv_expr.value)
                if recv and recv.rsplit(".", 1)[-1] not in ("self", "cls"):
                    singleton = recv.rsplit(".", 1)[-1]
            if singleton:
                index.reads.append(self._read(mod, node, singleton, key))

    @staticmethod
    def _read(mod: ParsedModule, node: ast.AST, singleton: str, key: str) -> SettingsRead:
        line = getattr(node, "lineno", 1)
        snippet = mod.lines[line - 1].strip() if 0 < line <= len(mod.lines) else ""
        return SettingsRead(singleton=singleton, key=key, rel=mod.rel, line=line,
                            col=getattr(node, "col_offset", 0), snippet=snippet)

    # -- cross-module checks --------------------------------------------------
    def finalize(self, index: RepoIndex) -> List[Finding]:
        out: List[Finding] = []
        for r in index.reads:
            comp = (r.singleton[1:] if r.singleton.startswith("@")
                    else index.singletons.get(r.singleton))
            decl = index.components.get(comp) if comp else None
            if decl is None or not decl.tunables:
                continue
            if r.key not in decl.tunables:
                out.append(Finding(
                    rule=self.id, path=r.rel, line=r.line, col=r.col,
                    message=(f"component {comp!r} reads undeclared settings key {r.key!r} "
                             f"(declared: {sorted(decl.tunables)})"),
                    snippet=r.snippet))
        for comp, decl in index.components.items():
            for key, t in decl.tunables.items():
                elsewhere = index.str_counter[key] - index.decl_str_counts[key]
                if elsewhere <= 0:
                    out.append(Finding(
                        rule=self.id, path=decl.rel, line=t.line, col=0,
                        message=(f"component {comp!r} declares tunable {key!r} that nothing "
                                 "in the repo reads: dead contract surface"),
                        snippet=f"{key} (declared line {t.line})"))
        return out


# =============================================================================
# MLOS007 — journal-append-only
# =============================================================================
_JOURNAL_MARKERS = ("results/campaign", "results/bench/trajectory", "trajectory.jsonl",
                    "results/online")


class JournalAppendOnly(Rule):
    """Campaign/trajectory journals are append-only and schema-versioned:
    resume correctness and the bench gate's pooled baselines both assume no
    writer ever truncates or rewrites history.  O_APPEND single-line writes
    only; ``"w"`` modes, seeks, and truncates against journal paths are
    corruption in waiting."""

    id = "MLOS007"
    name = "journal-append-only"

    SCOPE = ("src", "benchmarks", "examples")

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        if not _in(mod.rel, *self.SCOPE):
            return []
        if not any(m in mod.source for m in _JOURNAL_MARKERS):
            return []
        out: List[Finding] = []
        tainted = self._taint(mod.tree)
        handles = self._handles(mod.tree, tainted)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            # open(path, "w"/"r+"/...) and path.open("w")
            if ((isinstance(fn, ast.Name) and fn.id == "open" and node.args
                 and self._is_tainted(node.args[0], tainted))
                    or (isinstance(fn, ast.Attribute) and fn.attr == "open"
                        and self._is_tainted(fn.value, tainted))):
                mode = None
                if isinstance(fn, ast.Name) and len(node.args) > 1:
                    mode = const_str(node.args[1])
                elif isinstance(fn, ast.Attribute) and node.args:
                    mode = const_str(node.args[0])
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = const_str(kw.value)
                if mode and ("w" in mode or "+" in mode) and "a" not in mode:
                    out.append(self._f(mod, node,
                               f"mode {mode!r} open() against an append-only journal path: "
                               "journals only grow (O_APPEND single-line writes)"))
            elif isinstance(fn, ast.Attribute) and fn.attr == "write_text" \
                    and self._is_tainted(fn.value, tainted):
                out.append(self._f(mod, node,
                           "write_text() replaces an append-only journal wholesale"))
            elif (dotted_name(fn) or "").endswith("os.open") or \
                    (isinstance(fn, ast.Attribute) and fn.attr == "open"
                     and isinstance(fn.value, ast.Name) and fn.value.id == "os"):
                if node.args and self._is_tainted(node.args[0], tainted) \
                        and len(node.args) > 1:
                    flags = {n.rsplit(".", 1)[-1]
                             for sub in ast.walk(node.args[1])
                             if (n := dotted_name(sub))}
                    if "O_TRUNC" in flags or (
                            ("O_WRONLY" in flags or "O_RDWR" in flags)
                            and "O_APPEND" not in flags):
                        out.append(self._f(mod, node,
                                   "os.open() on a journal without O_APPEND (or with O_TRUNC): "
                                   "append-only writes required"))
            elif isinstance(fn, ast.Attribute) and fn.attr in ("seek", "truncate") \
                    and isinstance(fn.value, ast.Name) and fn.value.id in handles:
                out.append(self._f(mod, node,
                           f"{fn.attr}() on a journal file handle: journals are append-only"))
        return out

    # -- journal-path taint ---------------------------------------------------
    def _taint(self, tree: ast.Module) -> Set[str]:
        tainted: Set[str] = set()
        for _ in range(4):
            grew = False
            for node in ast.walk(tree):
                dsts: List[str] = []
                src: Optional[ast.AST] = None
                if isinstance(node, ast.Assign):
                    src = node.value
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            dsts.append(t.id)
                        elif isinstance(t, ast.Attribute) and \
                                isinstance(t.value, ast.Name) and t.value.id == "self":
                            dsts.append(f"self.{t.attr}")
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    a = node.args
                    pos = a.posonlyargs + a.args
                    offset = len(pos) - len(a.defaults)
                    for i, p in enumerate(pos):
                        if i >= offset and self._is_tainted(a.defaults[i - offset], tainted):
                            dsts, src = [p.arg], a.defaults[i - offset]
                if src is not None and dsts and self._is_tainted(src, tainted):
                    for d in dsts:
                        if d not in tainted:
                            tainted.add(d)
                            grew = True
            if not grew:
                break
        return tainted

    @staticmethod
    def _is_tainted(node: ast.AST, tainted: Set[str]) -> bool:
        for sub in ast.walk(node):
            s = const_str(sub)
            if s and any(m in s for m in _JOURNAL_MARKERS):
                return True
            if isinstance(sub, ast.JoinedStr):
                for v in sub.values:
                    vs = const_str(v)
                    if vs and any(m in vs for m in _JOURNAL_MARKERS):
                        return True
            if isinstance(sub, ast.Name) and sub.id in tainted:
                return True
            if isinstance(sub, ast.Attribute) and isinstance(sub.value, ast.Name) \
                    and sub.value.id == "self" and f"self.{sub.attr}" in tainted:
                return True
        return False

    def _handles(self, tree: ast.Module, tainted: Set[str]) -> Set[str]:
        """Names bound to file objects opened from journal paths."""
        out: Set[str] = set()
        for node in ast.walk(tree):
            val, names = None, []
            if isinstance(node, ast.Assign):
                val = node.value
                names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            elif isinstance(node, ast.With):
                for item in node.items:
                    if item.optional_vars is not None and isinstance(item.optional_vars, ast.Name):
                        v, n = item.context_expr, item.optional_vars.id
                        if self._is_open_of_tainted(v, tainted):
                            out.add(n)
                continue
            if val is not None and names and self._is_open_of_tainted(val, tainted):
                out.update(names)
        return out

    def _is_open_of_tainted(self, node: ast.AST, tainted: Set[str]) -> bool:
        return (isinstance(node, ast.Call)
                and ((isinstance(node.func, ast.Name) and node.func.id == "open")
                     or (dotted_name(node.func) or "").endswith("os.open")
                     or (isinstance(node.func, ast.Attribute) and node.func.attr == "open"))
                and bool(node.args) and self._is_tainted(node.args[0], tainted))


# =============================================================================
# MLOS008 — env-flag-bypass
# =============================================================================
class EnvFlagBypass(Rule):
    """``XLA_FLAGS`` is a tuned surface (the ``xla_runtime`` pseudo-component
    in ``repro.core.compilecache``), and plain assignment clobbers whatever
    the operator or the tuner already pinned.  Raw ``os.environ`` writes of
    the flag string outside the compilecache/compat layer bypass both the
    merge semantics and the config store — route through
    ``merge_xla_flags`` / ``child_env`` / ``force_host_device_count``."""

    id = "MLOS008"
    name = "env-flag-bypass"

    SCOPE = ("src", "benchmarks", "examples")
    EXEMPT = ("src/repro/core/compilecache.py", "src/repro/compat.py")
    _MSG = ("raw XLA_FLAGS environment write bypasses the xla_runtime "
            "component: merge via repro.core.compilecache "
            "(merge_xla_flags / child_env / force_host_device_count)")

    def check(self, mod: ParsedModule, index: RepoIndex) -> List[Finding]:
        if not _in(mod.rel, *self.SCOPE) or _in(mod.rel, *self.EXEMPT):
            return []
        if "XLA_FLAGS" not in mod.source:
            return []
        out: List[Finding] = []
        imports = import_map(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) \
                            and self._is_environ(t.value, imports) \
                            and const_str(t.slice) == "XLA_FLAGS":
                        out.append(self._f(mod, node, self._MSG))
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                fn = node.func
                if fn.attr in ("setdefault", "pop") and self._is_environ(fn.value, imports):
                    if node.args and const_str(node.args[0]) == "XLA_FLAGS":
                        out.append(self._f(mod, node, self._MSG))
                elif fn.attr == "update" and self._is_environ(fn.value, imports):
                    for arg in node.args:
                        if isinstance(arg, ast.Dict) and any(
                                const_str(k) == "XLA_FLAGS" for k in arg.keys if k):
                            out.append(self._f(mod, node, self._MSG))
                elif (resolve_call_target(node, imports) or "") == "os.putenv":
                    if node.args and const_str(node.args[0]) == "XLA_FLAGS":
                        out.append(self._f(mod, node, self._MSG))
        return out

    @staticmethod
    def _is_environ(node: ast.AST, imports: Dict[str, str]) -> bool:
        full = dotted_name(node)
        if not full:
            return False
        head, _, rest = full.partition(".")
        origin = imports.get(head)
        resolved = (f"{origin}.{rest}" if rest else origin) if origin else full
        return resolved == "os.environ"


ALL_RULES: List[Rule] = [
    CompatBypass(), SingletonSettings(), BarePerfClaim(), ForkHazard(),
    RejitHazard(), TunablesContract(), JournalAppendOnly(), EnvFlagBypass(),
]
RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in ALL_RULES}
