"""The MLOS Agent — a side-car daemon hosting optimizers/models/rules.

Paper §2.1 steps 4–5: models and optimizations are *deployed into the agent*,
which performs online inference on live telemetry and sends parameter-update
commands back over the shared-memory channel; the hooks enact them.

Three drivers share one deterministic core:

  * :class:`AgentCore` — pure logic for ONE tuning session: consume telemetry,
    aggregate per-config samples, step the optimizer, produce config-update
    commands.  Used in-process for tests and the notebook-style developer loop.
  * :class:`AgentMux` — N cores behind one telemetry stream.  The paper's
    agent is *instance-level*: one daemon concurrently tunes every annotated
    component instance in the process (§2.1 — e.g. each hash-table instance
    inside SQL Server gets its own custom tune).  The mux demultiplexes packed
    telemetry by the ``(component_id, instance_id)`` header and schedules
    ask/tell across the sessions independently.
  * :func:`agent_main` / :class:`AgentProcess` — run a mux in a separate OS
    process attached to the shared-memory channel (the production shape).
    Telemetry is drained in batches per poll (``ShmRing.drain``), not
    one-pop-one-sleep, so N interleaved sessions don't multiply wakeups.

Wire protocol (JSON over the control ring, packed structs on telemetry):

  * ``config_update``  {component, instance, settings} — host applies
    ``settings`` to the addressed instance's hooks.
  * ``session_report`` {component, instance, best_config, best_value,
    evaluations} — emitted per session the moment it exhausts its budget
    (and, best-so-far, on early STOP), so the host can act on finished
    sessions while others continue.

Everything the agent needs (schemas, spaces, objective) travels in a
JSON-serializable :class:`TuningSession`, so the agent process does not import
the host system's modules — the decoupling the paper insists on.  The agent
process is started with the ``spawn`` multiprocessing context: the host
typically has a multithreaded JAX runtime loaded, and forking that is a
latent deadlock (CPython emits a RuntimeWarning for exactly this).
"""
from __future__ import annotations

import dataclasses
import json
import multiprocessing
import struct
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

from .channel import MlosChannel
from .optimizers import make_optimizer, optimizer_defaults, set_optimizer_defaults
from .registry import ComponentMeta
from .tunable import TunableSpace

__all__ = ["TuningSession", "make_session", "AgentCore", "AgentMux", "AgentProcess",
           "AgentClient", "TrackedInstance", "drive_session", "promote_session_report"]

_CONTROL_STOP = b"\x00STOP"
_HEADER = struct.Struct("<II")  # (component_id, instance_id) telemetry prefix


@dataclasses.dataclass
class TuningSession:
    """Everything the agent needs to tune one component *instance*.

    ``context`` is the config-store coordinate of what is being tuned
    (component × workload signature × hardware × sw — see
    :mod:`repro.core.configstore`): it travels with the session into the
    spawned agent, comes back attached to the ``session_report``, and keys
    where the session's best config persists.

    ``prior`` warm-starts the session with observations measured under a
    *related* context (campaign cross-context transfer): a list of
    ``{"config": {...}, "value": <raw objective>}`` dicts, JSON-serializable
    so it travels into a spawned agent like everything else.  Values are in
    the session's raw objective convention (``mode`` is applied on injection)
    and seed the optimizer's surrogate only — they never count as
    evaluations of this session.
    """

    component: str
    component_id: int
    metric_fmt: str  # struct fmt of telemetry payloads
    metric_names: List[str]
    space_json: List[Dict[str, Any]]
    objective: str
    instance_id: int = 0
    mode: str = "min"  # 'min' | 'max'
    optimizer: str = "bo"
    samples_per_config: int = 1
    budget: int = 50
    seed: int = 0
    context: Optional[Dict[str, str]] = None
    prior: Optional[List[Dict[str, Any]]] = None

    @classmethod
    def for_component(cls, meta: ComponentMeta, objective: str,
                      workload: Optional[str] = None, **kw: Any) -> "TuningSession":
        """Legacy shim — prefer :func:`make_session` (the one factory)."""
        return make_session(meta, objective, workload=workload, **kw)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "TuningSession":
        return cls(**json.loads(s))

    @classmethod
    def direct(cls, name: str, space: "TunableSpace", objective: str, **kw: Any) -> "TuningSession":
        """Legacy shim — prefer :func:`make_session` (the one factory)."""
        return make_session(name, objective, space=space, packed=False,
                            workload=kw.pop("workload", None), **kw)


def make_session(component: Union[str, ComponentMeta], objective: str, *,
                 workload: Optional[str] = "*",
                 space: Optional[TunableSpace] = None,
                 mode: str = "min",
                 optimizer: str = "bo",
                 budget: int = 50,
                 samples_per_config: int = 1,
                 seed: int = 0,
                 instance_id: int = 0,
                 context: Optional[Dict[str, str]] = None,
                 prior: Optional[List[Dict[str, Any]]] = None,
                 packed: Optional[bool] = None) -> TuningSession:
    """THE session-construction entry point — every tuning path builds its
    :class:`TuningSession` here (campaign cells, the online serve controller,
    examples, ad-hoc driver loops), so every session carries a consistent
    config-store context and the promote path (``promote_session_report``)
    always knows where the result lands.

    ``component`` is a registered component name (or its :class:`ComponentMeta`):
    the session speaks the component's packed telemetry schema and searches its
    declared tunable space — or a ``space`` subset/override of it, which is how
    the online controller restricts search to hot-swappable knobs while still
    demuxing the full telemetry stream.  An *unregistered* name plus an explicit
    ``space`` builds a direct session (no packed telemetry; drive it with
    :meth:`AgentCore.observe_value`).

    The session is context-tagged with ``context_for(component, workload)``
    unless an explicit ``context`` is given; ``workload`` defaults to the
    component-wide ``"*"`` signature.  ``workload=None`` leaves the session
    untagged (legacy escape hatch — its reports cannot be auto-promoted).

    ``packed`` overrides telemetry-schema selection: ``None`` (default) infers
    from registration, ``False`` forces a direct session even for a registered
    name (requires ``space``).
    """
    meta: Optional[ComponentMeta]
    if isinstance(component, ComponentMeta):
        meta = component
    else:
        from .registry import _REGISTRY

        meta = _REGISTRY.get(str(component))
    if packed is False:
        meta = None
    elif packed and meta is None:
        raise ValueError(f"{component!r} is not a registered component: "
                         "packed telemetry needs a declared metric schema")
    if meta is not None:
        fmt = "<II" + "".join(m.fmt for m in meta.metrics)
        names = [m.name for m in meta.metrics]
        name, cid = meta.name, meta.component_id
        sp = space if space is not None else meta.space
        if objective not in names:
            raise ValueError(f"{name}: objective {objective!r} is not a declared "
                             f"metric {names}")
    else:
        if space is None:
            raise ValueError(f"{component!r} is not a registered component: "
                             "pass an explicit `space` to build a direct session")
        fmt, names = "", [objective]
        name, cid = str(component), 0
        sp = space
    if context is None and workload is not None:
        from .configstore import context_for

        context = context_for(name, workload).to_dict()
    return TuningSession(
        component=name, component_id=cid, metric_fmt=fmt, metric_names=names,
        space_json=sp.to_json(), objective=objective, instance_id=instance_id,
        mode=mode, optimizer=optimizer, samples_per_config=samples_per_config,
        budget=budget, seed=seed, context=context, prior=prior)


def sessions_to_json(sessions: Iterable[TuningSession]) -> str:
    return json.dumps([dataclasses.asdict(s) for s in sessions])


def sessions_from_json(s: str) -> List[TuningSession]:
    """Parse one session (legacy) or a list of sessions."""
    obj = json.loads(s)
    if isinstance(obj, dict):
        obj = [obj]
    return [TuningSession(**d) for d in obj]


class AgentCore:
    """Deterministic agent logic for one session: telemetry in, commands out."""

    def __init__(self, session: TuningSession):
        self.session = session
        self.space = TunableSpace.from_json(session.space_json)
        self.opt = make_optimizer(session.optimizer, self.space, seed=session.seed)
        self.prior_injected = 0
        if session.prior:
            # Warm start: raw objective values flip into the internal
            # minimized convention exactly as observe() does for telemetry.
            sign = -1.0 if session.mode == "max" else 1.0
            self.prior_injected = self.opt.inject_prior(
                [(p["config"], sign * float(p["value"])) for p in session.prior])
        # 0 for 'direct' sessions (metric_fmt="" — no packed telemetry)
        self.payload_size = struct.calcsize(session.metric_fmt) if session.metric_fmt else 0
        self._pending_cfg: Optional[Dict[str, Any]] = None
        self._samples: List[float] = []
        self.evaluations = 0
        self.done = False

    # -- protocol ------------------------------------------------------------
    @property
    def key(self) -> Tuple[int, int]:
        """The telemetry demux key of this session."""
        return (self.session.component_id, self.session.instance_id)

    def start_command(self) -> bytes:
        """First command: put the system on the optimizer's first proposal."""
        self._pending_cfg = self.opt.ask()
        return self._command(self._pending_cfg)

    def _command(self, cfg: Dict[str, Any]) -> bytes:
        msg = {
            "type": "config_update",
            "component": self.session.component,
            "instance": self.session.instance_id,
            "settings": cfg,
        }
        return json.dumps(msg).encode()

    def observe(self, payload: bytes) -> Optional[bytes]:
        """Feed one telemetry record; maybe emit the next config-update."""
        kind, out = self._ingest(payload)
        if kind == "ask":
            return self.resolve_ask(self.opt.ask())
        return out

    def _ingest(self, payload: bytes) -> Tuple[str, Optional[bytes]]:
        """Tell-side of :meth:`observe`: consume one record WITHOUT asking.

        Returns ``("none", None)`` (not ours / more samples needed),
        ``("park", cmd)`` (budget exhausted — park on the best config), or
        ``("ask", None)`` — the session needs its next proposal.  The caller
        either resolves the ask immediately (:meth:`observe`) or defers it so
        the mux can batch every pending ask into one device dispatch.
        While an ask is deferred ``_pending_cfg`` is None, so stray records
        for this instance are dropped rather than attributed to a config the
        optimizer has not chosen yet.
        """
        if self.done or self._pending_cfg is None:
            return "none", None
        vals = struct.unpack(self.session.metric_fmt, payload)
        if (vals[0], vals[1]) != self.key:
            return "none", None  # not ours
        metrics = dict(zip(self.session.metric_names, vals[2:]))
        v = float(metrics[self.session.objective])
        if self.session.mode == "max":
            v = -v
        self._samples.append(v)
        if len(self._samples) < self.session.samples_per_config:
            return "none", None
        value = sum(self._samples) / len(self._samples)
        self._samples = []
        self.opt.tell(self._pending_cfg, value)
        self.evaluations += 1
        if self.evaluations >= self.session.budget:
            self.done = True
            best = self.opt.best
            assert best is not None
            self._pending_cfg = None
            return "park", self._command(best.config)
        self._pending_cfg = None
        return "ask", None

    def resolve_ask(self, cfg: Dict[str, Any]) -> bytes:
        """Install a proposed config (from ``opt.ask()`` or a batched ask)
        as the pending one and emit its config-update command."""
        self._pending_cfg = cfg
        return self._command(cfg)

    def session_report(self) -> Optional[bytes]:
        """Final per-session summary for the host (None before any tell).

        Carries everything the host needs to *promote* the best config into
        the config store: the context it was tuned under, the objective and
        mode (so the raw best objective can be recovered from the internally
        minimized value), and the budget for provenance.
        """
        best = self.opt.best
        if best is None:
            return None
        return json.dumps(
            {
                "type": "session_report",
                "component": self.session.component,
                "instance": self.session.instance_id,
                "best_config": best.config,
                "best_value": best.value,
                "evaluations": self.evaluations,
                "objective": self.session.objective,
                "mode": self.session.mode,
                "budget": self.session.budget,
                "context": self.session.context,
            }
        ).encode()

    # -- in-process variant (no channel) --------------------------------------
    def ask(self) -> Dict[str, Any]:
        if self._pending_cfg is None and not self.done:
            self._pending_cfg = self.opt.ask()
        return dict(self._pending_cfg or (self.opt.best.config if self.opt.best else {}))

    def observe_value(self, config: Dict[str, Any], value: float) -> Dict[str, Any]:
        """Direct observation (bypasses the packed-telemetry protocol);
        returns the next configuration to run."""
        if self.done:
            return self.ask()
        v = -float(value) if self.session.mode == "max" else float(value)
        self.opt.tell(config, v)
        self.evaluations += 1
        if self.evaluations >= self.session.budget:
            self.done = True
            self._pending_cfg = None
            return dict(self.opt.best.config)
        self._pending_cfg = self.opt.ask()
        return dict(self._pending_cfg)

    @property
    def best(self):
        return self.opt.best


class AgentMux:
    """N concurrent :class:`AgentCore` sessions behind one telemetry stream.

    Telemetry records are routed by their ``(component_id, instance_id)``
    header; each session steps its own optimizer independently, so a slow
    session never stalls the others.  Records for unregistered instances are
    counted (``unrouted``) and dropped — the paper's drop-not-block stance.
    """

    def __init__(self, sessions: Sequence[TuningSession]):
        self.cores: Dict[Tuple[int, int], AgentCore] = {}
        for s in sessions:
            core = AgentCore(s)
            if core.key in self.cores:
                raise ValueError(f"duplicate session key {core.key} ({s.component})")
            self.cores[core.key] = core
        self._reported: set = set()
        self.unrouted = 0

    @property
    def done(self) -> bool:
        return all(c.done for c in self.cores.values())

    def start_commands(self) -> List[bytes]:
        return [c.start_command() for c in self.cores.values()]

    def _route(self, payload: bytes) -> Optional[AgentCore]:
        if len(payload) < _HEADER.size:
            self.unrouted += 1
            return None
        core = self.cores.get(_HEADER.unpack_from(payload, 0))
        if core is None or len(payload) != core.payload_size:
            # Unknown instance OR malformed frame for a known one: a truncated
            # record must not raise out of the daemon's poll loop.
            self.unrouted += 1
            return None
        return core

    def _maybe_report(self, core: AgentCore, out: List[bytes]) -> None:
        if core.done and core.key not in self._reported:
            rep = core.session_report()
            if rep is not None:
                self._reported.add(core.key)
                out.append(rep)

    def observe(self, payload: bytes) -> List[bytes]:
        """Route one record; returns messages to push (commands + reports)."""
        core = self._route(payload)
        if core is None:
            return []
        out: List[bytes] = []
        cmd = core.observe(payload)
        if cmd is not None:
            out.append(cmd)
        self._maybe_report(core, out)
        return out

    def observe_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        """Route a drained batch; collect every session that finished a
        config and issue ALL their next proposals as one batched ask.

        With jax-backed BO sessions the whole mux's suggest sweep is a single
        device dispatch (:class:`~.optimizers.engine.BatchedBayesOpt`); other
        optimizers fall back to per-session ``ask`` with identical results to
        the serial :meth:`observe` loop (asks are deferred only to the end of
        the batch, and each optimizer owns its rng).
        """
        out: List[bytes] = []
        need: List[AgentCore] = []
        pending_ids = set()
        for payload in payloads:
            core = self._route(payload)
            if core is None:
                continue
            if id(core) in pending_ids:
                # Second completed config for one instance inside a single
                # drained batch (possible when the host runs far ahead):
                # resolve the deferred ask serially to preserve tell→ask order.
                pending_ids.discard(id(core))
                need.remove(core)
                out.append(core.resolve_ask(core.opt.ask()))
            kind, msg = core._ingest(payload)
            if msg is not None:
                out.append(msg)
            if kind == "ask":
                need.append(core)
                pending_ids.add(id(core))
            self._maybe_report(core, out)
        if need:
            if any(getattr(c.opt, "backend", None) == "jax" for c in need):
                from .optimizers.engine import batched_ask  # deferred: jax is heavy

                cfgs = batched_ask([c.opt for c in need])
            else:
                cfgs = [c.opt.ask() for c in need]
            for core, cfg in zip(need, cfgs):
                out.append(core.resolve_ask(cfg))
        return out

    def final_reports(self) -> List[bytes]:
        """Best-so-far reports for sessions not yet reported (early STOP)."""
        out: List[bytes] = []
        for key, core in self.cores.items():
            if key in self._reported:
                continue
            rep = core.session_report()
            if rep is not None:
                self._reported.add(key)
                out.append(rep)
        return out


def agent_main(
    telemetry_name: str,
    control_name: str,
    sessions_json: str,
    poll_s: float = 0.0005,
    drain_batch: int = 256,
    optimizer_defaults_json: Optional[str] = None,
) -> None:
    """Entry point of the agent process: one mux over the duplex channel.

    Each idle poll sleeps once and then drains up to ``drain_batch`` records
    in one pass — under N interleaved sessions the per-record overhead is a
    dict lookup, not a syscall + sleep.

    ``optimizer_defaults_json`` replays the host's process-wide optimizer
    defaults (e.g. ``optimizer.backend=jax`` from launch/tuning) into this
    freshly *spawned* interpreter — without it, sessions naming a generic
    optimizer ("bo") would silently fall back to the module defaults.

    The daemon's JAX is pinned to the CPU before any backend starts: the
    host process holds the accelerator (a chip belongs to one process), and
    the optimizers compute on the host anyway.
    """
    import jax  # deferred: the rest of this module needs no JAX

    jax.config.update("jax_platforms", "cpu")
    if optimizer_defaults_json:
        set_optimizer_defaults(**json.loads(optimizer_defaults_json))
    chan = MlosChannel.attach(telemetry_name, control_name)
    mux = AgentMux(sessions_from_json(sessions_json))
    try:
        for cmd in mux.start_commands():
            chan.control.push(cmd)
        stopped = False
        while not mux.done and not stopped:
            batch = chan.telemetry.drain(limit=drain_batch)
            if not batch:
                time.sleep(poll_s)
                continue
            if _CONTROL_STOP in batch:
                stopped = True
                batch = batch[: batch.index(_CONTROL_STOP)]
            # One batched observe per poll: every session that completed a
            # config in this drain gets its next proposal from ONE device
            # dispatch (jax-backed BO) instead of N sequential model refits.
            for msg in mux.observe_batch(batch):
                chan.control.push(msg)
        for rep in mux.final_reports():
            chan.control.push(rep)
    finally:
        chan.telemetry.close()
        chan.control.close()


class AgentProcess:
    """Host-side handle that launches/stops the (multi-session) agent daemon.

    Accepts one session or a sequence — the daemon multiplexes them all over
    the single channel.  Started via the ``spawn`` context: the host process
    usually holds a multithreaded JAX runtime, which ``os.fork()`` would
    clone into a deadlock-prone child.
    """

    def __init__(
        self,
        channel: MlosChannel,
        sessions: Union[TuningSession, Sequence[TuningSession]],
        mp_context: str = "spawn",
    ):
        self.channel = channel
        if isinstance(sessions, TuningSession):
            sessions = [sessions]
        self.sessions = list(sessions)
        tele, ctrl = channel.names
        ctx = multiprocessing.get_context(mp_context)
        # Snapshot the host's optimizer defaults: the spawned interpreter
        # re-imports everything fresh, so launch-level overrides must travel.
        self.proc = ctx.Process(
            target=agent_main,
            args=(tele, ctrl, sessions_to_json(self.sessions)),
            kwargs={"optimizer_defaults_json": json.dumps(optimizer_defaults())},
            daemon=True,
        )

    def start(self) -> "AgentProcess":
        self.proc.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self.channel.telemetry.push(_CONTROL_STOP)
        self.proc.join(timeout)
        if self.proc.is_alive():  # pragma: no cover
            self.proc.terminate()
            self.proc.join(timeout)


def drive_session(session: TuningSession, measure: Any) -> AgentCore:
    """Drive ONE session to completion in-process through the packed-telemetry
    protocol — the deterministic single-session twin of an :class:`AgentProcess`
    (same core, same seeds, no channel).  ``measure(settings)`` applies the
    proposed settings to the live component and returns its metric dict.
    Used as the baseline against the multiplexed daemon in tests and
    ``benchmarks/multi_instance.py``.
    """
    core = AgentCore(session)
    fmt = struct.Struct(session.metric_fmt)
    cmd = json.loads(core.start_command().decode())
    while not core.done:
        metrics = measure(cmd["settings"])
        payload = fmt.pack(session.component_id, session.instance_id,
                           *[metrics[n] for n in session.metric_names])
        nxt = core.observe(payload)
        if nxt is not None:
            cmd = json.loads(nxt.decode())
    return core


def promote_session_report(store: Any, msg: Dict[str, Any], *,
                           rpi: Any = None, run: Any = None,
                           baseline: Optional[Sequence[float]] = None,
                           samples: Optional[Sequence[float]] = None,
                           tolerance: float = 0.05, alpha: float = 0.05) -> bool:
    """Persist a finished session's best config into the config store.

    This is the producer half of the paper's tune → validate → persist →
    redeploy loop: the session's context keys the entry, ``rpi`` (when given)
    gates the promotion on the learned performance envelope, and provenance
    (run id, budget, best objective, evaluations) rides along — logged into
    the tracked ``run`` as well, so the experiment store can answer "where
    did this config come from".  Returns False when the report carries no
    context or a gate rejects it.

    ``baseline``/``samples`` thread LIVE measurement evidence into the
    store's stats gate (``ConfigStore.promote``): the online serve controller
    passes the champion's live window samples as ``baseline`` and the
    challenger's as ``samples``, so a canary promotes against what the
    incumbent actually did on the same traffic — not against a stale recorded
    number.  The report's ``mode`` orients the comparison.  Extra provenance
    in ``msg["provenance"]`` (canary id, window count, source) rides into the
    stored entry.
    """
    from .configstore import Context

    if not msg.get("context"):
        return False
    ctx = Context.from_dict(msg["context"])
    # Internal values are minimized; recover the raw objective for the gate.
    best_objective = -msg["best_value"] if msg.get("mode") == "max" else msg["best_value"]
    objective = msg.get("objective", "objective")
    metrics = {objective: best_objective}
    if rpi is not None:
        # A session report only carries its objective, so only objective
        # bounds are enforceable here; bounds on other metrics would read as
        # "missing from measurement" violations and veto every promotion.
        # Those stay the job of the full-measurement assert_rpi gates.
        bounds = tuple(b for b in rpi.bounds if b.metric in metrics)
        rpi = dataclasses.replace(rpi, bounds=bounds) if bounds else None
    provenance = {
        "run_id": getattr(run, "run_id", None),
        "budget": msg.get("budget"),
        "evaluations": msg.get("evaluations"),
        "objective": objective,
        "best_objective": best_objective,
        **(msg.get("provenance") or {}),
    }
    ok = store.promote(ctx, msg["best_config"], rpi=rpi, metrics=metrics,
                       baseline=list(baseline) if baseline else None,
                       samples=list(samples) if samples else None,
                       mode=msg.get("mode", "min"), tolerance=tolerance,
                       alpha=alpha, provenance=provenance)
    if run is not None:
        run.log_metric(f"{ctx.component}@{ctx.workload}/{objective}", best_objective)
        run.set_tags({f"{ctx.component}@{ctx.workload}":
                      "promoted" if ok else "rejected_rpi"})
        if ok:
            run.log_params({f"{ctx.component}@{ctx.workload}": msg["best_config"]})
    return ok


class TrackedInstance:
    """Host-side wrapper for the multiplexed drive loop: remembers that a
    config landed (``dirty``) so the driver knows this instance needs a fresh
    measurement + telemetry emit.  Register it with :class:`AgentClient` in
    place of the bare component."""

    def __init__(self, instance: Any, rebuild: bool = True):
        self.instance = instance
        self._rebuild = rebuild and hasattr(instance, "apply_and_rebuild")
        self.dirty = False

    def apply_settings(self, settings: Dict[str, Any]) -> None:
        if self._rebuild:
            self.instance.apply_and_rebuild(settings)
        else:
            self.instance.apply_settings(settings)
        self.dirty = True


class AgentClient:
    """System-side: applies agent commands to live component instances.

    Instances are keyed by ``(component_name, instance_id)`` so one client
    can host many instances of the same component, each driven by its own
    agent session (the paper's instance-level tuning).  ``register(name,
    inst)`` without an id keeps the legacy single-instance shape (id 0).

    When constructed with a ``store``, session reports that carry a context
    are promoted into it as they arrive (:func:`promote_session_report`) —
    gated per context by ``rpi_lookup(component, workload) -> RPI | None``
    and tracked against ``run`` when given.  ``promotions`` records each
    attempt as ``(context_dict, promoted?)``.
    """

    def __init__(self, channel: MlosChannel, store: Any = None,
                 rpi_lookup: Any = None, run: Any = None):
        self.channel = channel
        self.store = store
        self.rpi_lookup = rpi_lookup
        self.run = run
        self._instances: Dict[Tuple[str, int], Any] = {}
        self.reports: List[Dict[str, Any]] = []
        self.promotions: List[Tuple[Dict[str, str], bool]] = []

    def register(self, name: str, instance: Any, instance_id: int = 0) -> None:
        self._instances[(name, instance_id)] = instance

    def report_for(self, name: str, instance_id: int = 0) -> Optional[Dict[str, Any]]:
        for rep in self.reports:
            if rep["component"] == name and rep.get("instance", 0) == instance_id:
                return rep
        return None

    def poll(self, wait_s: float = 0.0, deadline_s: float = 1.0) -> int:
        """Apply pending config updates; optionally block until one arrives."""
        applied = 0
        t0 = time.perf_counter()
        while True:
            payload = self.channel.control.pop()
            if payload is None:
                if wait_s and applied == 0 and time.perf_counter() - t0 < deadline_s:
                    time.sleep(wait_s)
                    continue
                return applied
            msg = json.loads(payload.decode())
            if msg["type"] == "config_update":
                inst = self._instances.get((msg["component"], msg.get("instance", 0)))
                if inst is not None:
                    inst.apply_settings(msg["settings"])
                    applied += 1
            elif msg["type"] == "session_report":
                self.reports.append(msg)
                if self.store is not None and msg.get("context"):
                    ctx = msg["context"]
                    rpi = (self.rpi_lookup(ctx["component"], ctx["workload"])
                           if self.rpi_lookup else None)
                    ok = promote_session_report(self.store, msg, rpi=rpi, run=self.run)
                    self.promotions.append((ctx, ok))
