"""MLOS core — the paper's contribution as a composable library.

Layers (paper §2.1):
  tunable/registry  — annotation surface ("auto-parameters")
  codegen           — externalization artifacts (hooks + binary schemas)
  channel           — shared-memory telemetry/control rings
  agent             — side-car daemon hosting optimizers for online tuning
  telemetry         — app metrics + OS (/proc) + compiled-HLO "HW" counters
  tracking          — MLflow-like experiment store
  configstore       — persistent, context-keyed store of tuned configurations
  campaign          — fleet orchestration of the component × workload grid
  stats             — noise-aware measurement + three-way A/B comparator
  baseline          — append-only perf trajectory + regression-gate baselines
  rpi               — Resource Performance Interfaces (perf-regression gates)
  optimizers        — RandomSearch / Grid / One-at-a-time / GP-BO (Matern-3/2)
  smartcomponents   — paper-faithful demo components (hashtable, spinlock)
"""
from . import config
from .agent import (AgentClient, AgentCore, AgentMux, AgentProcess, TrackedInstance,
                    TuningSession, drive_session, make_session, promote_session_report)
from .baseline import BaselineStore, BenchRecord, GateReport
from .campaign import Campaign, CampaignCell, CampaignJournal, CellResult, evals_to_reach
from .channel import MlosChannel, ShmRing
from .codegen import generate_source, load_generated, pack_telemetry, unpack_telemetry
from .configstore import ConfigStore, Context, context_for, default_store, resolve_settings
from .registry import MetricSpec, all_components, get_component, tunable_component
from .rpi import RPI, Bound, RpiReport, assert_rpi
from .stats import (Comparison, Measurement, StreamingAB, bootstrap_ci, compare,
                    measure_adaptive, measure_interleaved)
from .telemetry import TelemetryEmitter, collective_bytes, hlo_counters, os_counters, span
from .tracking import Tracker
from .tunable import Bool, Categorical, Float, Int, Tunable, TunableSpace

__all__ = [
    "AgentClient", "AgentCore", "AgentMux", "AgentProcess", "TrackedInstance",
    "TuningSession", "drive_session", "make_session", "promote_session_report",
    "Campaign", "CampaignCell", "CampaignJournal", "CellResult", "evals_to_reach",
    "MlosChannel", "ShmRing",
    "config",
    "generate_source", "load_generated", "pack_telemetry", "unpack_telemetry",
    "ConfigStore", "Context", "context_for", "default_store", "resolve_settings",
    "BaselineStore", "BenchRecord", "GateReport",
    "Comparison", "Measurement", "StreamingAB", "bootstrap_ci", "compare",
    "measure_adaptive", "measure_interleaved",
    "MetricSpec", "all_components", "get_component", "tunable_component",
    "RPI", "Bound", "RpiReport", "assert_rpi",
    "TelemetryEmitter", "collective_bytes", "hlo_counters", "os_counters", "span",
    "Tracker",
    "Bool", "Categorical", "Float", "Int", "Tunable", "TunableSpace",
]
