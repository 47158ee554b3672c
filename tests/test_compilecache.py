"""compilecache: compat shim across cache-API drift, the context-keyed jit
registry, xla_runtime flag assembly/merge, tuning integration, promote →
resolve round-trip, and the child re-exec apply path."""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import compilecache, configstore
from repro.core.compilecache import (XLA_RUNTIME_SPACE, cache_counters,
                                     cached_jit, child_env, clear_jit_registry,
                                     config_signature, ensure_host_device_count,
                                     force_host_device_count, merge_xla_flags,
                                     promote_xla_settings, resolve_xla_settings,
                                     xla_flags_string)
from repro.core.configstore import ConfigStore
from repro.launch.tuning import apply_overrides, current_settings, parse_override


@pytest.fixture
def store(tmp_path):
    st = ConfigStore(root=str(tmp_path / "configstore"))
    old = configstore.set_default_store(st)
    yield st
    configstore.set_default_store(old)


@pytest.fixture
def registry():
    clear_jit_registry()
    yield
    clear_jit_registry()


# ------------------------------------------------------------------ compat shim
def test_compat_modern_branch_sets_config(tmp_path):
    d = str(tmp_path / "cc")
    compat.enable_compilation_cache(d)
    assert jax.config.jax_compilation_cache_dir == d


def test_cache_dir_env_is_used_as_is(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache lives exactly there (no
    hardware/software subdirectories), and JAX's own config reads it."""
    d = tmp_path / "jaxcache"
    child = (
        "import jax\n"
        "from repro.core.compilecache import enable_persistent_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "active = enable_persistent_cache()\n"
        "print(before, active, jax.config.jax_compilation_cache_dir)\n")
    env = dict(compilecache.child_env({}), JAX_COMPILATION_CACHE_DIR=str(d))
    env.pop(compilecache.ENV_DISABLE, None)
    env["PYTHONPATH"] = str(compilecache.CHECKOUT / "src")
    r = subprocess.run([sys.executable, "-c", child], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    assert r.stdout.split() == [str(d)] * 3
    assert d.is_dir() and not any(d.iterdir())


def test_cache_dir_default_is_under_checkout_from_any_cwd(tmp_path, monkeypatch):
    monkeypatch.delenv(compilecache.ENV_CACHE_DIR, raising=False)
    dirs = []
    for sub in ("a", "b"):
        (tmp_path / sub).mkdir()
        monkeypatch.chdir(tmp_path / sub)
        dirs.append(compilecache.persistent_cache_dir())
    assert dirs[0] == dirs[1] and dirs[0].is_absolute()
    checkout = Path(__file__).resolve().parents[1]
    assert dirs[0].relative_to(checkout / "results" / "compilecache")


# ------------------------------------------------------------------- cached_jit
def test_cached_jit_memoizes_by_key_and_context(registry):
    f = cached_jit(lambda x: x + 1, key="t.step", context=("cfg-a",),
                   persistent=False)
    g = cached_jit(lambda x: x + 1, key="t.step", context=("cfg-a",),
                   persistent=False)
    h = cached_jit(lambda x: x + 1, key="t.step", context=("cfg-b",),
                   persistent=False)
    assert f is g and f is not h
    c = cache_counters()
    assert c["hits"] == 1 and c["misses"] == 2 and c["entries"] == 2.0


def test_cached_jit_no_retrace_across_reconstruction(registry):
    """Rebuilding 'the same step' (fresh lambda, same context) reuses the
    compiled callable: the trace body runs once per shape, not per rebuild."""
    traces = []

    def make(tag):
        def step(x):
            traces.append(tag)
            return x * 2
        return step

    x = np.ones((4,), np.float32)
    f = cached_jit(make("first"), key="t.retrace", context=("cfg",),
                   persistent=False)
    np.testing.assert_allclose(np.asarray(f(x)), 2 * x)
    g = cached_jit(make("second"), key="t.retrace", context=("cfg",),
                   persistent=False)
    np.testing.assert_allclose(np.asarray(g(x)), 2 * x)
    assert traces == ["first"]  # second build never traced
    assert cache_counters()["compile_seconds"] > 0


def test_cached_jit_donation_excludes_persistence(registry):
    """Donating executables must never be candidates for deserialization
    (jaxlib frees the donated buffer under a live aliased output), so the
    registry rejects the combination up front."""
    with pytest.raises(ValueError, match="use-after-free"):
        cached_jit(lambda x: x + 1, key="t.donate", donate_argnums=(0,))
    f = cached_jit(lambda x: x + 1, key="t.donate", donate_argnums=(0,),
                   persistent=False)
    x = jnp.ones((8,), jnp.float32)
    np.testing.assert_allclose(np.asarray(f(x)), 2.0)


def test_cached_jit_counters_exported_via_telemetry(registry):
    from repro.core.telemetry import compile_cache_counters

    cached_jit(lambda x: x, key="t.tel", persistent=False)
    assert compile_cache_counters()["misses"] == 1


def test_config_signature_dataclass_stability():
    from repro.configs import get_config

    a, b = get_config("olmo-1b"), get_config("olmo-1b")
    assert config_signature(a) == config_signature(b)
    assert config_signature(a) != config_signature(get_config("olmoe-1b-7b"))


# ----------------------------------------------------------------- flag strings
def test_xla_flags_string_defaults_and_gpu_gating():
    s = xla_flags_string()
    assert "--xla_force_host_platform_device_count=8" in s
    assert "--xla_cpu_multi_thread_eigen=true" in s
    assert "intra_op_parallelism_threads" not in s  # 0 = backend default
    assert "gpu" not in s                           # declared but inert-off
    s = xla_flags_string({"intra_op_threads": 4, "gpu_triton_gemm_any": True,
                          "eigen_multithread": False})
    assert "intra_op_parallelism_threads=4" in s
    assert "--xla_gpu_triton_gemm_any=true" in s
    assert "--xla_cpu_multi_thread_eigen=false" in s


def test_xla_flags_string_ignores_stale_keys():
    # a stored entry from an older space revision must degrade, not crash
    s = xla_flags_string({"host_device_count": 2, "removed_knob": 1})
    assert "--xla_force_host_platform_device_count=2" in s


def test_merge_preserves_foreign_flags_and_overrides_same_named():
    merged = merge_xla_flags(
        "--xla_dump_to=/tmp/d --xla_force_host_platform_device_count=8",
        "--xla_force_host_platform_device_count=512")
    assert "--xla_dump_to=/tmp/d" in merged
    assert "--xla_force_host_platform_device_count=512" in merged
    assert "device_count=8" not in merged


def test_force_and_ensure_host_device_count():
    env = {"XLA_FLAGS": "--xla_dump_to=/tmp/d"}
    force_host_device_count(512, env)
    assert "--xla_force_host_platform_device_count=512" in env["XLA_FLAGS"]
    assert "--xla_dump_to=/tmp/d" in env["XLA_FLAGS"]
    ensure_host_device_count(8, env)  # present: setdefault keeps 512
    assert "device_count=512" in env["XLA_FLAGS"]
    env2: dict = {}
    ensure_host_device_count(8, env2)
    assert "--xla_force_host_platform_device_count=8" in env2["XLA_FLAGS"]


# --------------------------------------------------- tuning + store integration
def test_xla_runtime_override_through_launch_tuning(store):
    ov = parse_override("xla_runtime.host_device_count=4")
    assert ov == {"xla_runtime": {"host_device_count": 4}}
    apply_overrides(ov)
    assert resolve_xla_settings()["host_device_count"] == 4
    assert current_settings(contexts=False)["xla_runtime"]["host_device_count"] == 4
    with pytest.raises(ValueError):
        parse_override("xla_runtime.not_a_flag=1")


def test_promote_resolve_roundtrip_with_provenance(store):
    tuned = dict(XLA_RUNTIME_SPACE.defaults(), intra_op_threads=8)
    assert promote_xla_settings(tuned, baseline=[2.0, 2.1, 2.2],
                                samples=[1.0, 1.1, 1.05],
                                provenance={"source": "test"})
    configstore.invalidate_cache()
    assert resolve_xla_settings()["intra_op_threads"] == 8
    entry = store.resolve_entry(configstore.context_for(compilecache.COMPONENT))
    assert entry["context"]["hardware"] == configstore.hardware_fingerprint()
    assert entry["provenance"]["source"] == "test"
    assert entry["provenance"]["gate"]["verdict"] in ("improved", "noise")


def test_promote_gates_out_significant_regression(store):
    worse = dict(XLA_RUNTIME_SPACE.defaults())
    assert not promote_xla_settings(
        worse, baseline=[1.0, 1.01, 0.99, 1.0, 1.02, 0.98],
        samples=[2.0, 2.01, 1.99, 2.0, 2.02, 1.98])
    assert store.resolve_entry(configstore.context_for(compilecache.COMPONENT)) is None


# ------------------------------------------------------------- child re-exec
@pytest.mark.slow
def test_child_env_applies_tuned_flags_on_reexec(store):
    """The component's apply path: a child built via child_env boots with the
    tuned device count (XLA_FLAGS is startup-only, so this IS the deploy)."""
    env = child_env({"host_device_count": 3})
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    r = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.device_count())"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    assert int(r.stdout.strip().splitlines()[-1]) == 3


# --------------------------------------------------- persistent cache plumbing
def test_persistent_cache_dir_is_context_keyed(tmp_path, monkeypatch):
    """Unset, the default root is namespaced <hw-fingerprint>/<sw-fingerprint>;
    set, JAX_COMPILATION_CACHE_DIR is taken as is, with no subdirectories."""
    monkeypatch.delenv(compilecache.ENV_CACHE_DIR, raising=False)
    parts = compilecache.persistent_cache_dir().relative_to(compilecache.CACHE_ROOT).parts
    assert len(parts) == 2
    assert all(p and "/" not in p and ":" not in p for p in parts)
    monkeypatch.setenv(compilecache.ENV_CACHE_DIR, str(tmp_path))
    assert compilecache.persistent_cache_dir() == tmp_path


def test_env_kill_switch_disables_persistence(monkeypatch):
    monkeypatch.setenv(compilecache.ENV_DISABLE, "off")
    assert compilecache.enable_persistent_cache() is None
