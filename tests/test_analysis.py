"""mloslint: every rule fires on a planted violation, stays silent on a
clean twin, the ratchet only shrinks, and the real repo is clean."""
from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis.findings import Finding
from repro.analysis.lint import collect_findings, main as lint_main, run_lint
from repro.analysis.ratchet import apply_ratchet, load_baseline, save_baseline

REPO_ROOT = Path(__file__).resolve().parents[1]


def mini_repo(tmp_path: Path, files: dict) -> Path:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def rules_fired(root: Path, paths=None):
    findings, _ = collect_findings(root, paths)
    return findings, {f.rule for f in findings}


# =============================================================================
# MLOS001 compat-bypass
# =============================================================================
def test_mlos001_fires_on_drifted_imports(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad.py": """\
            from jax.experimental.shard_map import shard_map
            import jax

            def mesh(jax, devices):
                return jax.sharding.Mesh(devices, ("x",), axis_types=None)
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS001" in rules
    assert sum(f.rule == "MLOS001" for f in findings) == 2  # import + axis_types


def test_mlos001_silent_on_compat_routed_twin(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good.py": """\
            from repro.compat import make_mesh, shard_map

            def mesh(devices):
                return make_mesh(devices, ("x",))
            """,
        # the shim itself is the one sanctioned home for drifted APIs
        "src/repro/compat.py": """\
            from jax.experimental.shard_map import shard_map  # noqa: F401
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS001" not in rules


# =============================================================================
# MLOS002 singleton-settings
# =============================================================================
def test_mlos002_fires_on_singleton_reads_and_module_config(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad.py": """\
            from repro.kernels import attention_settings

            global_config = {"impl": "naive"}

            def pick():
                return attention_settings.settings["impl"]
            """,
    })
    findings, rules = rules_fired(root)
    msgs = [f.message for f in findings if f.rule == "MLOS002"]
    assert len(msgs) == 2
    assert any("settings_for" in m for m in msgs)
    assert any("module-level mutable config" in m for m in msgs)


def test_mlos002_silent_on_settings_for_and_self(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good.py": """\
            from repro.kernels import attention_settings

            def pick(workload):
                return attention_settings.settings_for(workload)["impl"]

            class Comp:
                def use(self):
                    return self.settings["impl"]
            """,
        # tests/ may poke internals; out of scope
        "tests/test_poke.py": "def test_x(c):\n    assert c.settings['impl']\n",
    })
    _, rules = rules_fired(root)
    assert "MLOS002" not in rules


# =============================================================================
# MLOS003 bare-perf-claim
# =============================================================================
def test_mlos003_fires_on_raw_timing_and_bare_median(tmp_path):
    root = mini_repo(tmp_path, {
        "benchmarks/bench_bad.py": """\
            import time
            import numpy as np

            def measure(op):
                t0 = time.perf_counter()
                op()
                return (time.perf_counter() - t0) * 1e6

            def claim(rows):
                vals = [r["time_us"] for r in rows]
                return float(np.median(vals)), min(rows, key=lambda r: r["time_us"])
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS003" in rules
    assert sum(f.rule == "MLOS003" for f in findings) >= 3


def test_mlos003_silent_on_stats_routed_and_registered_bench(tmp_path):
    root = mini_repo(tmp_path, {
        # routes claims through core.stats -> exempt
        "benchmarks/bench_stats.py": """\
            from repro.core import stats

            def claim(base, cand):
                return stats.compare(base, cand, mode="min").verdict
            """,
        # registered runner benchmark: raw samples feed the gate
        "benchmarks/bench_registered.py": """\
            import time

            def bench(quick, seed):
                t0 = time.perf_counter()
                return {"samples": [time.perf_counter() - t0]}
            """,
        # tests may use wall-clock deadlines freely
        "tests/test_wait.py": """\
            import time

            def test_waits():
                deadline = time.time() + 5
                while time.time() < deadline:
                    break
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS003" not in rules


# =============================================================================
# MLOS004 fork-hazard
# =============================================================================
def test_mlos004_fires_on_fork_paths(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad.py": """\
            import os
            import multiprocessing

            def spawn_worker(target):
                os.fork()
                multiprocessing.Process(target=target).start()
                ctx = multiprocessing.get_context("fork")
                return ctx
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS004" in rules
    assert sum(f.rule == "MLOS004" for f in findings) == 3


def test_mlos004_silent_on_spawn_and_param_default(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good.py": """\
            import multiprocessing

            def make(mp_context: str = "spawn"):
                return multiprocessing.get_context(mp_context)

            def make_direct():
                return multiprocessing.get_context("spawn")
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS004" not in rules


# =============================================================================
# MLOS005 rejit-hazard
# =============================================================================
def test_mlos005_fires_on_unbucketed_len_and_unguarded_x64(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad_shapes.py": """\
            import jax.numpy as jnp

            def pad(history):
                return jnp.zeros(len(history))
            """,
        "src/repro/bad_x64.py": """\
            import jax.numpy as jnp
            from repro.compat import enable_x64

            def upload(vals):
                return jnp.asarray(vals)

            def never_guarded(vals):
                return upload(vals)
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS005" in rules
    assert sum(f.rule == "MLOS005" for f in findings) == 2


def test_mlos005_silent_on_bucketed_and_guarded_twin(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good_shapes.py": """\
            import jax.numpy as jnp
            from repro.core.optimizers.engine import bucket_of

            def pad(history):
                return jnp.zeros(bucket_of(len(history)))
            """,
        # numpy-only module: no jit in play, len() shapes are fine
        "src/repro/numpy_only.py": """\
            import numpy as np

            def pad(history):
                return np.zeros(len(history))
            """,
        # constructor outside the with, but every call site is guarded
        "src/repro/good_x64.py": """\
            import jax.numpy as jnp
            from repro.compat import enable_x64

            def _upload(vals):
                return jnp.asarray(vals)

            def tell(vals):
                with enable_x64():
                    return _upload(vals)
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS005" not in rules


# =============================================================================
# MLOS006 tunables-contract
# =============================================================================
def test_mlos006_fires_on_contract_breaks(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad_comp.py": """\
            from repro.core.registry import tunable_component
            from repro.core.tunable import Int, Categorical

            @tunable_component("bad_comp", tunables=(
                Int("block", 512, 16, 256),
                Int("dead_knob", 1, 0, 8),
            ))
            class BadComp:
                def use(self):
                    return self.settings["block"] + self.settings["ghost_key"]
            """,
    })
    findings, rules = rules_fired(root)
    msgs = [f.message for f in findings if f.rule == "MLOS006"]
    assert any("outside declared domain" in m for m in msgs)      # 512 not in [16,256]
    assert any("ghost_key" in m for m in msgs)                    # undeclared read
    assert any("dead_knob" in m and "dead" in m for m in msgs)    # never consumed


def test_mlos006_silent_on_honest_contract(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good_comp.py": """\
            from repro.core.registry import tunable_component
            from repro.core.tunable import Int, Categorical

            @tunable_component("good_comp", tunables=(
                Int("block", 64, 16, 256),
                Categorical("impl", "fast", ("fast", "naive")),
            ))
            class GoodComp:
                def use(self):
                    return self.settings["block"], self.settings["impl"]
            """,
        "src/repro/consumer.py": """\
            from repro.good_comp import comp

            def pick(wl):
                s = comp.settings_for(wl)
                return s["block"], s["impl"]
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS006" not in rules


def test_mlos006_fires_on_undeclared_settings_for_read(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/comp.py": """\
            from repro.core.registry import tunable_component
            from repro.core.tunable import Int, Categorical

            @tunable_component("comp", tunables=(Int("block", 64, 16, 256),))
            class Comp:
                def use(self):
                    return self.settings["block"]

            comp = Comp()
            """,
        "src/repro/consumer.py": """\
            from repro.comp import comp

            def pick(wl):
                s = comp.settings_for(wl)
                return s["block_q"]
            """,
    })
    findings, _ = rules_fired(root)
    msgs = [f.message for f in findings if f.rule == "MLOS006"]
    assert any("block_q" in m and "undeclared" in m for m in msgs)


# =============================================================================
# MLOS007 journal-append-only
# =============================================================================
def test_mlos007_fires_on_truncating_journal_writes(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad_journal.py": """\
            import os

            ROOT = "results/campaign"

            def rewrite(campaign_id, lines):
                path = f"{ROOT}/{campaign_id}.jsonl"
                with open(path, "w") as f:
                    f.writelines(lines)

            def truncate(path="results/bench/trajectory.jsonl"):
                fd = os.open(path, os.O_WRONLY | os.O_TRUNC)
                return fd

            def rewind(campaign_id):
                f = open(f"{ROOT}/{campaign_id}.jsonl")
                f.seek(0)
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS007" in rules
    assert sum(f.rule == "MLOS007" for f in findings) == 3


def test_mlos007_fires_on_online_journal_rewrites(tmp_path):
    # the online tuner's transition journal is under the same append-only
    # contract as the campaign journal: resume-after-kill replays it
    root = mini_repo(tmp_path, {
        "src/repro/bad_online.py": """\
            ROOT = "results/online"

            def compact(tuner_id, rows):
                with open(f"{ROOT}/{tuner_id}.jsonl", "w") as f:
                    f.writelines(rows)
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS007" in rules


def test_mlos007_silent_on_append_only_twin(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good_journal.py": """\
            import os

            ROOT = "results/campaign"

            def append(campaign_id, line):
                path = f"{ROOT}/{campaign_id}.jsonl"
                fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
                try:
                    os.write(fd, line.encode())
                finally:
                    os.close(fd)

            def read(campaign_id):
                with open(f"{ROOT}/{campaign_id}.jsonl") as f:
                    return f.readlines()
            """,
        # tests may build fixture journals however they like; out of scope
        "tests/test_fixture.py": """\
            def test_plant(tmp_path):
                (tmp_path / "results/campaign/c.jsonl").write_text("{}")
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS007" not in rules


# =============================================================================
# MLOS008 env-flag-bypass
# =============================================================================
def test_mlos008_fires_on_raw_xla_flags_writes(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/bad_flags.py": """\
            import os
            from os import environ

            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

            def prep():
                environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")
                os.environ.update({"XLA_FLAGS": "-x", "OTHER": "1"})
                os.putenv("XLA_FLAGS", "-x")
            """,
    })
    findings, rules = rules_fired(root)
    assert "MLOS008" in rules
    assert sum(f.rule == "MLOS008" for f in findings) == 4


def test_mlos008_silent_on_merged_twin(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/good_flags.py": """\
            import os
            from repro.core.compilecache import child_env, force_host_device_count

            def prep():
                force_host_device_count(512)
                env = child_env()
                env["XLA_FLAGS"] = "-x"        # plain dict, not os.environ
                os.environ["PYTHONPATH"] = "src"  # a different variable entirely
                return env
            """,
        # the component itself is the sanctioned home for the raw write
        "src/repro/core/compilecache.py": """\
            import os

            def apply(flags):
                os.environ["XLA_FLAGS"] = flags
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS008" not in rules


# =============================================================================
# Escape hatch: # mloslint: disable=
# =============================================================================
_FORK = """\
    import os

    def f():
        os.fork(){trailing}
"""


def test_justified_disable_suppresses(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": _FORK.format(
            trailing="  # mloslint: disable=MLOS004 -- sandboxed helper with no jax runtime"),
    })
    findings, rules = rules_fired(root)
    assert rules == set(), [f.render() for f in findings]


def test_unjustified_disable_is_ignored_and_reported(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": _FORK.format(trailing="  # mloslint: disable=MLOS004"),
    })
    _, rules = rules_fired(root)
    assert rules == {"MLOS004", "MLOS000"}  # not honored + flagged as malformed


def test_standalone_disable_targets_next_code_line(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": """\
            import os

            def f():
                # mloslint: disable=MLOS004 -- justification long enough here, and it
                # continues over a second comment line before the governed code
                os.fork()
            """,
    })
    _, rules = rules_fired(root)
    assert rules == set()


def test_file_level_disable(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": """\
            # mloslint: disable-file=MLOS004 -- whole module runs pre-jax by construction
            import os

            def f():
                os.fork()

            def g():
                os.fork()
            """,
    })
    _, rules = rules_fired(root)
    assert rules == set()


def test_disable_only_covers_named_rule(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": """\
            import os

            def f():
                os.fork()  # mloslint: disable=MLOS001 -- wrong rule id on purpose here
            """,
    })
    _, rules = rules_fired(root)
    assert "MLOS004" in rules


# =============================================================================
# Baseline ratchet
# =============================================================================
def _finding(rule="MLOS004", path="src/repro/a.py", snippet="os.fork()"):
    return Finding(rule=rule, path=path, line=4, col=4,
                   message="planted", snippet=snippet)


def test_ratchet_tolerates_baselined_flags_new(tmp_path):
    old, new = _finding(), _finding(rule="MLOS001", snippet="import bad")
    bl = tmp_path / "baseline.json"
    save_baseline(bl, [old])
    r = apply_ratchet([old, new], load_baseline(bl))
    assert [f.rule for f in r.new] == ["MLOS001"]
    assert [f.rule for f in r.grandfathered] == ["MLOS004"]
    assert r.stale == []


def test_ratchet_reports_stale_entries(tmp_path):
    gone = _finding()
    bl = tmp_path / "baseline.json"
    save_baseline(bl, [gone])
    r = apply_ratchet([], load_baseline(bl))
    assert r.stale == [gone.fingerprint]


def test_fingerprint_survives_line_shifts():
    a = _finding()
    b = Finding(rule=a.rule, path=a.path, line=99, col=0,
                message=a.message, snippet=a.snippet)
    assert a.fingerprint == b.fingerprint


def test_update_baseline_refuses_growth(tmp_path, capsys):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": "import os\n\n\ndef f():\n    os.fork()\n",
    })
    bl = root / "baseline.json"
    save_baseline(bl, [_finding(rule="MLOS001", snippet="something else")])
    rc = lint_main(["--root", str(root), "--baseline", str(bl), "--update-baseline"])
    assert rc == 1
    assert "refusing to grow" in capsys.readouterr().err
    # the baseline file was not rewritten
    assert load_baseline(bl) and "MLOS001" in next(iter(load_baseline(bl).values()))["rule"]
    # explicit override is the only way in
    rc = lint_main(["--root", str(root), "--baseline", str(bl),
                    "--update-baseline", "--allow-growth"])
    assert rc == 0
    assert any(r["rule"] == "MLOS004" for r in load_baseline(bl).values())


def test_cli_exit_codes_and_json_report(tmp_path):
    root = mini_repo(tmp_path, {
        "src/repro/a.py": "import os\n\n\ndef f():\n    os.fork()\n",
    })
    report = tmp_path / "out" / "report.json"
    rc = lint_main(["--root", str(root), "--no-baseline",
                    "--json", str(report), "-q"])
    assert rc == 1
    data = json.loads(report.read_text())
    assert data["total"] == 1 and data["new"][0]["rule"] == "MLOS004"
    assert data["new"][0]["fingerprint"]
    # baselining the finding brings the exit code to 0
    bl = root / "baseline.json"
    rc = lint_main(["--root", str(root), "--baseline", str(bl),
                    "--update-baseline", "--allow-growth"])
    assert rc == 0
    rc = lint_main(["--root", str(root), "--baseline", str(bl), "-q"])
    assert rc == 0


# =============================================================================
# The real repo is clean
# =============================================================================
def test_whole_repo_zero_unbaselined_findings():
    report = run_lint(REPO_ROOT, baseline_path=REPO_ROOT / "mloslint_baseline.json")
    assert report.files_scanned > 50
    assert report.ratchet.new == [], "un-baselined findings:\n" + "\n".join(
        f.render() for f in report.ratchet.new)
    assert report.ratchet.stale == [], (
        "baseline entries no longer fire; shrink mloslint_baseline.json: "
        f"{report.ratchet.stale}")


def test_planted_violation_breaks_the_repo_run(tmp_path):
    # same rules, scratch tree: a fresh violation must flip the verdict
    root = mini_repo(tmp_path, {
        "src/repro/sneaky.py": "from jax.experimental.shard_map import shard_map\n",
    })
    report = run_lint(root, baseline_path=root / "mloslint_baseline.json")
    assert not report.ok and report.ratchet.new[0].rule == "MLOS001"
