"""The trace reduction: busy time as a union, module times, and idle gaps
named by the innermost benchmark span around them."""
import pytest
import benchcells  # noqa: F401  (puts bench/ and src/ on the path)
from benchkit import trace as T


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]


def test_module_kind_drops_execution_ids():
    assert T.module_kind("jit__fused_step(1234)") == "jit__fused_step"
    assert T.module_kind("jit_train_step.3") == "jit_train_step"
    assert T.module_kind("jit__lambda") == "jit__lambda"


def test_reduce_a_made_up_window():
    ms = 1e6
    spans = [("bench.window", 0, 100 * ms), ("bench.step", 0, 60 * ms),
             ("bench.submit", 58 * ms, 68 * ms), ("bench.step", 68 * ms, 100 * ms)]
    ops = [("%while.2", 0, 30 * ms), ("fusion.1", 0, 20 * ms), ("fusion.2", 10 * ms, 30 * ms), ("dot.3", 40 * ms, 55 * ms),
           ("fusion.1", 70 * ms, 90 * ms), ("late", 95 * ms, 120 * ms)]
    mods = [("jit__fused_step(1)", 0, 30 * ms), ("jit__lambda(2)", 40 * ms, 55 * ms),
            ("jit__fused_step(3)", 70 * ms, 90 * ms)]
    red = T.reduce(spans, [{"name": "/device:TPU:0", "modules": mods, "ops": ops}])
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.030 + 0.015 + 0.020 + 0.005)
    assert red["modules"]["jit__fused_step"] == {"count": 2, "seconds": pytest.approx(0.05)}
    assert T.module_seconds(red, "fused_step") == (2, pytest.approx(0.05))
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.04)]
    # gaps: 30-40 in the first step, 55-70 spans the submit, 90-95 in the second step
    gaps = dict((round(t * 1e3), n) for n, t in red["idle_gaps"])
    assert gaps == {10: "bench.step", 15: "bench.submit", 5: "bench.step"}


def test_no_device_work_gives_nothing():
    spans = [("bench.window", 0, 10)]
    assert T.reduce(spans, []) is None
    assert T.reduce(spans, [{"name": "/device:TPU:0", "modules": [], "ops": []}]) is None


def test_reduce_a_trace_recorded_on_the_chip():
    """One second of `olmo1b-chat` traced on a TPU v5e (``--trace 1``)."""
    from pathlib import Path

    path = Path(__file__).resolve().parent / "data" / "chat_1s.xplane.pb"
    spans, devices = T.read_planes(str(path))
    assert [d["name"] for d in devices] == ["/device:TPU:0"]
    assert {n for n, _, _ in spans} >= {"bench.window", "bench.step"}
    red = T.reduce(spans, devices)
    assert 0.9 < red["window_s"] < 2.0
    assert 0 < red["busy_s"] <= red["window_s"]
    n, t = T.module_seconds(red, "fused_step")
    assert n >= 4 and t > 0
    assert all(not T.CONTAINER.match(name) and " = " not in name for name, _ in red["device_ops"])
    assert len(red["idle_gaps"]) <= 10
