"""The readers of the serve engine's own spans: admission wait, device idle
per step, prefill pads, and the idle split by the innermost span, on a
made-up window and on one second of ``olmo1b-chat`` traced on a TPU v5e."""
from pathlib import Path

import pytest
import benchcells  # noqa: F401  (puts bench/ and src/ on the path)
from benchkit import spans as S
from benchkit import trace as T

MS = 1e6
DATA = Path(__file__).resolve().parent / "data"


def _made_up():
    """Two steps in a 100 ms window: the first admits two requests."""
    sp = [S.Span("serve.step", 0, 60 * MS, {"sync": 0}),
          S.Span("serve.admit", 0, 20 * MS, {"queued": 2}),
          S.Span("serve.prefill", 1 * MS, 9 * MS, {"rid": 0, "n_prompt": 100, "width": 128,
                                                     "wait_us": 4000}),
          S.Span("serve.prefill", 10 * MS, 19 * MS, {"rid": 1, "n_prompt": 2000, "width": 1024,
                                                      "wait_us": 12000}),
          S.Span("serve.decode", 20 * MS, 22 * MS, {"n": 4}),
          S.Span("serve.sync", 22 * MS, 58 * MS, {}),
          S.Span("serve.fetch", 22 * MS, 50 * MS, {}),
          S.Span("serve.telemetry", 58 * MS, 60 * MS, {}),
          S.Span("serve.step", 70 * MS, 100 * MS, {"sync": 1}),
          S.Span("serve.admit", 70 * MS, 71 * MS, {"queued": 0}),
          S.Span("serve.decode", 71 * MS, 72 * MS, {"n": 4}),
          S.Span("serve.sync", 72 * MS, 98 * MS, {}),
          S.Span("serve.fetch", 72 * MS, 97 * MS, {}),
          S.Span("serve.telemetry", 98 * MS, 100 * MS, {})]
    busy = [(5 * MS, 30 * MS), (45 * MS, 52 * MS), (75 * MS, 95 * MS)]
    return S.Traced(0, 100 * MS, sp, [S.gaps(busy, 0, 100 * MS)])


def test_gaps_and_overlap():
    assert S.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert S.gaps([], 0, 4) == [(0, 4)]
    assert S.overlap([(0, 5), (6, 9)], [(4, 7), (8, 20)]) == 1 + 1 + 1


def test_innermost_names_every_instant():
    parts = S.innermost(_made_up().spans, 0, 100 * MS)
    assert parts[0] == (0, 1 * MS, "serve.admit")
    assert (1 * MS, 9 * MS, "serve.prefill") in parts
    assert (22 * MS, 50 * MS, "serve.fetch") in parts
    assert (50 * MS, 58 * MS, "serve.sync") in parts
    assert (60 * MS, 70 * MS, S.OUTSIDE) in parts
    assert sum(b - a for a, b, _ in parts) == 100 * MS
    assert all(parts[i][1] == parts[i + 1][0] for i in range(len(parts) - 1))


def test_readers_on_a_made_up_window():
    tr = _made_up()
    # waits 4 and 12 ms: p95 by linear interpolation
    assert S.admit_wait_p95_ms(tr) == pytest.approx(4 + 0.95 * 8)
    # pads: 28 of 128, and none of the 1024 a long prompt fills
    assert S.prefill_pad_share(tr) == pytest.approx(100 * 28 / (128 + 1024))
    # idle inside steps: 0-5, 30-45, 52-60 in the first; 70-75, 95-100 in the second
    assert S.step_idle_ms(tr) == pytest.approx((5 + 15 + 8 + 5 + 5) / 2)
    split = S.split(tr.idle[0], S.innermost(tr.spans, tr.lo, tr.hi))
    assert {k: round(v / MS, 6) for k, v in split.items()} == {
        "serve.admit": 1 + 1, "serve.prefill": 4, "serve.decode": 1, "serve.fetch": 15 + 3 + 2,
        "serve.sync": 6 + 1, "serve.telemetry": 2 + 2, S.OUTSIDE: 10}


def test_notes_name_the_idle_and_the_gaps():
    lines = S.notes(_made_up())
    assert lines[0].startswith("spans: device idle 48.000 ms of the 100.000 ms window")
    assert "serve.fetch 20.000 ms (41.7 %)" in lines[0] and f"{S.OUTSIDE} 10.000 ms" in lines[0]
    # each gap by the span that holds most of it, with the step it starts in
    assert lines[1] == ("spans: longest idle gaps: 23.000 ms in outside the engine (sync 0), "
                        "15.000 ms in serve.fetch (sync 0), 5.000 ms in serve.prefill (sync 0), "
                        "5.000 ms in serve.fetch (sync 1)")
    assert "serve.fetch 28.000 ms" in lines[2] and "serve.step 60.000 ms" in lines[2]
    assert lines[3] == "compile: no window to count compiles in"


def test_no_program_spans_gives_nothing():
    tr = S.Traced(0, 10 * MS, [], [[(0, 10 * MS)]])
    assert S.admit_wait_p95_ms(tr) is None
    assert S.prefill_pad_share(tr) is None
    assert S.step_idle_ms(tr) is None
    assert S.notes(tr) == ["spans: the program opened no serve.* span in the window"]


def test_untraced_run_reads_nothing():
    ctx = {"trace": None, "cell": None, "rec": {}}
    assert S.of(ctx, S.step_idle_ms) is None


def test_a_run_finds_its_own_trace(tmp_path, monkeypatch):
    """The harness's trace directory, checked against the reduced trace's window."""
    prof = tmp_path / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes((DATA / "chat_1s_spans.xplane.pb").read_bytes())
    monkeypatch.setattr("sys.argv", ["bench/run.py", "--keep-trace", str(tmp_path)])
    assert S.trace_dirs("olmo1b-chat")[0] == str(tmp_path)
    red = T.reduce(*T.read_planes(str(prof / "host.xplane.pb")))

    class Cell:
        name = "olmo1b-chat"

    ctx = {"trace": red, "cell": Cell, "rec": {}}
    assert S.of(ctx, S.prefill_pad_share) == pytest.approx(22.8515625)
    assert S.of(ctx, S.step_idle_ms) == pytest.approx(2.12438775)     # read once, kept
    assert isinstance(ctx["spans"], S.Traced)
    other = {"trace": dict(red, window_s=red["window_s"] + 1e-9), "cell": Cell, "rec": {}}
    assert S.of(other, S.prefill_pad_share) is None                   # another run's window


def test_clock_offset_puts_no_module_before_its_enqueue():
    enqueued = {1: 100.0, 2: 500.0, 3: 900.0}
    # module 1 shows 10 ns before its enqueue; 2 queued behind others; 4 has no enqueue
    assert S.clock_offset(enqueued, [(1, 90.0), (2, 800.0), (3, 880.0), (4, 0.0)]) == 20.0
    assert S.clock_offset(enqueued, [(2, 800.0)]) == 0.0
    assert S.clock_offset({}, [(1, 5.0)]) == 0.0


def test_readers_on_a_trace_recorded_on_the_chip():
    """One second of ``olmo1b-chat`` traced on a TPU v5e (``--trace 1``,
    seed 3000061006) with the engine's spans.  Its modules show up to 1.290
    ms before the host enqueued them, so the device's times move by that
    much onto the host's clock (the run itself printed 2.19122825 for
    ``step_idle_ms.tpot``, before that correction)."""
    tr = S.read(str(DATA / "chat_1s_spans.xplane.pb"))
    assert tr.offset == [pytest.approx(1.289763e6)]
    steps = tr.named("serve.step")
    assert len(steps) == 4 and len(tr.named("serve.prefill")) == 2
    for name in ("serve.admit", "serve.decode", "serve.sync", "serve.fetch", "serve.telemetry"):
        assert len(tr.named(name)) == len(steps)
    for s in tr.spans:
        if s.name != "serve.step":
            assert any(p.start <= s.start and s.end <= p.end for p in steps), s
    for p in tr.named("serve.prefill"):
        assert p.args["width"] >= min(p.args["n_prompt"], p.args["width"]) > 0
        assert p.args["wait_us"] >= 0
    assert S.step_idle_ms(tr) == pytest.approx(2.12438775)
    assert S.prefill_pad_share(tr) == pytest.approx(22.8515625)
    assert S.admit_wait_p95_ms(tr) == pytest.approx(4.0019)
    lines = S.notes(tr)
    assert lines[0].startswith("spans: device idle 9.015 ms of the 1120.940 ms window, device "
                               "clock +1.290 ms, by the innermost program span around it: "
                               "serve.fetch 4.367 ms (48.4 %), serve.prefill 2.576 ms (28.6 %)")
    assert lines[1].startswith("spans: longest idle gaps: 2.303 ms in serve.prefill (sync 4), ")
