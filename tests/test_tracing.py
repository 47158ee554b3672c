"""The serve engine's spans: one admitting step traced on the CPU gives the
span tree the benchmark reads, with the ids as event stats; the profiler
leaves tokens and host syncs as they are; spans open only through
``telemetry.span``."""
import glob
import os
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import telemetry
from repro.models import model as M
from repro.runtime import serve_loop
from repro.runtime.serve_loop import BatchedServer

CAPACITY = 32
SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.decode", "serve.sync",
         "serve.fetch", "serve.telemetry")


@pytest.fixture(scope="module")
def served():
    import jax
    cfg = get_config("olmo-1b").reduced().validate()
    return M.init_params(jax.random.PRNGKey(0), cfg), cfg


def _server(served, sync_interval=3):
    params, cfg = served
    return BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                         settings={"max_batch": 2, "sync_interval": sync_interval})


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 250, size=int(k)).astype(np.int32)
            for k in rng.integers(3, 14, size=n)]


def _traced(log_dir, fn):
    """Run ``fn`` under the profiler (host spans only); the serve.* events."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        out = fn()
    path = sorted(glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = float(ev.start_ns)
                    events.append((ev.name, s, s + float(ev.duration_ns), dict(ev.stats)))
    return out, events


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_one_admitting_step_gives_the_span_tree(served, tmp_path):
    srv = _server(served)
    srv.submit(_prompts(1, seed=1)[0])
    srv.step()                                 # compiles outside the trace
    prompt = _prompts(1, seed=2)[0]
    rid = srv.submit(prompt)
    _, ev = _traced(tmp_path, srv.step)
    by = {}
    for e in ev:
        by.setdefault(e[0], []).append(e)
    # one of each per step: no span around per-token work
    assert {n: len(v) for n, v in by.items()} == {n: 1 for n in SPANS}
    step, admit, prefill, decode, sync, fetch, tele = (by[n][0] for n in SPANS)
    assert _inside(admit, step) and _inside(prefill, admit)
    assert all(_inside(e, step) for e in (decode, sync, tele))
    assert _inside(fetch, sync)
    assert not any(_inside(e, admit) for e in (decode, sync, tele))
    assert step[3] == {"sync": 1}
    assert admit[3] == {"queued": 1}
    assert decode[3] == {"n": 3}
    args = prefill[3]
    assert set(args) == {"rid", "n_prompt", "width", "wait_us"}
    assert (args["rid"], args["n_prompt"]) == (rid, len(prompt))
    assert args["width"] == srv._width_of(len(prompt)) >= len(prompt)
    assert args["wait_us"] >= 0


def test_decode_span_counts_the_kv_blocks_read(tmp_path):
    """With KV heads the decode kernel can copy (8 of 128; the reduced
    config's heads of 16 have no block, and its spans no counter),
    ``serve.decode`` carries ``kv_read_share``: 2 live slots of 4, each
    reading its whole 32-token row, one block, at each of 3 decodes."""
    import dataclasses

    import jax
    cfg = dataclasses.replace(get_config("olmo-1b").reduced(), n_heads=8, n_kv_heads=8,
                              head_dim=128).validate()
    srv = BatchedServer(M.init_params(jax.random.PRNGKey(0), cfg), cfg, capacity=CAPACITY,
                        eos_id=-1, settings={"max_batch": 4, "sync_interval": 3})
    srv.submit(_prompts(1, seed=1)[0])
    srv.step()                                 # compiles outside the trace
    srv.submit(_prompts(1, seed=2)[0])
    _, ev = _traced(tmp_path, srv.step)
    (decode,) = [e for e in ev if e[0] == "serve.decode"]
    assert decode[3] == {"n": 3, "kv_read_share": 0.5}


def test_a_step_with_nothing_to_admit_has_no_prefill(served, tmp_path):
    srv = _server(served)
    srv.submit(_prompts(1, seed=3)[0], budget=20)
    srv.step()
    _, ev = _traced(tmp_path, srv.step)
    assert sorted(e[0] for e in ev) == sorted(n for n in SPANS if n != "serve.prefill")
    assert [e[3] for e in ev if e[0] == "serve.admit"] == [{"queued": 0}]


def test_profiler_leaves_tokens_and_host_syncs_unchanged(served, tmp_path, monkeypatch):
    prompts = _prompts(5, seed=4)
    real = serve_loop._host_fetch
    calls = []

    def counted(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(serve_loop, "_host_fetch", counted)

    def serve():
        srv = _server(served, sync_interval=4)
        for p in prompts:
            srv.submit(p)
        srv.run(max_new_tokens=9)
        return srv

    plain = serve()
    n_plain = len(calls)
    calls.clear()
    traced, ev = _traced(tmp_path, serve)
    assert {r.rid: r.tokens for r in traced.results.values()} == \
        {r.rid: r.tokens for r in plain.results.values()}
    # one _host_fetch per sync_interval decode steps, traced or not
    assert len(calls) == n_plain == plain.decode_syncs == traced.decode_syncs
    assert plain.decode_steps == 4 * plain.decode_syncs
    assert sum(e[0] == "serve.fetch" for e in ev) == traced.decode_syncs
    assert sum(e[0] == "serve.prefill" for e in ev) == len(prompts)


def test_span_is_the_profilers_annotation():
    from jax.profiler import TraceAnnotation

    with telemetry.span("serve.test", rid=1) as s:
        assert isinstance(s, TraceAnnotation)


def test_src_opens_spans_only_through_telemetry_span():
    src = Path(serve_loop.__file__).resolve().parents[1]
    users = sorted(str(p.relative_to(src)) for p in src.rglob("*.py")
                   if any(k in p.read_text() for k in ("TraceAnnotation", "named_scope",
                                                        "TraceMe", "annotate_function")))
    assert users == ["core/telemetry.py"]
