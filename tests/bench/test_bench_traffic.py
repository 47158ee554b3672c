"""The traffic generator: seeded, clipped, at the file's mean rate, and the
same work for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest
import benchcells  # noqa: F401  (puts bench/ and src/ on the path)
from benchkit import traffic

MIXES = sorted(p.stem for p in (Path(__file__).resolve().parents[2] / "bench" / "traffic").glob(
    "*.json") if json.loads(p.read_text())["kind"] == "serve")


def _mix(name):
    return json.loads((Path(__file__).resolve().parents[2] / "bench" / "traffic" /
                       f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_arrivals(name):
    a = traffic.requests(_mix(name), 2**31 + 7, 5.0, 50304)
    b = traffic.requests(_mix(name), 2**31 + 7, 5.0, 50304)
    assert [r.at for r in a] == [r.at for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.budget == y.budget for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_their_ranges(name):
    mix = _mix(name)
    reqs = traffic.requests(mix, 123, 20.0, 1000)
    lens = [len(r.prompt) for r in reqs]
    outs = [r.budget for r in reqs]
    assert mix["prompt_len"]["min"] <= min(lens) and max(lens) <= mix["prompt_len"]["max"]
    assert mix["output_len"]["min"] <= min(outs) and max(outs) <= mix["output_len"]["max"]
    assert all(((r.prompt >= traffic.FIRST_ID) & (r.prompt < 1000)).all() for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_mean_rate_matches_the_file(name):
    mix = _mix(name)
    seconds = 30.0
    reqs = traffic.requests(mix, 5, seconds, 1000)
    at = np.array([r.at for r in reqs])
    assert (np.diff(at) >= 0).all() and at[0] == 0.0 and at[-1] < seconds
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * seconds)


def test_every_seed_gets_the_files_schedule():
    mix = _mix(MIXES[0])
    a = traffic.requests(mix, 1, 10.0, 1000)
    b = traffic.requests(mix, 2, 10.0, 1000)
    assert [(r.at, len(r.prompt), r.budget) for r in a] == [(r.at, len(r.prompt), r.budget)
                                                            for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = traffic.requests(dict(mix, schedule_seed=mix["schedule_seed"] + 1), 1, 10.0, 1000)
    assert sorted(r.budget for r in other) == sorted(r.budget for r in a)
    assert [r.budget for r in other] != [r.budget for r in a]


def test_burstier_arrivals_spread_their_gaps():
    base = {"rate_per_s": 10.0}
    poisson = traffic.gaps(dict(base, shape=1.0), 200, 20.0)
    bursty = traffic.gaps(dict(base, shape=0.25), 200, 20.0)
    assert np.isclose(poisson.sum(), 20.0) and np.isclose(bursty.sum(), 20.0)
    assert bursty.std() > 1.5 * poisson.std()


def test_packed_batches_fill_rows_with_documents():
    job = {"batch": 4, "seq_len": 256,
           "doc_len": {"dist": "pareto", "x_min": 8, "alpha": 1.2, "median": 14, "min": 4,
                       "max": 2000}}
    toks, labels = traffic.packed_batch(job, 9, 0, 500)
    assert toks.shape == labels.shape == (4, 256)
    assert (labels[:, :-1] == toks[:, 1:]).all()
    assert (toks == traffic.EOS).sum() >= 4
    again, _ = traffic.packed_batch(job, 9, 0, 500)
    other, _ = traffic.packed_batch(job, 9, 1, 500)
    assert (again == toks).all() and not (other == toks).all()
    assert len({r.tobytes() for r in toks}) == 4
