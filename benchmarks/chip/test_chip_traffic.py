"""The benchmark's copy of the traffic generators yields exactly what the
runtime's own generators (`repro.data.streams`, `repro.data.arrivals`)
yield, so that the copy is the same yardstick."""
import os

import numpy as np
import pytest

import harness

from repro.data import arrivals, streams

gen = harness.load_module(os.path.join(harness.HERE, "traffic", "generator.py"),
                          "traffic_generator")


def _same_split(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_stream_matches_the_runtime_generator(seed):
    kw = dict(num_classes=10, num_scenarios=5, batches=3, batch_size=4,
              image_size=16, test_size=8, seed=seed)
    mine, theirs = gen.nc_benchmark(**kw), streams.nc_benchmark(**kw)
    assert mine.num_scenarios == theirs.num_scenarios
    for a, b in zip(mine.scenarios, theirs.scenarios):
        assert a.classes == b.classes
        assert len(a.train_batches) == len(b.train_batches)
        for x, y in zip(a.train_batches, b.train_batches):
            _same_split(x, y)
        _same_split(a.val, b.val)
        _same_split(a.test, b.test)


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 13])
@pytest.mark.parametrize("dist", ["poisson", "trace"])
def test_timeline_matches_the_runtime_generator(seed, dist):
    kw = dict(num_scenarios=4, batches_per_scenario=6, inferences_total=30,
              data_dist=dist, inf_dist=dist, seed=seed)
    mine, theirs = gen.build_timeline(**kw), arrivals.build_timeline(**kw)
    assert [(e.time, e.kind, e.scenario, e.index) for e in mine] == \
        [(e.time, e.kind, e.scenario, e.index) for e in theirs]


def test_mix_file_makes_the_session_stream():
    mix = gen.load_mix("nc.etuner")
    bench, timeline = gen.make_traffic(mix, num_classes=10, image_size=16,
                                       seed=9)
    s = mix["stream"]
    streamed = s["num_scenarios"] - 1
    data = [e for e in timeline if e.kind == "data"]
    reqs = [e for e in timeline if e.kind == "inference"]
    assert bench.num_scenarios == s["num_scenarios"]
    assert len(data) == streamed * s["batches_per_scenario"]
    assert len(reqs) == len(data) * mix["arrivals"]["requests_per_batch"]
    assert {e.scenario for e in timeline} == set(range(1, streamed + 1))
    assert all(b["images"].shape == (s["batch_size"], 16, 16, 3)
               for b in bench.scenarios[1].train_batches)


def test_every_seed_streams_the_same_arrivals_in_another_order():
    mix = gen.load_mix("nc.etuner")
    a = gen.make_traffic(mix, num_classes=10, image_size=16, seed=1)[1]
    b = gen.make_traffic(mix, num_classes=10, image_size=16, seed=2)[1]

    def gaps(tl, kind, scenario=None):
        t = [e.time for e in tl if e.kind == kind
             and (scenario is None or e.scenario == scenario)]
        start = 0.0 if scenario is None else \
            (scenario - 1) * mix["arrivals"]["scenario_span"]
        return np.diff(np.concatenate([[start], t]))

    assert [e.time for e in a] != [e.time for e in b]
    np.testing.assert_allclose(np.sort(gaps(a, "inference")),
                               np.sort(gaps(b, "inference")), atol=1e-9)
    for s in range(1, mix["stream"]["num_scenarios"]):
        np.testing.assert_allclose(np.sort(gaps(a, "data", s)),
                                   np.sort(gaps(b, "data", s)), atol=1e-9)
    for tl in (a, b):
        span = mix["arrivals"]["scenario_span"]
        assert all((e.scenario - 1) * span <= e.time <= e.scenario * span
                   for e in tl)
