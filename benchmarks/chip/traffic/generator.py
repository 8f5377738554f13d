"""The benchmark's traffic generator: one CORe50-NC-style labelled stream
and one Poisson timeline of labelled batches and inference requests,
both made from the seed.

The stream (`nc_benchmark`, `_ImageWorld`) and the timeline
(`build_timeline`, `interarrivals`) are copies of the runtime's own
generators (`repro.data.streams`, `repro.data.arrivals`), kept here so
that a change to the program cannot move the yardstick. A test checks
that the copies yield what the originals yield. A traffic mix is a JSON
file of parameters beside this module; `make_traffic` reads one.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Scenario:
    index: int
    train_batches: List[dict]
    val: dict
    test: dict
    classes: List[int]
    kind: str = "nc"


@dataclass
class ContinualBenchmark:
    name: str
    scenarios: List[Scenario]
    num_classes: int
    modality: str = "image"

    @property
    def num_scenarios(self) -> int:
        return len(self.scenarios)


@dataclass(frozen=True)
class Arrival:
    """One timeline event: a labelled batch ("data") or a request
    ("inference") of scenario `scenario`, the `index`-th of its kind."""
    time: float
    kind: str
    scenario: int
    index: int


class _ImageWorld:
    """Latent class prototypes + per-scenario appearance transforms."""

    def __init__(self, num_classes: int, size: int, seed: int):
        rng = np.random.default_rng(seed)
        self.size = size
        self.rng = rng
        base = rng.normal(0, 1, (num_classes, 8, 8, 3))
        self.protos = np.stack([_upsample(b, size) for b in base])

    def sample(self, cls: np.ndarray, transform_id: int, n_noise: float = 0.35):
        rng = self.rng
        imgs = self.protos[cls] + rng.normal(0, n_noise, (len(cls), self.size, self.size, 3))
        if transform_id:
            t = np.random.default_rng(1000 + transform_id)
            bright = t.uniform(0.5, 1.6)
            mix = np.eye(3) + t.normal(0, 0.25, (3, 3))
            roll = t.integers(0, self.size // 2)
            imgs = (imgs * bright) @ mix
            imgs = np.roll(imgs, roll, axis=2)
        return imgs.astype(np.float32)


def _upsample(x: np.ndarray, size: int) -> np.ndarray:
    reps = size // x.shape[0]
    return np.repeat(np.repeat(x, reps, axis=0), reps, axis=1)


def _make_image_scenario(world: _ImageWorld, idx: int, classes: List[int],
                         transform_id: int, batches: int, batch_size: int,
                         test_size: int, kind: str, seed: int) -> Scenario:
    rng = np.random.default_rng(seed)
    train_batches = []
    n_train = batches * batch_size
    cls = rng.choice(classes, n_train + max(test_size, 8))
    imgs = world.sample(cls, transform_id)
    val_n = max(batch_size, int(0.05 * n_train))
    test = {"images": imgs[n_train:], "labels": cls[n_train:].astype(np.int32)}
    val = {"images": imgs[:val_n], "labels": cls[:val_n].astype(np.int32)}
    for b in range(batches):
        sl = slice(b * batch_size, (b + 1) * batch_size)
        train_batches.append({"images": imgs[sl], "labels": cls[sl].astype(np.int32)})
    return Scenario(index=idx, train_batches=train_batches, val=val, test=test,
                    classes=list(classes), kind=kind)


def nc_benchmark(num_classes=10, num_scenarios=5, batches=24, batch_size=16,
                 image_size=32, test_size=64, seed=0) -> ContinualBenchmark:
    """Class-incremental: scenario s adds `num_classes/num_scenarios` new
    classes; train data covers the new classes, test covers all seen."""
    world = _ImageWorld(num_classes, image_size, seed)
    per = num_classes // num_scenarios
    scenarios = []
    seen: List[int] = []
    for s in range(num_scenarios):
        new = list(range(s * per, (s + 1) * per))
        seen = seen + new
        sc = _make_image_scenario(world, s, new if s else seen, 0, batches,
                                  batch_size, test_size, "nc", seed + 7 * s + 1)
        rng = np.random.default_rng(seed + 91 * s)
        cls = rng.choice(seen, test_size)
        sc.test = {"images": world.sample(cls, 0),
                   "labels": cls.astype(np.int32)}
        scenarios.append(sc)
    return ContinualBenchmark("nc", scenarios, num_classes)


def interarrivals(dist: str, n: int, mean_gap: float,
                  rng: np.random.Generator,
                  trace: Sequence[float] = ()) -> np.ndarray:
    """`n` inter-arrival gaps with the given mean (paper §V-D
    distributions)."""
    if n <= 0:
        return np.zeros(0)
    if dist == "poisson":
        return rng.exponential(mean_gap, n)
    if dist == "uniform":
        return rng.uniform(0.0, 2.0 * mean_gap, n)
    if dist == "normal":
        return np.clip(rng.normal(mean_gap, 0.3 * mean_gap, n), 0.01 * mean_gap, None)
    if dist == "trace":
        t = np.asarray(trace if len(trace) else _DEFAULT_TRACE, np.float64)
        t = t / t.mean() * mean_gap
        reps = int(np.ceil(n / t.size))
        return np.tile(t, reps)[:n]
    raise ValueError(dist)


_DEFAULT_TRACE = [0.2, 0.1, 0.15, 0.1, 3.0, 0.2, 0.1, 0.1, 4.5, 0.3,
                  0.1, 0.2, 0.1, 0.1, 6.0, 0.5, 0.2, 0.1, 2.5, 0.2]


def build_timeline(*, num_scenarios: int, batches_per_scenario: int,
                   inferences_total: int, scenario_span: float = 100.0,
                   data_dist: str = "poisson", inf_dist: str = "poisson",
                   seed: int = 0) -> List[Arrival]:
    """Merged, time-sorted event list. Scenario s occupies
    [s*span, (s+1)*span); its batches arrive inside it; requests arrive
    over the whole horizon."""
    rng = np.random.default_rng(seed)
    events: List[Arrival] = []
    for s in range(num_scenarios):
        gaps = interarrivals(data_dist, batches_per_scenario,
                             scenario_span / max(batches_per_scenario, 1) * 0.9,
                             rng)
        t = s * scenario_span + np.cumsum(gaps)
        t = np.minimum(t, (s + 1) * scenario_span - 1e-3)
        for i, ti in enumerate(t):
            events.append(Arrival(float(ti), "data", s, i))
    horizon = num_scenarios * scenario_span
    gaps = interarrivals(inf_dist, inferences_total,
                         horizon / max(inferences_total, 1), rng)
    t = np.cumsum(gaps)
    t = t * (horizon / max(t[-1], 1e-9)) if len(t) else t
    for i, ti in enumerate(t):
        s = min(int(ti // scenario_span), num_scenarios - 1)
        events.append(Arrival(float(ti), "inference", s, i))
    events.sort(key=lambda e: (e.time, e.kind))
    return events


def load_mix(name: str) -> dict:
    """The parameters of traffic mix `name` (`traffic/<name>.json`)."""
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def permuted(timeline: List[Arrival], seed: int, *, num_scenarios: int,
             scenario_span: float) -> List[Arrival]:
    """The same arrivals in another order: within each scenario the gaps
    between labelled batches, and over the horizon the gaps between
    requests, are shuffled by `seed`. Every seed then streams the same
    set of gaps, and the same number of labelled batches per scenario."""
    rng = np.random.default_rng(seed)
    out: List[Arrival] = []
    for s in range(num_scenarios):
        t = np.array([e.time for e in timeline
                      if e.kind == "data" and e.scenario == s])
        start = s * scenario_span
        gaps = np.diff(np.concatenate([[start], t]))
        new = start + np.cumsum(rng.permutation(gaps))
        out.extend(Arrival(float(x), "data", s, i) for i, x in enumerate(new))
    t = np.array([e.time for e in timeline if e.kind == "inference"])
    gaps = np.diff(np.concatenate([[0.0], t]))
    new = np.cumsum(rng.permutation(gaps))
    out.extend(Arrival(float(x), "inference",
                       min(int(x // scenario_span), num_scenarios - 1), i)
               for i, x in enumerate(new))
    out.sort(key=lambda e: (e.time, e.kind))
    return out


def make_traffic(mix: dict, *, num_classes: int, image_size: int, seed: int):
    """(stream, timeline) of one session. The images come from `seed`;
    the arrivals are drawn once from the mix's own `arrivals.seed` and put
    in the order `seed` gives (`permuted`). Scenario 0 of the stream is
    pretraining; the timeline streams scenarios 1.. with scenario ids
    shifted by one, as the runtime's own default timeline does."""
    stream = mix["stream"]
    if stream["benchmark"] != "nc":
        raise ValueError(f"unknown stream benchmark {stream['benchmark']!r}")
    bench = nc_benchmark(num_classes=num_classes,
                         num_scenarios=stream["num_scenarios"],
                         batches=stream["batches_per_scenario"],
                         batch_size=stream["batch_size"],
                         image_size=image_size,
                         test_size=stream["test_size"], seed=seed)
    arrivals = mix["arrivals"]
    streamed = stream["num_scenarios"] - 1
    timeline = build_timeline(
        num_scenarios=streamed,
        batches_per_scenario=stream["batches_per_scenario"],
        inferences_total=(streamed * stream["batches_per_scenario"]
                          * arrivals["requests_per_batch"]),
        scenario_span=arrivals["scenario_span"],
        data_dist=arrivals["data_dist"], inf_dist=arrivals["inf_dist"],
        seed=arrivals["seed"])
    timeline = permuted(timeline, seed, num_scenarios=streamed,
                        scenario_span=arrivals["scenario_span"])
    return bench, [Arrival(e.time, e.kind, e.scenario + 1, e.index)
                   for e in timeline]
