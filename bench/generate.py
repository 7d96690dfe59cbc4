"""Seeded traffic of the benchmark: the pool of samples a run submits, and
the query stream a query client sends.

A pool is the whole of a run's traffic, made in set-up from the seed: per
tick, the ``(series, chunk)`` deliveries in a fixed order.  Its samples
come from the configuration's data kind, ``bench/data/<kind>.py``.  The
same seed gives the same pool on any machine with the same numpy.

``queries`` is TSBS's query generator for the types a traffic file lists:
hosts drawn without replacement, a window start drawn uniformly over the
data span.  Each type names its engine call and reference by ``op``,
``bench/ops/<op>.py``.

These are the yardstick's own copies: nothing here imports the program, so
a change to ``src/repro/data`` cannot move what a cell measures.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import by_name


@dataclasses.dataclass
class Pool:
    """A run's traffic: ``values[series]`` is each series' whole stream, and
    tick ``k`` delivers ``values[s][offsets[k, s]:offsets[k + 1, s]]`` to
    every series ``s`` with a non-empty slice, in series order."""

    values: list[np.ndarray]
    offsets: np.ndarray  # [ticks + 1, series] int64, cumulative per series

    @property
    def n_series(self) -> int:
        return len(self.values)

    @property
    def n_ticks(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def n_samples(self) -> int:
        return int(self.offsets[-1].sum())

    def tick(self, k: int) -> list[tuple[int, np.ndarray]]:
        lo, hi = self.offsets[k], self.offsets[k + 1]
        return [
            (s, self.values[s][lo[s] : hi[s]])
            for s in np.flatnonzero(hi > lo).tolist()
        ]


def rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per purpose, so that adding one draw never
    shifts another's numbers."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & (2**64 - 1), tag])


def pool_ticks(data: dict, seed: int, ticks: int) -> Pool:
    """``ticks`` ticks of the configuration's traffic."""
    return by_name("data", data["kind"]).pool_ticks(data, seed, ticks)


def make_pool(data: dict, seed: int, samples: float) -> Pool:
    """At least ``samples`` samples of the configuration's traffic."""
    per_tick = by_name("data", data["kind"]).mean_samples_per_tick(data)
    return pool_ticks(data, seed, max(1, int(np.ceil(samples / per_tick))))


@dataclasses.dataclass
class Query:
    """One TSBS query: ``calls`` are its engine calls, each
    ``(series, op, t0, t1, arg)`` over sample indices [t0, t1)."""

    qid: int
    kind: str
    calls: list[tuple[int, str, int, int, float | None]]


def queries(data: dict, mix: dict, seed: int, span: int, stream: str = "queries"):
    """An endless stream of TSBS queries over a corpus of ``span`` samples
    per series.  The types are dealt in seeded rounds that each hold every
    type of ``mix["types"]`` once: a uniform mix whose share of each type
    does not move with the seed.  A type gives the fields it reads (the
    first ``fields`` of the cpu fields, as TSBS takes them), the hosts, the
    range, its ``op`` and ``arg``, and optionally a bucket: with one, a call
    per bucket of the range, else one call over the range."""
    interval = int(data["interval_s"])
    n_fields = len(data["fields"])
    types = mix["types"]
    for qt in types:
        if int(qt["range_s"]) // interval > span:
            raise ValueError(f"query {qt['name']} spans more than the corpus")
        by_name("ops", qt["op"])
    g = rng(seed, stream)
    qid = 0
    while True:
        for t in g.permutation(len(types)).tolist():
            qt = types[t]
            rows = int(qt["range_s"]) // interval
            step = int(qt.get("bucket_s", qt["range_s"])) // interval
            arg = qt.get("arg")
            hosts = g.choice(int(data["hosts"]), size=int(qt["hosts"]), replace=False)
            t0 = int(g.integers(0, span - rows + 1))
            calls = [
                (h * n_fields + f, qt["op"], a, a + step, arg)
                for h in hosts.tolist()
                for f in range(int(qt["fields"]))
                for a in range(t0, t0 + rows, step)
            ]
            yield Query(qid=qid, kind=qt["name"], calls=calls)
            qid += 1
