"""One TSBS query client in a closed loop, as ``tsbs_run_queries
--workers=1`` runs it.

Set-up seals the corpus through the configuration's ingest path, opens the
analytics engine over the sealed container and runs warm-up queries (a
stream of their own) until they stop compiling.  The window runs the
seed's query stream and closes with the first query that ends after
``--seconds``.  The check recomputes every answer of the window from the
generated samples in float64 numpy: each is exact (``eps=0.0``), so it has
to equal the truth.
"""
from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from bench import by_name, generate

STATS = ("frames_decoded", "frame_hits", "layers_decoded")


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float, watch):
        self.cfg, self.mix, self.seed, self.seconds = cfg, mix, seed, seconds
        self.watch = watch
        self.counters: dict = {}
        self.info: dict = {}

    def _run(self, q):
        """Answers of one query's engine calls; an exception is recorded as
        the call's answer, and counts as wrong."""
        out = []
        for sid, op, a, b, arg in q.calls:
            try:
                out.append(by_name("ops", op).call(self.engine, sid, a, b, arg))
            except Exception as e:  # noqa: BLE001 - a failed answer, reported below
                out.append(e)
                if not self.errors:
                    traceback.print_exc(file=sys.stderr)
                self.errors += 1
        return out

    def setup(self, spans) -> None:
        from repro.analytics import AnalyticsEngine
        from repro.core.serialize import parse_framed_container
        from repro.serving import RangeQueryBatcher

        data = self.cfg["data"]
        ticks = int(self.mix["corpus_s"]) // int(data["deliver_every_s"])
        with spans.span("bench.generate"):
            self.pool = generate.pool_ticks(data, self.seed, ticks)
        self.span = int(self.pool.offsets[-1].min())
        system = by_name("systems", self.cfg["system"]).build(self.cfg)
        for k in range(self.pool.n_ticks):
            for sid, chunk in self.pool.tick(k):
                system.submit(sid, chunk)
        (blob,) = system.seal()
        self.errors = 0
        self.engine = AnalyticsEngine(
            RangeQueryBatcher(blob, cache_frames=self.cfg["query"]["cache_frames"])
        )
        warm = generate.queries(data, self.mix, self.seed, self.span, stream="warmup")
        n = quiet = 0
        while n < self.mix["warm_max_queries"]:
            before = self.watch.compiles
            self._run(next(warm))
            n += 1
            quiet = quiet + 1 if self.watch.compiles == before else 0
            if n >= self.mix["warm_min_queries"] and quiet >= self.mix["warm_quiet_queries"]:
                break
        self.info.update(corpus_samples=self.pool.n_samples,
                         frames=len(parse_framed_container(blob)[0]),
                         warm_queries=n, warm_quiet_queries=quiet)

    def window(self, spans) -> float:
        now = time.perf_counter_ns
        stats = self.engine.batcher.stats
        before = {k: stats[k] for k in STATS}
        stream = generate.queries(self.cfg["data"], self.mix, self.seed, self.span)
        done, lat = [], []
        start = now()
        deadline = start + int(self.seconds * 1e9)
        while True:
            q = next(stream)
            a = now()
            answers = self._run(q)
            b = now()
            spans.add(f"bench.query.{q.kind}", a, b)
            lat.append((b - a) * 1e-6)
            done.append((q, answers))
            if b >= deadline:
                break
        self.done = done
        self.counters.update({k: stats[k] - before[k] for k in STATS})
        self.counters.update(query_latency_ms=lat, queries=len(done),
                             engine_calls=sum(len(q.calls) for q, _ in done))
        return (b - start) * 1e-9

    def close(self) -> None:
        pass

    def verify(self, control: bool, trace: bool) -> tuple[dict, int, int]:
        wrong = bad_queries = 0
        worst = 0.0
        for q, answers in self.done:
            q_bad = False
            for (sid, op, a, b, arg), ans in zip(q.calls, answers):
                raw = self.pool.values[sid][a:b]
                ref = by_name("ops", op).ref
                truth = ref(raw, arg)
                if control:
                    lo = hi = ref(raw.astype(np.float32), arg)
                elif isinstance(ans, Exception):
                    lo, hi = -np.inf, np.inf
                else:
                    lo, hi = ans.lo, ans.hi
                err = max(abs(lo - truth), abs(hi - truth))
                worst = max(worst, err)
                if err != 0.0:
                    wrong += 1
                    q_bad = True
            bad_queries += q_bad
        checks = {
            "wrong_answers": (wrong, 0),
            "max_abs_err": (worst, 0.0),
        }
        self.info.update(answers_checked=sum(len(q.calls) for q, _ in self.done))
        return checks, len(self.done), bad_queries
