"""Closed-loop bulk ingest: one client submits the configuration's traffic
as fast as the system takes it, as TSBS's loader does.

Set-up makes the pool, builds the system and drives it until its flushes
stop compiling.  The window submits the next chunks and closes with the
first sealing call that ends after ``--seconds``, so it holds whole
flushes.  Afterwards the system seals its containers, and the check
decodes a seeded sample of the frames sealed in the window (the longest
among them) at every tier against the generated samples.
"""
from __future__ import annotations

import resource
import struct
import sys
import time

import numpy as np

from bench import by_name, generate

RANS_TAG = 3  # wire tag of the rANS backend (docs/wire-format.md)


class PoolExhausted(RuntimeError):
    pass


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float, watch):
        self.cfg, self.mix, self.seed, self.seconds = cfg, mix, seed, seconds
        self.watch = watch
        self.counters: dict = {}
        self.info: dict = {}

    # -- traffic ---------------------------------------------------------- #
    def _deliveries(self):
        for k in range(self.pool.n_ticks):
            self.pos = (k, 0)
            for j, (sid, chunk) in enumerate(self.pool.tick(k)):
                self.pos = (k, j)
                yield sid, chunk
        self.pos = (self.pool.n_ticks, 0)

    def fed(self) -> np.ndarray:
        """Samples submitted per series so far."""
        k, j = self.pos
        fed = self.pool.offsets[min(k, self.pool.n_ticks)].copy()
        if k < self.pool.n_ticks and self.submitted_in_tick:
            nxt = self.pool.offsets[k + 1]
            live = np.flatnonzero(nxt > fed)[: self.submitted_in_tick]
            fed[live] = nxt[live]
        return fed

    def _submit(self, sid, chunk):
        _, j = self.pos
        sealed = self.system.submit(sid, chunk)
        self.submitted_in_tick = j + 1
        return sealed

    def _next(self):
        try:
            return next(self.stream)
        except StopIteration:
            raise PoolExhausted(
                f"the pool of {self.pool.n_samples} samples ran out"
            ) from None

    # -- phases ----------------------------------------------------------- #
    def setup(self, spans) -> None:
        ing = self.cfg["ingest"]
        with spans.span("bench.generate"):
            self.pool = generate.make_pool(
                self.cfg["data"], self.seed,
                ing["pool_samples_per_s"] * self.seconds + ing["warm_max_samples"],
            )
        self.system = by_name("systems", self.cfg["system"]).build(self.cfg)
        self.stream = self._deliveries()
        self.pos, self.submitted_in_tick = (0, 0), 0
        seals = quiet = fed = 0
        while fed < ing["warm_max_samples"]:
            sid, chunk = self._next()
            before = self.watch.compiles
            sealed = self._submit(sid, chunk)
            fed += chunk.size
            if sealed:
                seals += 1
                quiet = quiet + 1 if self.watch.compiles == before else 0
                if seals >= ing["warm_min_seals"] and quiet >= ing["warm_quiet_seals"]:
                    break
        self.info.update(pool_samples=self.pool.n_samples, warm_seals=seals,
                         warm_quiet_seals=quiet, warm_samples=fed)

    def window(self, spans) -> float:
        now = time.perf_counter_ns
        submit = self._submit
        admit_ns = flush_ns = admit_chunks = chunks = sealed_samples = 0
        frames: list = []
        # per flush: admission s, flush s, compiles so far, and over the
        # cycle (admission and flush) the process's CPU s, the main
        # thread's CPU s, involuntary context switches and page faults
        cycles: list = []
        admit_open = last = None
        ru0, th0 = resource.getrusage(resource.RUSAGE_SELF), time.thread_time()
        start = cycle0 = now()
        deadline = start + int(self.seconds * 1e9)
        compiles0 = self.watch.compiles
        while True:
            sid, chunk = self._next()
            a = now()
            sealed = submit(sid, chunk)
            b = now()
            chunks += 1
            if not sealed:
                admit_ns += b - a
                admit_chunks += 1
                if admit_open is None:
                    admit_open = a
                last = b
                continue
            if admit_open is not None:
                spans.add("bench.admit", admit_open, last)
                admit_open = None
            spans.add("bench.flush", a, b)
            flush_ns += b - a
            ru, th = resource.getrusage(resource.RUSAGE_SELF), time.thread_time()
            cycles.append([(a - cycle0) * 1e-9, (b - a) * 1e-9,
                           self.watch.compiles - compiles0,
                           ru.ru_utime + ru.ru_stime - ru0.ru_utime - ru0.ru_stime,
                           th - th0, ru.ru_nivcsw - ru0.ru_nivcsw,
                           ru.ru_minflt - ru0.ru_minflt, ru.ru_majflt - ru0.ru_majflt])
            cycle0, ru0, th0 = b, ru, th
            frames.extend(sealed)
            sealed_samples += sum(hi - lo for _, lo, hi in sealed)
            if b >= deadline:
                break
        self.frames = frames
        self.info["cycles"] = cycles
        self.counters.update(
            samples_sealed=sealed_samples,
            admit_ns=admit_ns, admit_chunks=admit_chunks, flush_ns=flush_ns,
            chunks=chunks, frames=len(frames), flushes=spans.count("bench.flush"),
        )
        return (b - start) * 1e-9

    def close(self) -> None:
        """Seal the containers: the remainder flushes outside the window."""
        self.fed_final = self.fed()
        self.blobs = self.system.seal()

    # -- the check -------------------------------------------------------- #
    def verify(self, control: bool, trace: bool) -> tuple[dict, int, int]:
        from repro.core.serialize import parse_framed_container
        from repro.serving import RangeQuery, RangeQueryBatcher

        cfg = self.cfg
        tiers = list(cfg["tiers"])
        metas = [parse_framed_container(blob)[0] for blob in self.blobs]
        where = {}
        by_series: dict[int, list] = {}
        for shard, ms in enumerate(metas):
            for m in ms:
                where[(m.series_id, m.t_lo, m.t_hi)] = (shard, m)
                by_series.setdefault(m.series_id, []).append((m.t_lo, m.t_hi))
        # every submitted sample sealed once, in order, in its own series
        bad_series = 0
        for sid, n in enumerate(self.fed_final.tolist()):
            spans = sorted(by_series.pop(sid, []))
            ends = [0] + [hi for _, hi in spans]
            ok = all(lo == e for (lo, _), e in zip(spans, ends)) and ends[-1] == n
            bad_series += not ok
        bad_series += len(by_series)  # frames of series never submitted
        missing = [f for f in self.frames if tuple(f) not in where]
        sizes = self._frame_sizes(where)
        n_sealed = max(self.counters["samples_sealed"], 1)
        self.counters["frame_bytes"] = sum(b for b, _, _ in sizes)
        self.counters["symbols_encoded"] = sum(k for _, _, k in sizes)
        # what a reader of the lossy tier pays: the frame less its finer
        # layers; the control stores float32 samples, 4 bytes at any tier
        tier0_bytes = 4.0 if control else sum(t for _, t, _ in sizes) / n_sealed

        rng = np.random.default_rng([self.seed & (2**64 - 1), 0x5E1EC7])
        frames = sorted(set(map(tuple, self.frames)) - set(map(tuple, missing)))
        order = rng.permutation(len(frames))
        longest = max(range(len(frames)), key=lambda i: frames[i][2] - frames[i][1], default=None)
        picked, budget = [], self.mix["verify_samples"]
        for i in ([longest] if longest is not None else []) + order.tolist():
            if budget <= 0:
                break
            if i == longest and picked:
                continue
            picked.append(frames[i])
            budget -= frames[i][2] - frames[i][1]
        decoders = [RangeQueryBatcher(blob, cache_frames=2) for blob in self.blobs]
        worst = [0.0] * len(tiers)
        mismatched = errors = bad_frames = 0
        qid = 0
        for sid, lo, hi in picked:
            raw = self.pool.values[sid][lo:hi]
            dec = decoders[where[(sid, lo, hi)][0]]
            frame_bad = False
            for t, eps in enumerate(tiers):
                q = RangeQuery(qid=qid, series_id=sid, t0=lo, t1=hi, eps=eps)
                qid += 1
                dec.submit(q)
                dec.run()
                if q.error is not None:
                    errors += 1
                    frame_bad = True
                    print(f"check: frame ({sid}, {lo}, {hi}) at {eps}: {q.error}", file=sys.stderr)
                    continue
                got = raw.astype(np.float32).astype(np.float64) if control else q.result
                if eps == 0.0:
                    bad = int(np.count_nonzero(got != raw))
                    mismatched += bad
                    frame_bad |= bad > 0
                else:
                    err = float(np.max(np.abs(got - raw))) if raw.size else 0.0
                    worst[t] = max(worst[t], err)
                    frame_bad |= err > eps * (1 + 1e-9)
            bad_frames += frame_bad
        checks = {}
        for t, eps in enumerate(tiers):
            if eps > 0.0:
                # the tier's stated bound, with the float64 rounding of the
                # decimal grid (a few ulp) as its only slack
                checks[f"max_err_tier{t}"] = (worst[t], eps * (1 + 1e-9))
        checks["lossless_mismatches"] = (mismatched, 0)
        checks["decode_errors"] = (errors, 0)
        checks["coverage_bad_series"] = (bad_series + len(missing), 0)
        checks["bytes_per_sample_tier0"] = (tier0_bytes,
                                            float(cfg["limits"]["bytes_per_sample_tier0"]))
        checks.update(self.system.checks())
        self.info.update(frames_checked=len(picked),
                         samples_checked=int(sum(hi - lo for _, lo, hi in picked)),
                         bytes_per_sample=self.counters["frame_bytes"] / n_sealed)
        return checks, len(self.frames), bad_frames + len(missing)

    def _frame_sizes(self, where) -> list[tuple[int, int, int]]:
        """Per frame of the window: its bytes, its bytes less the layers
        finer than the first tier, and the plane symbols of its rANS
        streams, read from each stream's own header (sample count times
        byte planes)."""
        from repro.core import cs_from_bytes
        from repro.core.serialize import frame_payload

        out = []
        for f in self.frames:
            if tuple(f) not in where:
                continue
            shard, meta = where[tuple(f)]
            cs = cs_from_bytes(frame_payload(self.blobs[shard], meta))
            finer = symbols = 0
            for i, layer in enumerate(cs.pyramid.layers):
                p = layer.payload
                if p is None:
                    continue
                finer += len(p) if i > 0 else 0
                if len(p) >= 19 and p[0] == RANS_TAG:
                    _med, count, planes, _k = struct.unpack_from("<qQBB", p, 1)
                    symbols += count * planes
            out.append((meta.length, meta.length - finer, symbols))
        return out
