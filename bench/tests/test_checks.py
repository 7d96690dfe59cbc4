"""The check decides ``correct``: its control and the faults a cell can
have each come out as not correct.

The control puts the reference, computed in float32, in the program's
place.  Each fault breaks the timed path underneath a tiny run on the CPU
and drives the rest of the run as the chip would.
"""
import dataclasses

import numpy as np
import pytest

from bench.loops.ingest import PoolExhausted
from bench.tests.tiny import FLEET, run_tiny

INGEST = ["tsbs_cpu.ingest", FLEET]


@pytest.mark.parametrize("workload", INGEST + ["tsbs_cpu.query"])
def test_control_is_not_correct(workload):
    _, line = run_tiny(workload, control=True)
    assert line["correct"] is False
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert failed == ({"wrong_answers", "max_abs_err"} if workload.endswith("query")
                      else {"lossless_mismatches", "bytes_per_sample_tier0"})


def _altered_batch(monkeypatch):
    """A sample altered where it is produced: every flush's first series."""
    from repro.core.shrink import ShrinkCodec

    orig = ShrinkCodec.compress_batch

    def compress_batch(self, values, *a, **k):
        values = [np.array(v, dtype=np.float64) for v in values]
        values[0][0] += 1e-4
        return orig(self, values, *a, **k)

    monkeypatch.setattr(ShrinkCodec, "compress_batch", compress_batch)


def _half_batch(monkeypatch):
    """Half of each flush's series left out of the sealed frames."""
    from repro.serving.ragged import RaggedBatcher

    orig = RaggedBatcher.flush

    def flush(self, series_ids=None):
        sids = sorted(self._pending if series_ids is None else series_ids)
        for sid in sids[: len(sids) // 2]:
            self._pending_samples -= self._pending.pop(sid).samples
        return orig(self, sids[len(sids) // 2 :])

    monkeypatch.setattr(RaggedBatcher, "flush", flush)


def _no_exchange(monkeypatch):
    """The fleet's cross-shard knowledge-base sync left out."""
    from repro.serving.fleet import ShrinkFleet

    monkeypatch.setattr(ShrinkFleet, "sync_kbs", lambda self: {})


@pytest.mark.parametrize("workload", INGEST)
@pytest.mark.parametrize("fault", [_altered_batch, _half_batch])
def test_ingest_faults_are_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    _, line = run_tiny(workload)
    assert line["correct"] is False


@pytest.mark.parametrize("workload", INGEST)
def test_state_left_unchanged_gives_no_result(workload, monkeypatch):
    """A submit that keeps nothing seals nothing: the window never closes,
    the pool runs out and the run fails."""
    from repro.serving.ragged import RaggedBatcher

    monkeypatch.setattr(RaggedBatcher, "submit", lambda self, sid, chunk: [])
    with pytest.raises(PoolExhausted):
        run_tiny(workload)


def test_fleet_without_exchange_is_not_correct(monkeypatch):
    _no_exchange(monkeypatch)
    _, line = run_tiny(FLEET)
    assert line["correct"] is False
    assert line["checks"]["kb_sync_stale"]["value"] > 0


@pytest.mark.parametrize("op", ["aggregate", "count_where"])
def test_query_answer_altered_is_not_correct(op, monkeypatch):
    from repro.analytics import AnalyticsEngine

    orig = getattr(AnalyticsEngine, op)

    def altered(self, *a, **k):
        ans = orig(self, *a, **k)
        return dataclasses.replace(ans, lo=ans.lo + 1.0, hi=ans.hi + 1.0)

    monkeypatch.setattr(AnalyticsEngine, op, altered)
    _, line = run_tiny("tsbs_cpu.query")
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0
