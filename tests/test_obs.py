"""The span recorder (``repro.obs``) and the spans the ingest and query
paths open."""
import collections
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.analytics import AnalyticsEngine
from repro.core.types import ShrinkConfig
from repro.serving.ragged import RaggedBatcher

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def recorder():
    obs.disable()
    obs.take()
    yield
    obs.disable()
    obs.take()


@pytest.fixture
def clock(monkeypatch):
    """A fake clock that advances by the given steps, one per reading."""
    ticks = []

    def now():
        return ticks.pop(0)

    monkeypatch.setattr(obs, "_now", now)
    return ticks


def by_id(spans):
    return {s[3]: s for s in spans}


def test_off_reads_no_clock_and_records_nothing(monkeypatch):
    def no_clock():
        raise AssertionError("the clock was read while the recorder was off")

    monkeypatch.setattr(obs, "_now", no_clock)
    a, b = obs.span("ragged.flush"), obs.span("shrink.pyramid")
    assert a is b
    with a:
        with b:
            pass
    assert obs.take() == ([], 0)


def test_parents_roots_and_self_time(clock):
    # a [0, 100) holds b [10, 40) and d [50, 70); b holds c [20, 30)
    clock.extend([0, 10, 20, 30, 40, 50, 70, 100, 200, 210])
    obs.enable()
    with obs.span("a"):
        with obs.span("b"):
            with obs.span("c"):
                pass
        with obs.span("d"):
            pass
    with obs.span("e"):
        pass
    spans, dropped = obs.take()
    assert dropped == 0
    named = {s[0]: s for s in spans}
    a, b, c, d, e = (named[k] for k in "abcde")
    assert [s[0] for s in spans] == ["c", "b", "d", "a", "e"]  # in the order they end
    assert (a[1], a[2], b[1], b[2], c[1], c[2], d[1], d[2]) == (0, 100, 10, 40, 20, 30, 50, 70)
    assert a[4] is None and a[5] == a[3]
    assert b[4] == a[3] and d[4] == a[3] and c[4] == b[3]
    assert {s[5] for s in (a, b, c, d)} == {a[3]}
    assert e[4] is None and e[5] == e[3] != a[3]
    assert obs.self_time(spans, "a") == 100 - 30 - 20
    assert obs.self_time(spans, "b") == 30 - 10
    assert obs.self_time(spans, "c") == 10
    assert obs.self_time(spans, "e") == 10
    assert obs.self_time(spans, "absent") == 0


def test_each_thread_has_its_own_parents():
    obs.enable()
    done = []

    def other():
        with obs.span("other"):
            done.append(True)

    with obs.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive() and done
    named = {s[0]: s for s in obs.take()[0]}
    assert named["other"][4] is None and named["other"][5] == named["other"][3]
    assert named["main"][4] is None


def test_cap_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(obs, "CAP", 3)
    obs.enable()
    for _ in range(5):
        with obs.span("x"):
            pass
    spans, dropped = obs.take()
    assert len(spans) == 3 and dropped == 2
    assert obs.take() == ([], 0)


def _walk(series: int, length: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.round(np.clip(50 + np.cumsum(rng.normal(size=(series, length)), axis=1),
                            0, 100), 4)


@pytest.mark.parametrize("ragged", [False, True])
def test_flush_span_tree(ragged):
    """One flush is one ``ragged.flush`` root over the semantics, the
    residual pyramid (holding the entropy encode) and the frame seal, which
    cover at least 90% of it."""
    vals = _walk(8, 3 * 2048, seed=3)
    lengths = [2048 - 240 * i if ragged else 2048 for i in range(8)]
    b = RaggedBatcher(ShrinkConfig(eps_b=5.0, lam=1e-5), eps_targets=[0.5, 0.0],
                      decimals=4, flush_samples=None)

    def flush(k):
        for i, n in enumerate(lengths):
            b.submit(i, vals[i, k * 2048: k * 2048 + n])
        return b.flush()

    flush(0)  # warm-up: first calls compile and import
    obs.enable()
    assert len(flush(1)) == 8
    spans, dropped = obs.take()
    assert dropped == 0
    ids = by_id(spans)
    roots = [s for s in spans if s[4] is None]
    assert [s[0] for s in roots] == ["ragged.flush"]
    root = roots[0]
    assert all(s[5] == root[3] for s in spans)
    parent_of = {s[0]: ids[s[4]][0] for s in spans if s[4] is not None}
    assert parent_of["shrink.semantics"] == "ragged.flush"
    assert parent_of["shrink.pyramid"] == "ragged.flush"
    assert parent_of["ragged.seal"] == "ragged.flush"
    assert parent_of["entropy.encode"] == "shrink.pyramid"
    for s in spans:
        if s[0].startswith("device."):
            assert ids[s[4]][0] in ("entropy.encode", "shrink.semantics")
        assert root[1] <= s[1] <= s[2] <= root[2]
    children = sum(s[2] - s[1] for s in spans if s[4] == root[3])
    assert children >= 0.9 * (root[2] - root[1])
    assert obs.self_time(spans, "ragged.flush") == root[2] - root[1] - children


def test_query_span_tree():
    """Each engine call is one ``planner.*`` root; a frame that misses the
    cache opens once and parses its base once, and every entropy-decoded
    layer is one ``decoder.layer`` span, as the batcher's counters say."""
    vals = _walk(4, 4096, seed=5)
    b = RaggedBatcher(ShrinkConfig(eps_b=5.0, lam=1e-5), eps_targets=[0.5, 0.0],
                      decimals=4, flush_samples=4 * 1024)
    for k in range(4):
        for i in range(4):
            b.submit(i, vals[i, k * 1024:(k + 1) * 1024])
    eng = AnalyticsEngine(b.finalize(), cache_frames=2)
    stats = eng.batcher.stats
    obs.enable()
    for i in range(4):
        eng.aggregate(i, "max", 100, 3000, eps=0.0)
        eng.count_where(i, "gt", 55.0, 500, 4000, eps=0.0)
    spans, dropped = obs.take()
    assert dropped == 0
    ids = by_id(spans)
    names = collections.Counter(s[0] for s in spans)
    roots = [s for s in spans if s[4] is None]
    assert {s[0] for s in roots} == {"planner.aggregate", "planner.count_where"}
    assert len(roots) == 8
    for s in spans:
        root = ids[s[5]]
        assert root[4] is None and root[0].startswith("planner.")
        assert root[1] <= s[1] <= s[2] <= root[2]
        if s[0].startswith(("batching.", "decoder.")):
            assert ids[s[4]] is root
    assert names["batching.open_frame"] == stats["frames_decoded"] > 0
    assert names["decoder.base"] == stats["frames_decoded"]
    assert names["decoder.layer"] == stats["layers_decoded"] > 0
    assert obs.self_time(spans, "planner.aggregate") + obs.self_time(
        spans, "planner.count_where") < sum(s[2] - s[1] for s in roots)


@pytest.mark.parametrize("workload", ["tsbs_cpu.ingest", "tsbs_cpu.query"])
def test_untraced_benchmark_run_records_no_span(workload):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.tests.tiny import run_tiny

    _, line = run_tiny(workload, trace=False)
    assert line["correct"] is True
    assert obs.take() == ([], 0)
