"""Continuous-batching request schedulers: LLM decode loop + SHRINK range
queries.

``ContinuousBatcher`` drives the token decode loop (fixed-slot batch,
static shapes for jit).  ``RangeQueryBatcher`` serves time-series range
queries against a SHRKS framed container: queries are queued, grouped by
the frames they touch, and each (frame, eps) is decoded at most once per
batch via an LRU of reconstructed frames — the batching win is that N
queries hitting the same hot frame cost one frame decode, not N.

Fixed-slot batch (static shapes for jit): requests occupy slots; finished
slots are recycled for queued requests.  All slots share one decode step —
the per-slot position mask lives in the KV cache's kpos (-1 = empty), so a
fresh request starting at position 0 coexists with one at position 10k.
Slot admission resets the slot's cache region lazily via position masking
(kpos entries of stale data are overwritten as decode proceeds; correctness
comes from the per-slot `pos` counters used to build attention masks).

This container's single CPU device runs the same code the 512-chip mesh
would jit — the scheduler is device-count agnostic.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict, deque
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.errors import (
    CorruptFrameError,
    LayerCorruptError,
    RangeCoverageError,
    UnknownSeriesError,
)
from ..core.serialize import frame_payload, parse_framed_container, read_snapshot_ref
from ..core.shrink import ProgressiveDecoder, cs_from_bytes

__all__ = ["Request", "ContinuousBatcher", "RangeQuery", "RangeQueryBatcher"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """decode_fn(tokens[B,1], caches, index) -> (logits, caches).

    NOTE: this simple scheduler advances all slots with a single shared
    cache_index (the max position across slots); per-slot validity is
    enforced by kpos masks.  Prompts are fed token-by-token (prefill==decode
    path) which keeps the demo simple; a production system would batch
    prefill separately (see examples/serve_decode.py).
    """

    def __init__(
        self,
        decode_fn: Callable,
        make_caches: Callable[[], object],
        n_slots: int,
        eos_token: int = 2,
        greedy: bool = True,
    ):
        self.decode_fn = decode_fn
        self.caches = make_caches()
        self.n_slots = n_slots
        self.eos = eos_token
        self.greedy = greedy
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, dtype=np.int64)  # next prompt idx
        self.global_index = 0
        self.completed: list[Request] = []

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for i in range(self.n_slots):
            if self.slots[i] is None and self.queue:
                self.slots[i] = self.queue.popleft()
                self.slot_pos[i] = 0

    def step(self) -> bool:
        """One decode step for all active slots; returns True if any work
        remains."""
        self._admit()
        if all(s is None for s in self.slots) and not self.queue:
            return False
        tokens = np.zeros((self.n_slots, 1), dtype=np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            p = int(self.slot_pos[i])
            if p < len(req.prompt):
                tokens[i, 0] = req.prompt[p]
            elif req.generated:
                tokens[i, 0] = req.generated[-1]
        logits, self.caches = self.decode_fn(
            jnp.asarray(tokens), self.caches, jnp.asarray(self.global_index, jnp.int32)
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.slot_pos[i] += 1
            if self.slot_pos[i] >= len(req.prompt):
                tok = int(nxt[i])
                req.generated.append(tok)
                if tok == self.eos or len(req.generated) >= req.max_new_tokens:
                    req.done = True
                    self.completed.append(req)
                    self.slots[i] = None
        self.global_index += 1
        return True

    def run(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while self.step() and steps < max_steps:
            steps += 1
        return self.completed


# --------------------------------------------------------------------- #
# SHRINK range-query serving
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class RangeQuery:
    """One range-decode request against a streamed container: reconstruct
    samples [t0, t1) of ``series_id`` at resolution ``eps``.  ``achieved``
    reports the guarantee of the tier the pyramid actually served (always
    <= eps on success; coarser than eps only for ``peek`` sketches)."""

    qid: int
    series_id: int
    t0: int
    t1: int
    eps: float
    result: Optional[np.ndarray] = None
    achieved: Optional[float] = None
    error: Optional[str] = None
    # True when corruption forced a coarser answer than requested:
    # ``achieved`` is then the (still valid) guarantee actually served,
    # possibly > eps.  Never set on a full-resolution answer.
    degraded: bool = False


class RangeQueryBatcher:
    """Progressive batched random-access decode over a ``SHRKS`` container.

    The container directory is parsed once; each submitted query resolves
    to the frames overlapping its range.  Each frame payload holds a
    residual refinement *pyramid*, and the LRU caches one
    ``ProgressiveDecoder`` per hot frame — i.e. the frame's decoded **layer
    prefix**, not a single-eps reconstruction:

    * a query at a coarse eps decodes only the coarse layers;
    * a later query at a finer eps on the same frame pays only the
      refinement layers below the cached prefix (``layer_hits`` counts the
      layers it did NOT have to re-decode);
    * ``peek`` answers from whatever prefix is already materialized with
      ZERO entropy work — serve the dashboard a coarse sketch immediately,
      let ``run`` fetch refinement layers on demand.

    Frame payload CRCs are verified on first touch (lazily, per the SHRKS
    contract).

    ``degraded_ok=True`` turns corruption from an error into *scoped
    degradation* (docs/robustness.md): a corrupt pyramid layer quarantines
    only that layer and the query is served from the finest intact prefix
    (``q.degraded=True``, ``q.achieved`` = the bound actually delivered);
    a frame whose residual section is unusable but whose header/base CRC
    holds falls back to base-only (segment) reconstruction.  Answers are
    never silently wrong — a frame that cannot even prove its base is
    intact still errors.
    """

    def __init__(
        self,
        blob: bytes,
        cache_frames: int = 32,
        degraded_ok: bool = False,
        kb_store=None,  # serving.kbstore.KBStore
    ):
        self.degraded_ok = bool(degraded_ok)
        self._blob = bytes(blob)
        metas, kb_bytes = parse_framed_container(self._blob)
        self._frames: dict[int, list] = {}
        for m in metas:
            self._frames.setdefault(m.series_id, []).append(m)
        for frames in self._frames.values():
            frames.sort(key=lambda m: m.t_lo)
        self._cache: OrderedDict[int, ProgressiveDecoder] = OrderedDict()
        self._cache_frames = cache_frames
        self.queue: deque[RangeQuery] = deque()
        self.completed: list[RangeQuery] = []
        # decode never needs the KB (frame payloads carry their bases), but
        # a router wants the dictionary binding validated BEFORE serving:
        # with a kb_store, resolve the container's kb_snapshot_ref now — a
        # stale ref either falls back to the inline footer KB or raises a
        # typed StaleSnapshotError here, never binds silently wrong.
        if kb_store is not None:
            from .kbstore import resolve_container_kb

            _, kb_source = resolve_container_kb(self._blob, kb_store)
        elif kb_bytes:
            kb_source = "inline"
        else:
            kb_source = (
                "ref-unresolved" if read_snapshot_ref(self._blob) else "none"
            )
        self.stats = {
            "queries": 0,
            "frames_decoded": 0,
            "frame_hits": 0,
            "layers_decoded": 0,
            "layer_hits": 0,
            "errors": 0,
            "degraded": 0,
            "kb_source": kb_source,
        }

    @property
    def blob(self) -> bytes:
        """The raw container bytes (frame payloads are slices of this)."""
        return self._blob

    @property
    def series_ids(self) -> list[int]:
        return sorted(self._frames)

    def span(self, series_id: int) -> tuple[int, int]:
        """[t_lo, t_hi) covered by a series' frames."""
        frames = self._frames.get(series_id)
        if not frames:
            raise UnknownSeriesError(f"unknown series {series_id}", series_id=series_id)
        return frames[0].t_lo, frames[-1].t_hi

    def submit(self, q: RangeQuery) -> None:
        self.queue.append(q)

    def decoder(self, meta) -> ProgressiveDecoder:
        """The cached :class:`ProgressiveDecoder` for one frame (decoding
        the frame's container bytes on first touch).  Public so the
        compressed-domain analytics engine (``repro.analytics``) can
        refine through the SAME layer-prefix LRU range queries use — a
        dashboard mixing range decodes and aggregates never decodes a
        layer twice."""
        return self._decoder(meta)

    def _decoder(self, meta) -> ProgressiveDecoder:
        dec = self._cache.get(meta.offset)
        if dec is not None:
            self._cache.move_to_end(meta.offset)
            self.stats["frame_hits"] += 1
            return dec
        with obs.span("batching.open_frame"):
            try:
                dec = ProgressiveDecoder(cs_from_bytes(frame_payload(self._blob, meta)))
            except CorruptFrameError:
                if not self.degraded_ok:
                    raise
                # Tolerant path: skip the frame-level CRC and parse the SHRK
                # blob quarantining corrupt pyramid layers.  The SHRK header
                # CRC (eps_hat + base) is STILL verified inside cs_from_bytes
                # — if the base itself cannot be trusted, this re-raises and
                # the query errors rather than serving unprovable data.
                dec = ProgressiveDecoder(
                    cs_from_bytes(
                        frame_payload(self._blob, meta, verify_crc=False), strict=False
                    )
                )
        self.stats["frames_decoded"] += 1
        self._cache[meta.offset] = dec
        while len(self._cache) > self._cache_frames:
            self._cache.popitem(last=False)
        return dec

    def _decoded_frame(self, meta, eps: float) -> tuple[np.ndarray, float, bool]:
        dec = self._decoder(meta)
        k = dec.cs.pyramid.resolve(eps, dec.cs.eps_b_practical)
        degraded = False
        intact = dec.intact_depth()
        if k > intact:
            if not self.degraded_ok:
                raise LayerCorruptError(
                    f"frame needs layer prefix {k} but finest intact prefix is "
                    f"{intact}",
                    series_id=meta.series_id, layer=intact + 1,
                )
            k = intact  # serve the finest intact prefix, flagged
            degraded = True
        before = dec.layers_decoded
        vals = dec.prefix(k)
        paid = dec.layers_decoded - before
        self.stats["layers_decoded"] += paid
        # layers the cached prefix already covered (k+1 layers needed, minus
        # identity layers which are free by construction)
        needed = sum(
            1 for layer in dec.cs.pyramid.layers[: k + 1] if layer.mode != "identity"
        )
        self.stats["layer_hits"] += needed - paid
        return vals, dec.guarantee(k), degraded

    def frames_overlapping(self, series_id: int, t0: int, t1: int) -> list:
        """Directory entries of the frames covering samples [t0, t1) of a
        series, in time order; raises :class:`UnknownSeriesError` /
        :class:`RangeCoverageError` for an unknown series or a range the
        frames do not fully cover."""
        frames = self._frames.get(series_id)
        if not frames:
            raise UnknownSeriesError(f"unknown series {series_id}", series_id=series_id)
        touched = [m for m in frames if m.t_lo < t1 and m.t_hi > t0]
        if t1 <= t0 or not touched or touched[0].t_lo > t0 or touched[-1].t_hi < t1:
            raise RangeCoverageError(
                f"range [{t0}, {t1}) not covered by series {series_id} frames "
                f"[{frames[0].t_lo}, {frames[-1].t_hi})",
                series_id=series_id,
            )
        return touched

    def _frames_for(self, q: RangeQuery) -> list:
        return self.frames_overlapping(q.series_id, q.t0, q.t1)

    def _serve(self, q: RangeQuery) -> None:
        touched = self._frames_for(q)
        out = np.empty(q.t1 - q.t0, dtype=np.float64)
        achieved = 0.0
        degraded = False
        expected = q.t0
        for i, m in enumerate(touched):
            if m.t_lo > expected:
                raise RangeCoverageError(
                    f"gap in series {q.series_id} frames at sample {expected} "
                    f"(next frame covers [{m.t_lo}, {m.t_hi}))",
                    series_id=q.series_id, frame_index=i,
                )
            vals, guarantee, frame_degraded = self._decoded_frame(m, q.eps)
            achieved = max(achieved, guarantee)
            degraded = degraded or frame_degraded
            lo, hi = max(q.t0, m.t_lo), min(q.t1, m.t_hi)
            out[lo - q.t0 : hi - q.t0] = vals[lo - m.t_lo : hi - m.t_lo]
            expected = hi
        q.result = out
        q.achieved = achieved
        q.degraded = degraded
        if degraded:
            self.stats["degraded"] += 1

    def peek(self, q: RangeQuery) -> Optional[np.ndarray]:
        """Serve ``q`` from already-cached layer prefixes with NO entropy
        decode: returns a coarse sketch (setting ``q.result`` and
        ``q.achieved`` to the coarsest cached guarantee among touched
        frames), or ``None`` when any touched frame is cold.  The query
        stays in / may still be submitted to the refinement queue —
        ``run`` will then only pay for the missing layers."""
        try:
            touched = self._frames_for(q)
        except ValueError:
            return None
        parts: list[tuple] = []
        achieved = 0.0
        expected = q.t0
        for m in touched:
            if m.t_lo > expected:
                return None
            dec = self._cache.get(m.offset)
            avail = dec.available() if dec is not None else None
            if avail is None:
                return None
            vals, guarantee = avail
            achieved = max(achieved, guarantee)
            parts.append((m, vals))
            expected = m.t_hi
        out = np.empty(q.t1 - q.t0, dtype=np.float64)
        for m, vals in parts:
            lo, hi = max(q.t0, m.t_lo), min(q.t1, m.t_hi)
            out[lo - q.t0 : hi - q.t0] = vals[lo - m.t_lo : hi - m.t_lo]
        q.result = out
        q.achieved = achieved
        return out

    def run(self) -> list[RangeQuery]:
        """Drain the queue; returns the queries completed by this call."""
        done: list[RangeQuery] = []
        while self.queue:
            q = self.queue.popleft()
            self.stats["queries"] += 1
            try:
                self._serve(q)
            except (ValueError, KeyError) as e:
                q.error = str(e)
                self.stats["errors"] += 1
            done.append(q)
        self.completed.extend(done)
        return done
