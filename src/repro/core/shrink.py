"""The SHRINK codec (Alg. 1 of the paper): one base, many resolutions.

Residuals are stored as a **layered refinement pyramid**: tier 0 quantizes
the residual at the coarsest eps, every finer tier k quantizes the
reconstruction error left by tiers 0..k-1 (the lossless tier as the final
integer-domain refinement), so an archive with tiers {1e-1, 1e-2, 1e-3, 0}
stores each bit of residual information once — decode-at-eps_k is
``base + Σ layers 0..k`` and a multi-resolution archive is strictly
smaller than independent per-eps streams.

Usage:

    codec = ShrinkCodec.from_fraction(values, frac=0.05)     # eps_b = 5% range
    cs    = codec.compress(values, eps_targets=[1e-2, 1e-4], decimals=8)
    vhat  = codec.decompress_at(cs, 1e-4)                    # |vhat-v| <= 1e-4
    mid   = codec.decompress_at(cs, 3e-3)                    # nearest tier <= 3e-3 (here 1e-4)
    exact = codec.decompress_at(cs, 0.0)                     # lossless
    blob  = cs_to_bytes(cs); cs2 = cs_from_bytes(blob)

    # gateway-scale: S series in one vectorized pass — equal-length [S, T]
    # or a ragged list of 1-D arrays (length-bucketed, masked lanes)
    css   = codec.compress_batch(values_st, eps_targets=[1e-2])   # [S, T]
    css   = codec.compress_batch([v1, v2, v3], eps_targets=[1e-2])  # ragged

``decompress_at`` accepts ANY eps: it resolves the cheapest layer prefix
whose guarantee is <= the request (raising ``ValueError`` only when no
tier qualifies).  ``eps == 0.0`` denotes the lossless tier (requires
``decimals``: the fixed decimal precision of the source data, Table II's
"Decimal" column).  ``ProgressiveDecoder`` exposes the same ladder
incrementally — decode coarse now, refine later, paying only the delta.
"""
from __future__ import annotations

import math
import struct
import zlib
from dataclasses import dataclass

import numpy as np

from . import entropy
from .errors import (
    CorruptFrameError,
    FormatError,
    LayerCorruptError,
    ShrinkError,
    TruncatedArchiveError,
)
from .base import (
    base_predictions,
    base_predictions_batch,
    base_predictions_ragged,
    construct_base,
    practical_eps_b,
)
from .residuals import (
    encode_residuals_batch,
    normalize_tiers,
    quantize_pyramid,
    quantize_pyramid_batch,
)
from .semantics import (
    extract_semantics,
    extract_semantics_batch,
    extract_semantics_batch_pallas,
    global_range,
)
from .serialize import (
    decode_base,
    decode_pyramid,
    encode_base,
    encode_pyramid,
    pyramid_layers,
)
from .types import Base, CompressedSeries, ResidualStream, ShrinkConfig

__all__ = [
    "ShrinkCodec",
    "ProgressiveDecoder",
    "cs_to_bytes",
    "cs_from_bytes",
    "decompress_at",
    "encode_frames_with_bases",
    "encode_with_base",
    "original_size_bytes",
]

_CONTAINER_MAGIC = b"SHRK"
_CONTAINER_VERSION = 2

# The paper's Table II datasets store (timestamp, value) pairs; we account the
# original size as 16 bytes/row (two float64) — same accounting for every
# method in benchmarks/, so CRs are comparable across methods and with the
# paper's relative claims.
BYTES_PER_ROW = 16


def original_size_bytes(n: int) -> int:
    return BYTES_PER_ROW * n


@dataclass
class ShrinkCodec:
    config: ShrinkConfig
    backend: str = "best"

    @classmethod
    def from_fraction(
        cls,
        values: np.ndarray,
        frac: float = 0.05,
        lam: float = 1e-5,
        beta_levels: int = 16,
        backend: str = "best",
    ) -> "ShrinkCodec":
        vmin, vmax = global_range(np.asarray(values, dtype=np.float64))
        rng = max(vmax - vmin, 1e-12)
        return cls(
            config=ShrinkConfig(eps_b=frac * rng, lam=lam, beta_levels=beta_levels),
            backend=backend,
        )

    # ------------------------------------------------------------------ #
    def build_base(
        self,
        values: np.ndarray,
        value_range: tuple[float, float] | None = None,
        n_hint: int | None = None,
    ) -> Base:
        values = np.asarray(values, dtype=np.float64)
        segments = extract_semantics(values, self.config, value_range=value_range, n_hint=n_hint)
        if value_range is None:
            vmin, vmax = global_range(values)
        else:
            vmin, vmax = float(value_range[0]), float(value_range[1])
        return construct_base(segments, len(values), vmin, vmax, self.config)

    def compress(
        self,
        values: np.ndarray,
        eps_targets: list[float],
        decimals: int | None = None,
        value_range: tuple[float, float] | None = None,
        n_hint: int | None = None,
    ) -> CompressedSeries:
        """Alg. 1: extract semantics once, then the residual refinement
        pyramid over the eps-target ladder (tier k stores only the delta
        below tier k-1's guarantee; 0.0 = lossless, needs ``decimals``).


        ``value_range``/``n_hint`` pin the scan's global quantities (see
        ``extract_semantics``) so an incremental scan over the same data —
        ``core.streaming.ShrinkStreamCodec`` — produces byte-identical
        output; ``None`` derives them from ``values`` as before.
        """
        values = np.asarray(values, dtype=np.float64)
        base = self.build_base(values, value_range=value_range, n_hint=n_hint)
        return encode_with_base(values, base, eps_targets, decimals, backend=self.backend)

    def compress_batch(
        self,
        values: np.ndarray | list[np.ndarray],
        eps_targets: list[float],
        decimals: int | None = None,
        semantics: str = "auto",
        lengths: np.ndarray | None = None,
        max_buckets: int | None = None,
    ) -> list[CompressedSeries]:
        """Batched Alg. 1 over S independent series — rectangular or ragged.

        Accepted inputs:
        * ``values[S, T]`` ndarray — S equal-length series (the PR 1 fast
          path, unchanged);
        * ``values[S, T]`` + ``lengths[S]`` — ragged lanes padded to T, row
          i holding ``lengths[i]`` real samples;
        * a list of 1-D arrays of ANY mix of lengths (including empty and
          length-1 series) — the gateway's real multi-sensor regime.

        Ragged inputs are length-bucketed into ≤ ``max_buckets`` padded
        lanes (percentile buckets over the sorted lengths, so each bucket
        holds similarly sized series and padding waste stays bounded;
        ``None`` scales the bucket count with the series count — about one
        bucket per 4 series, between 4 and 16, so wide length spreads
        don't drown the masked scans in padding) and
        every stage runs the valid-length mask path: the multi-series cone
        scan carries per-lane segment IDs/lengths so padding never leaks
        into cones, residual quantization cuts each stream at its series'
        end, and ALL streams of all buckets share one rANS entropy pass
        (the masked ragged state machine).

        Semantics extraction runs as one multi-series cone scan per bucket —
        the lane-parallel Pallas kernel with XLA segment compaction on TPU,
        a chunked-vectorized numpy scan elsewhere.  With
        ``semantics="numpy"`` (the off-TPU default) every output is
        byte-identical to ``[self.compress(v, ...) for v in values]``,
        ragged or not (property-tested in tests/test_ragged_property.py).

        semantics: "auto" (pallas on TPU, numpy otherwise) | "numpy" |
        "pallas" (force the kernel route, e.g. for testing in interpret
        mode).
        """
        if semantics == "auto":
            import jax

            semantics = "pallas" if jax.default_backend() == "tpu" else "numpy"
        if semantics not in ("numpy", "pallas"):
            raise ValueError(f"unknown semantics impl {semantics!r}")

        if isinstance(values, (list, tuple)):
            if lengths is not None:
                raise ValueError("pass lengths only with a padded [S, T] array")
            arrs = [np.asarray(v, dtype=np.float64).ravel() for v in values]
            ns = np.array([a.size for a in arrs], dtype=np.int64)
            if ns.size and (ns == ns[0]).all():  # rectangular in disguise
                return self._compress_batch_rect(
                    np.stack(arrs) if ns[0] else np.zeros((ns.size, 0)),
                    eps_targets, decimals, semantics,
                )
            return self._compress_batch_ragged(arrs, ns, eps_targets, decimals,
                                               semantics, max_buckets)
        values = np.asarray(values, dtype=np.float64)
        if values.ndim != 2:
            raise ValueError(f"expected values[S, T], got shape {values.shape}")
        if lengths is not None:
            ns = np.asarray(lengths, dtype=np.int64).ravel()
            if ns.shape != (values.shape[0],):
                raise ValueError(
                    f"lengths must be [S]={values.shape[0]}, got shape {ns.shape}"
                )
            if (ns < 0).any() or (ns > values.shape[1]).any():
                raise ValueError(f"lengths must lie in [0, T={values.shape[1]}]")
            if (ns == values.shape[1]).all():
                return self._compress_batch_rect(values, eps_targets, decimals, semantics)
            arrs = [values[i, : ns[i]] for i in range(values.shape[0])]
            return self._compress_batch_ragged(arrs, ns, eps_targets, decimals,
                                               semantics, max_buckets)
        return self._compress_batch_rect(values, eps_targets, decimals, semantics)

    def _compress_batch_rect(
        self,
        values: np.ndarray,
        eps_targets: list[float],
        decimals: int | None,
        semantics: str,
    ) -> list[CompressedSeries]:
        """The equal-length fast path: one full-width scan, no masks."""
        s, n = values.shape
        if semantics == "pallas" and n:
            seg_lists = extract_semantics_batch_pallas(values, self.config)
        else:
            # scalar early-exit scan per row: faster than the masked
            # multi-series scan on CPU (see _compress_batch_ragged), and
            # segment-identical to it
            seg_lists = [extract_semantics(values[i], self.config) for i in range(s)]

        vmins = values.min(axis=1) if n else np.zeros(s)
        vmaxs = values.max(axis=1) if n else np.zeros(s)
        bases = [
            construct_base(seg_lists[i], n, float(vmins[i]), float(vmaxs[i]), self.config)
            for i in range(s)
        ]
        return encode_frames_with_bases(
            values, bases, eps_targets, decimals, backend=self.backend
        )

    def _compress_batch_ragged(
        self,
        arrs: list[np.ndarray],
        ns: np.ndarray,
        eps_targets: list[float],
        decimals: int | None,
        semantics: str,
        max_buckets: int | None,
    ) -> list[CompressedSeries]:
        """Mixed-length lanes: percentile length-buckets, masked scans, one
        shared entropy pass.  Byte-identical (numpy semantics) to a
        per-series ``compress`` loop."""
        tiers = normalize_tiers(eps_targets, decimals)
        s = len(arrs)
        if max_buckets is None:
            max_buckets = int(np.clip(s // 4, 4, 16))
        if max_buckets < 1:
            raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
        bases: list[Base | None] = [None] * s
        base_bytes: list[bytes | None] = [None] * s
        eps_hats = np.zeros(s)
        streams_of: list[list[ResidualStream | None]] = [
            [None] * len(tiers) for _ in range(s)
        ]
        pyramids: list = [None] * s
        todo: list[tuple[int, int, ResidualStream]] = []  # (series, layer, stream)

        nonempty = np.flatnonzero(ns > 0)
        for i in np.flatnonzero(ns == 0):
            # an empty series carries an empty base and empty/absent layers;
            # no batching to be had
            b = construct_base([], 0, 0.0, 0.0, self.config)
            cs = encode_with_base(arrs[i], b, tiers, decimals, backend=self.backend)
            bases[i], base_bytes[i] = cs.base, cs.base_bytes
            pyramids[i] = cs.pyramid
            eps_hats[i] = cs.eps_b_practical

        # percentile buckets: equal-count groups of the length-sorted series,
        # each padded to its own max — bounded padding waste for any spread
        order = nonempty[np.argsort(ns[nonempty], kind="stable")]
        buckets = (
            [b for b in np.array_split(order, min(max_buckets, order.size)) if b.size]
            if order.size
            else []
        )
        for bucket in buckets:
            nb = ns[bucket]
            t_pad = int(nb.max())
            vals = np.zeros((bucket.size, t_pad))
            for row, i in enumerate(bucket):
                vals[row, : nb[row]] = arrs[i]
            if semantics == "pallas":
                seg_lists = extract_semantics_batch_pallas(vals, self.config, lengths=nb)
            else:
                # On CPU the adaptive early-exit scalar scan beats the
                # masked multi-series scan (which pre-computes division
                # tables for every position to feed the TPU lanes); the
                # segments are identical either way (property-tested)
                seg_lists = [extract_semantics(arrs[i], self.config) for i in bucket]
            valid = np.arange(t_pad)[None, :] < nb[:, None]
            vmins = np.where(valid, vals, np.inf).min(axis=1)
            vmaxs = np.where(valid, vals, -np.inf).max(axis=1)
            bkt_bases = [
                construct_base(
                    seg_lists[row], int(nb[row]), float(vmins[row]), float(vmaxs[row]),
                    self.config,
                )
                for row in range(bucket.size)
            ]
            preds = base_predictions_ragged(bkt_bases, t_pad)
            r = vals - preds
            bkt_eps_hats = np.abs(np.where(valid, r, 0.0)).max(axis=1)
            for row, i in enumerate(bucket):
                bases[i] = bkt_bases[row]
                base_bytes[i] = encode_base(bkt_bases[row])
                eps_hats[i] = bkt_eps_hats[row]
            bkt_streams = quantize_pyramid_batch(vals, preds, tiers, decimals, lengths=nb)
            for row, i in enumerate(bucket):
                streams_of[int(i)] = bkt_streams[row]
                todo.extend(
                    (int(i), k, st)
                    for k, st in enumerate(bkt_streams[row])
                    if st is not None
                )
        # ONE entropy pass across every layer of every bucket and series:
        # the ragged rANS machine interleaves all of them
        blobs = encode_residuals_batch([st for _, _, st in todo], backend=self.backend)
        payloads: list[list[bytes | None]] = [[None] * len(tiers) for _ in range(s)]
        for (i, k, _), blob in zip(todo, blobs):
            payloads[i][k] = blob
        for i in range(s):
            if pyramids[i] is None:
                pyramids[i] = pyramid_layers(tiers, streams_of[i], payloads[i])
        return [
            CompressedSeries(
                base=bases[i],
                base_bytes=base_bytes[i],
                pyramid=pyramids[i],
                eps_b_practical=float(eps_hats[i]),
            )
            for i in range(s)
        ]

    def decompress_at(self, cs: CompressedSeries, eps: float) -> np.ndarray:
        return decompress_at(cs, eps)


class ProgressiveDecoder:
    """Incremental pyramid decode over one :class:`CompressedSeries`.

    Layer prefixes are materialized on demand and every intermediate
    reconstruction is kept, so refining from tier j to tier k > j pays
    only for the layers in between — the serving layer's frame LRU caches
    one of these per hot frame and a dashboard that first wants a coarse
    sketch and then zooms in never decodes a layer twice.

    ``prefix(k)``/``at(eps)`` return the reconstruction through layer k /
    the cheapest tier satisfying ``eps``; arrays are cached and must be
    treated as read-only by callers.
    """

    def __init__(self, cs: CompressedSeries):
        self.cs = cs
        self._layers = cs.pyramid.layers
        # _recons[0] = base predictions; _recons[d + 1] = reconstruction
        # through layer d (identity layers alias the previous entry)
        self._recons: list[np.ndarray | None] = [None] * (len(self._layers) + 1)
        self._depth = -1  # deepest materialized layer
        self.layers_decoded = 0  # entropy decodes actually paid

    # -- introspection ------------------------------------------------- #
    @property
    def depth(self) -> int:
        """Deepest decoded layer index (-1 = base predictions only)."""
        return self._depth

    def intact_depth(self) -> int:
        """Deepest layer index reachable without crossing a quarantined
        (``corrupt``) layer (-1 = base only; every layer below the first
        corrupt one is unreachable because layer k refines the
        reconstruction error OF the prefix through k-1)."""
        for k, layer in enumerate(self._layers):
            if layer.corrupt:
                return k - 1
        return len(self._layers) - 1

    def guarantee(self, k: int | None = None) -> float:
        """Error bound of the prefix through layer ``k`` (default: the
        deepest decoded prefix)."""
        d = self._depth if k is None else k
        g = self.cs.eps_b_practical
        if d >= 0:
            g = min(g, self._layers[d].eps)
        return g

    def available(self) -> tuple[np.ndarray, float] | None:
        """Best reconstruction decodable with ZERO additional entropy work:
        ``(values, guarantee)``, or ``None`` when nothing is materialized
        yet.  This is what lets a server answer coarse immediately and
        fetch refinement layers on demand."""
        if self._recons[self._depth + 1] is None:
            return None
        return self._recons[self._depth + 1], self.guarantee()

    # -- decode -------------------------------------------------------- #
    def _ensure_base(self) -> None:
        if self._recons[0] is None:
            base = self.cs.base if self.cs.base is not None else decode_base(self.cs.base_bytes)
            self._recons[0] = base_predictions(base)

    def prefix(self, k: int) -> np.ndarray:
        """Reconstruction through layer ``k`` (-1 = base only), decoding
        only the layers not yet materialized."""
        self._ensure_base()
        if k > self._depth:
            recon = self._recons[self._depth + 1]
            for d in range(self._depth + 1, k + 1):
                layer = self._layers[d]
                if layer.corrupt:
                    raise LayerCorruptError(
                        "cannot decode past quarantined pyramid layer "
                        f"(tier eps={layer.eps:g}); finest intact prefix is "
                        f"layer {d - 1}",
                        layer=d,
                    )
                if layer.mode == "identity":
                    out = recon  # tier exists, carries no bytes
                elif layer.mode == "midpoint":
                    q = self._decode_payload(layer, d, len(recon))
                    out = recon + (layer.r_lo + (q.astype(np.float64) + 0.5) * layer.step)
                    recon = out
                elif layer.mode == "exact":
                    q = self._decode_payload(layer, d, len(recon))
                    decimals = int(round(-math.log10(layer.step)))
                    scale = 10.0**decimals
                    rec_int = np.round(recon * scale).astype(np.int64)
                    out = (rec_int + q) / scale
                else:  # pragma: no cover - constructor enforces modes
                    raise ValueError(f"unknown layer mode {layer.mode!r}")
                self._recons[d + 1] = out
            self._depth = k
        return self._recons[k + 1]

    def _decode_payload(self, layer, d: int, n: int) -> np.ndarray:
        """Entropy-decode one layer's payload defensively: a payload that
        slipped past the CRC (or was handed in without one) must surface
        as a typed :class:`LayerCorruptError`, never a raw
        ``KeyError``/``IndexError`` from the entropy coder or a
        wrong-length array that would silently mis-add."""
        try:
            q = entropy.decode_ints(layer.payload)
        except ShrinkError:
            raise
        except Exception as e:
            raise LayerCorruptError(
                f"pyramid layer payload failed entropy decode: {e}", layer=d
            ) from e
        if len(q) != n:
            raise LayerCorruptError(
                f"pyramid layer decoded to {len(q)} residuals for {n} samples",
                layer=d,
            )
        self.layers_decoded += 1
        return q

    def at(self, eps: float) -> np.ndarray:
        """Reconstruction with guarantee <= ``eps`` via the cheapest
        sufficient layer prefix."""
        return self.prefix(self.cs.pyramid.resolve(eps, self.cs.eps_b_practical))


def decompress_at(cs: CompressedSeries, eps: float) -> np.ndarray:
    """Reconstruct the series from ``cs`` at resolution ``eps``: the
    cheapest layer prefix whose guarantee is <= ``eps`` (any requested eps
    resolves to the nearest sufficient tier; ``ValueError`` only when no
    tier qualifies).  Stateless — everything needed lives in the compressed
    series itself, which is what lets range-decode consumers reconstruct
    frames without a codec."""
    return ProgressiveDecoder(cs).at(eps)


def encode_with_base(
    values: np.ndarray,
    base: Base,
    eps_targets: list[float],
    decimals: int | None = None,
    backend: str = "best",
) -> CompressedSeries:
    """Residual-encoding tail of Alg. 1: given an already-constructed base,
    emit the refinement pyramid over the (normalized) eps-target ladder.
    Shared by ``ShrinkCodec.compress`` and the streaming frame sealer so
    both produce identical bytes for identical (values, base) inputs.  All
    layers run through one batched entropy pass."""
    values = np.asarray(values, dtype=np.float64)
    base_bytes = encode_base(base)
    pred = base_predictions(base)
    eps_hat = practical_eps_b(values, base, pred=pred)
    tiers = normalize_tiers(eps_targets, decimals)
    streams = quantize_pyramid(values, pred, tiers, decimals)
    todo = [(k, st) for k, st in enumerate(streams) if st is not None]
    blobs = encode_residuals_batch([st for _, st in todo], backend=backend)
    payloads: list[bytes | None] = [None] * len(tiers)
    for (k, _), blob in zip(todo, blobs):
        payloads[k] = blob
    return CompressedSeries(
        base=base,
        base_bytes=base_bytes,
        pyramid=pyramid_layers(tiers, streams, payloads),
        eps_b_practical=eps_hat,
    )


def encode_frames_with_bases(
    values: np.ndarray,
    bases: list[Base],
    eps_targets: list[float],
    decimals: int | None = None,
    backend: str = "best",
) -> list[CompressedSeries]:
    """Batched ``encode_with_base`` over F equal-length frames whose bases
    are already constructed: one prediction pass, one pyramid
    quantization, and ONE entropy pass across every layer of every frame
    — each output byte-identical to
    ``encode_with_base(values[f], bases[f], ...)``.  Shared by the
    rectangular batch compressor and the streaming sealer (which batches
    every frame completed by a single ingest call)."""
    f_count, n = values.shape
    base_bytes = [encode_base(b) for b in bases]
    preds = base_predictions_batch(bases) if f_count else np.zeros((0, n))
    eps_hats = [
        practical_eps_b(values[i], bases[i], pred=preds[i]) for i in range(f_count)
    ]
    tiers = normalize_tiers(eps_targets, decimals)
    layer_streams = quantize_pyramid_batch(values, preds, tiers, decimals)
    # ONE entropy pass for every layer of every frame: the rANS batch
    # interleaves all of them into a single vectorized state machine
    todo = [
        (i, k, st)
        for i in range(f_count)
        for k, st in enumerate(layer_streams[i])
        if st is not None
    ]
    blobs = encode_residuals_batch([st for _, _, st in todo], backend=backend)
    payloads: list[list[bytes | None]] = [[None] * len(tiers) for _ in range(f_count)]
    for (i, k, _), blob in zip(todo, blobs):
        payloads[i][k] = blob
    return [
        CompressedSeries(
            base=bases[i],
            base_bytes=base_bytes[i],
            pyramid=pyramid_layers(tiers, layer_streams[i], payloads[i]),
            eps_b_practical=float(eps_hats[i]),
        )
        for i in range(f_count)
    ]


def cs_to_bytes(cs: CompressedSeries) -> bytes:
    """``SHRK`` v2 container: version byte, header (eps_hat, base length),
    a CRC32 over header-fields + base blob, the ``SHRB`` base, then the
    ``SHRR`` v3 residual pyramid blob (normative byte layout in
    docs/wire-format.md).

    The header CRC covers ``eps_hat || base_len || base_bytes`` — without
    it a flipped bit in the eps_hat f64 would silently change the
    *reported guarantee* of every answer served from this blob, which is
    exactly the "silent wrong data" failure degradation must rule out.
    A trusted header + base is also what makes base-only fallback sound
    when the pyramid section is damaged."""
    pyr = encode_pyramid(cs.pyramid)
    header = struct.pack("<dI", cs.eps_b_practical, len(cs.base_bytes))
    buf = bytearray()
    buf += _CONTAINER_MAGIC
    buf.append(_CONTAINER_VERSION)
    buf += header
    buf += struct.pack("<I", zlib.crc32(header + cs.base_bytes) & 0xFFFFFFFF)
    buf += cs.base_bytes
    buf += struct.pack("<I", len(pyr))
    buf += pyr
    return bytes(buf)


def cs_from_bytes(data: bytes, strict: bool = True) -> CompressedSeries:
    """Parse a ``SHRK`` v2 container.  Raises a :class:`ShrinkError`
    subclass (never a raw ``struct.error``/``IndexError``) on foreign,
    truncated, or trailing-garbage input — every length is validated
    before it is read, and the header/base CRC is always verified.

    ``strict`` is forwarded to :func:`decode_pyramid`: with
    ``strict=False`` a corrupt pyramid *layer* comes back quarantined
    (``layer.corrupt``) instead of raising, so a degraded reader can still
    serve the intact layer prefix under the (CRC-trusted) base and
    eps_hat."""
    data = bytes(data)
    if len(data) < 4 or data[:4] != _CONTAINER_MAGIC:
        raise FormatError("bad container magic: not a SHRK blob")
    if len(data) < 5:
        raise TruncatedArchiveError("truncated SHRK container: missing version")
    if data[4] != _CONTAINER_VERSION:
        raise FormatError(
            f"unsupported SHRK version {data[4]} (this build reads "
            f"v{_CONTAINER_VERSION} containers)"
        )
    if len(data) < 21:
        raise TruncatedArchiveError("truncated SHRK container: incomplete header")
    eps_hat, base_len = struct.unpack_from("<dI", data, 5)
    (hdr_crc,) = struct.unpack_from("<I", data, 17)
    pos = 21
    if pos + base_len > len(data):
        raise TruncatedArchiveError("truncated SHRK container: base blob cut short")
    base_bytes = data[pos : pos + base_len]
    pos += base_len
    if zlib.crc32(data[5:17] + base_bytes) & 0xFFFFFFFF != hdr_crc:
        raise CorruptFrameError("corrupt SHRK container: header/base CRC mismatch")
    if pos + 4 > len(data):
        raise TruncatedArchiveError("truncated SHRK container: missing pyramid length")
    (pyr_len,) = struct.unpack_from("<I", data, pos)
    pos += 4
    if pos + pyr_len > len(data):
        raise TruncatedArchiveError(
            "truncated SHRK container: residual pyramid cut short"
        )
    pyramid = decode_pyramid(data[pos : pos + pyr_len], strict=strict)
    pos += pyr_len
    if pos != len(data):
        raise CorruptFrameError("corrupt SHRK container: trailing bytes after pyramid")
    return CompressedSeries(
        base=decode_base(base_bytes),
        base_bytes=bytes(base_bytes),
        pyramid=pyramid,
        eps_b_practical=eps_hat,
    )
