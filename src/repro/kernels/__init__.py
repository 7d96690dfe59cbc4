"""SHRINK compute hot-spots as Pallas TPU kernels.

Kernels (each has a pure-jnp oracle in ref.py, validated in
tests/test_kernels.py over shape/dtype sweeps):

* interval_stats — per-window min/max (Alg. 2 fluctuation stats)
* cone_scan      — shrinking-cone recurrence, sequential-grid state carry,
                   lane-parallel across series (Alg. 3); cone_scan_segments
                   adds on-device (XLA) segment compaction for the batched
                   codec pipeline
* residual_quant — fused residual + quantize + clip + error feedback (Alg. 6)
* pyramid_quant  — fused multi-layer refinement quantization (the device
                   half of the residual pyramid: layer l quantizes the
                   error layers 0..l-1 left behind, one VMEM pass)
* dequant        — fused dequantize + linear reconstruct
* pyramid_reconstruct — fused pred + Σ_l q_l·step_l over any layer prefix
* segment_agg    — closed-form per-segment aggregates (sum/sumsq/min/max):
                   the device counterpart of core.segment_algebra for
                   batched compressed-domain analytics; O(segments), no
                   per-sample work (host engine runs the numpy path today)
* flash_attention — online-softmax fused attention (sequential-kv grid)
* rans           — interleaved K-lane rANS entropy coder (encode + decode)
                   as jitted lax.scan machines, not Pallas: states on the
                   lane axis, (stream, plane) rows on sublanes, the serial
                   step axis as the scan; byte-identical to core.entropy's
                   numpy machine, which routes big jobs here as its device
                   engine
"""
from .ops import (  # noqa: F401
    cone_scan,
    cone_scan_segments,
    flash_attention,
    dequant_reconstruct,
    interval_stats,
    pyramid_quant,
    pyramid_reconstruct,
    rans_decode_rows,
    rans_encode_rows,
    residual_quant,
    segment_agg,
    use_interpret,
)
from . import ref  # noqa: F401
