"""Compile the codec's device path for a TPU v5e without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described v5e topology and refuses what the chip would refuse (tiles
that do not fit VMEM, layouts Mosaic cannot lower).  Interpret-mode tests
cannot see those failures, so these compiles guard the kernels at the
widths the gateway path runs:

* the cone scan at the gateway ingest's widest bucket (1,024 sensors in 16
  percentile buckets, 4M-sample flushes: T = 32768 after power-of-two
  padding, 64 series padded to 128 lanes) and at (16384, 4096);
* the XLA segment compaction behind it at the same shapes;
* the rANS encode and decode scans at the padded shapes ``encode_rows``
  and ``decode_rows`` build for a 4M-symbol job.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels import rans  # noqa: E402
from repro.kernels.cone_scan import cone_scan_pallas  # noqa: E402
from repro.kernels.ops import _compact_segments  # noqa: E402

CONE_SHAPES = [(32768, 128), (16384, 4096)]
# 4M symbols as 64 rows x 64k and as one 4M row
RANS_JOBS = [(64, 1 << 16), (1, 1 << 22)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("t,s", CONE_SHAPES)
def test_cone_scan_compiles_for_v5e(one_chip, t, s):
    x = _spec((t, s), jnp.float32, one_chip)
    lengths = _spec((s,), jnp.int32, one_chip)
    compiled = (
        jax.jit(lambda a, e, n: cone_scan_pallas(a, e, n, interpret=False))
        .lower(x, x, lengths)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("t,s", CONE_SHAPES)
def test_compact_segments_compiles_for_v5e(one_chip, t, s):
    i32 = _spec((t, s), jnp.int32, one_chip)
    f32 = _spec((t, s), jnp.float32, one_chip)
    fin = _spec((1, s), jnp.float32, one_chip)
    _compact_segments.lower(i32, f32, f32, f32, fin, fin).compile()


def _padded(rows: int, cols: int) -> tuple[int, int, int]:
    """(steps, rows, words) after ``encode_rows``/``decode_rows`` padding."""
    steps = rans._pow2(-(-cols // rans._K))
    return steps, rans._pow2(rows), rans._pow2(cols)


@pytest.mark.parametrize("rows,cols", RANS_JOBS)
def test_rans_encode_compiles_for_v5e(one_chip, rows, cols):
    steps, rp, _ = _padded(rows, cols)
    cube = _spec((steps, rp, rans._K), jnp.int32, one_chip)
    table = _spec((rp, 257), jnp.uint32, one_chip)
    compiled = rans._enc_ref_jit.lower(
        cube, table, table, unroll=rans._ENC_UNROLL
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("rows,cols", RANS_JOBS)
def test_rans_decode_compiles_for_v5e(one_chip, rows, cols):
    steps, rp, words = _padded(rows, cols)
    compiled = rans._dec_ref_jit.lower(
        _spec((rp, rans._K), jnp.uint32, one_chip),
        _spec((rp, rans._M), jnp.int32, one_chip),
        _spec((rp, 256), jnp.uint32, one_chip),
        _spec((rp, 256), jnp.uint32, one_chip),
        _spec((rp, words), jnp.uint16, one_chip),
        _spec((steps, rp, rans._K), jnp.bool_, one_chip),
        unroll=rans._DEC_UNROLL,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
