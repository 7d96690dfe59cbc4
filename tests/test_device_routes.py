"""Route selection for the device path, on the CPU.

* A kernel whose compiled path fails raises: nothing reruns it in
  interpret mode, and nothing swaps the numpy coder in for the device
  rANS engine.
* The route a job takes depends on the platform and the job size only,
  not on whether something imported jax first.
* The compile cache lands where ``JAX_COMPILATION_CACHE_DIR`` says, or
  at a fixed path in the checkout.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import entropy  # noqa: E402
from repro.kernels import ops, rans  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def _lowering_fails(*args, interpret=None, **kwargs):
    raise RuntimeError(f"Mosaic lowering failed (interpret={interpret})")


def test_compiled_cone_scan_failure_propagates(monkeypatch):
    """On a TPU the wrapper runs the compiled kernel once and its failure
    reaches the caller; interpret mode is never tried behind it."""
    seen = []

    def kernel(*args, interpret, **kwargs):
        seen.append(interpret)
        return _lowering_fails(*args, interpret=interpret)

    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    monkeypatch.setattr(ops, "cone_scan_pallas", kernel)
    x = jnp.zeros((256, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret=False"):
        ops.cone_scan(x, x + 0.1)
    assert seen == [False]


@pytest.mark.parametrize("name", ["pyramid_quant", "segment_agg"])
def test_compiled_kernel_failure_propagates(monkeypatch, name):
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    monkeypatch.setattr(ops, f"{name}_pallas", _lowering_fails)
    col = jnp.zeros((8, 1), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret=False"):
        if name == "pyramid_quant":
            ops.pyramid_quant(jnp.zeros((8, 128)), col, col, jnp.ones(2))
        else:
            ops.segment_agg(col, col, col, col + 4)


def test_device_encode_failure_propagates(monkeypatch, rans_engine):
    """An engine failure is an error, not a quiet switch to numpy."""
    monkeypatch.setattr(rans, "encode_rows", _lowering_fails)
    monkeypatch.setattr(rans, "encode_cube", _lowering_fails)
    q = np.arange(5_000, dtype=np.int64) % 97
    with rans_engine("device"):
        with pytest.raises(RuntimeError, match="lowering"):
            entropy.encode_ints(q, backend="rans")
        with pytest.raises(RuntimeError, match="lowering"):
            entropy.encode_ints_batch([q, q[:3_000]], backend="rans")


def test_device_decode_failure_propagates(monkeypatch, rans_engine):
    q = np.arange(5_000, dtype=np.int64) % 97
    with rans_engine("numpy"):
        blob = entropy.encode_ints(q, backend="rans")
    monkeypatch.setattr(rans, "decode_rows", _lowering_fails)
    with rans_engine("device"), pytest.raises(RuntimeError, match="lowering"):
        entropy.decode_ints(blob)


_PROBE = """
import hashlib, json, sys
{first}
import numpy as np
from repro.core import ShrinkCodec, ShrinkConfig, cs_to_bytes, entropy
jax_loaded = "jax" in sys.modules
rng = np.random.default_rng(3)
series = [np.round(np.cumsum(rng.standard_normal(n)) * 0.1, 4) for n in (700, 90, 2_000)]
css = ShrinkCodec(ShrinkConfig(eps_b=0.4), backend="rans").compress_batch(
    series, eps_targets=[1e-2, 0.0], decimals=4)
from repro.kernels.calls import call_counts
print(json.dumps({{
    "jax_loaded_before_codec": jax_loaded,
    "engine_at_min": entropy._rans_device(entropy._RANS_DEVICE_MIN, False) is not None,
    "engine_below_min": entropy._rans_device(entropy._RANS_DEVICE_MIN - 1, False) is not None,
    "cone_scan_calls": sum(v for k, v in call_counts().items() if k[0] == "cone_scan"),
    "bytes": hashlib.sha256(b"".join(cs_to_bytes(cs) for cs in css)).hexdigest(),
}}))
"""


def _probe(first: str) -> dict:
    env = dict(os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(first=first)],
        env=env, capture_output=True, text=True, timeout=300, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_route_does_not_depend_on_import_order():
    """The codec first, then jax (through the codec's own lazy import), or
    jax first: the same routes and the same bytes."""
    codec_first = _probe("")
    jax_first = _probe("import jax; jax.numpy.zeros(1)")
    assert not codec_first.pop("jax_loaded_before_codec")
    assert jax_first.pop("jax_loaded_before_codec")
    assert codec_first == jax_first
    assert codec_first["engine_at_min"] and not codec_first["engine_below_min"]


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache(tmp_path) == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
