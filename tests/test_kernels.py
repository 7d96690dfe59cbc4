"""Pallas kernel validation: interpret=True vs pure-jnp oracles (ref.py),
swept over shapes and dtypes per the kernel-testing contract."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (
    cone_scan,
    dequant_reconstruct,
    interval_stats,
    pyramid_quant,
    pyramid_reconstruct,
    residual_quant,
)
from repro.kernels import ref

_RNG = np.random.default_rng(42)


# ------------------------------------------------------------ interval_stats
@pytest.mark.parametrize("shape,window", [
    ((128, 128), 16),
    ((512, 256), 64),
    ((1024, 128), 128),
    ((64, 512), 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interval_stats(shape, window, dtype):
    x = jnp.asarray(_RNG.standard_normal(shape), dtype=dtype)
    mn, mx = interval_stats(x, window)
    mn_r, mx_r = ref.interval_stats_ref(x, window)
    np.testing.assert_array_equal(np.asarray(mn), np.asarray(mn_r))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(mx_r))


def test_interval_stats_rejects_ragged():
    x = jnp.zeros((100, 128), jnp.float32)
    with pytest.raises(AssertionError):
        interval_stats(x, 64)


# ------------------------------------------------------------ residual_quant
@pytest.mark.parametrize("m,n", [(8, 128), (32, 256), (128, 128), (5, 384)])
@pytest.mark.parametrize("qmax", [127, 32767])
def test_residual_quant(m, n, qmax):
    x = jnp.asarray(_RNG.standard_normal((m, n)), dtype=jnp.float32)
    theta = jnp.asarray(_RNG.standard_normal((m, 1)), dtype=jnp.float32)
    slope = jnp.asarray(_RNG.standard_normal((m, 1)) * 0.01, dtype=jnp.float32)
    step = jnp.full((m, 1), 0.05, jnp.float32)
    q, err = residual_quant(x, theta, slope, step, qmax=qmax)
    q_r, err_r = ref.residual_quant_ref(x, theta, slope, step, qmax=qmax)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(err), np.asarray(err_r), atol=2e-6)


def test_residual_quant_clipping():
    """Huge residuals must saturate at +-qmax, and error feedback must carry
    the clipped mass."""
    m, n = 8, 128
    x = jnp.full((m, n), 100.0, jnp.float32)
    theta = jnp.zeros((m, 1), jnp.float32)
    slope = jnp.zeros((m, 1), jnp.float32)
    step = jnp.full((m, 1), 0.01, jnp.float32)
    q, err = residual_quant(x, theta, slope, step, qmax=127)
    assert int(np.asarray(q).max()) == 127
    np.testing.assert_allclose(np.asarray(err), 100.0 - 127 * 0.01, atol=1e-5)


# ------------------------------------------------------------ dequant
@pytest.mark.parametrize("m,n", [(8, 128), (64, 256), (3, 640)])
def test_dequant_roundtrip(m, n):
    q = jnp.asarray(_RNG.integers(-127, 128, (m, n)), dtype=jnp.int32)
    theta = jnp.asarray(_RNG.standard_normal((m, 1)), dtype=jnp.float32)
    slope = jnp.asarray(_RNG.standard_normal((m, 1)) * 0.01, dtype=jnp.float32)
    step = jnp.full((m, 1), 0.05, jnp.float32)
    xh = dequant_reconstruct(q, theta, slope, step)
    xh_r = ref.dequant_reconstruct_ref(q, theta, slope, step)
    np.testing.assert_allclose(np.asarray(xh), np.asarray(xh_r), atol=2e-6)


def test_quant_dequant_error_bound():
    """|x - dequant(quant(x))| <= step/2 wherever no clipping occurred."""
    m, n = 16, 256
    x = jnp.asarray(_RNG.standard_normal((m, n)), dtype=jnp.float32)
    theta = jnp.zeros((m, 1), jnp.float32)
    slope = jnp.zeros((m, 1), jnp.float32)
    step = jnp.full((m, 1), 0.05, jnp.float32)
    q, err = residual_quant(x, theta, slope, step, qmax=127)
    xh = dequant_reconstruct(q, theta, slope, step)
    assert np.max(np.abs(np.asarray(xh) - np.asarray(x))) <= 0.025 + 1e-6


# ------------------------------------------------------------ pyramid_quant
@pytest.mark.parametrize("m,n", [(8, 128), (32, 256), (5, 384)])
@pytest.mark.parametrize("num_layers", [1, 3])
def test_pyramid_quant_matches_ref(m, n, num_layers):
    x = jnp.asarray(_RNG.standard_normal((m, n)), dtype=jnp.float32)
    theta = jnp.asarray(_RNG.standard_normal((m, 1)), dtype=jnp.float32)
    slope = jnp.asarray(_RNG.standard_normal((m, 1)) * 0.01, dtype=jnp.float32)
    steps = jnp.asarray([0.5, 0.05, 0.005][:num_layers], jnp.float32)
    qs, err = pyramid_quant(x, theta, slope, steps)
    qs_r, err_r = ref.pyramid_quant_ref(x, theta, slope, steps)
    assert qs.shape == (num_layers, m, n)
    np.testing.assert_array_equal(np.asarray(qs), np.asarray(qs_r))
    np.testing.assert_allclose(np.asarray(err), np.asarray(err_r), atol=2e-6)


def test_pyramid_quant_ragged_tails_inert():
    m, n = 6, 256
    x = jnp.asarray(_RNG.standard_normal((m, n)), dtype=jnp.float32)
    theta = jnp.zeros((m, 1), jnp.float32)
    slope = jnp.zeros((m, 1), jnp.float32)
    steps = jnp.asarray([0.5, 0.05], jnp.float32)
    lengths = jnp.asarray([n, 0, 17, 100, 1, 255], jnp.int32)
    qs, err = pyramid_quant(x, theta, slope, steps, lengths=lengths)
    qs_r, err_r = ref.pyramid_quant_ref(x, theta, slope, steps, lengths=lengths)
    np.testing.assert_array_equal(np.asarray(qs), np.asarray(qs_r))
    np.testing.assert_allclose(np.asarray(err), np.asarray(err_r), atol=2e-6)
    pos = np.arange(n)[None, :]
    pad = pos >= np.asarray(lengths)[:, None]
    assert (np.asarray(qs)[:, pad] == 0).all()
    assert (np.asarray(err)[pad] == 0).all()


def test_pyramid_reconstruct_prefix_refines():
    """Each successive layer prefix tightens the reconstruction error down
    to that layer's step/2 (no clipping in this regime), and the fused
    kernel matches the oracle at every prefix."""
    m, n = 16, 256
    x = jnp.asarray(_RNG.standard_normal((m, n)), dtype=jnp.float32)
    theta = jnp.asarray(_RNG.standard_normal((m, 1)), dtype=jnp.float32)
    slope = jnp.asarray(_RNG.standard_normal((m, 1)) * 0.01, dtype=jnp.float32)
    steps = jnp.asarray([0.5, 0.05, 0.005], jnp.float32)
    qs, err = pyramid_quant(x, theta, slope, steps, qmax=32767)
    prev = np.inf
    for k in range(3):
        xh = pyramid_reconstruct(qs[: k + 1], theta, slope, steps[: k + 1])
        xh_r = ref.pyramid_reconstruct_ref(qs[: k + 1], theta, slope, steps[: k + 1])
        np.testing.assert_allclose(np.asarray(xh), np.asarray(xh_r), atol=2e-6)
        bound = float(steps[k]) / 2
        worst = np.max(np.abs(np.asarray(xh) - np.asarray(x)))
        assert worst <= bound + 1e-5
        assert worst <= prev
        prev = worst
    # the full stack's remaining error is exactly the kernel's err output
    xh = pyramid_reconstruct(qs, theta, slope, steps)
    np.testing.assert_allclose(
        np.asarray(x - xh), np.asarray(err), atol=1e-5
    )


# ------------------------------------------------------------ cone_scan
def _compare_cone(x, eps, block_t, lengths=None):
    out_k = cone_scan(x, eps, block_t=block_t, lengths=lengths)
    out_r = ref.cone_scan_ref(x, eps, lengths=lengths)
    assert all(a.shape == b.shape for a, b in zip(out_k, out_r))
    brk_k, theta_k = np.asarray(out_k[0]), np.asarray(out_k[1])
    brk_r, theta_r = np.asarray(out_r[0]), np.asarray(out_r[1])
    np.testing.assert_array_equal(brk_k, brk_r)
    # compare only at defined (break) positions
    mask = brk_r.astype(bool)
    np.testing.assert_allclose(theta_k[mask], theta_r[mask], rtol=1e-5, atol=1e-5)
    for idx in (2, 3):  # psi_lo / psi_hi at break positions, skip sentinels
        a, b = np.asarray(out_k[idx]), np.asarray(out_r[idx])
        m = mask & (np.abs(b) < 1e30)
        np.testing.assert_allclose(a[m], b[m], rtol=1e-4, atol=1e-4)
    for idx in (4, 5):  # final spans
        a, b = np.asarray(out_k[idx]), np.asarray(out_r[idx])
        m = np.abs(b) < 1e30
        np.testing.assert_allclose(a[m], b[m], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t,s,block_t", [
    (256, 128, 64),
    (512, 128, 256),
    (512, 256, 512),
    (128, 384, 32),
    (256, 640, 64),  # two 512-lane blocks, the second partly padding
])
def test_cone_scan_shapes(t, s, block_t):
    x = jnp.asarray(
        np.cumsum(_RNG.standard_normal((t, s)) * 0.05, axis=0), dtype=jnp.float32
    )
    eps = jnp.full((t, s), 0.1, jnp.float32)
    _compare_cone(x, eps, block_t)


def test_cone_scan_adaptive_eps():
    """Per-point eps (the adaptive threshold path) must be honored."""
    t, s = 256, 128
    x = jnp.asarray(np.cumsum(_RNG.standard_normal((t, s)) * 0.05, axis=0), jnp.float32)
    eps = jnp.asarray(0.05 + 0.2 * _RNG.random((t, s)), jnp.float32)
    _compare_cone(x, eps, 64)


def test_cone_scan_segments_cover_series():
    """Break flags reconstruct a partition; each segment's span approximates
    its points within eps (the end-to-end semantic check)."""
    t, s = 512, 128
    x_np = np.cumsum(_RNG.standard_normal((t, s)) * 0.02, axis=0).astype(np.float32)
    eps_v = 0.15
    x = jnp.asarray(x_np)
    eps = jnp.full((t, s), eps_v, jnp.float32)
    brk, theta, lo, hi, fin_lo, fin_hi = (np.asarray(a) for a in cone_scan(x, eps, block_t=128))
    for col in range(0, s, 17):
        starts = np.flatnonzero(brk[:, col])
        assert starts[0] == 0
        ends = np.append(starts[1:], t)
        for st, en in zip(starts, ends):
            th = theta[st, col]
            if en < t:
                plo, phi = lo[en, col], hi[en, col]
            else:
                plo, phi = fin_lo[0, col], fin_hi[0, col]
            if en - st == 1:
                continue  # single-point: any slope works
            slope = 0.5 * (max(plo, -1e30) + min(phi, 1e30))
            tt = np.arange(en - st)
            err = np.max(np.abs(x_np[st:en, col] - (th + slope * tt)))
            assert err <= eps_v * (1 + 1e-4) + 1e-6


def test_cone_scan_nonaligned_t_padding():
    t, s = 300, 128  # t % block_t != 0
    x = jnp.asarray(np.cumsum(_RNG.standard_normal((t, s)) * 0.05, axis=0), jnp.float32)
    eps = jnp.full((t, s), 0.1, jnp.float32)
    out_k = cone_scan(x, eps, block_t=128)
    out_r = ref.cone_scan_ref(x, eps)
    np.testing.assert_array_equal(np.asarray(out_k[0]), np.asarray(out_r[0]))
    # the mask keeps alignment padding out of the open segment's final span
    for idx in (4, 5):
        a, b = np.asarray(out_k[idx]), np.asarray(out_r[idx])
        m = np.abs(b) < 1e30
        np.testing.assert_allclose(a[m], b[m], rtol=1e-4, atol=1e-4)


def test_cone_scan_valid_length_mask():
    """Ragged lanes: the kernel's segment-ID/valid-length mask path must
    match the masked oracle, produce no breaks inside padding, and freeze
    each lane's final span at its own end."""
    t, s = 384, 128
    x = jnp.asarray(np.cumsum(_RNG.standard_normal((t, s)) * 0.05, axis=0), jnp.float32)
    eps = jnp.full((t, s), 0.08, jnp.float32)
    lengths = _RNG.integers(1, t + 1, s).astype(np.int32)
    lengths[0], lengths[1] = 1, t  # degenerate + full lanes
    out_k = cone_scan(x, eps, block_t=128, lengths=jnp.asarray(lengths))
    out_r = ref.cone_scan_ref(x, eps, lengths=jnp.asarray(lengths))
    brk_k = np.asarray(out_k[0])
    np.testing.assert_array_equal(brk_k, np.asarray(out_r[0]))
    for col in range(s):
        assert brk_k[lengths[col] :, col].sum() == 0, col  # padding never breaks
    for idx in (4, 5):  # final spans match the masked oracle exactly
        a, b = np.asarray(out_k[idx]), np.asarray(out_r[idx])
        m = np.abs(b) < 1e30
        np.testing.assert_allclose(a[m], b[m], rtol=1e-4, atol=1e-4)
    # a fully-valid lengths vector is the unmasked scan
    out_full = cone_scan(x, eps, block_t=128, lengths=jnp.full((s,), t, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_full[0]), np.asarray(cone_scan(x, eps, block_t=128)[0]))


@pytest.mark.parametrize("t,s,block_t", [
    (64, 37, 64),  # one lane group, mostly padding
    (300, 640, 64),  # T bucketed to 512; two lane blocks
    (128, 1100, 32),  # three lane blocks, the last padded past S
])
def test_cone_scan_valid_length_mask_lane_blocks(t, s, block_t):
    """Ragged lanes across lane blocks: the carry restarts at the first time
    block of every 512-lane block, and padded lanes and rows are dropped —
    every output matches the masked oracle."""
    x = jnp.asarray(np.cumsum(_RNG.standard_normal((t, s)) * 0.05, axis=0), jnp.float32)
    eps = jnp.full((t, s), 0.08, jnp.float32)
    lengths = _RNG.integers(1, t + 1, s).astype(np.int32)
    lengths[0], lengths[-1] = 1, t
    _compare_cone(x, eps, block_t, lengths=jnp.asarray(lengths))


def test_residual_quant_ragged_tails():
    """Padded row tails must emit q = 0 and err = 0 (no symbols, no error
    feedback), with valid prefixes untouched."""
    m, n = 8, 256
    x = jnp.asarray(_RNG.standard_normal((m, n)), jnp.float32)
    theta = jnp.asarray(_RNG.standard_normal((m, 1)), jnp.float32)
    slope = jnp.asarray(_RNG.standard_normal((m, 1)) * 0.01, jnp.float32)
    step = jnp.full((m, 1), 0.05, jnp.float32)
    lengths = jnp.asarray(np.array([256, 0, 1, 100, 255, 7, 128, 13], np.int32))
    q, err = residual_quant(x, theta, slope, step, lengths=lengths)
    q_r, err_r = ref.residual_quant_ref(x, theta, slope, step, lengths=lengths)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(err), np.asarray(err_r), atol=2e-6)
    q_full, err_full = residual_quant(x, theta, slope, step)
    qn, en = np.asarray(q), np.asarray(err)
    for i, ln in enumerate(np.asarray(lengths)):
        assert not qn[i, ln:].any() and not en[i, ln:].any()
        np.testing.assert_array_equal(qn[i, :ln], np.asarray(q_full)[i, :ln])


# ------------------------------------------------------------ property sweeps
# hypothesis is a dev extra: without it the fixed-shape tests above still run
# and only the property sweeps report as skipped.
try:
    from hypothesis import given, settings, strategies as st

    _HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    _HAS_HYPOTHESIS = False

if not _HAS_HYPOTHESIS:

    def test_property_sweeps_need_hypothesis():
        pytest.importorskip("hypothesis", reason="property sweeps need the hypothesis dev extra")

else:

    @given(
        m=st.integers(min_value=1, max_value=48),
        n=st.sampled_from([128, 256, 384, 512]),
        step=st.floats(min_value=1e-4, max_value=1.0),
        seed=st.integers(min_value=0, max_value=2**16),
    )
    @settings(max_examples=15, deadline=None)
    def test_residual_quant_property(m, n, step, seed):
        """Any block geometry: kernel == oracle exactly on q, and the
        quant/dequant error bound |err| <= step/2 holds wherever unclipped."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        theta = jnp.asarray(rng.standard_normal((m, 1)), jnp.float32)
        slope = jnp.asarray(rng.standard_normal((m, 1)) * 0.01, jnp.float32)
        st_arr = jnp.full((m, 1), step, jnp.float32)
        q, err = residual_quant(x, theta, slope, st_arr)
        q_r, err_r = ref.residual_quant_ref(x, theta, slope, st_arr)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_r))
        unclipped = np.abs(np.asarray(q)) < 127
        bound = step / 2 + 1e-5 + np.abs(np.asarray(x)).max() * 1e-6
        assert np.all(np.abs(np.asarray(err))[unclipped] <= bound)

    @given(
        t=st.sampled_from([64, 128, 192, 256]),
        s=st.sampled_from([128, 256]),
        eps=st.floats(min_value=0.02, max_value=0.5),
        seed=st.integers(min_value=0, max_value=2**16),
    )
    @settings(max_examples=8, deadline=None)
    def test_cone_scan_property(t, s, eps, seed):
        """Break flags from the Pallas kernel match the lax.scan oracle for any
        geometry/threshold."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray(np.cumsum(rng.standard_normal((t, s)) * 0.05, axis=0), jnp.float32)
        ee = jnp.full((t, s), eps, jnp.float32)
        brk_k = np.asarray(cone_scan(x, ee, block_t=64)[0])
        brk_r = np.asarray(ref.cone_scan_ref(x, ee)[0])
        np.testing.assert_array_equal(brk_k, brk_r)


# ------------------------------------------------------------- segment_agg
from repro.kernels import segment_agg


@pytest.mark.parametrize("m", [1, 8, 129, 1024])
def test_segment_agg_matches_ref(m):
    rng = np.random.default_rng(11)
    theta = jnp.asarray(rng.standard_normal((m, 1)), jnp.float32)
    slope = jnp.asarray(rng.standard_normal((m, 1)) * 0.01, jnp.float32)
    a = jnp.asarray(rng.integers(0, 64, (m, 1)).astype(np.float32))
    b = a + jnp.asarray(rng.integers(-8, 256, (m, 1)).astype(np.float32))
    outs = segment_agg(theta, slope, a, b)
    exps = segment_agg(theta, slope, a, b, force_ref=True)
    for got, exp in zip(outs, exps):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)


def test_segment_agg_matches_dense_sums():
    """The closed forms must agree with per-sample numpy aggregation."""
    rng = np.random.default_rng(12)
    m = 24
    theta = rng.standard_normal(m)
    slope = rng.standard_normal(m) * 0.05
    a = rng.integers(0, 32, m).astype(np.float64)
    b = a + rng.integers(1, 128, m).astype(np.float64)
    outs = segment_agg(
        jnp.asarray(theta[:, None], jnp.float32),
        jnp.asarray(slope[:, None], jnp.float32),
        jnp.asarray(a[:, None], jnp.float32),
        jnp.asarray(b[:, None], jnp.float32),
    )
    s_k, ss_k, mn_k, mx_k = (np.asarray(o)[:, 0].astype(np.float64) for o in outs)
    for i in range(m):
        vals = theta[i] + slope[i] * np.arange(a[i], b[i])
        np.testing.assert_allclose(s_k[i], vals.sum(), rtol=1e-4)
        np.testing.assert_allclose(ss_k[i], (vals * vals).sum(), rtol=1e-3)
        np.testing.assert_allclose(mn_k[i], vals.min(), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(mx_k[i], vals.max(), rtol=1e-5, atol=1e-5)


def test_segment_agg_empty_window_is_identity():
    theta = jnp.ones((4, 1), jnp.float32)
    slope = jnp.ones((4, 1), jnp.float32)
    a = jnp.full((4, 1), 10.0, jnp.float32)
    b = jnp.asarray([[10.0], [9.0], [11.0], [10.0]], jnp.float32)  # rows 0,1,3 empty
    s, ss, mn, mx = segment_agg(theta, slope, a, b)
    assert np.asarray(s)[0, 0] == 0.0 and np.asarray(ss)[1, 0] == 0.0
    assert np.asarray(mn)[0, 0] > 1e38 and np.asarray(mx)[0, 0] < -1e38
    assert np.asarray(s)[2, 0] == 11.0  # the one live row: value theta+slope*10


# ------------------------------------------------------------ flash attention
from repro.kernels import flash_attention


@pytest.mark.parametrize("s,d,causal", [
    (256, 128, True),
    (256, 128, False),
    (512, 64, True),
    (128, 256, True),
])
def test_flash_attention_matches_ref(s, d, causal):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 2, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    exp = flash_attention(q, k, v, causal=causal, force_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 128)), jnp.bfloat16)
    out = np.asarray(flash_attention(q, k, v), np.float32)
    exp = np.asarray(flash_attention(q, k, v, force_ref=True), np.float32)
    np.testing.assert_allclose(out, exp, atol=3e-2, rtol=3e-2)


def test_flash_attention_rectangular_kv():
    """Cross-attention shape: S_q != S_kv, no causal mask."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 1, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 384, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 384, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False)
    exp = flash_attention(q, k, v, causal=False, force_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)
