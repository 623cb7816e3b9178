"""Sequence-resident fused ΔGRU kernel + streaming-session parity tests.

The fused full-sequence kernel (one pallas_call per utterance) must be a
drop-in replacement for the per-step scan: bit-for-bit at Δ_TH=0 (where
the scan itself equals the dense GRU), elementwise-close at Δ_TH>0
across batch tilings, with identical op-count statistics.  Streaming
sessions must make chunk boundaries invisible.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import delta_gru as dg
from repro.core.delta_gru import (DeltaState, delta_gru_scan,
                                  dense_gru_scan, init_delta_gru,
                                  init_delta_state)
from repro.kernels.delta_gru_seq import delta_gru_seq

KEY = jax.random.PRNGKey(0)


def _setup(T=24, B=8, I=10, H=16, seed=0):
    p = init_delta_gru(jax.random.PRNGKey(seed), I, H)
    xs = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, B, I))
    return p, xs


def _run_seq(p, xs, th, block_b=None, state=None):
    T, B, I = xs.shape
    H = p.w_h.shape[0]
    s = state or init_delta_state(B, I, H, p)
    return delta_gru_seq(xs, s.h, s.x_hat, s.h_hat, s.m_x, s.m_h,
                         p.w_x, p.w_h, th, block_b=block_b)


def test_seq_bitexact_at_threshold_zero_vs_scan_and_dense():
    p, xs = _setup()
    hs, final, nz_dx, nz_dh = _run_seq(p, xs, 0.0)
    hs_scan, fs_scan, _ = delta_gru_scan(p, xs, threshold=0.0)
    # bit-for-bit against the scan (same op order, same f32 math)
    np.testing.assert_array_equal(np.asarray(hs), np.asarray(hs_scan))
    for a, b in zip(final, fs_scan):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and allclose against the dense GRU oracle (different op order)
    hs_dense = dense_gru_scan(p, xs)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_dense),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("block_b", [None, 4, 2, 1])
@pytest.mark.parametrize("th", [0.05, 0.2, 0.5])
def test_seq_matches_scan_across_thresholds_and_batch_tiles(th, block_b):
    p, xs = _setup(T=20, B=8, I=12, H=24, seed=3)
    hs, final, nz_dx, nz_dh = _run_seq(p, xs, th, block_b=block_b)
    hs_scan, fs_scan, stats = delta_gru_scan(p, xs, threshold=th)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_scan),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(final, fs_scan):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    # op-count telemetry identical: same frames transmitted
    np.testing.assert_array_equal(np.asarray(nz_dx), np.asarray(stats.nz_dx))
    np.testing.assert_array_equal(np.asarray(nz_dh), np.asarray(stats.nz_dh))


@pytest.mark.parametrize("block_t", [2, 4, 10, 20])
@pytest.mark.parametrize("block_b", [None, 2])
def test_time_tiling_bit_identical_to_untiled(block_t, block_b):
    """block_t advances several frames per grid step through the SAME
    sequential fori_loop — every tiling must match block_t=1 bit for
    bit, state and telemetry included."""
    p, xs = _setup(T=20, B=8, I=12, H=24, seed=11)
    ref = _run_seq(p, xs, 0.2, block_b=block_b)
    T, B, I = xs.shape
    s = init_delta_state(B, I, p.w_h.shape[0], p)
    got = delta_gru_seq(xs, s.h, s.x_hat, s.h_hat, s.m_x, s.m_h,
                        p.w_x, p.w_h, 0.2, block_b=block_b,
                        block_t=block_t)
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
    for a, b in zip(ref[1], got[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(got[2]))
    np.testing.assert_array_equal(np.asarray(ref[3]), np.asarray(got[3]))


def test_bad_tiles_raise_named_valueerror():
    p, xs = _setup(T=20, B=8)
    with pytest.raises(ValueError,
                       match=r"delta_gru_seq: block_b=3 .*B=8"):
        _run_seq(p, xs, 0.1, block_b=3)
    with pytest.raises(ValueError,
                       match=r"delta_gru_seq: block_t=7 .*T=20"):
        delta_gru_scan(p, xs, threshold=0.1, backend="pallas", block_t=7)
    with pytest.raises(ValueError, match=r"delta_gru_seq_int: block_b=5"):
        delta_gru_scan(p, xs, threshold=0.1, backend="pallas-int",
                       block_b=5)


def test_backend_dispatch_pallas_equals_xla():
    p, xs = _setup(T=16, B=4, I=10, H=16, seed=7)
    for th in [0.0, 0.15]:
        hs_p, fs_p, st_p = delta_gru_scan(p, xs, threshold=th,
                                          backend="pallas")
        hs_x, fs_x, st_x = delta_gru_scan(p, xs, threshold=th, backend="xla")
        np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_x),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(st_p.macs),
                                      np.asarray(st_x.macs))
        assert isinstance(fs_p, DeltaState)


def test_backend_rejects_unknown():
    p, xs = _setup(T=4, B=2)
    with pytest.raises(ValueError):
        delta_gru_scan(p, xs, backend="cuda")


@pytest.mark.parametrize("seed", range(8))
def test_differential_fuzz_xla_pallas_pallasint(seed):
    """Differential fuzz: random shapes, thresholds and UNALIGNED T/B
    through ``delta_gru_scan`` on all three backends — ``xla``,
    ``pallas`` and ``pallas-int`` with identity quantization (the int
    kernel's skeleton executing the float math).  Decisions (argmax of
    an FC head over the hidden trajectory) and nz-counts must agree
    bit-for-bit."""
    rng = np.random.default_rng(seed)
    T = int(rng.integers(1, 40))
    B = int(rng.integers(1, 11))           # deliberately not power-of-2
    I = int(rng.integers(2, 20))
    H = int(rng.integers(3, 48))
    th = float(rng.uniform(0.0, 0.6))
    p = init_delta_gru(jax.random.PRNGKey(seed + 100), I, H)
    xs = jnp.asarray(rng.normal(0, 0.5, (T, B, I)), jnp.float32)
    w_fc = jnp.asarray(rng.normal(0, 0.3, (H, 12)), jnp.float32)

    outs = {be: delta_gru_scan(p, xs, threshold=th, backend=be)
            for be in ("xla", "pallas", "pallas-int")}
    hs_ref, fin_ref, st_ref = outs["xla"]
    votes_ref = jnp.argmax(hs_ref @ w_fc, -1)
    for be in ("pallas", "pallas-int"):
        hs, fin, st = outs[be]
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(hs_ref),
                                      err_msg=be)
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(hs @ w_fc, -1)), np.asarray(votes_ref),
            err_msg=be)
        np.testing.assert_array_equal(np.asarray(st.nz_dx),
                                      np.asarray(st_ref.nz_dx), err_msg=be)
        np.testing.assert_array_equal(np.asarray(st.nz_dh),
                                      np.asarray(st_ref.nz_dh), err_msg=be)
        for a, b in zip(fin, fin_ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=be)


def test_pallas_int_identity_rejects_qat():
    p, xs = _setup(T=4, B=2)
    from repro.core.quantize import QFormat
    with pytest.raises(ValueError):
        delta_gru_scan(p, xs, backend="pallas-int",
                       h_qformat=QFormat(0, 15))


def test_pallas_blocked_fallback_when_weights_exceed_vmem():
    """Weights over the VMEM budget must route through the block-sparse
    delta_matvec composition and still match the XLA scan."""
    p = init_delta_gru(jax.random.PRNGKey(5), 256, 128)
    xs = jax.random.normal(jax.random.PRNGKey(6), (8, 4, 256))
    hs_b, fs_b, st_b = delta_gru_scan(p, xs, threshold=0.3,
                                      backend="pallas",
                                      vmem_budget_bytes=1024)
    hs_x, fs_x, st_x = delta_gru_scan(p, xs, threshold=0.3, backend="xla")
    np.testing.assert_allclose(np.asarray(hs_b), np.asarray(hs_x),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(st_b.nz_dx),
                                  np.asarray(st_x.nz_dx))


def test_seq_carried_state_resumes_mid_sequence():
    """Splitting a sequence at an arbitrary frame and feeding the final
    state back must equal the one-shot run (the streaming contract at
    kernel level)."""
    p, xs = _setup(T=30, B=4, I=10, H=16, seed=9)
    th = 0.2
    hs_once = _run_seq(p, xs, th)[0]
    hs_a, final_a, _, _ = _run_seq(p, xs[:13], th)
    state_a = DeltaState(*final_a)
    hs_b, _, _, _ = _run_seq(p, xs[13:], th, state=state_a)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([hs_a, hs_b], axis=0)),
        np.asarray(hs_once))


def test_kws_forward_backend_parity():
    from repro.configs import get_config
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg, input_dim=10)
    feats = jax.random.normal(jax.random.PRNGKey(1), (4, 20, 10)) * 0.5
    lg_x, st_x = kws.forward(params, cfg, feats, threshold=0.1,
                             backend="xla")
    lg_p, st_p = kws.forward(params, cfg, feats, threshold=0.1,
                             backend="pallas")
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_x),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(st_p.macs),
                                  np.asarray(st_x.macs))


class TestStreamingSession:
    def _session(self, batch=1, threshold=0.1):
        from repro.configs import get_config
        from repro.launch.streaming import StreamingKwsSession
        from repro.models import kws
        cfg = get_config("deltakws")
        params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg, input_dim=10)
        sess = StreamingKwsSession(params, cfg, threshold=threshold,
                                   batch=batch)
        return cfg, params, sess

    def test_chunked_equals_oneshot(self):
        from repro.models import kws
        cfg, params, sess = self._session()
        feats = jax.random.normal(jax.random.PRNGKey(1), (32, 10)) * 0.5
        outs = [sess.process_chunk(feats[a:b])
                for a, b in [(0, 10), (10, 17), (17, 32)]]
        logits_chunked = jnp.concatenate([o.logits for o in outs], axis=0)

        gru = kws._gru_params(params, False)
        hs, _, _ = delta_gru_scan(gru, feats[:, None, :], threshold=0.1,
                                  backend="pallas")
        logits_once = hs @ params["w_fc"] + params["b_fc"]
        np.testing.assert_array_equal(np.asarray(logits_chunked),
                                      np.asarray(logits_once))

    def test_batched_streams_and_summary(self):
        cfg, params, sess = self._session(batch=3)
        feats = jax.random.normal(jax.random.PRNGKey(2), (12, 3, 10)) * 0.5
        out = sess.process_chunk(feats)
        assert out.votes.shape == (12, 3)
        out = sess.process_chunk(feats)
        s = sess.summary()
        # frames counts DECISIONS: 2 chunks × 12 frames × 3 streams
        assert s.frames == 72 and s.chunks == 2
        assert 0.0 <= s.sparsity <= 1.0
        assert s.energy_nj_per_decision <= s.dense_energy_nj + 1e-9

    def test_reset_forgets_state(self):
        cfg, params, sess = self._session()
        feats = jax.random.normal(jax.random.PRNGKey(3), (8, 10)) * 0.5
        first = sess.process_chunk(feats)
        sess.reset()
        again = sess.process_chunk(feats)
        np.testing.assert_array_equal(np.asarray(first.logits),
                                      np.asarray(again.logits))
        assert sess.summary().frames == 8


# ------------------------------------------------ the int kernel's plan
# (batch tile, VMEM bytes) of every served configuration's int layers at
# the utterance cells' 1024 slots × 32 frames (and one frame), as the
# plan gave them before it could hold weights as int8 planes: each keeps
# its float32 image and compiles exactly as before.
SERVED_INT_PLANS = {
    (10, 64, 32): (512, 13757696),           # deltakws-int8
    (10, 64, 1): (512, 13503744),            # deltakws-int8, live
    (10, 768, 32): (128, 39535616),          # edgedrnn layer 1
    (768, 768, 32): (128, 52756480),         # edgedrnn layer 2
}


@pytest.mark.parametrize("shape", sorted(SERVED_INT_PLANS))
def test_int_vmem_plan_keeps_every_served_gru_layer(shape):
    from repro.kernels import delta_gru_seq as k
    I, H, T = shape
    plan = k.int_vmem_plan(I, H, T, 1024, 1, None, interpret=False)
    assert (plan.block_b, plan.vmem_bytes) == SERVED_INT_PLANS[shape]
    assert plan.weights == k.F32_IMAGE


def test_int_vmem_plan_holds_spartus_layer_2_as_int8_planes():
    """Spartus's ΔLSTM-1024: layer 1 (10 in) keeps the float32 image
    (76.2 MB at 128 slots); layer 2 (1024 in) would need 104.92 MB with
    it, above the cap, so it holds its int8 weights once (8.4 MB) and
    fits; weights neither holding fits are refused, naming the kernel."""
    from repro.kernels import delta_gru_seq as k
    one = k.int_vmem_plan(10, 1024, 32, 1024, 1, None, interpret=False,
                          cell="lstm")
    two = k.int_vmem_plan(1024, 1024, 32, 1024, 1, None, interpret=False,
                          cell="lstm")
    assert one == (128, 76212224, k.F32_IMAGE)
    assert two.block_b == 128 and two.weights == k.INT8_PLANES
    assert k.SCOPED_VMEM_DEFAULT_BYTES < two.vmem_bytes <= k.VMEM_CAP_BYTES
    # The float32 image's plan at 128 slots: weights, tile, temporaries.
    tile = k._slot_vmem_bytes(1024, 1024, 32, 1, 4, True)
    f32_need = 6 * 2048 * 4096 + 128 * (tile + 4 * (12 * 4096 + 2 * 2048))
    assert f32_need == 104_923_136 > k.VMEM_CAP_BYTES
    with pytest.raises(NotImplementedError,
                       match="delta_lstm_seq_int.*int8 planes"):
        k.int_vmem_plan(8192, 8192, 32, 1024, 1, None, interpret=False,
                        cell="lstm")


@pytest.mark.parametrize("seed", [0, 1])
def test_int8_plane_dot_equals_f32_planes_and_int32_dot(seed):
    """The int8-plane contraction is exact: equal to the float32 byte
    planes and to the int32 dot on Δs of two int16 codes (rails
    included) against int8 codes, at 2048 contraction rows."""
    from repro.core import fixed_point as fp
    rng = np.random.default_rng(seed)
    K, N, Bk = 2048, 256, 16
    dx = rng.integers(-32768, 32768, (Bk, K)) - rng.integers(-32768, 32768,
                                                             (Bk, K))
    dx[0, :] = 65535
    dx[1, :] = -65535
    dh = rng.integers(-65535, 65536, (Bk, K))
    wx = rng.integers(-128, 128, (K, N)).astype(np.int8)
    wh = rng.integers(-128, 128, (K, N)).astype(np.int8)
    dx, dh = jnp.asarray(dx, jnp.int32), jnp.asarray(dh, jnp.int32)
    planes = fp.int8_plane_dot_pair(dx, dh, jnp.asarray(wx),
                                    jnp.asarray(wh))
    f32 = fp.packed_int8_dot_pair(dx, dh, jnp.asarray(wx, jnp.float32),
                                  jnp.asarray(wh, jnp.float32))
    i32 = jnp.concatenate(
        [jnp.dot(dx, jnp.asarray(wx, jnp.int32),
                 preferred_element_type=jnp.int32),
         jnp.dot(dh, jnp.asarray(wh, jnp.int32),
                 preferred_element_type=jnp.int32)], axis=-1)
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(f32))
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(i32))
