"""Differential conformance suite for the integer inference path.

The acceptance contract of the bit-true pipeline (ISSUE 4):

  1. the ``pallas-int`` kernels are BIT-IDENTICAL to the golden
     fixed-point model (``core.fixed_point``) on fuzzed shapes,
     thresholds and batch tilings — integer arithmetic, so equality is
     exact or the implementation is wrong;
  2. integer state carries across chunk boundaries bit-invisibly (the
     streaming contract, in code domain);
  3. a QAT-trained model promoted to int8 serves through
     ``StreamingKwsSession`` within 1%% accuracy of the float path on
     the synthetic GSCD task;
  4. the promotion artifact round-trips through disk bit-true.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import delta_gru as dg
from repro.core import fixed_point as fp

KEY = jax.random.PRNGKey(0)


def _rand_gru(rng: np.random.Generator):
    """Random shapes/threshold (unaligned T and B included) + promoted
    weights for one fuzz case."""
    T = int(rng.integers(1, 34))
    B = int(rng.integers(1, 10))
    I = int(rng.integers(2, 24))
    H = int(rng.integers(4, 40))
    th = float(rng.uniform(0.0, 0.5))
    p = dg.init_delta_gru(jax.random.PRNGKey(int(rng.integers(1 << 30))),
                          I, H)
    w, fmt = fp.quantize_gru(p)
    xs = fp.to_code(
        jnp.asarray(rng.uniform(-1, 1, (T, B, I)), jnp.float32) * 0.8,
        fmt.feat_frac, 16, jnp.int16)
    return w, fmt, xs, th


# ------------------------------------------------- helpers / primitives
def test_rshift_round_matches_reference():
    x = jnp.arange(-1000, 1000, 7)
    for s in (1, 4, 11):
        want = np.floor((np.asarray(x) + 2 ** (s - 1)) / 2 ** s)
        np.testing.assert_array_equal(np.asarray(fp.rshift_round(x, s)),
                                      want)


def test_sat_bounds():
    x = jnp.asarray([-(1 << 20), -129, -128, 0, 127, 128, 1 << 20])
    np.testing.assert_array_equal(
        np.asarray(fp.sat(x, 8)),
        np.asarray([-128, -128, -128, 0, 127, 127, 127]))


def test_to_code_from_code_roundtrip_exact_on_grid():
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(-2048, 2048, 256), jnp.int32)
    vals = fp.from_code(codes, 11)
    np.testing.assert_array_equal(
        np.asarray(fp.to_code(vals, 11, 16)), np.asarray(codes))


def test_threshold_codes_floor_matches_float_gate():
    """For on-grid deltas, the integer compare must transmit exactly the
    deltas the float ``|Δ| > th`` transmits (the FLOOR contract)."""
    fmt = fp.GruFormats()
    for th in (0.0, 0.1, 0.25, 0.3):
        th_x, _ = fmt.th_codes(th)
        codes = np.arange(0, 4096)
        int_gate = codes > th_x
        float_gate = codes * 2.0 ** -11 > th
        np.testing.assert_array_equal(int_gate, float_gate)


# ------------------------------------------ golden vs kernel: bit-true
@pytest.mark.parametrize("seed", range(6))
def test_int_gru_pallas_bit_identical_to_golden(seed):
    rng = np.random.default_rng(seed)
    w, fmt, xs, th = _rand_gru(rng)
    hs_x, fin_x, nzx_x, nzh_x = fp.int_gru_scan(w, fmt, xs, th,
                                                backend="xla")
    hs_p, fin_p, nzx_p, nzh_p = fp.int_gru_scan(w, fmt, xs, th,
                                                backend="pallas")
    np.testing.assert_array_equal(np.asarray(hs_x), np.asarray(hs_p))
    for a, b in zip(fin_x, fin_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(nzx_x), np.asarray(nzx_p))
    np.testing.assert_array_equal(np.asarray(nzh_x), np.asarray(nzh_p))


def test_int_gru_batch_tiles_bit_identical():
    rng = np.random.default_rng(99)
    p = dg.init_delta_gru(jax.random.PRNGKey(9), 12, 24)
    w, fmt = fp.quantize_gru(p)
    xs = fp.to_code(jnp.asarray(rng.uniform(-0.8, 0.8, (16, 8, 12)),
                                jnp.float32), fmt.feat_frac, 16, jnp.int16)
    ref = fp.int_gru_scan(w, fmt, xs, 0.15, backend="pallas")
    for bb in (4, 2, 1):
        got = fp.int_gru_scan(w, fmt, xs, 0.15, backend="pallas",
                              block_b=bb)
        np.testing.assert_array_equal(np.asarray(ref[0]),
                                      np.asarray(got[0]))


def test_packed_int8_dot_exact_at_extremes():
    """Unit proof of the byte-plane dot: exact against the int32 dot for
    extreme deltas (±2^16, the saturated-code worst case), full-scale
    int8 weights, and contractions past one 512-row block (the
    ΔGRU-768's 768 and 1536; DESIGN.md §12)."""
    rng = np.random.default_rng(0)
    for K in (10, 512, 768, 1536):
        d = rng.integers(-(1 << 16), (1 << 16) + 1, (4, K)).astype(np.int32)
        d[0, :] = 1 << 16                 # all-max positive deltas
        d[1, :] = -(1 << 16)              # all-max negative
        w = rng.integers(-128, 128, (K, 8)).astype(np.int8)
        w[:, 0] = 127
        w[:, 1] = -128
        ref = d @ w.astype(np.int32)      # wraps mod 2^32 like the dot
        w32 = jnp.asarray(w, jnp.float32)
        got = fp.packed_int8_dot_pair(jnp.asarray(d), jnp.asarray(d[:, ::-1]),
                                      w32, w32[::-1])
        np.testing.assert_array_equal(ref, np.asarray(got)[:, :8],
                                      err_msg=str(K))
        np.testing.assert_array_equal(ref, np.asarray(got)[:, 8:],
                                      err_msg=str(K))


@pytest.mark.parametrize("seed", range(3))
def test_int_gru_packed_and_tiled_bit_identical_to_golden(seed):
    """The byte-plane datapath + time tiling vs the golden scan — the
    conformance gate of the kernel's MXU contraction."""
    rng = np.random.default_rng(100 + seed)
    w, fmt, xs, th = _rand_gru(rng)
    T = xs.shape[0]
    golden = fp.int_gru_scan(w, fmt, xs, th, backend="xla")
    for kw in ({}, {"block_t": T}, {"block_t": 1}):
        got = fp.int_gru_scan(w, fmt, xs, th, backend="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(golden[0]),
                                      np.asarray(got[0]))
        for a, b in zip(golden[1], got[1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(golden[2]),
                                      np.asarray(got[2]))


def test_int_kernel_exact_past_512_contraction_at_saturating_deltas():
    """I = H = 640, so each contraction is 640 rows (past the old
    512-row bound), with every carried code on a rail: x̂, ĥ at −2^15
    against x, h at 2^15 − 1, so every Δ is 2^16 − 1 in the first
    frame.  The kernel equals the golden scan bit for bit (state,
    outputs, transmit counts)."""
    from repro.core.delta_gru import DeltaState
    from repro.kernels.delta_gru_seq import delta_gru_seq_int
    T, B, I, H = 4, 8, 640, 640
    rng = np.random.default_rng(7)
    p = dg.init_delta_gru(jax.random.PRNGKey(3), I, H)
    w, fmt = fp.quantize_gru(p, fp.GruFormats(feat_frac=15))
    w = w._replace(w_x=jnp.asarray(rng.choice([-128, 127], (I, 3 * H)),
                                   jnp.int8))
    xs = jnp.asarray(rng.choice([-32768, 32767], (T, B, I)), jnp.int16)
    xs = xs.at[0].set(32767)
    rail = lambda n: jnp.full((B, n), -32768, jnp.int16)
    state = DeltaState(h=jnp.full((B, H), 32767, jnp.int16), x_hat=rail(I),
                       h_hat=rail(H),
                       m_x=jnp.zeros((B, 3 * H), jnp.int32),
                       m_h=jnp.zeros((B, 3 * H), jnp.int32))
    golden = fp.int_gru_scan(w, fmt, xs, 0.2, state=state, backend="xla")
    th_x, th_h = fmt.th_codes(0.2)
    got = delta_gru_seq_int(xs, *state, w.w_x, w.w_h,
                            jnp.asarray([[th_x, th_h]], jnp.int32), fmt=fmt,
                            interpret=True)
    assert int(np.asarray(golden[2])[0].min()) == I     # every Δ sent
    for a, b in zip(jax.tree.leaves(golden), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int_vmem_plan_counts_weights_and_tile_at_768():
    """The int kernel's VMEM plan at the ΔGRU-768's layers, compiled:
    the resident int8 weights (double-buffered) alone pass the default
    tile budget, so the plan takes the smallest legal tile (128 slots of
    1024), counts weights + tile + temporaries, and that lies above the
    default scoped limit (raised for the kernel) and under the cap.  The
    64-wide kernel keeps its 512-slot tile under the default limit, and
    weights that cannot stay resident are refused."""
    from repro.kernels import delta_gru_seq as k
    for I in (10, 768):
        bb, need, weights = k.int_vmem_plan(I, 768, 32, 1024, 1, None,
                                            interpret=False)
        resident = 2 * (I + 768) * 3 * 768
        assert bb == 128 and weights == k.F32_IMAGE
        assert need >= resident + bb * k._slot_vmem_bytes(I, 768, 32, 1)
        assert k.SCOPED_VMEM_DEFAULT_BYTES < need <= k.VMEM_CAP_BYTES
    bb, need, weights = k.int_vmem_plan(10, 64, 32, 1024, 1, None,
                                        interpret=False)
    assert bb == 512 and need <= k.SCOPED_VMEM_DEFAULT_BYTES
    assert weights == k.F32_IMAGE
    with pytest.raises(NotImplementedError, match="cannot stay resident"):
        k.int_vmem_plan(4096, 4096, 32, 1024, 1, None, interpret=False)
    with pytest.raises(NotImplementedError, match="cannot stay resident"):
        k.int_vmem_plan(4096, 4096, 32, 8, 1, None, interpret=True)


def test_int_gru_state_carry_bit_invisible():
    rng = np.random.default_rng(5)
    w, fmt, xs, th = _rand_gru(rng)
    T = xs.shape[0]
    cut = T // 2
    hs_once, _, nz_once, _ = fp.int_gru_scan(w, fmt, xs, th)
    hs_a, st_a, nz_a, _ = fp.int_gru_scan(w, fmt, xs[:cut], th)
    hs_b, _, nz_b, _ = fp.int_gru_scan(w, fmt, xs[cut:], th, state=st_a)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([hs_a, hs_b], 0)), np.asarray(hs_once))
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([nz_a, nz_b], 0)), np.asarray(nz_once))


def test_accumulator_saturates_not_wraps():
    """Drive the accumulator past the 24-bit limit: it must clamp at the
    word boundary (the ASIC's saturating MAC), never wrap."""
    fmt = fp.GruFormats()
    big = jnp.full((1, 3), (1 << (fmt.acc_bits - 1)) - 5, jnp.int32)
    out = fp.sat(big + 100, fmt.acc_bits)
    assert int(out[0, 0]) == (1 << (fmt.acc_bits - 1)) - 1


@pytest.mark.parametrize("seed", range(3))
def test_int_fex_pallas_bit_identical_to_golden(seed):
    from repro.frontend.fex import FExConfig, build_sos_bank, sos_formats
    from repro.kernels.iir_fex import pack_coefficients
    rng = np.random.default_rng(seed)
    cfg = FExConfig()
    bank = build_sos_bank(cfg)
    b_fmt, a_fmt = sos_formats(bank, cfg.b_bits, cfg.a_bits)
    coef, ffmt = fp.quantize_fex(pack_coefficients(bank), cfg.env_alpha,
                                 b_fmt.frac_bits, a_fmt.frac_bits)
    B = int(rng.integers(1, 5))
    T = int(rng.integers(129, 1200))
    audio = fp.to_code(jnp.asarray(rng.uniform(-0.9, 0.9, (B, T)),
                                   jnp.float32), ffmt.feat_frac, 16,
                       jnp.int16)
    s0 = fp.init_int_fex_state(B, cfg.n_active)
    f_x, s_x = fp.int_fex_scan(audio, coef, s0, ffmt, backend="xla")
    f_p, s_p = fp.int_fex_scan(audio, coef, s0, ffmt, backend="pallas")
    np.testing.assert_array_equal(np.asarray(f_x), np.asarray(f_p))
    np.testing.assert_array_equal(np.asarray(s_x), np.asarray(s_p))


def test_fex_scan_pallas_int_chunk_carry_bit_invisible():
    """The float-typed ``fex_scan(backend="pallas-int")`` surface: codes
    round-trip through the FExState floats exactly, so chunked == one-
    shot bit for bit."""
    from repro.frontend.fex import FExConfig, build_sos_bank, fex_scan
    from repro.kernels.iir_fex import pack_coefficients
    from repro.core.quantize import quantize_audio_12b
    cfg = FExConfig()
    coef = pack_coefficients(build_sos_bank(cfg))
    rng = np.random.default_rng(11)
    audio = quantize_audio_12b(
        jnp.asarray(rng.uniform(-0.7, 0.7, (2, 1024)), jnp.float32))
    kw = dict(env_alpha=cfg.env_alpha, backend="pallas-int")
    once, _ = fex_scan(audio, coef, None, **kw)
    f1, s1 = fex_scan(audio[:, :384], coef, None, **kw)
    f2, _ = fex_scan(audio[:, 384:], coef, s1, **kw)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([f1, f2], 1)), np.asarray(once))
    # features live on the 12-bit grid
    steps = np.asarray(once) / 2.0 ** -11
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-4)


# ------------------------------------------------- promotion artifacts
def test_bundle_save_load_roundtrip_bit_true(tmp_path):
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    from repro.train.promote import load_bundle, save_bundle
    cfg = get_config("deltakws")
    fex = FeatureExtractor()
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    bundle = fp.promote_kws(params, 0.1, fex=fex)
    # bare name: np.savez appends .npz — the returned path must load
    path = save_bundle(tmp_path / "b", bundle)
    assert path.exists() and path.name.endswith(".npz")
    loaded = load_bundle(path)
    assert loaded.gfmt == bundle.gfmt and loaded.ffmt == bundle.ffmt
    assert loaded.threshold == bundle.threshold
    feats = jax.random.normal(jax.random.PRNGKey(2), (3, 12, 10)) * 0.4
    feats = fp.from_code(fp.to_code(feats, 11, 16), 11)
    lg_a, nzx_a, _ = fp.int_forward(bundle, feats)
    lg_b, nzx_b, _ = fp.int_forward(loaded, feats)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))
    np.testing.assert_array_equal(np.asarray(nzx_a), np.asarray(nzx_b))


def test_promote_checkpoint_equals_in_memory_fold(tmp_path):
    """The offline checkpoint fold produces the same bundle as promoting
    the in-memory tree it was saved from."""
    from repro.configs import get_config
    from repro.models import kws
    from repro.train import checkpoint as ck
    from repro.train.promote import promote_checkpoint
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    ck.save(tmp_path, 7, {"params": params})
    a = fp.promote_kws(params, 0.1)
    b = promote_checkpoint(tmp_path, cfg, 0.1)
    assert a.gfmt == b.gfmt
    for x, y in zip(a.gru, b.gru):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(a.w_fc), np.asarray(b.w_fc))
    np.testing.assert_array_equal(np.asarray(a.b_fc), np.asarray(b.b_fc))


def test_promote_formats_follow_dynamic_range():
    from repro.core.delta_gru import DeltaGRUParams
    w_x = jnp.asarray(np.full((4, 12), 3.0), jnp.float32)      # |w| ≤ 4
    w_h = jnp.asarray(np.full((4, 12), 0.4), jnp.float32)      # |w| ≤ 0.5
    p = DeltaGRUParams(w_x, w_h, jnp.zeros((12,)))
    w, fmt = fp.quantize_gru(p)
    assert fmt.e_x == 2 and fmt.e_h == -1
    # dequantized codes reproduce the weights within half an LSB
    np.testing.assert_allclose(
        np.asarray(w.w_x, np.float32) * 2.0 ** (fmt.e_x - 7),
        np.asarray(w_x), atol=2.0 ** (fmt.e_x - 8) + 1e-9)


# ----------------------------------------------- session-level contracts
def _int_session(params, cfg, fex, batch=1, mesh=None):
    from repro.launch.streaming import StreamingKwsSession
    return StreamingKwsSession(params, cfg, threshold=0.1, batch=batch,
                               fex=fex, numerics="int8", mesh=mesh)


def test_int8_session_matches_golden_model():
    """Session decisions == golden fixed-point forward per frame: the
    serving engine IS the golden model."""
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    cfg = get_config("deltakws")
    fex = FeatureExtractor()
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    rng = np.random.default_rng(3)
    audio = rng.uniform(-0.6, 0.6, (2, 2048)).astype(np.float32)

    sess = _int_session(params, cfg, fex, batch=2)
    out = sess.process_audio(audio)
    bundle = sess._bundle

    # golden: int FEx (from the quantized ADC input) → int GRU → int FC
    from repro.core.quantize import quantize_audio_12b
    codes = fp.to_code(quantize_audio_12b(jnp.asarray(audio)), 11, 16,
                       jnp.int16)
    feats, _ = fp.int_fex_scan(codes, bundle.coef,
                               fp.init_int_fex_state(2, 10), bundle.ffmt,
                               backend="xla")
    xs = jnp.moveaxis(feats, 1, 0)
    hs, _, _, _ = fp.int_gru_scan(bundle.gru, bundle.gfmt, xs,
                                  bundle.threshold, backend="xla")
    logits = fp.int_fc(hs, bundle.w_fc, bundle.b_fc)
    np.testing.assert_array_equal(np.asarray(out.votes),
                                  np.asarray(jnp.argmax(logits, -1)))


@pytest.mark.parametrize("numerics", ["float32", "int8"])
def test_mesh1_bit_identical_to_unsharded(numerics):
    """The sharded engine at mesh=1 is bit-identical to the unsharded
    session — in BOTH numerics (the int8 sharded-serving contract)."""
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.launch.mesh import make_slot_mesh
    from repro.launch.streaming import StreamingKwsSession
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    rng = np.random.default_rng(7)
    audio = rng.uniform(-0.6, 0.6, (2, 1536)).astype(np.float32)

    def run(mesh):
        sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=2,
                                   fex=FeatureExtractor(), mesh=mesh,
                                   numerics=numerics)
        out = sess.process_audio(audio)
        return np.asarray(out.logits), np.asarray(out.votes), sess.summary()

    lg_a, v_a, s_a = run(None)
    lg_b, v_b, s_b = run(make_slot_mesh(1))
    np.testing.assert_array_equal(lg_a, lg_b)
    np.testing.assert_array_equal(v_a, v_b)
    assert s_a.frames == s_b.frames and s_a.sparsity == s_b.sparsity


def test_int8_session_rejects_unknown_backend():
    from repro.configs import get_config
    from repro.launch.streaming import StreamingKwsSession
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    with pytest.raises(ValueError):
        StreamingKwsSession(params, cfg, numerics="int8", backend="cuda")


def test_fold_fex_copies_never_mutates():
    """A bundle shared across sessions must not pick up the first
    session's FEx fold."""
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    bare = fp.promote_kws(params, 0.1)                 # no FEx folded
    folded = fp.fold_fex(bare, FeatureExtractor())
    assert bare.ffmt is None and bare.coef is None
    assert folded.ffmt is not None and folded.coef is not None
    assert fp.fold_fex(folded, FeatureExtractor()) is folded   # no-op


def test_int8_reset_stream_isolates_one_slot():
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(KEY, cfg, input_dim=10)
    sess = _int_session(params, cfg, FeatureExtractor(), batch=2)
    rng = np.random.default_rng(13)
    audio = rng.uniform(-0.6, 0.6, (2, 2048)).astype(np.float32)
    first = np.asarray(sess.process_audio(audio).logits)
    sess.reset_stream(0)
    again = np.asarray(sess.process_audio(audio).logits)
    np.testing.assert_array_equal(again[:, 0], first[:, 0])
    assert not np.array_equal(again[:, 1], first[:, 1])


# --------------------------------------- QAT → promote → serve accuracy
@pytest.fixture(scope="module")
def qat_trained():
    """QAT-train the paper's model (8-bit STE weights + Q0.15 hidden
    grid) for the acceptance comparison.  Module-scoped: the int8
    accuracy tests share one training run."""
    from repro.configs import get_config
    from repro.data.gscd import synth_batch
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    from repro.train import optimizer as opt
    cfg = get_config("deltakws")
    fex = FeatureExtractor()
    params, _ = kws.init_kws(KEY, cfg)
    ocfg = opt.AdamWConfig(lr=3e-3, weight_decay=0.01, warmup_steps=20,
                           total_steps=300)
    state = opt.init(params)
    rng = np.random.default_rng(0)

    @jax.jit
    def step(params, state, feats, labels):
        (_, m), g = jax.value_and_grad(kws.loss_fn, has_aux=True)(
            params, cfg, {"feats": feats, "labels": labels}, 0.1, qat=True)
        params, state, _ = opt.update(ocfg, g, state, params)
        return params, state

    for _ in range(300):
        audio, labels = synth_batch(rng, 64)
        params, state = step(params, state, fex(jnp.asarray(audio)),
                             jnp.asarray(labels))
    audio, labels = synth_batch(np.random.default_rng(1234), 192)
    return cfg, params, fex, audio, jnp.asarray(labels)


def test_qat_promoted_forward_within_1pct(qat_trained):
    """Acceptance: the promoted integer pipeline classifies within 1%% of
    the float forward pass on held-out synthetic GSCD."""
    from repro.models import kws
    cfg, params, fex, audio, labels = qat_trained
    feats = fex(jnp.asarray(audio))
    lg_f, _ = kws.forward(params, cfg, feats, threshold=0.1)
    bundle = fp.promote_kws(params, 0.1, fex=fex)
    lg_i, _, _ = fp.int_forward(bundle, feats)
    acc_f = float(jnp.mean(jnp.argmax(lg_f, -1) == labels))
    acc_i = float(jnp.mean(jnp.argmax(lg_i, -1) == labels))
    assert acc_f > 0.5, acc_f
    assert acc_i >= acc_f - 0.01, (acc_f, acc_i)


def test_qat_promoted_serves_within_1pct(qat_trained):
    """Acceptance: int8 SERVING (StreamingKwsSession, per-utterance
    majority vote over raw audio) within 1%% of the float session."""
    from repro.launch.streaming import StreamingKwsSession
    cfg, params, fex, audio, labels = qat_trained
    B = audio.shape[0]

    def serve(numerics):
        sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=B,
                                   fex=fex, numerics=numerics)
        votes = np.asarray(sess.process_audio(audio).votes)   # (F, B)
        pred = np.array([np.bincount(votes[:, i], minlength=12).argmax()
                         for i in range(B)])
        return float(np.mean(pred == np.asarray(labels)))

    acc_f = serve("float32")
    acc_i = serve("int8")
    assert acc_f > 0.5, acc_f
    assert acc_i >= acc_f - 0.01, (acc_f, acc_i)
