"""The plain reference agrees with the program's served path on the CPU.

The reference imports nothing of the program; these tests do, to show
that at small sizes the session's votes, events, gate and hidden state
equal the reference's, bit for bit, in both numerics.
"""
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, model, spec

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
REF = spec.load_module(ROOT / "bench" / "references" / "deltakws.py",
                       "reference")
CONFIGS = ("deltakws-int8", "deltakws-f32")


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _session(cfg, params, batch, detect=False):
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.frontend.vad import VADConfig
    from repro.launch.streaming import StreamingKwsSession
    from repro.models.detector import DetectorConfig
    kw = {}
    if detect:
        kw = dict(detector=DetectorConfig(**cfg["detector"]),
                  vad=VADConfig(**cfg["vad"]))
    return StreamingKwsSession(
        params, get_config("deltakws"), threshold=cfg["delta_threshold"],
        batch=batch, fex=FeatureExtractor(), numerics=cfg["numerics"], **kw)


def test_filter_bank_matches_the_program():
    from repro.core import fixed_point as fp
    from repro.frontend import FeatureExtractor
    cfg = _config("deltakws-int8")
    fex = dict(cfg["fex"], sample_rate_hz=cfg["sample_rate_hz"])
    rows, b_frac, a_frac = REF.filter_bank(fex)
    prog = FeatureExtractor()
    np.testing.assert_array_equal(np.asarray(prog.coef),
                                  rows.astype(np.float32))
    bundle = fp.fold_fex(fp.promote_kws(
        {k: np.asarray(v) for k, v in model.make_params(cfg, 0).items()},
        0.2), prog)
    assert (bundle.ffmt.b_frac, bundle.ffmt.a_frac) == (b_frac, a_frac)
    m, _ = REF.build(cfg, model.make_params(cfg, 0))
    np.testing.assert_array_equal(np.asarray(m.coef), np.asarray(bundle.coef))
    np.testing.assert_array_equal(np.asarray(m.gru[0].w_x).astype(np.int8),
                                  np.asarray(bundle.gru.w_x))
    np.testing.assert_array_equal(np.asarray(m.b_fc), np.asarray(bundle.b_fc))


@pytest.mark.parametrize("name", CONFIGS)
def test_utterance_votes_and_state_equal(name):
    cfg = _config(name)
    params = model.make_params(cfg, 2 ** 40 + 3)
    B = 6
    audio = np.zeros((B, 8192), np.float32)
    audio[:, :8000] = generator.utterances(np.random.default_rng(5), B)
    sess = _session(cfg, params, B)
    votes = np.concatenate([np.asarray(sess.process_audio(audio[:, o:o + 4096])
                                       .votes) for o in (0, 4096)])
    m, st = REF.build(cfg, params)
    state, out = REF.block(m, REF.init_state(m, st, B), jnp.asarray(audio),
                           st=st, detect=False)
    np.testing.assert_array_equal(votes, np.asarray(out.votes))
    np.testing.assert_array_equal(np.asarray(sess.state.h),
                                  np.asarray(state.h[0]).astype(
                                      np.asarray(sess.state.h).dtype))
    assert len(np.unique(votes)) > 2          # the model is not constant


@pytest.mark.parametrize("name", CONFIGS)
def test_detection_equal(name):
    cfg = _config(name)
    params = model.make_params(cfg, 17)
    S, n = 4, 8192 * 3
    rng = np.random.default_rng(9)
    audio = np.stack([generator.stream(rng, n, 20.0, 40.0, 0.4)
                      for _ in range(S)])
    sess = _session(cfg, params, S, detect=True)
    events = np.concatenate([np.asarray(sess.process_audio(
        audio[:, o:o + 2048]).events) for o in range(0, n, 2048)])
    m, st = REF.build(cfg, params)
    state, out = REF.block(m, REF.init_state(m, st, S), jnp.asarray(audio),
                           st=st, detect=True)
    np.testing.assert_array_equal(events, np.asarray(out.events))
    assert sess.summary().vad_duty == pytest.approx(
        float(np.asarray(out.gate).mean()))
    np.testing.assert_array_equal(np.asarray(sess.state.h),
                                  np.asarray(state.h[0]).astype(
                                      np.asarray(sess.state.h).dtype))
    assert (events >= 0).sum() > 0            # the head fires
