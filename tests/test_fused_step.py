"""The fused serving step's positional contract, in every mode and numerics.

``process_audio`` hands the jitted step ``_audio_step_args(block)``: the
replicated weights (the ΔGRU stack, the FC head, [stage 0's ΔGRU and
head], the FEx coefficient bank), then the slot state's present fields
in ``SlotState`` order, the telemetry and the block.  The step returns
the slot state in the same trees, the telemetry, the result and a
(batch,) health mask.  The benchmark's fault injection relies on this
layout (it replaces ``_audio_step`` and reads ``args[-1]`` as the audio
block, ``args[4:4 + n_state]`` as the state in audio and detect mode).
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.delta_gru import DeltaState
from repro.frontend import FeatureExtractor
from repro.frontend.fex import FExState
from repro.frontend.vad import VADState
from repro.launch.streaming import (CascadeConfig, CascadeState,
                                    ChunkResult, DetectResult,
                                    StreamingKwsSession, _Accum)
from repro.models import kws
from repro.models.detector import DetectorConfig, DetectorState

BATCH, SAMPLES = 4, 256                     # 2 frames of 128 samples

STATE_TREES = {
    "audio": (FExState, DeltaState),
    "detect": (FExState, DeltaState, VADState, DetectorState),
    "cascade": (FExState, DeltaState, CascadeState, VADState,
                DetectorState),
}


def _session(mode: str, numerics: str) -> StreamingKwsSession:
    cfg = get_config("deltakws")
    fex = FeatureExtractor()
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg,
                             input_dim=fex.cfg.n_active)
    kw = {}
    if mode != "audio":
        kw["detector"] = DetectorConfig()
    if mode == "cascade":
        cfg0 = dataclasses.replace(cfg, vocab_size=2, d_model=16)
        kw["stage0_params"], _ = kws.init_kws(jax.random.PRNGKey(1), cfg0,
                                              input_dim=4)
        kw["cascade"] = CascadeConfig(s0_channels=4)
    return StreamingKwsSession(params, cfg, threshold=0.1, batch=BATCH,
                               fex=fex, numerics=numerics, **kw)


def _leaf_specs(tree):
    return jax.tree.structure(tree), [(a.shape, a.dtype)
                                      for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("numerics", ["float32", "int8"])
@pytest.mark.parametrize("mode", ["audio", "detect", "cascade"])
def test_fused_step_positional_contract(mode, numerics):
    sess = _session(mode, numerics)
    step, _ = sess.fused_audio_step(SAMPLES)
    assert step is sess._audio_step
    block = jnp.zeros((BATCH, SAMPLES), jnp.float32)
    args = sess._audio_step_args(block)
    trees = STATE_TREES[mode]
    n_weights = len(args) - len(trees) - 2

    # Replicated weights first: the stage-1 ΔGRU and FC head, stage 0's
    # three under the cascade, the FEx bank last.
    assert n_weights == (7 if mode == "cascade" else 4)
    assert args[:3] == (sess._gru, sess._w_fc, sess._b_fc)
    assert args[n_weights - 1] is sess._coef
    if mode == "cascade":
        assert args[3].w_h.shape[0] == 16 and args[5].shape == (2,)
    # Then the slot state, the telemetry, the block.
    states = args[n_weights:-2]
    assert [type(s) for s in states] == list(trees)
    assert states[1] is sess.state and states[0] is sess.fex_state
    assert isinstance(args[-2], _Accum)
    assert args[-1] is block

    outs = sess._audio_step(*args)
    assert len(outs) == len(trees) + 3
    for before, after in zip(states, outs):
        assert type(after) is type(before)
        assert _leaf_specs(after) == _leaf_specs(before)
    assert _leaf_specs(outs[-3]) == _leaf_specs(args[-2])
    result = outs[-2]
    assert type(result) is (ChunkResult if mode == "audio" else DetectResult)
    assert result.votes.shape == (SAMPLES // 128, BATCH)
    if mode != "audio":
        assert result.events.shape == result.gate.shape == (2, BATCH)
        assert (result.awake is not None) == (mode == "cascade")
    health = outs[-1]
    assert health.shape == (BATCH,) and health.dtype == jnp.int32
