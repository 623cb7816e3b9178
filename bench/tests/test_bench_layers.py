"""A configuration's stack of ΔGRU layers: weights, reference, state.

The two committed one-layer configurations read as they did before a
configuration could state ``layers``: their weights and the reference's
votes, events and final state are pinned, by digest, to the values the
one-layer code gave for two seeds.  A two-layer configuration, made here
and never committed, gets one more layer's weights, and a reference whose
stack equals its layers run one after another.
"""
import dataclasses
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, model, probes, spec
from bench.harness import Reference

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
REF = spec.load_module(ROOT / "bench" / "references" / "deltakws.py",
                       "reference")
BENCH = spec.load_benchmark(ROOT)
CELLS = {"deltakws-int8": "int8-live-c128", "deltakws-f32": "f32-utt-c4096"}
SEEDS = (7, 2 ** 40 + 3)
ONE_LAYER_KEYS = {"w_x", "w_h", "b", "w_fc", "b_fc"}

# Recorded from the one-layer code, before ``layers`` existed.
PINNED_PARAMS = {
    7: {"b": "72b28636d304eb3a", "b_fc": "144636d3bcd0b339",
        "w_fc": "52b8a85c765dcad9", "w_h": "8f2ffb48c9738ab3",
        "w_x": "ae3b929f0a208279"},
    2 ** 40 + 3: {"b": "47e745192ebc9b47", "b_fc": "144636d3bcd0b339",
                  "w_fc": "42839c99fab04ad3", "w_h": "0cb314d14470eeea",
                  "w_x": "7ace700201acb3d7"},
}
PINNED_REFERENCE = {
    ("deltakws-int8", 7): {
        "votes": "215bf01c46093a17", "final_hidden": "da6714611988ee09",
        "events": "0af60db8b56bcf6e", "detect_hidden": "c68fa476bc0bbe7d"},
    ("deltakws-int8", 2 ** 40 + 3): {
        "votes": "ab6b1ad871e75016", "final_hidden": "aa6cabe075342a51",
        "events": "58746cfceaa08bb9", "detect_hidden": "b8d2eaaf29178993"},
    ("deltakws-f32", 7): {
        "votes": "bfc2d10c8e3f746e", "final_hidden": "9641c4139e5d5c0f",
        "events": "f5f29da8cc9c1cb7", "detect_hidden": "7840d00b2a18318a"},
    ("deltakws-f32", 2 ** 40 + 3): {
        "votes": "0fcbc3eade255b0a", "final_hidden": "84322d251219ba95",
        "events": "0d72b7f8ac91b1df", "detect_hidden": "19ca76073fb54527"},
}


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode() + str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def _config(name: str, **changes) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    return dict(cfg, **changes)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_one_layer_weights_are_pinned(name, seed):
    params = model.make_params(_config(name), seed)
    assert {k: digest(v) for k, v in params.items()} == PINNED_PARAMS[seed]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_one_layer_reference_is_pinned(name, seed):
    cell = spec.find_cell(BENCH, CELLS[name], ROOT)
    ref = Reference(cell, model.make_params(cell.config, seed))
    rng = np.random.default_rng(seed)
    audio = np.zeros((4, 8192), np.float32)
    audio[:, :8000] = generator.utterances(rng, 4)
    got = {"votes": digest(ref.votes(audio)),
           "final_hidden": digest(ref.final_hidden(audio, 4096,
                                                   np.array([0, 1, 2, 0])))}
    n = 64 * 128 * 2
    views = [generator.stream(rng, n, 20.0, 40.0, 0.4) for _ in range(3)]
    events, hidden = ref.detect(views, n)
    got.update(events=digest(events), detect_hidden=digest(hidden))
    assert got == PINNED_REFERENCE[(name, seed)]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_two_layer_weights(name):
    cfg = _config(name, layers=2)
    params = model.make_params(cfg, 2 ** 36 + 9)
    H, H3, I = cfg["hidden"], 3 * cfg["hidden"], cfg["input_dim"]
    assert set(params) == ONE_LAYER_KEYS | {"w_x_1", "w_h_1", "b_1"}
    assert params["w_x"].shape == (I, H3)
    assert params["w_x_1"].shape == params["w_h_1"].shape == (H, H3)
    assert params["b_1"].shape == (H3,)
    w_h_max = cfg["weights"]["w_h_max"]
    for k in ("w_x_1", "w_h_1"):
        assert float(jnp.max(jnp.abs(params[k]))) == pytest.approx(
            w_h_max, rel=1e-6)
    # The first layer and the head are the one-layer configuration's.
    one = model.make_params(_config(name), 2 ** 36 + 9)
    for k in ONE_LAYER_KEYS:
        np.testing.assert_array_equal(params[k], one[k])
    # Deterministic in the seed; the second layer draws its own.
    again = model.make_params(cfg, 2 ** 36 + 9)
    other = model.make_params(cfg, 2 ** 36 + 10)
    for k in params:
        np.testing.assert_array_equal(params[k], again[k])
        assert not np.array_equal(params[k], other[k]) or k == "b_fc"
    assert not np.array_equal(params["w_h_1"], params["w_h"])
    assert not np.array_equal(params["b_1"], params["b"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_two_layer_stack_equals_its_layers_one_after_another(name):
    """Layer 1 alone, frame by frame, gives the hidden sequence that
    layer 2, run by hand, takes as its input; the head reads layer 2."""
    cfg1, cfg2 = _config(name), _config(name, layers=2)
    params = model.make_params(cfg2, 2 ** 35 + 1)
    m2, st2 = REF.build(cfg2, params)
    m1, st1 = REF.build(cfg1, {k: params[k] for k in ONE_LAYER_KEYS})
    B, F, shift = 3, 48, st1.frame_shift
    audio = generator.utterances(np.random.default_rng(3), B)[:, :F * shift]

    s1 = REF.init_state(m1, st1, B)
    h1 = []
    for f in range(F):
        s1, _ = REF.block(m1, s1, jnp.asarray(audio[:, f * shift:
                                                     (f + 1) * shift]),
                          st=st1, detect=False)
        h1.append(s1.h[0])

    # Compiled as the block compiles it: float32 arithmetic run op by op
    # may round otherwise.
    @jax.jit
    def layer2(x, *carry):
        out = REF._gru_frame(x, *carry, m2.gru[1], 1, st2)
        return out[:5], REF._logits(out[0], m2, st2)[0]

    s2 = REF.init_state(m2, st2, B)
    carry = (s2.h[1], s2.x_hat[1], s2.h_hat[1], s2.m_x[1], s2.m_h[1])
    votes = []
    for x in h1:
        carry, v = layer2(x, *carry)
        votes.append(v)
    h, m_x = carry[0], carry[3]

    state, out = REF.block(m2, s2, jnp.asarray(audio), st=st2,
                           detect=False)
    np.testing.assert_array_equal(np.asarray(state.h[0]),
                                  np.asarray(s1.h[0]))
    np.testing.assert_array_equal(np.asarray(state.h[1]), np.asarray(h))
    np.testing.assert_array_equal(np.asarray(state.m_x[1]), np.asarray(m_x))
    np.testing.assert_array_equal(np.asarray(out.votes), np.stack(votes))
    assert not np.array_equal(np.asarray(state.h[1]),
                              np.asarray(state.h[0]))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reference_state_holds_every_layer(name):
    """(B, L·H), the first layer's first: the one-layer state, then the
    second layer's."""
    cell = spec.find_cell(BENCH, CELLS[name], ROOT)
    two = dataclasses.replace(cell, config=dict(cell.config, layers=2))
    params = model.make_params(two.config, 5)
    clips = np.zeros((3, 4096), np.float32)
    clips[:, :4000] = generator.utterances(np.random.default_rng(1), 3,
                                           4000)
    tails = np.array([0, 1, 0])
    h2 = Reference(two, params).final_hidden(clips, 4096, tails)
    h1 = Reference(cell, {k: params[k] for k in ONE_LAYER_KEYS}
                   ).final_hidden(clips, 4096, tails)
    H = cell.config["hidden"]
    assert h2.shape == (3, 2 * H) and h1.shape == (3, H)
    np.testing.assert_array_equal(h2[:, :H], h1)
    assert np.any(h2[:, H:] != 0)


@dataclasses.dataclass
class _Session:
    state: object


def test_session_state_of_a_stack_is_read_layer_by_layer():
    """The program's session holds one ΔGRU state, or one per layer."""
    from repro.core.delta_gru import DeltaState
    B, H = 2, 3

    def layer(v, dt):
        h = np.full((B, H), v, dt)
        return DeltaState(h=h, x_hat=h, h_hat=h, m_x=h, m_h=h)

    one = probes.hidden_values(_Session(layer(0.5, np.float32)))
    np.testing.assert_array_equal(one, np.full((B, H), 0.5))
    two = probes.hidden_values(_Session((layer(0.5, np.float32),
                                         layer(-0.25, np.float32))))
    assert two.shape == (B, 2 * H)
    np.testing.assert_array_equal(two[:, :H], 0.5)
    np.testing.assert_array_equal(two[:, H:], -0.25)
    codes = probes.hidden_values(_Session([layer(1 << 14, np.int16),
                                           layer(-(1 << 13), np.int16)]))
    np.testing.assert_array_equal(codes[:, :H], 0.5)
    np.testing.assert_array_equal(codes[:, H:], -0.25)
