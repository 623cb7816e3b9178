"""The reference's ΔLSTM cell against plain loops written here.

A configuration that states ``"cell": "lstm"`` runs each layer of its
stack as a ΔLSTM (``bench/references/deltakws.py``).  Its frame is checked
on seeded random weights at a tiny size, two layers over 48 frames of
inputs whose steps fall both over and under the Δ threshold: the float32
reference against a numpy float64 loop, and the int8 reference against a
numpy int64 loop of the integer spec, in which only σ and tanh, the
"table", are evaluated as the reference evaluates them, in float32.  One
case drives c into saturation.  A block of audio carries c from call to
call, and the comparison's control reads an LSTM configuration.
"""
import dataclasses
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, generator, model, spec
from bench.harness import Reference
from bench.tests.small import small_cell

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
REF = spec.load_module(ROOT / "bench" / "references" / "deltakws.py",
                       "reference")
H, L, F, B = 8, 2, 48, 3


def _config(numerics: str, **changes) -> dict:
    name = "deltakws-int8" if numerics == "int8" else "deltakws-f32"
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg.update(cell="lstm", layers=L, hidden=H,
               dtypes=dict(cfg["dtypes"],
                           cell="int16" if numerics == "int8" else "float32"))
    if numerics == "int8":
        cfg["formats"] = dict(cfg["formats"], cell_frac=12)
    return dict(cfg, **changes)


def _params(cfg: dict, seed: int, saturate: bool) -> dict:
    """Random weights; ``saturate`` biases i, f and g high, so that c
    grows by about one every frame."""
    rng = np.random.default_rng(seed)
    I, K = cfg["input_dim"], cfg["classes"]
    p = {"w_fc": rng.uniform(-0.5, 0.5, (H, K)), "b_fc": np.zeros(K)}
    for layer in range(L):
        w_x, w_h, b = model.layer_keys(layer)
        p[w_x] = rng.uniform(-0.8, 0.8, (I if layer == 0 else H, 4 * H))
        p[w_h] = rng.uniform(-0.6, 0.6, (H, 4 * H))
        p[b] = rng.normal(0.0, 0.3, 4 * H) + (6.0 if saturate else 0.0) \
            * (np.arange(4 * H) < 3 * H)
    return p


def _inputs(cfg: dict, seed: int) -> np.ndarray:
    """(F, B, I) features, a random walk on [-1, 1) whose steps are half
    small (under the Δ threshold 0.2) and half large."""
    rng = np.random.default_rng(seed)
    shape = (F, B, cfg["input_dim"])
    step = np.where(rng.random(shape) < 0.5, 0.05, 0.6) \
        * rng.standard_normal(shape)
    return np.clip(np.cumsum(step, axis=0), -1.0, 1.0 - 2.0 ** -11)


def _reference(cfg: dict, params: dict, xs) -> tuple:
    """(h, c) of every frame and layer, (F, L, B, H), from the
    reference's ΔLSTM frame, layer above layer as its block runs them."""
    m, st = REF.build(cfg, params)
    assert st.cell == "lstm"

    @jax.jit
    def run(s, xs):
        def step(s, x):
            fields = [list(f) for f in (s.h, s.x_hat, s.h_hat, s.m_x, s.m_h)]
            c = list(s.c)
            for layer, g in enumerate(m.gru):
                *out, _, c[layer] = REF._lstm_frame(
                    x, *(f[layer] for f in fields), c[layer], g, layer, st)
                for f, v in zip(fields, out):
                    f[layer] = v
                x = fields[0][layer]
            h, x_hat, h_hat, m_x, m_h = (tuple(f) for f in fields)
            s = s._replace(h=h, c=tuple(c), x_hat=x_hat, h_hat=h_hat,
                           m_x=m_x, m_h=m_h)
            return s, (jnp.stack(h), jnp.stack(c))
        return jax.lax.scan(step, s, xs)

    _, (h, c) = run(REF.init_state(m, st, B), jnp.asarray(xs))
    return np.asarray(h), np.asarray(c)


def _float_loop(cfg: dict, params: dict, xs: np.ndarray) -> tuple:
    """The ΔLSTM equations in float64, one frame and layer at a time."""
    th = cfg["delta_threshold"]

    def sigmoid(v):
        return 1.0 / (1.0 + np.exp(-v))

    keys = [model.layer_keys(layer) for layer in range(L)]
    h = [np.zeros((B, H)) for _ in range(L)]
    c = [np.zeros((B, H)) for _ in range(L)]
    h_hat = [np.zeros((B, H)) for _ in range(L)]
    x_hat = [np.zeros((B, xs.shape[-1]))] + [np.zeros((B, H))] * (L - 1)
    mem = [np.broadcast_to(params[b], (B, 4 * H)) for _, _, b in keys]
    hs, cs = [], []
    for x in xs:
        for layer, (w_x, w_h, _) in enumerate(keys):
            dx = np.where(np.abs(x - x_hat[layer]) > th, x - x_hat[layer], 0)
            x_hat[layer] = np.where(dx != 0, x, x_hat[layer])
            dh = np.where(np.abs(h[layer] - h_hat[layer]) > th,
                          h[layer] - h_hat[layer], 0)
            h_hat[layer] = np.where(dh != 0, h[layer], h_hat[layer])
            mem[layer] = mem[layer] + dx @ params[w_x] + dh @ params[w_h]
            i, f, g, o = np.split(mem[layer], 4, axis=-1)
            c[layer] = sigmoid(f) * c[layer] + sigmoid(i) * np.tanh(g)
            h[layer] = sigmoid(o) * np.tanh(c[layer])
            x = h[layer]
        hs.append(np.stack(h))
        cs.append(np.stack(c))
    return np.stack(hs), np.stack(cs)


@functools.partial(jax.jit, static_argnames=("fn", "frac"))
def _table(codes, *, fn, frac: int):
    """The σ / tanh table: a code at ``frac`` fraction bits in, the
    hidden-grid code (15 fraction bits) out, in float32 as the
    reference evaluates it."""
    v = codes.astype(jnp.float32) * float(2.0 ** -frac)
    return jnp.round(fn(v) * (1 << 15)).astype(jnp.int32)


def _int_loop(cfg: dict, params: dict, xs: np.ndarray) -> tuple:
    """The integer ΔLSTM spec in int64, one frame and layer at a time,
    on feature codes ``xs``."""
    fm = cfg["formats"]
    hid, cell_frac = fm["hid_frac"], fm["cell_frac"]
    acc_frac, acc_bits = fm["acc_frac"], fm["acc_bits"]

    def sat(v, bits):
        return np.clip(v, -(1 << (bits - 1)), (1 << (bits - 1)) - 1)

    def rshr(v, s):
        return (v + (1 << (s - 1))) >> s

    def grid(v, shift):
        return v << shift if shift >= 0 else rshr(v, -shift)

    def codes(w):
        e = int(np.ceil(np.log2(np.max(np.abs(w)))))
        return np.clip(np.round(w / 2.0 ** e * 128), -128, 127).astype(
            np.int64), e

    def table(v, fn, frac):
        return np.asarray(_table(jnp.asarray(v, jnp.int32), fn=fn,
                                 frac=frac)).astype(np.int64)

    layers = []
    for layer, (k_x, k_h, k_b) in enumerate(
            model.layer_keys(layer) for layer in range(L)):
        in_frac = fm["feat_frac"] if layer == 0 else hid
        w_x, e_x = codes(params[k_x])
        w_h, e_h = codes(params[k_h])
        b = sat(np.round(params[k_b] * 2.0 ** acc_frac), acc_bits)
        layers.append(dict(
            w_x=w_x, w_h=w_h, s_x=acc_frac - (in_frac + 7 - e_x),
            s_h=acc_frac - (hid + 7 - e_h),
            th_x=int(np.floor(cfg["delta_threshold"] * (1 << in_frac))),
            th_h=int(np.floor(cfg["delta_threshold"] * (1 << hid))),
            h=np.zeros((B, H), np.int64), c=np.zeros((B, H), np.int64),
            x_hat=np.zeros((B, w_x.shape[0]), np.int64),
            h_hat=np.zeros((B, H), np.int64),
            m_x=np.broadcast_to(b.astype(np.int64), (B, 4 * H)),
            m_h=np.zeros((B, 4 * H), np.int64)))
    hs, cs = [], []
    for x in xs.astype(np.int64):
        for y in layers:
            dx = np.where(np.abs(x - y["x_hat"]) > y["th_x"],
                          x - y["x_hat"], 0)
            y["x_hat"] = np.where(dx != 0, x, y["x_hat"])
            dh = np.where(np.abs(y["h"] - y["h_hat"]) > y["th_h"],
                          y["h"] - y["h_hat"], 0)
            y["h_hat"] = np.where(dh != 0, y["h"], y["h_hat"])
            y["m_x"] = sat(y["m_x"] + grid(dx @ y["w_x"], y["s_x"]),
                           acc_bits)
            y["m_h"] = sat(y["m_h"] + grid(dh @ y["w_h"], y["s_h"]),
                           acc_bits)
            m = y["m_x"] + y["m_h"]
            i, f, o = (table(m[:, k * H:(k + 1) * H], jax.nn.sigmoid,
                             acc_frac) for k in (0, 1, 3))
            g = table(m[:, 2 * H:3 * H], jnp.tanh, acc_frac)
            y["c"] = sat(rshr(f * y["c"], hid)
                         + rshr(i * g, 2 * hid - cell_frac), 16)
            y["h"] = sat(rshr(o * table(y["c"], jnp.tanh, cell_frac), hid),
                         16)
            x = y["h"]
        hs.append(np.stack([y["h"] for y in layers]))
        cs.append(np.stack([y["c"] for y in layers]))
    return np.stack(hs), np.stack(cs)


@pytest.mark.parametrize("saturate", [False, True])
@pytest.mark.parametrize("seed", [3, 2 ** 38 + 11])
def test_float_lstm_equals_a_float64_loop(seed, saturate):
    cfg = _config("float32")
    params = _params(cfg, seed, saturate)
    xs = _inputs(cfg, seed + 1)
    h, c = _reference(cfg, params, xs.astype(np.float32))
    h64, c64 = _float_loop(cfg, params, xs.astype(np.float32)
                           .astype(np.float64))
    np.testing.assert_allclose(h, h64, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(c, c64, rtol=1e-4, atol=1e-5)
    assert np.std(h[:, 1]) > 0.05                   # layer 2 is not still
    if saturate:
        assert np.max(c) > 20.0


@pytest.mark.parametrize("saturate", [False, True])
@pytest.mark.parametrize("seed", [3, 2 ** 38 + 11])
def test_int8_lstm_equals_an_int64_loop_of_the_spec(seed, saturate):
    cfg = _config("int8")
    params = _params(cfg, seed, saturate)
    xs = np.round(_inputs(cfg, seed + 1) * (1 << cfg["formats"]["feat_frac"]))
    h, c = _reference(cfg, params, xs.astype(np.int32))
    h64, c64 = _int_loop(cfg, params, xs)
    np.testing.assert_array_equal(h, h64)
    np.testing.assert_array_equal(c, c64)
    assert len(np.unique(h[:, 1])) > 50             # layer 2 is not still
    # c saturates at its int16 word only where the biases drive it.
    assert np.any(c == 32767) == saturate


@pytest.mark.parametrize("numerics", ["int8", "float32"])
def test_lstm_block_carries_c_from_call_to_call(numerics):
    """Two blocks of audio give what one block of both gives: c is part
    of the carried state, and every layer's h is what the check reads."""
    cfg = _config(numerics)
    params = model.make_params(cfg, 2 ** 35 + 7)
    assert params["w_h_1"].shape == (H, 4 * H)
    m, st = REF.build(cfg, params)
    audio = generator.utterances(np.random.default_rng(4), B)[:, :64 * 128]
    s0 = REF.init_state(m, st, B)
    whole, out = REF.block(m, s0, jnp.asarray(audio), st=st, detect=False)
    half, out1 = REF.block(m, s0, jnp.asarray(audio[:, :4096]), st=st,
                           detect=False)
    split, out2 = REF.block(m, half, jnp.asarray(audio[:, 4096:]), st=st,
                            detect=False)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(split)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(out.votes),
        np.concatenate([np.asarray(out1.votes), np.asarray(out2.votes)]))
    assert len(whole.c) == L and np.any(np.asarray(whole.c[1]) != 0)
    cell = dataclasses.replace(small_cell("int8-utt-c4096"), config=cfg)
    hidden = Reference(cell, params).final_hidden(
        audio[:, :4096], 4096, np.array([0, 1, 0]))
    assert hidden.shape == (B, L * H)


@pytest.mark.parametrize("name", ["int8-utt-c4096", "f32-utt-c4096"])
def test_the_control_reads_an_lstm_config(name):
    """``bench/control.py`` on an LSTM configuration: int4 weights, or
    three bfloat16 passes, at a tiny size."""
    cell = small_cell(name)
    cfg = _config(cell.config["numerics"], hidden=16)
    cell = dataclasses.replace(cell, config=cfg)
    r = control.readings(cell, 2 ** 33 + 1)
    assert set(r) == {"unanswered", "decision_mismatch_pct",
                      "vote_mismatch_pct", "state_gap"}
    assert 0.0 < r["state_gap"] < 2.0
    if cfg["numerics"] == "int8":
        assert r["vote_mismatch_pct"] > 0.0
