"""A ΔLSTM stack served through the session, against the plain reference.

The ``spartus`` smoke configuration (two ΔLSTM layers of 32 units) with
the benchmark's seeded random weights (``bench/model.py``, the dict a
served configuration hands the session), run through
``StreamingKwsSession.process_audio`` in int8 (the kernel in interpret
mode) and through the benchmark's plain reference
(``bench/references/deltakws.py``, which imports nothing of the
program).  The int8 datapath is bit-true: votes and every layer's final
state, h and c included, are compared exactly.  The core golden scan
``int_lstm_scan`` is held to the kernel at several time tiles and at the
width where the kernel holds its weights as int8 planes.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import model as bench_model  # noqa: E402
from bench import spec  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core import fixed_point as fp  # noqa: E402
from repro.core.delta_gru import DeltaLstmState  # noqa: E402
from repro.frontend import FeatureExtractor  # noqa: E402
from repro.launch.streaming import StreamingKwsSession  # noqa: E402

B = 4
REF = spec.load_module(ROOT / "bench" / "references" / "deltakws.py",
                       "reference deltakws")


def _config(layers: int = 2) -> dict:
    """The served ΔLSTM configuration file at the smoke width."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "spartus-2l1024-int8.json").read_text())
    cfg.update(layers=layers, hidden=get_smoke_config("spartus").d_model)
    return cfg


def _arch(layers: int = 2):
    return dataclasses.replace(get_smoke_config("spartus"),
                               num_layers=layers)


def _session(params, layers: int = 2, **kw):
    return StreamingKwsSession(params, _arch(layers), batch=B,
                               fex=FeatureExtractor(), numerics="int8",
                               threshold=0.2, **kw)


def _audio(seed: int, n: int = 4096) -> np.ndarray:
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, (B, n)).astype(np.float32)


def _saturating(params: dict, layers: int) -> dict:
    """Biases that drive i, f and g high in every layer, so that c grows
    by about one a frame into its int16 rail."""
    p = dict(params)
    for layer in range(layers):
        b = bench_model.layer_keys(layer)[2]
        H4 = p[b].shape[0]
        p[b] = p[b] + 6.0 * (jnp.arange(H4) < 3 * H4 // 4)
    return p


def _reference(cfg: dict, params, audio):
    host = {k: np.asarray(v) for k, v in params.items()}
    model, static = REF.build(cfg, host)
    state = REF.init_state(model, static, B)
    return REF.block(model, state, jnp.asarray(audio), st=static,
                     detect=False)


@pytest.mark.parametrize("layers,saturate", [(2, False), (2, True),
                                             (1, False)])
def test_lstm_session_matches_reference(layers, saturate):
    """Votes and every layer's h, c, x̂, ĥ and memories equal the
    reference's bit for bit: a 2-layer stack, one in which c saturates,
    and one layer."""
    cfg = _config(layers)
    params = bench_model.make_params(cfg, 11)
    if saturate:
        params = _saturating(params, layers)
    audio = _audio(1)
    sess = _session(params, layers)
    votes = np.concatenate([np.asarray(sess.process_audio(a).votes)
                            for a in (audio[:, :1536], audio[:, 1536:])])
    state, frames = _reference(cfg, params, audio)
    np.testing.assert_array_equal(votes, np.asarray(frames.votes))
    states = sess.state if layers > 1 else (sess.state,)
    assert len(states) == layers
    for layer, st in enumerate(states):
        assert isinstance(st, DeltaLstmState)
        for field in st._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(st, field), np.int32),
                np.asarray(getattr(state, field)[layer]),
                err_msg=f"layer {layer} {field}")
    c = np.stack([np.asarray(st.c) for st in states])
    assert np.any(c != 0)
    assert np.any(c == 32767) == saturate


def test_lstm_chunk_split_bit_invariant():
    params = bench_model.make_params(_config(), 5)
    audio = _audio(2, 4096 + 300)
    one, split = _session(params), _session(params)
    v1 = np.asarray(one.process_audio(audio).votes)
    v2 = np.concatenate([np.asarray(split.process_audio(audio[:, a:b]).votes)
                         for a, b in ((0, 700), (700, 2900), (2900, None))])
    np.testing.assert_array_equal(v1, v2)
    for s1, s2 in zip(one.state, split.state):
        for a, b in zip(s1, s2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s1, s2 = one.summary(), split.summary()
    assert (s1.frames, s1.layer_transmits, s1.sparsity) == \
        (s2.frames, s2.layer_transmits, s2.sparsity)


def _rand_layer(rng, I: int, H: int):
    w = fp.IntGruWeights(
        w_x=jnp.asarray(rng.integers(-128, 128, (I, 4 * H)), jnp.int8),
        w_h=jnp.asarray(rng.integers(-128, 128, (H, 4 * H)), jnp.int8),
        b=jnp.asarray(rng.integers(-2 ** 20, 2 ** 20, (4 * H,)), jnp.int32))
    return w, fp.GruFormats(feat_frac=15, e_x=-1, e_h=-1)


@pytest.mark.parametrize("block_t", [1, 2, 4])
def test_golden_int_lstm_scan_equals_the_kernel(block_t):
    """``int_lstm_scan``'s golden ``lax.scan`` against the Pallas kernel
    (interpret mode) over int16 codes that swing rail to rail, carried
    state included, at one frame and several a grid step."""
    rng = np.random.default_rng(7)
    T, Bk, I, H = 8, 8, 16, 32
    w, fmt = _rand_layer(rng, I, H)
    xs = jnp.asarray(rng.integers(-32768, 32768, (T, Bk, I)), jnp.int16)
    golden = fp.int_lstm_scan(w, fmt, xs[:4], 0.2, backend="xla")
    golden = fp.int_lstm_scan(w, fmt, xs[4:], 0.2, state=golden[1],
                              backend="xla")
    st = fp.int_lstm_scan(w, fmt, xs[:4], 0.2, backend="pallas",
                          block_t=block_t, interpret=True)[1]
    kern = fp.int_lstm_scan(w, fmt, xs[4:], 0.2, state=st,
                            backend="pallas", block_t=block_t,
                            interpret=True)
    assert isinstance(kern[1], DeltaLstmState)
    for a, b in zip(jax.tree.leaves(golden), jax.tree.leaves(kern)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.any(np.asarray(golden[1].c) != 0)


@pytest.mark.parametrize("block_t", [1, 2])
def test_kernel_with_int8_planes_equals_the_golden_scan(block_t):
    """At Spartus's layer-2 width (1024 in, 1024 out, 128 slots) the
    float32 image does not fit the kernel's VMEM plan, so it holds the
    int8 weights once and contracts int8 planes: still bit for bit."""
    from repro.kernels.delta_gru_seq import int_vmem_plan
    T, Bk, I, H = 2, 128, 1024, 1024
    assert int_vmem_plan(I, H, T, Bk, block_t, None, True,
                         cell="lstm").weights == "int8_planes"
    rng = np.random.default_rng(3)
    w, fmt = _rand_layer(rng, I, H)
    xs = jnp.asarray(rng.integers(-32768, 32768, (T, Bk, I)), jnp.int16)
    golden = fp.int_lstm_scan(w, fmt, xs, 0.2, backend="xla")
    kern = fp.int_lstm_scan(w, fmt, xs, 0.2, backend="pallas",
                            block_t=block_t, interpret=True)
    for a, b in zip(jax.tree.leaves(golden), jax.tree.leaves(kern)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bump_counts_the_cells_gates():
    """A ΔLSTM layer's Δ costs 4H MACs and its dense frame (I+H)·4H; a
    ΔGRU's stay 3H and (I+H)·3H."""
    from repro.launch.streaming import (LayerCounts, _bump, _count_value,
                                        _zero_accum)
    F, Bk, I, H = 4, 8, 10, 32
    nz = lambda n: jnp.full((F, Bk), n, jnp.int32)
    for gates in (3, 4):
        counts = (LayerCounts(nz(3), nz(5), I, H, gates),
                  LayerCounts(nz(2), nz(7), H, H, gates))
        acc = _bump(_zero_accum(1, 2), counts, F * Bk, 0)
        assert _count_value(acc.macs) == (F * Bk * (8 + 9) * gates * H,
                                          False)
        assert _count_value(acc.macs_dense) == (
            F * Bk * ((I + H) + 2 * H) * gates * H, False)
    assert LayerCounts(nz(1), nz(1), I, H).gates == 3


def test_summary_counts_lstm_macs_and_carries_each_layers_plan():
    params = bench_model.make_params(_config(), 8)
    sess = _session(params)
    nz = int(np.asarray(sess.process_audio(_audio(9)).nz).sum())
    summ = sess.summary()
    H = _arch().d_model
    assert sum(summ.layer_transmits) == nz
    assert summ.sparsity == pytest.approx(
        1.0 - nz * 4 * H / (summ.frames * ((10 + H) + 2 * H) * 4 * H))
    assert [p["weights"] for p in summ.layer_plans] == ["f32_image"] * 2
    assert [p["block_b"] for p in summ.layer_plans] == [B, B]
    assert all(p["vmem_bytes"] > 0 for p in summ.layer_plans)
    xla = _session(params, backend="xla")
    xla.process_audio(_audio(9))
    assert xla.summary().layer_plans == ()


def test_reset_and_admission_zero_c():
    """``reset_streams`` — the wave that churn and the scheduler's
    admission run — returns c, with every other field, to a fresh
    stream's in the masked slots only."""
    from repro.launch.streaming import SlotScheduler
    params = _saturating(bench_model.make_params(_config(), 6), 2)
    sess = _session(params)
    sess.process_audio(_audio(3))
    before = jax.tree.map(np.asarray, sess.state)
    assert all(np.all(st.c != 0) for st in before)
    sess.reset_streams([1, 3])
    kept, masked = np.array([0, 2]), np.array([1, 3])
    for st, old in zip(sess.state, before):
        c = np.asarray(st.c)
        np.testing.assert_array_equal(c[kept], old.c[kept])
        assert not np.any(c[masked])
    sched = SlotScheduler(sess)
    for r in range(B):
        sched.submit(r)
    sess.process_audio(_audio(4))
    admitted = [s for s, _ in sched.admit()]
    assert sorted(admitted) == list(range(B))
    assert all(not np.any(np.asarray(st.c)) for st in sess.state)


def test_the_session_refuses_a_float_or_cascaded_lstm():
    from repro.launch.streaming import CascadeConfig
    from repro.models import kws
    from repro.models.detector import DetectorConfig
    params = bench_model.make_params(_config(), 1)
    with pytest.raises(ValueError, match="'lstm' cell"):
        StreamingKwsSession(params, _arch(), batch=B, numerics="float32")
    s0, _ = kws.init_kws(jax.random.PRNGKey(0),
                         get_smoke_config("deltakws"), input_dim=4)
    with pytest.raises(ValueError, match="'lstm' cell"):
        _session(params, detector=DetectorConfig(), cascade=CascadeConfig(),
                 stage0_params=s0)


def test_init_kws_builds_four_gate_layers():
    """The program's own initialiser gives a ΔLSTM config G·H = 4H-wide
    layers, and its params serve through the int8 session."""
    from repro.models import kws
    params, _ = kws.init_kws(jax.random.PRNGKey(0), _arch(), input_dim=10)
    H = _arch().d_model
    assert params["w_x"].shape == (10, 4 * H)
    assert params["w_h_1"].shape == (H, 4 * H)
    assert params["b_1"].shape == (4 * H,)
    sess = _session(params)
    assert np.asarray(sess.process_audio(_audio(5)).votes).shape == (32, B)
