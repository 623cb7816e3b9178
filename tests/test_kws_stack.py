"""A ΔGRU stack served through the session, against the plain reference.

The ``edgedrnn`` smoke configuration (two layers of 32 units) with the
benchmark's seeded random weights (``bench/model.py``, the same dict a
served configuration hands the session), run through
``StreamingKwsSession.process_audio`` and through the benchmark's plain
reference (``bench/references/deltakws.py``, which imports nothing of
the program).  int8 is bit-true: votes and every layer's final state are
compared exactly.  float32 contracts at HIGHEST on both sides; the state
may differ by rounding only, so it is held to ``F32_STATE_TOL`` — the
benchmark's own float limit — which three bfloat16 passes (the
reference's "high" control) exceed by 2.5× and more.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import model as bench_model  # noqa: E402
from bench import spec  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.frontend import FeatureExtractor  # noqa: E402
from repro.launch.streaming import StreamingKwsSession  # noqa: E402

B = 4
F32_STATE_TOL = 2e-6
REF = spec.load_module(ROOT / "bench" / "references" / "deltakws.py",
                       "reference deltakws")


def _config(numerics: str) -> dict:
    """The served stack's configuration file at the smoke width."""
    name = {"int8": "edgedrnn-2l768-int8", "float32": "deltakws-f32"}
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{name[numerics]}.json").read_text())
    edge = json.loads((ROOT / "bench" / "configs"
                       / "edgedrnn-2l768-int8.json").read_text())
    cfg.update(layers=2, hidden=get_smoke_config("edgedrnn").d_model,
               weights=edge["weights"])
    return cfg


def _session(numerics: str, params, **kw):
    return StreamingKwsSession(params, get_smoke_config("edgedrnn"),
                               batch=B, fex=FeatureExtractor(),
                               numerics=numerics, threshold=0.2, **kw)


def _audio(seed: int, n: int = 4096) -> np.ndarray:
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, (B, n)).astype(np.float32)


def _reference(cfg: dict, params, audio, precision="highest"):
    host = {k: np.asarray(v) for k, v in params.items()}
    model, static = REF.build(cfg, host, precision=precision)
    state = REF.init_state(model, static, B)
    return REF.block(model, state, jnp.asarray(audio), st=static,
                     detect=False)


@pytest.mark.parametrize("numerics", ["int8", "float32"])
def test_stack_session_matches_reference(numerics):
    cfg = _config(numerics)
    params = bench_model.make_params(cfg, 11)
    audio = _audio(1)
    sess = _session(numerics, params)
    votes = np.concatenate([np.asarray(sess.process_audio(a).votes)
                            for a in (audio[:, :1536], audio[:, 1536:])])
    state, frames = _reference(cfg, params, audio)
    assert len(sess.state) == 2
    if numerics == "int8":
        np.testing.assert_array_equal(votes, np.asarray(frames.votes))
        for layer, st in enumerate(sess.state):
            for field in st._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(st, field), np.int32),
                    np.asarray(getattr(state, field)[layer]),
                    err_msg=f"layer {layer} {field}")
        return
    gap = max(float(np.max(np.abs(np.asarray(st.h) - np.asarray(h))))
              for st, h in zip(sess.state, state.h))
    assert gap <= F32_STATE_TOL
    # The tolerance sees three bfloat16 passes.
    ctl, _ = _reference(cfg, params, audio, precision="high")
    ctl_gap = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                  for a, b in zip(ctl.h, state.h))
    assert ctl_gap > 2 * F32_STATE_TOL


@pytest.mark.parametrize("numerics", ["int8", "float32"])
def test_stack_chunk_split_bit_invariant(numerics):
    params = bench_model.make_params(_config(numerics), 5)
    audio = _audio(2, 4096 + 300)
    one, split = _session(numerics, params), _session(numerics, params)
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


@pytest.mark.parametrize("numerics", ["int8", "float32"])
def test_stack_reset_streams_clears_every_layer_of_masked_slots(numerics):
    params = bench_model.make_params(_config(numerics), 6)
    sess = _session(numerics, params)
    sess.process_audio(_audio(3))
    before = jax.tree.map(np.asarray, sess.state)
    sess.reset_streams([1, 3])
    fresh = _session(numerics, params)
    fresh.process_audio(np.zeros((B, 0), np.float32))   # state, no frames
    kept, masked = np.array([0, 2]), np.array([1, 3])
    for layer, (st, old, new) in enumerate(zip(sess.state, before,
                                               fresh.state)):
        for field, a, b, c in zip(st._fields, st, old, new):
            a = np.asarray(a)
            np.testing.assert_array_equal(a[kept], b[kept],
                                          err_msg=f"{layer} {field}")
            np.testing.assert_array_equal(a[masked], np.asarray(c)[masked],
                                          err_msg=f"{layer} {field}")
    assert any(np.any(np.asarray(b)[masked] != 0) for b in before[1][:3])


def test_stack_layer_sparsity_and_counts():
    """Per-layer Δ counts add up to the chunks' per-frame counts, and
    each layer's sparsity is 1 − transmitted / (frames · (I + H))."""
    params = bench_model.make_params(_config("int8"), 7)
    sess = _session("int8", params)
    nz = sum(int(np.asarray(sess.process_audio(_audio(s)).nz).sum())
             for s in (8, 9))
    summ = sess.summary()
    H = get_smoke_config("edgedrnn").d_model
    assert sum(summ.layer_transmits) == nz
    assert summ.layer_sparsity == tuple(
        1.0 - t / (summ.frames * w)
        for t, w in zip(summ.layer_transmits, (10 + H, 2 * H)))
    assert 0.0 < min(summ.layer_sparsity) <= max(summ.layer_sparsity) < 1.0


def test_one_layer_counters_sum_to_overall_counts():
    """One layer: the per-layer Δ counter is today's overall count — the
    chunks' per-frame ``nz`` — and its sparsity is the overall one."""
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(jax.random.PRNGKey(3), cfg, input_dim=10)
    sess = StreamingKwsSession(params, cfg, batch=B, fex=FeatureExtractor(),
                               numerics="int8")
    nz = sum(int(np.asarray(sess.process_audio(_audio(s)).nz).sum())
             for s in (1, 2))
    summ = sess.summary()
    assert summ.layer_transmits == (nz,)
    assert summ.layer_sparsity == (summ.sparsity,)


def _digest(arrays) -> str:
    import hashlib
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# Digests of the one-layer program before it served stacks (same code
# path, same seeds): weights, per-chunk outputs and final state.
ONE_LAYER_DIGESTS = {
    "params": "ce56f7277834d096",
    "int8": ("41f2821c967c2415", "459debde24f2435b"),
    "float32": ("1160736b1e75b0d7", "11cee989c636c950"),
}


@pytest.mark.parametrize("numerics", ["int8", "float32"])
def test_one_layer_session_unchanged(numerics):
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(jax.random.PRNGKey(3), cfg, input_dim=10)
    assert _digest([params[k] for k in sorted(params)]) == \
        ONE_LAYER_DIGESTS["params"]
    audio = np.random.default_rng(4).uniform(
        -0.6, 0.6, (B, 2 * 1024 + 77)).astype(np.float32)
    sess = StreamingKwsSession(params, cfg, batch=B, fex=FeatureExtractor(),
                               numerics=numerics)
    outs = []
    for a in (audio[:, :1100], audio[:, 1100:]):
        o = sess.process_audio(a)
        outs += [o.logits, o.votes, o.nz]
        sess.reset_streams([2])
    assert (_digest(outs), _digest(list(sess.state))) == \
        ONE_LAYER_DIGESTS[numerics]


SHARDED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, os.environ["REPO_ROOT"])
import numpy as np
from tests.test_kws_stack import _audio, _config, _session
from bench import model as bench_model
from repro.launch.mesh import make_slot_mesh

for numerics in ("int8", "float32"):
    params = bench_model.make_params(_config(numerics), 12)
    ref, eng = _session(numerics, params), _session(
        numerics, params, mesh=make_slot_mesh(2))
    assert eng.n_shards == 2
    for sess in (ref, eng):
        sess.process_audio(_audio(4))
        sess.reset_streams([1, 2])
    o_r, o_e = ref.process_audio(_audio(5)), eng.process_audio(_audio(5))
    np.testing.assert_array_equal(np.asarray(o_r.votes),
                                  np.asarray(o_e.votes))
    for s_r, s_e in zip(ref.state, eng.state):
        for a, b in zip(s_r, s_e):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.summary() == eng.summary()
print("STACK_SHARDED_OK")
"""


def test_stack_on_a_two_device_slot_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPO_ROOT=str(ROOT),
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run([sys.executable, "-c", SHARDED_CHILD], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "STACK_SHARDED_OK" in out.stdout


def test_cascade_refuses_a_stack():
    from repro.launch.streaming import CascadeConfig
    from repro.models import kws
    from repro.models.detector import DetectorConfig
    params = bench_model.make_params(_config("float32"), 1)
    s0, _ = kws.init_kws(jax.random.PRNGKey(0),
                         get_smoke_config("deltakws"), input_dim=4)
    with pytest.raises(ValueError, match="2-layer stack"):
        _session("float32", params, detector=DetectorConfig(),
                 cascade=CascadeConfig(), stage0_params=s0)
