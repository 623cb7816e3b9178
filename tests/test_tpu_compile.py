"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with JAX
compiles for a v5e that is described, not attached, and refuses what
Mosaic would refuse on the chip — block shapes off the (8, 128) tiling,
dynamic lane indices, unaligned slices, scoped-VMEM overflow.  Interpret
mode accepts all of those, so these compiles guard the compiled path.

Every kernel compiles at its DEFAULT tiling (``interpret=False``) for
B ∈ {16, 1024} slots and T ∈ {32, 12, 1} frames — an aligned 4096-sample
chunk, a non-aligned chunk and a one-frame chunk — and the session's
fused audio step compiles at 1024 slots in both numerics, for the
ΔGRU-768 stack and for the ΔLSTM-1024 stack, whose second layer holds
its weights as int8 planes.

The topology is described inside a module fixture (never at import or
collection time): only one process may load the TPU library at a time,
so under several test workers only the worker that runs this file does.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fixed_point as fp
from repro.kernels.delta_gru_seq import delta_gru_seq, delta_gru_seq_int
from repro.kernels.iir_fex import batched_iir_fex, batched_iir_fex_int

I, H, C, SHIFT = 10, 64, 10, 128          # published deltakws widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip cannot read back what it would write to the
    # persistent cache; keep the cache out of these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_args(kernel: str, B: int, T: int, I: int = I, H: int = H):
    f32, i16, i32 = jnp.float32, jnp.int16, jnp.int32
    if kernel == "delta_gru_seq":
        return (functools.partial(delta_gru_seq, interpret=False),
                [((T, B, I), f32), ((B, H), f32), ((B, I), f32),
                 ((B, H), f32), ((B, 3 * H), f32), ((B, 3 * H), f32),
                 ((I, 3 * H), f32), ((H, 3 * H), f32), ((), f32)])
    if kernel == "delta_gru_seq_int":
        return (functools.partial(delta_gru_seq_int, fmt=fp.GruFormats(),
                                  interpret=False),
                [((T, B, I), i16), ((B, H), i16), ((B, I), i16),
                 ((B, H), i16), ((B, 3 * H), i32), ((B, 3 * H), i32),
                 ((I, 3 * H), jnp.int8), ((H, 3 * H), jnp.int8),
                 ((1, 2), i32)])
    if kernel == "batched_iir_fex":
        return (functools.partial(batched_iir_fex, interpret=False),
                [((B, T * SHIFT), f32), ((6, C), f32), ((B, 5, C), f32)])
    return (functools.partial(batched_iir_fex_int, fmt=fp.FexFormats(),
                              interpret=False),
            [((B, T * SHIFT), i16), ((6, C), i32), ((B, 5, C), i16)])


def _custom_calls(text: str) -> set[str]:
    return {m.group(1) for m in re.finditer(
        r"%([\w-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)}


@pytest.mark.parametrize("T", [32, 12, 1])
@pytest.mark.parametrize("B", [16, 1024])
@pytest.mark.parametrize("kernel", ["delta_gru_seq", "delta_gru_seq_int",
                                    "batched_iir_fex", "batched_iir_fex_int"])
def test_kernel_compiles_for_v5e(one_chip, kernel, B, T):
    fn, shapes = _kernel_args(kernel, B, T)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernel in _custom_calls(text)


@pytest.mark.parametrize("I_, H_", [(10, 768), (768, 768)])
def test_wide_int_kernel_compiles_for_v5e(one_chip, I_, H_):
    """The ΔGRU-768's two layers at 1024 slots × 32 frames: the int8
    plane dots at contraction 768 and the raised VMEM limit compile."""
    fn, shapes = _kernel_args("delta_gru_seq_int", 1024, 32, I_, H_)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "delta_gru_seq_int" in _custom_calls(text)


@pytest.mark.parametrize("I_, weights", [(10, "f32_image"),
                                          (1024, "int8_planes")])
def test_lstm_kernel_compiles_for_v5e(one_chip, I_, weights):
    """Spartus's two ΔLSTM-1024 layers at 1024 slots × 32 frames: layer
    1 with its float32 weight image, layer 2 with its int8 weights DMA'd
    once and contracted as int8 planes, each under the raised VMEM
    limit, as ``delta_lstm_seq_int``."""
    from repro.kernels.delta_gru_seq import int_vmem_plan
    B, T, H4 = 1024, 32, 4 * 1024
    assert int_vmem_plan(I_, 1024, T, B, 1, None, interpret=False,
                         cell="lstm").weights == weights
    i16, i32 = jnp.int16, jnp.int32
    fn = functools.partial(delta_gru_seq_int, fmt=fp.GruFormats(),
                           cell="lstm", interpret=False)
    shapes = [((T, B, I_), i16), ((B, 1024), i16), ((B, I_), i16),
              ((B, 1024), i16), ((B, H4), i32), ((B, H4), i32),
              ((I_, H4), jnp.int8), ((1024, H4), jnp.int8), ((1, 2), i32),
              ((B, 1024), i16)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "delta_lstm_seq_int" in _custom_calls(text)


@pytest.mark.parametrize("numerics,kernels", [
    ("float32", {"batched_iir_fex", "delta_gru_seq"}),
    ("int8", {"batched_iir_fex_int", "delta_gru_seq_int"}),
])
def test_fused_audio_step_compiles_for_v5e(one_chip, numerics, kernels):
    """The session's fused audio→decision step at 1024 slots and a
    4096-sample chunk: both kernels compiled, no XLA FEx fallback."""
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.launch.streaming import StreamingKwsSession
    from repro.models import kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg, input_dim=C)
    sess = StreamingKwsSession(params, cfg, batch=1024,
                               fex=FeatureExtractor(), numerics=numerics,
                               interpret=False)
    step, args = sess.fused_audio_step(4096)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    text = step.lower(*shapes).compile().as_text()
    assert kernels <= _custom_calls(text)


def test_stacked_fused_audio_step_compiles_for_v5e(one_chip):
    """The ``edgedrnn`` configuration's int8 step at 1024 slots: both
    layers' kernels, each under its ``kws.gru.<l>`` scope."""
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.launch.streaming import StreamingKwsSession
    from repro.models import kws
    cfg = get_config("edgedrnn")
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg, input_dim=C)
    sess = StreamingKwsSession(params, cfg, batch=1024,
                               fex=FeatureExtractor(), numerics="int8",
                               interpret=False)
    step, args = sess.fused_audio_step(4096)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    text = step.lower(*shapes).compile().as_text()
    assert {"batched_iir_fex_int", "delta_gru_seq_int"} <= _custom_calls(text)
    for layer in (0, 1):
        assert f"/kws.gru/kws.gru.{layer}/" in text


def test_spartus_fused_audio_step_compiles_for_v5e(one_chip):
    """The ``spartus`` configuration's int8 step at 1024 slots: both
    ΔLSTM layers' kernels, named ``delta_lstm_seq_int``, each under its
    ``kws.gru.<l>`` scope."""
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.launch.streaming import StreamingKwsSession
    from repro.models import kws
    cfg = get_config("spartus")
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg, input_dim=C)
    sess = StreamingKwsSession(params, cfg, batch=1024,
                               fex=FeatureExtractor(), numerics="int8",
                               interpret=False)
    step, args = sess.fused_audio_step(4096)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    text = step.lower(*shapes).compile().as_text()
    assert {"batched_iir_fex_int", "delta_lstm_seq_int"} <= \
        _custom_calls(text)
    assert "delta_gru_seq_int" not in _custom_calls(text)
    for layer in (0, 1):
        assert f"/kws.gru/kws.gru.{layer}/" in text
