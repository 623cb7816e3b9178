"""The paper's model: IIR FEx → ΔGRU(64) → FC(12) keyword spotter.

Training/eval entry points (``forward``, ``forward_audio``, ``loss_fn``)
are single-device; serving goes through ``launch.streaming`` which keeps
all stream state device-resident.  For the sharded serving engine
(DESIGN.md §6) the weights are deliberately REPLICATED over the mesh —
at 64 hidden units the whole model is ~100 KB, so partitioning it would
trade a free local read for per-step collectives; ``serving_weights``
packages exactly that contract.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import delta_gru as dg
from repro.core.quantize import QFormat, WEIGHT_Q, ste_quantize
from repro.parallel.sharding import AxTree, Sharder

Array = jax.Array

N_CLASSES = 12
CLASSES = ["silence", "unknown", "down", "go", "left", "no",
           "off", "on", "right", "stop", "up", "yes"]


def layer_keys(layer: int) -> tuple[str, str, str]:
    """The parameter keys of ΔGRU layer ``layer`` (0: the first, which
    reads the features): ``w_x, w_h, b``, and ``w_x_<l>, w_h_<l>, b_<l>``
    for layer ``l`` above it, which reads the hidden state below."""
    suffix = f"_{layer}" if layer else ""
    return ("w_x" + suffix, "w_h" + suffix, "b" + suffix)


def n_gru_layers(params) -> int:
    """Depth of the ΔGRU stack a parameter tree holds."""
    n = 1
    while layer_keys(n)[0] in params:
        n += 1
    return n


def init_kws(key, cfg, input_dim: int = 10):
    """cfg.d_model = GRU hidden size (64 in the paper); cfg.vocab_size =
    FC head width (12 for the paper's GSCD head — but the head is fully
    parameterized: an 11-class head, a 35-class GSCD-v2 head or the
    2-class stage-0 wake gate all train/promote/serve through the same
    code, the class count riding the weight shapes end to end).

    ``cfg.num_layers`` recurrent layers of width ``d_model``, each of
    ``cfg.cell`` (``core.delta_gru.CELLS``: G·H-wide weights, G = 3 for
    the ΔGRU, 4 for the ΔLSTM): the first reads the features and draws
    from ``key`` as a one-layer model always has; layer ``l`` above it
    reads the hidden state below, under ``layer_keys(l)``, from
    ``fold_in(key, l)``."""
    k1, k2 = jax.random.split(key)
    n_classes = getattr(cfg, "vocab_size", N_CLASSES)
    H = cfg.d_model
    G = dg.CELLS[getattr(cfg, "cell", "gru")].gates
    t = AxTree()
    for layer in range(getattr(cfg, "num_layers", 1)):
        gru = (dg.init_delta_gru(k1, input_dim, H, gates=G) if layer == 0
               else dg.init_delta_gru(jax.random.fold_in(key, layer), H, H,
                                      gates=G))
        for name, w in zip(layer_keys(layer), gru):
            t.add(name, w, (None,) * w.ndim)
    t.add("w_fc", jax.random.normal(k2, (H, n_classes)) /
          np.sqrt(H), (None, None))
    t.add("b_fc", jnp.zeros((n_classes,)), (None,))
    return t.build()


def _gru_params(params, quantize_8b: bool, layer: int = 0):
    w_x, w_h, b = (params[k] for k in layer_keys(layer))
    if quantize_8b:
        # Per-tensor power-of-two scale, 8-bit STE (IC weight format).
        def q(w):
            scale = 2.0 ** jnp.ceil(jnp.log2(jnp.maximum(
                jax.lax.stop_gradient(jnp.max(jnp.abs(w))), 1e-8)))
            return ste_quantize(w / scale, WEIGHT_Q) * scale
        w_x, w_h = q(w_x), q(w_h)
    return dg.DeltaGRUParams(w_x, w_h, b)


def gru_stack(params, quantize_8b: bool = False):
    """The recurrent weights a session serves: one ``DeltaGRUParams``,
    or for a stack a tuple of them, the first layer's first.  Each holds
    a layer of any cell: its (·, G·H) weights as the tree has them."""
    layers = tuple(_gru_params(params, quantize_8b, layer)
                   for layer in range(n_gru_layers(params)))
    return layers[0] if len(layers) == 1 else layers


def serving_weights(params, quantize_8b: bool = False, mesh=None):
    """(ΔGRU weights (``gru_stack``), w_fc, b_fc) for a serving session,
    replicated over ``mesh`` (no-op when ``mesh`` is None).

    Replication is the serving sharding contract: every shard reads its
    weights from local memory and admission/eviction never moves them —
    only per-stream state is partitioned (see parallel/sharding.py).
    """
    from repro.parallel import sharding as shp
    gru = gru_stack(params, quantize_8b)
    return shp.put_replicated((gru, params["w_fc"], params["b_fc"]), mesh)


# The integer serving path stores ĥ on the Q0.15 grid; QAT snaps the
# training-time hidden state to the same grid (straight-through).
QAT_H_FORMAT = QFormat(int_bits=0, frac_bits=15)


def _gru_hidden(params, cfg, feats: Array, threshold, quantize_8b,
                backend, qat):
    """Shared forward scaffolding: feats (B, F, C) → (hs (F, B, H),
    stats).  Single source for threshold/backend resolution and the
    QAT wiring, so ``forward`` (mean-pool classification) and
    ``forward_frames`` (per-frame detection) stay bit-identical up to
    the pooling."""
    th = cfg.delta_threshold if threshold is None else threshold
    be = (getattr(cfg, "gru_backend", "xla") if backend is None else backend)
    gru = _gru_params(params, quantize_8b or qat)
    xs = jnp.moveaxis(feats, 1, 0)                    # (F, B, C)
    hs, _, stats = dg.delta_gru_scan(
        gru, xs, threshold=th, backend=be,
        h_qformat=QAT_H_FORMAT if qat else None)
    return hs, stats


def forward(params, cfg, feats: Array, threshold: float | None = None,
            quantize_8b: bool = False, backend: str | None = None,
            qat: bool = False):
    """feats: (B, F, C) → (logits (B, 12), stats).

    ``backend`` overrides ``cfg.gru_backend``: "xla" (differentiable
    training path) or "pallas" (fused sequence-resident serving kernel,
    identical numerics — see core.delta_gru.delta_gru_scan).

    ``qat=True`` makes training simulate the deployed integer numerics:
    8-bit STE weights (implies ``quantize_8b``) and the hidden state
    snapped to the Q0.15 grid with a straight-through gradient, so the
    delta-threshold compares the loss sees are the ones the promoted
    int8 bundle will perform.  Features are already on the 12-bit grid
    (the FEx quantizes in-datapath).  XLA backend only.
    """
    hs, stats = _gru_hidden(params, cfg, feats, threshold, quantize_8b,
                            backend, qat)
    h_mean = jnp.mean(hs, axis=0)                     # mean-pool over frames
    logits = h_mean @ params["w_fc"] + params["b_fc"]
    return logits, stats


def forward_audio(params, cfg, audio: Array, fex, *,
                  threshold: float | None = None, quantize_8b: bool = False,
                  backend: str | None = None, fex_backend: str | None = None):
    """Raw audio (B, T) → (logits (B, 12), stats): one device-side
    audio→decision graph — FEx → ΔGRU → FC with no host hop.

    ``fex`` is a ``frontend.fex.FeatureExtractor`` (static: close over it
    when jitting).  ``fex_backend`` picks the FEx path ("pallas" = the
    batched sequence-resident kernel, "xla" = the bit-exact scan); both
    are float-exact against each other, so the choice is invisible.
    """
    feats, _ = fex.scan(audio, None, backend=fex_backend)
    return forward(params, cfg, feats, threshold, quantize_8b, backend)


def forward_frames(params, cfg, feats: Array, threshold: float | None = None,
                   quantize_8b: bool = False, backend: str | None = None,
                   qat: bool = False):
    """feats: (B, F, C) → (per-frame logits (F, B, 12), stats).

    The DETECTION-mode forward: no mean-pooling — every 16 ms frame gets
    its own logit vector, exactly what the serving step's FC head
    computes per decision.  Same Δ-threshold/QAT semantics as
    ``forward`` (shared scaffolding: ``_gru_hidden``)."""
    hs, stats = _gru_hidden(params, cfg, feats, threshold, quantize_8b,
                            backend, qat)
    logits = hs @ params["w_fc"] + params["b_fc"]     # (F, B, 12)
    return logits, stats


def _edge_weights(labels: Array, smear_frames: int) -> Array:
    """(F, B) float32 label-smearing weights: 1 everywhere except within
    ``smear_frames`` frames of a label TRANSITION, where the weight is 0.

    Event onsets/offsets at frame granularity are arbitrary (an
    utterance's tails straddle the 16 ms grid), so hard targets at the
    edges teach the model to fight its own smoothing head — the standard
    fix the Hello Edge line of work assumes is to stop scoring the
    edge frames instead of pretending the boundary is exact."""
    if smear_frames <= 0:
        return jnp.ones(labels.shape, jnp.float32)
    edge = jnp.zeros(labels.shape, bool)
    edge = edge.at[1:].set(labels[1:] != labels[:-1])    # transition frames
    smeared = edge
    for k in range(1, smear_frames + 1):
        smeared = smeared.at[:-k].set(smeared[:-k] | edge[k:])
        smeared = smeared.at[k:].set(smeared[k:] | edge[:-k])
    return jnp.where(smeared, 0.0, 1.0)


def frame_loss_fn(params, cfg, batch: dict, threshold: float | None = None,
                  quantize_8b: bool = False, qat: bool = False, *,
                  loss_mode: str = "frame_ce", smear_frames: int = 0):
    """Detection-training loss over per-frame logits.

    batch: {"feats": (B, F, C), "frame_labels": (B, F) int32} — frame
    labels come from ``data.continuous.synth_frame_batch`` (the event's
    class during its span, silence elsewhere).  Training per frame is
    what calibrates the posterior trace the detection head smooths: a
    mean-pool-trained model is confidently wrong on noise frames
    (DESIGN.md §10).

    loss_mode:
      "frame_ce" (default): per-frame cross-entropy on every frame —
        the PR-5 recipe, unchanged bit-for-bit at ``smear_frames=0``.
      "maxpool": the max-pool detection loss the scenario matrix trains
        with (DESIGN.md §15).  Background (label 0) frames keep their
        per-frame CE, but each keyword occurrence is scored only at the
        frame where the model is MOST confident in the target class
        (per (row, class): the max-target-logit frame among the frames
        labeled with that class).  The model is free to place one sharp
        posterior peak anywhere inside the event instead of sustaining
        confidence across every frame of it — which is exactly what the
        hysteresis head detects, and what per-frame CE under noise
        punishes into mush.
    smear_frames: zero the loss weight of frames within this many frames
      of a label transition (label smearing at event edges; applies to
      both modes' frame-wise terms).
    """
    if loss_mode not in ("frame_ce", "maxpool"):
        raise ValueError(f"unknown loss_mode {loss_mode!r} "
                         f"(choose frame_ce / maxpool)")
    logits, stats = forward_frames(params, cfg, batch["feats"], threshold,
                                   quantize_8b, qat=qat)
    labels = jnp.moveaxis(batch["frame_labels"], 1, 0)   # (F, B)
    logp = jax.nn.log_softmax(logits)                    # (F, B, K)
    w = _edge_weights(labels, smear_frames)              # (F, B)
    frame_ce = -jnp.take_along_axis(logp, labels[..., None],
                                    axis=-1)[..., 0]     # (F, B)
    if loss_mode == "frame_ce":
        ce = jnp.sum(w * frame_ce) / jnp.maximum(jnp.sum(w), 1.0)
    else:
        n_classes = logits.shape[-1]
        bg = w * (labels == 0)
        bg_ce = jnp.sum(bg * frame_ce) / jnp.maximum(jnp.sum(bg), 1.0)
        # Per (row, class) max-pool: f*(b, k) = the frame with the
        # largest class-k logit among frames labeled k; CE is applied
        # to the full logit vector at that frame only.
        klass = jnp.arange(n_classes)
        owns = labels[..., None] == klass                # (F, B, K)
        cls_score = jnp.where(owns, logits, -jnp.inf)
        fstar = jnp.argmax(cls_score, axis=0)            # (B, K)
        b_ix = jnp.arange(labels.shape[1])[:, None]
        pooled_logp = jax.nn.log_softmax(logits[fstar, b_ix, :])  # (B, K, K)
        pooled_ce = -pooled_logp[:, klass, klass]        # (B, K)
        present = jnp.any(owns, axis=0) & (klass > 0)    # keywords only
        ev_ce = jnp.sum(jnp.where(present, pooled_ce, 0.0)) / \
            jnp.maximum(jnp.sum(present), 1)
        ce = bg_ce + ev_ce
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return ce, {"ce": ce, "acc": acc,
                "sparsity": dg.temporal_sparsity(stats)}


def loss_fn(params, cfg, batch: dict, threshold: float | None = None,
            quantize_8b: bool = False, qat: bool = False):
    logits, stats = forward(params, cfg, batch["feats"], threshold,
                            quantize_8b, qat=qat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return ce, {"ce": ce, "acc": acc,
                "sparsity": dg.temporal_sparsity(stats)}


def accuracy_11class(logits: Array, labels: Array) -> Array:
    """11-class GSCD metric [6]: 'unknown' (class 1) excluded."""
    keep = labels != 1
    logits11 = logits.at[:, 1].set(-jnp.inf)
    pred = jnp.argmax(logits11, -1)
    correct = jnp.where(keep, pred == labels, 0.0)
    return jnp.sum(correct) / jnp.maximum(jnp.sum(keep), 1)
