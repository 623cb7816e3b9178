"""Kernel autotuning — sweep legal block configs, time them, cache winners.

The Pallas kernels in this package expose tiling knobs whose best values
depend on platform and shape, not on numerics:

  * ``delta_gru_seq`` / ``delta_gru_seq_int`` — ``block_b`` (batch tile)
    and ``block_t`` (frames per grid step: the kernel advances ``block_t``
    sequential frames inside one grid invocation, amortizing per-step
    grid overhead; the recurrence order is unchanged).
  * ``batched_iir_fex`` / ``batched_iir_fex_int`` — ``block_b`` and
    ``unroll`` (inner per-sample ``fori_loop`` unroll factor).

Every knob is NUMERICS-INVARIANT: batch rows are independent, and the
time tile / unroll execute the identical per-frame/per-sample op sequence
(asserted in tests/test_autotune.py against the default configs, bit for
bit, in both float and integer numerics).  The one carve-out: the FLOAT
FEx at ``block_b=1`` — XLA's elementwise codegen for a length-1 batch can
fuse multiply-adds differently, perturbing the carried biquad state by
1 ulp — so ``block_b=1`` is excluded from that kernel's candidate set
(the integer FEx is exact at every tile size).

The tuner times each candidate (interpret mode on CPU — the honest
number for this container — compiled on TPU/GPU) and persists the winner
in a JSON cache keyed on ``(kernel, shape, dtype, threshold-bucket,
platform)``.  The dispatch layers (``core.delta_gru.delta_gru_scan``,
``core.fixed_point.int_gru_scan``/``int_fex_scan``,
``frontend.fex.fex_scan``) consult the cache transparently at trace time
— a ``StreamingKwsSession`` therefore picks tuned configs up when its
step compiles, with the static defaults as the cold-cache fallback, so
behavior is unchanged until someone tunes.  Lookups NEVER raise: a
missing, corrupt, or stale-schema cache silently resolves to "no entry".

Cache environment knobs:

  * ``REPRO_AUTOTUNE_CACHE`` — cache file path (default
    ``<checkout>/.autotune/autotune.json``).
  * ``REPRO_AUTOTUNE=0`` — disable cache consultation entirely (tuned
    entries are ignored; recording still works).

Threshold bucketing: Δ_TH changes temporal sparsity and therefore the
relative cost of the delta branches, so keys carry the threshold rounded
to the 0.1 grid (clipped to [0, 1]); a traced/non-concrete threshold
falls back to bucket 0.0 — a timing-only approximation, never a
numerics one.  The time axis is deliberately NOT part of the key: a
config's per-frame cost is T-invariant, and keying on T would fragment
the cache across chunk lengths; ``block_t`` is applied only when it
divides the chunk actually being run (see ``resolve``).
"""
from __future__ import annotations

import json
import logging
import os
import pathlib
import time
from typing import Any

from repro.kernels.platform import REPO_ROOT, resolve_interpret

SCHEMA_VERSION = 1
ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
ENV_ENABLE = "REPRO_AUTOTUNE"
_DEFAULT_CACHE = REPO_ROOT / ".autotune" / "autotune.json"

# Mosaic's block rule: each of a block's last two dims is a multiple of
# the (8 sublane, 128 lane) tiling or the whole array dim.
LANES = 128
# Scoped-VMEM share a default batch tile may fill (the v5e default
# scoped limit is 16 MiB; the rest is headroom for kernel temporaries).
VMEM_TILE_BUDGET_BYTES = 12 * 2 ** 20

_log = logging.getLogger(__name__)

# In-memory memo of the parsed cache file, invalidated on (path, mtime)
# change so a tune in the same process is visible to later lookups.
_memo: dict[str, Any] = {"stamp": None, "entries": {}}


# ------------------------------------------------------------ legality
def legal_block_b(B: int, compiled: bool = False) -> list[int]:
    """All legal batch-tile sizes for a batch of ``B`` slots.

    Every tile divides ``B``.  A compiled (Mosaic) kernel carries the
    slot axis on the LANE dim of some block — the FEx audio/state/
    feature blocks and the ΔGRU transmit counts — so a compiled tile is
    ``B`` itself or a multiple of 128.  The interpreter takes any divisor.
    """
    return [d for d in range(1, B + 1) if B % d == 0
            and (not compiled or d == B or d % LANES == 0)]


def validate_block_b(kernel: str, B: int, block_b: int | None, *,
                     interpret: bool | None = None,
                     slot_bytes: int = 0, fixed_bytes: int = 0) -> int:
    """Resolve/validate a batch tile against ``legal_block_b``.

    ``None`` picks the default: one tile (``B``) under the interpreter;
    compiled, the largest legal tile whose VMEM footprint —
    ``fixed_bytes`` held whatever the tile, plus ``slot_bytes`` per slot
    — fits ``VMEM_TILE_BUDGET_BYTES`` (the smallest legal tile when none
    fits).  Raises ``ValueError`` naming the kernel, ``B`` and
    the offending ``block_b`` — instead of the opaque grid/BlockSpec
    error Pallas or Mosaic produces.
    """
    compiled = not resolve_interpret(interpret)
    legal = legal_block_b(B, compiled)
    if block_b is None:
        if not compiled:
            return B
        fits = [d for d in legal
                if fixed_bytes + d * slot_bytes <= VMEM_TILE_BUDGET_BYTES]
        return max(fits) if fits else min(legal)
    if (isinstance(block_b, bool) or not isinstance(block_b, int)
            or block_b < 1 or B % block_b != 0):
        raise ValueError(
            f"{kernel}: block_b={block_b!r} is not a positive divisor of "
            f"the batch dimension B={B} (legal values: {legal})")
    if block_b not in legal:
        raise ValueError(
            f"{kernel}: block_b={block_b!r} is not a compiled batch tile "
            f"for B={B}: Mosaic needs B itself or a multiple of {LANES} "
            f"(legal values: {legal})")
    return block_b


def validate_divisor(kernel: str, name: str, value: int | None,
                     axis: str, n: int, default: int = 1) -> int:
    """Shared validation for the other tiling knobs (block_t, unroll) —
    any positive divisor is legal, compiled or interpreted."""
    if value is None:
        return default
    if (isinstance(value, bool) or not isinstance(value, int)
            or value < 1 or n % value != 0):
        raise ValueError(
            f"{kernel}: {name}={value!r} is not a positive divisor of "
            f"{axis}={n}")
    return value


# ------------------------------------------------------------ cache I/O
def cache_path() -> pathlib.Path:
    return pathlib.Path(
        os.environ.get(ENV_CACHE) or _DEFAULT_CACHE).expanduser()


def autotune_enabled() -> bool:
    return os.environ.get(ENV_ENABLE, "1").lower() not in ("0", "false",
                                                           "no")


def clear_memo() -> None:
    """Drop the in-memory cache memo (tests / after env changes)."""
    _memo["stamp"] = None
    _memo["entries"] = {}


def _load_entries() -> dict:
    """Parsed cache entries; {} on ANY problem (missing/corrupt/stale).

    Never raises — a broken cache file must degrade to the static
    defaults, not take the serving path down.
    """
    path = cache_path()
    try:
        stamp = (str(path), path.stat().st_mtime_ns)
    except OSError:
        return {}
    if _memo["stamp"] == stamp:
        return _memo["entries"]
    try:
        blob = json.loads(path.read_text())
        if not isinstance(blob, dict) or blob.get("schema") != SCHEMA_VERSION:
            raise ValueError(f"schema {blob.get('schema')!r} != "
                             f"{SCHEMA_VERSION}")
        entries = blob["entries"]
        if not isinstance(entries, dict):
            raise ValueError("entries is not a mapping")
    except Exception as e:                       # corrupt / stale / unreadable
        _log.warning("autotune cache %s unusable (%s); using defaults",
                     path, e)
        entries = {}
    _memo["stamp"] = stamp
    _memo["entries"] = entries
    return entries


def threshold_bucket(threshold) -> float:
    """Δ_TH → the 0.1-grid bucket used in cache keys (see module doc)."""
    try:
        th = float(threshold)
    except Exception:                # traced value inside jit — see module doc
        return 0.0
    return min(max(round(th * 10.0) / 10.0, 0.0), 1.0)


def platform_tag(interpret: bool | None = None) -> str:
    import jax
    mode = "interpret" if resolve_interpret(interpret) else "compiled"
    return f"{jax.default_backend()}-{mode}"


def cache_key(kernel: str, shape: tuple[int, ...], dtype: str,
              threshold, interpret: bool | None = None) -> str:
    return "|".join([kernel, "x".join(str(int(d)) for d in shape),
                     str(dtype), f"th{threshold_bucket(threshold):g}",
                     platform_tag(interpret)])


def lookup(kernel: str, shape: tuple[int, ...], dtype: str, threshold,
           interpret: bool | None = None) -> dict | None:
    """Raw cache hit for a key, or None.  Never raises."""
    entry = _load_entries().get(cache_key(kernel, shape, dtype, threshold,
                                          interpret))
    if not isinstance(entry, dict):
        return None
    cfg = entry.get("config")
    return dict(cfg) if isinstance(cfg, dict) else None


def record(kernel: str, shape: tuple[int, ...], dtype: str, threshold,
           config: dict, *, tuned_us: float, default_us: float,
           interpret: bool | None = None) -> str:
    """Persist a tuned winner (atomic write: tmp file + rename)."""
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    try:
        blob = json.loads(path.read_text())
        assert blob.get("schema") == SCHEMA_VERSION
        entries = dict(blob["entries"])
    except Exception:
        entries = {}
    key = cache_key(kernel, shape, dtype, threshold, interpret)
    entries[key] = {
        "config": {k: int(v) for k, v in config.items()},
        "tuned_us": float(tuned_us), "default_us": float(default_us),
        "speedup": float(default_us / tuned_us) if tuned_us else None,
        "recorded_unix": time.time(),
    }
    # Writer-unique tmp name: concurrent tuners (separate processes
    # sharing one cache file) must never interleave writes into the
    # same tmp file — each stages its own complete blob and the atomic
    # rename makes last-writer-wins the worst case, never corruption.
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    try:
        tmp.write_text(json.dumps({"schema": SCHEMA_VERSION,
                                   "entries": entries}, indent=2) + "\n")
        tmp.replace(path)
    finally:
        tmp.unlink(missing_ok=True)
    clear_memo()
    return key


def resolve(kernel: str, shape: tuple[int, ...], dtype: str, threshold, *,
            interpret: bool | None = None, B: int | None = None,
            T: int | None = None, frame_shift: int | None = None) -> dict:
    """Dispatch-side consult: the tuned config SANITIZED for this call.

    Drops any knob that is illegal for the current invocation (a
    ``block_b`` outside ``legal_block_b`` for this ``B`` and execution
    mode, a ``block_t`` that does not divide this chunk's ``T``, an
    ``unroll`` that does not divide ``frame_shift``) and the float-FEx
    ``block_b=1`` carve-out, so a cache tuned at one geometry can never
    produce an error — at worst a knob falls back to its static default.
    Returns {} when autotuning is disabled or there is no entry.  Never
    raises.
    """
    if not autotune_enabled():
        return {}
    cfg = lookup(kernel, shape, dtype, threshold, interpret)
    if not cfg:
        return {}
    out = {}
    bb = cfg.get("block_b")
    if (isinstance(bb, int) and B
            and bb in legal_block_b(B, not resolve_interpret(interpret))):
        if not (kernel == "batched_iir_fex" and bb == 1):
            out["block_b"] = bb
    bt = cfg.get("block_t")
    if isinstance(bt, int) and T and T % bt == 0 and bt >= 1:
        out["block_t"] = bt
    un = cfg.get("unroll")
    if (isinstance(un, int) and frame_shift and frame_shift % un == 0
            and un >= 1):
        out["unroll"] = un
    return out


# --------------------------------------------------------------- timing
def _time_us(fn, iters: int = 3, warmup: int = 1) -> float:
    import jax
    import numpy as np
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _block_b_candidates(B: int, *, interpret: bool | None = None,
                        exclude_one: bool = False) -> list[int]:
    cands = [d for d in legal_block_b(B, not resolve_interpret(interpret))
             if d == B or d in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)]
    if exclude_one and len(cands) > 1:
        cands = [d for d in cands if d != 1]
    return cands


def _tile_candidates(n: int, cap: int = 32) -> list[int]:
    """All divisors of ``n`` up to ``cap`` (∪ {n} when n <= cap).

    Not just powers of two: the bench workloads have T=100-ish frame
    counts whose best tile is often 10 or 20 — a pow2-only grid cannot
    even express the winner."""
    cands = [d for d in range(1, min(n, cap) + 1) if n % d == 0]
    if n <= cap and n not in cands:
        cands.append(n)
    return cands


def _greedy_sweep(time_config, default_cfg: dict,
                  axes: list[tuple[str, list[int]]]) -> dict:
    """Tune one axis at a time, holding winners fixed — |axes| · |cands|
    timings instead of the full cross product.  Returns the report."""
    sweep = []
    best_cfg = dict(default_cfg)
    default_us = time_config(default_cfg)
    sweep.append(dict(default_cfg, us=default_us, role="default"))
    best_us = default_us
    for name, cands in axes:
        for v in cands:
            cfg = dict(best_cfg, **{name: v})
            if cfg == best_cfg or cfg == default_cfg:
                continue
            us = time_config(cfg)
            sweep.append(dict(cfg, us=us, role="candidate"))
            if us < best_us:
                best_us, best_cfg = us, cfg
    return {"default_config": default_cfg, "default_us": default_us,
            "best_config": best_cfg, "best_us": best_us,
            "speedup": default_us / best_us if best_us else None,
            "sweep": sweep}


# --------------------------------------------------------------- tuners
def tune_delta_gru_seq(*, T: int = 100, B: int = 8, I: int = 64,
                       H: int = 64, threshold: float = 0.2,
                       variant: str = "float", iters: int = 3,
                       interpret: bool | None = None, write: bool = True,
                       seed: int = 0) -> dict:
    """Sweep (block_t, block_b) for the fused ΔGRU sequence kernel.

    ``variant="float"`` times ``delta_gru_seq``; ``"int"`` times the
    promoted int8 path through ``fixed_point.int_gru_scan`` (packed dot
    included — the config is tuned for what serving actually runs).
    Records the winner under the dispatch's cache key and returns the
    full before/after report.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import delta_gru as dg

    p = dg.init_delta_gru(jax.random.PRNGKey(seed), I, H)
    xs = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, B, I),
                           jnp.float32) * 0.5
    s0 = dg.init_delta_state(B, I, H, p)

    if variant == "float":
        from repro.kernels.delta_gru_seq import delta_gru_seq
        kernel, dtype = "delta_gru_seq", "float32"

        def time_config(cfg):
            return _time_us(lambda: delta_gru_seq(
                xs, s0.h, s0.x_hat, s0.h_hat, s0.m_x, s0.m_h,
                p.w_x, p.w_h, threshold, interpret=interpret, **cfg),
                iters=iters)
    elif variant == "int":
        from repro.core import fixed_point as fp
        kernel, dtype = "delta_gru_seq_int", "int8"
        w, fmt = fp.quantize_gru(p)
        xs_codes = fp.to_code(xs, fmt.feat_frac, 16, jnp.int16)
        si = fp.init_int_delta_state(B, I, H, w)

        def time_config(cfg):
            return _time_us(lambda: fp.int_gru_scan(
                w, fmt, xs_codes, threshold, state=si, backend="pallas",
                interpret=interpret, **cfg), iters=iters)
    else:
        raise ValueError(f"unknown ΔGRU tune variant: {variant!r}")

    report = _greedy_sweep(
        time_config, {"block_t": 1},
        [("block_t", _tile_candidates(T)),
         ("block_b", _block_b_candidates(B, interpret=interpret))])
    report.update(kernel=kernel, shape=[B, I, H], dtype=dtype, T=T,
                  threshold=threshold, platform=platform_tag(interpret))
    if write:
        report["cache_key"] = record(
            kernel, (B, I, H), dtype, threshold, report["best_config"],
            tuned_us=report["best_us"], default_us=report["default_us"],
            interpret=interpret)
    return report


def tune_batched_iir_fex(*, B: int = 8, seconds: float = 0.5,
                         variant: str = "float", iters: int = 3,
                         interpret: bool | None = None, write: bool = True,
                         seed: int = 0, fex_cfg=None) -> dict:
    """Sweep (unroll, block_b) for the sequence-resident FEx kernel.

    Uses the repo's deployed filterbank geometry (``FExConfig`` defaults:
    10 active channels, 128-sample frames) unless ``fex_cfg`` overrides.
    """
    import jax
    import jax.numpy as jnp
    from repro.frontend.fex import FExConfig, build_sos_bank
    from repro.kernels.iir_fex import (init_fex_kernel_state,
                                       pack_coefficients)

    cfg = fex_cfg or FExConfig()
    coef = pack_coefficients(build_sos_bank(cfg))
    C, fs = coef.shape[1], int(cfg.fs)
    n = int(fs * seconds)
    audio = (jax.random.normal(jax.random.PRNGKey(seed), (B, n),
                               jnp.float32) * 0.1)
    frame_shift = cfg.frame_shift

    if variant == "float":
        from repro.kernels.iir_fex import batched_iir_fex
        kernel, dtype = "batched_iir_fex", "float32"
        state = init_fex_kernel_state(B, C)

        def time_config(c):
            return _time_us(lambda: batched_iir_fex(
                audio, coef, state, frame_shift=frame_shift,
                env_alpha=cfg.env_alpha, log_eps=cfg.log_eps,
                interpret=interpret, **c), iters=iters)
    elif variant == "int":
        from repro.core import fixed_point as fp
        from repro.frontend.fex import sos_formats
        from repro.kernels.iir_fex import batched_iir_fex_int
        kernel, dtype = "batched_iir_fex_int", "int16"
        bank = build_sos_bank(cfg)
        b_fmt, a_fmt = sos_formats(bank, cfg.b_bits, cfg.a_bits)
        codes, ffmt = fp.quantize_fex(coef, cfg.env_alpha, b_fmt.frac_bits,
                                      a_fmt.frac_bits, log_eps=cfg.log_eps)
        audio_codes = fp.to_code(audio, ffmt.feat_frac, 16, jnp.int16)
        state = fp.init_int_fex_state(B, C)

        def time_config(c):
            return _time_us(lambda: batched_iir_fex_int(
                audio_codes, codes, state, fmt=ffmt,
                frame_shift=frame_shift, interpret=interpret, **c),
                iters=iters)
    else:
        raise ValueError(f"unknown FEx tune variant: {variant!r}")

    report = _greedy_sweep(
        time_config, {"unroll": 1},
        [("unroll", _tile_candidates(frame_shift, cap=16)),
         ("block_b", _block_b_candidates(
             B, interpret=interpret, exclude_one=variant == "float"))])
    report.update(kernel=kernel, shape=[B, C, frame_shift], dtype=dtype,
                  seconds=seconds, threshold=0.0,
                  platform=platform_tag(interpret))
    if write:
        report["cache_key"] = record(
            kernel, (B, C, frame_shift), dtype, 0.0, report["best_config"],
            tuned_us=report["best_us"], default_us=report["default_us"],
            interpret=interpret)
    return report
