#!/usr/bin/env python3
"""Readings of the comparison's control, which the limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--steps N]

The control is the plain reference put in the program's place, computed
one precision below what the configuration states: float32 contractions
in three bfloat16 passes ("high") where the configuration states HIGHEST,
int4 weights where it states int8.  For each seed this makes the cell's
weights and traffic as a run does, at the cell's own size, and prints the
numbers a run compares (``bench/limits/<cell>.json``) with the control's
outputs in place of the program's.  A limit lies between the largest
reading of sound runs and the smallest reading of the control; a control
that stays within every limit means the comparison cannot see the step
that would tempt a later change.

Runs on whatever JAX finds (the chip, for the readings in PERF.md).  The
streams cells take ``--steps`` frames per slot (default: ``max_steps``
of the traffic file, rounded to whole reference blocks).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def control_kwargs(config: dict) -> dict:
    """The precision one step below the configuration's."""
    if config["numerics"] == "int8":
        return {"weight_bits": 4}
    return {"precision": "high"}


def readings(cell, seed: int, steps: int | None = None) -> dict:
    """The cell's compared numbers with the control as the program."""
    from bench import generator, model
    from bench.harness import Reference
    t = cell.traffic
    params = model.make_params(cell.config, seed)
    ref = Reference(cell, params)
    ctl = Reference(cell, params, **control_kwargs(cell.config))
    if t["driver"] == "utterances":
        pool = generator.pool(t, seed)
        chunk = int(t["chunk_samples"])
        per = -(-int(t["utterance_samples"]) // chunk) * chunk
        audio = np.zeros((len(pool), per), np.float32)
        audio[:, :pool.shape[1]] = pool
        R = int(t["utterance_samples"]) // int(cell.config["fex"]["frame_shift"])
        rv, cv = ref.votes(audio)[:R], ctl.votes(audio)[:R]
        K = int(cell.config["classes"])

        def dec(v):
            return np.array([np.bincount(v[:, p], minlength=K).argmax()
                             for p in range(v.shape[1])])

        clips = audio[np.arange(int(t["slots"])) % len(audio)]
        tails = np.zeros(len(clips), np.int64)
        gap = np.max(np.abs(ctl.final_hidden(clips, chunk, tails)
                            - ref.final_hidden(clips, chunk, tails)), -1)
        return {"unanswered": 0,
                "decision_mismatch_pct": 100.0 * float(np.mean(dec(cv) != dec(rv))),
                "vote_mismatch_pct": 100.0 * float(np.mean(cv != rv)),
                "state_gap": float(np.median(gap))}
    from bench import spec
    streams = spec.driver(cell)
    slots, chunk = int(t["slots"]), int(t["chunk_samples"])
    block = int(t["block_frames"])
    steps = steps or int(t["max_steps"])
    n_samples = -(-steps // block) * block * chunk
    stride = int(t["offset_stride"])
    pool = generator.pool(t, seed, samples=n_samples
                          + (slots // int(t["pool"])) * stride)
    views = streams.views(pool, slots, stride)
    ev_r, h_r = ref.detect(views, n_samples)
    ev_c, h_c = ctl.detect(views, n_samples)

    def fires(ev):
        return {(s, int(f), int(ev[f, s])) for f, s in zip(*np.nonzero(ev >= 0))}

    fr, fc = fires(ev_r), fires(ev_c)
    return {"missing_frames": 0,
            "fire_mismatch_pct": 100.0 * len(fr ^ fc) / max(len(fr) + len(fc), 1),
            "state_gap": float(np.median(np.max(np.abs(h_c - h_r), -1))),
            "reference_fires": len(fr)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import spec
    cell = spec.find_cell(spec.load_benchmark(ROOT), args.workload, ROOT)
    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.steps)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": control_kwargs(cell.config),
                          "device": dev.device_kind, "readings": r}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
