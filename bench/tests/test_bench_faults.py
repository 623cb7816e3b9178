"""The check catches a broken timed path, and the control.

Each fault breaks the program's fused step underneath a whole run (the
look for a chip skipped, everything else as on the chip) and the run must
come out not correct: a step that returns its state unchanged, half of
the slots left out (their results copied from the other half), and an
answer altered where it is produced.  The cells have no exchange between
chips to leave out: weights are replicated and slots are independent.

The control (the reference one precision below the configuration's, put
in the program's place) must fail the same limits at a small size.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness
from bench.tests.small import run_small, small_cell


def _state_unchanged(out, args):
    n_state = len(out) - 3                  # (states..., acc, out, health)
    return tuple(args[4:4 + n_state]) + tuple(out[n_state:])


def _answer_altered(out, args):
    res = out[-2]
    if hasattr(res, "events"):
        ev = res.events.at[:, 0].set(jnp.where(res.events[:, 0] < 0, 2, -1))
        res = res._replace(events=ev)
    else:
        res = res._replace(votes=res.votes.at[:, 0].set(
            (res.votes[:, 0] + 1) % 12))
    return tuple(out[:-2]) + (res, out[-1])


FAULTS = {"state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered,
          "half_batch": None}


def _breaker(fault: str, chunk: int):
    def apply(session):
        step = session._audio_step

        def broken(*args):
            if fault == "half_batch":
                audio = args[-1]
                half = audio[:audio.shape[0] // 2]
                return step(*args[:-1], jnp.concatenate([half, half]))
            return FAULTS[fault](step(*args), args)

        broken = jax.jit(broken)
        block = jnp.zeros((session.batch, chunk), jnp.float32)
        jax.block_until_ready(broken(*session._audio_step_args(block)))
        session._audio_step = broken
    return apply


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["int8-utt-c4096", "f32-utt-c4096",
                                  "int8-live-c128"])
def test_a_broken_timed_path_is_not_correct(name, fault):
    cell = small_cell(name)
    line = run_small(cell, fault=_breaker(fault,
                                          cell.traffic["chunk_samples"]))
    assert line["correct"] is False, (fault, line["checks"])


@pytest.mark.parametrize("name", ["int8-utt-c4096", "f32-utt-c4096",
                                  "int8-live-c128"])
def test_the_control_is_not_correct(name):
    cell = small_cell(name, slots=64, pool=128) if "utt" in name else \
        small_cell(name, slots=32, pool=8, max_steps=512)
    for seed in (1, 2, 3):
        numbers = control.readings(cell, seed)
        correct, shown = harness._judge(numbers, cell.limits)
        assert correct is False, (seed, shown)
