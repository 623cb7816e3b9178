"""Small cells for the CPU tests: the committed cells, cut to a few slots."""
import dataclasses
import time

from bench import harness, spec


def small_cell(name: str, **traffic) -> spec.Cell:
    cell = spec.find_cell(spec.load_benchmark(), name)
    t = dict(cell.traffic)
    if t["driver"] == "utterances":
        t.update(slots=8, pool=16)
    else:
        t.update(slots=8, pool=4, max_steps=128, warm_steps=8)
    t.update(traffic)
    return dataclasses.replace(cell, traffic=t)


def run_small(cell: spec.Cell, *, seed: int = 2 ** 33 + 5,
              seconds: float = 0.5, fault=None, devices=None) -> dict:
    import jax
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            devices or jax.devices(), fault=fault)
