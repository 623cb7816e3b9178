#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration, traffic and limits by name, makes the
weights and audio from ``--seed``, builds the program's serving session
and warms every program the window runs (set-up), serves for
``--seconds`` through the program's own loop, then checks what the loop
produced against the plain reference.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window and reports its
per-layer metrics.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and ``checks``: each number compared beside
its limit, which standard error repeats as its last lines).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, when the program is not in the checkout, or when
a step fails.  JAX's compilation cache is ``$JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


class NoChip(RuntimeError):
    """JAX runs on no TPU, or on fewer chips than the cell needs."""


def devices_for(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform!r}, not a TPU; the "
                     f"benchmark does not fall back")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    # The TPU runtime otherwise logs under a fixed /tmp path.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise harness.RunError(f"no program under {ROOT / 'src'}")
        cell = spec.find_cell(spec.load_benchmark(ROOT), args.workload, ROOT)
        devices = devices_for(cell.chips)
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START, devices)
    except (NoChip, harness.RunError, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"setup: {line.pop('setup_phases')}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
