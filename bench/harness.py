"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is everything after the look for a chip, so the CPU tests
drive it with small traffic.  The order is fixed by what each step may
disturb: the window runs with nothing else on the device; the device's
peak memory is read before the reference runs; the session is dropped
before the reference, which runs in blocks so that it fits.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time

import numpy as np

from bench import costs, model, probes, spec
from bench import trace as trace_mod


class RunError(RuntimeError):
    """The run cannot produce a result line."""


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    params: dict
    mesh: object = None
    marks: list = dataclasses.field(default_factory=list)

    def mark(self, name: str):
        """Note the end of a set-up phase (printed to standard error)."""
        self.marks.append((name, time.perf_counter()))

    def session(self, *, slots: int, detect: bool = False):
        """The program's serving session for this cell's configuration."""
        from repro.configs import get_config
        from repro.frontend import FeatureExtractor
        from repro.launch.streaming import StreamingKwsSession
        cfg = self.cell.config
        arch = get_config(cfg["model"])
        fex = FeatureExtractor()
        _check_program_config(cfg, arch, fex.cfg)
        kw = {}
        if detect:
            from repro.frontend.vad import VADConfig
            from repro.models.detector import DetectorConfig
            kw = dict(detector=DetectorConfig(**cfg["detector"]),
                      vad=VADConfig(**cfg["vad"]))
        return StreamingKwsSession(
            self.params, arch, threshold=float(cfg["delta_threshold"]),
            batch=slots, fex=fex, mesh=self.mesh,
            numerics="int8" if cfg["numerics"] == "int8" else "float32", **kw)


def _check_program_config(cfg: dict, arch, fcfg):
    """The program must serve the sizes this configuration file states."""
    f = cfg["fex"]
    pairs = {
        "layers": (arch.num_layers, model.layers(cfg)),
        "cell": (getattr(arch, "cell", "gru"), model.cell(cfg)),
        "hidden": (arch.d_model, cfg["hidden"]),
        "classes": (arch.vocab_size, cfg["classes"]),
        "sample_rate_hz": (fcfg.fs, cfg["sample_rate_hz"]),
        "fex.n_channels": (fcfg.n_channels, f["n_channels"]),
        "fex.fmin_hz": (fcfg.fmin, f["fmin_hz"]),
        "fex.fmax_hz": (fcfg.fmax, f["fmax_hz"]),
        "fex.selection": (list(fcfg.selection), f["selection"]),
        "fex.frame_shift": (fcfg.frame_shift, f["frame_shift"]),
        "fex.env_tau_s": (fcfg.env_tau_s, f["env_tau_s"]),
        "fex.log_eps": (fcfg.log_eps, f["log_eps"]),
        "fex.b_bits": (fcfg.b_bits, f["b_bits"]),
        "fex.a_bits": (fcfg.a_bits, f["a_bits"]),
        "input_dim": (fcfg.n_active, cfg["input_dim"]),
    }
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise RunError(f"the program's configuration differs from "
                       f"{cfg['name']}: {bad} (program, file)")


class Reference:
    """The plain reference of a configuration, run in blocks."""

    def __init__(self, cell: spec.Cell, params: dict, **control):
        import jax
        self._mod = spec.reference(cell)
        host = {k: np.asarray(jax.device_get(v)) for k, v in params.items()}
        self.model, self.static = self._mod.build(cell.config, host,
                                                  **control)
        self.block_frames = int(cell.traffic.get("block_frames", 64))

    def _run(self, state, audio, detect: bool):
        import jax.numpy as jnp
        return self._mod.block(self.model, state, jnp.asarray(audio),
                               st=self.static, detect=detect)

    def votes(self, audio: np.ndarray) -> np.ndarray:
        """(frames, B) votes of clips that each start from a fresh state."""
        st0 = self._mod.init_state(self.model, self.static, len(audio))
        _, out = self._run(st0, audio, False)
        return np.asarray(out.votes)

    def final_hidden(self, clips: np.ndarray, chunk: int,
                     tails: np.ndarray) -> np.ndarray:
        """Hidden state of each row after its clip and ``tails[row]``
        further chunks of silence (zeros), as values."""
        state = self._mod.init_state(self.model, self.static, len(clips))
        state, _ = self._run(state, clips, False)
        out = self._hidden(state)
        zeros = np.zeros((len(clips), chunk), np.float32)
        for t in range(1, int(tails.max(initial=0)) + 1):
            state, _ = self._run(state, zeros, False)
            out = np.where((tails == t)[:, None], self._hidden(state), out)
        return out

    def _hidden(self, state) -> np.ndarray:
        """Every layer's hidden state, (B, L·H), the first layer's first."""
        h = np.concatenate([np.asarray(h) for h in state.h],
                           axis=-1).astype(np.float64)
        return h * 2.0 ** -15 if self.static.numerics == "int8" else h

    def detect(self, views: list, n_samples: int):
        """Always-on mode over per-slot audio: (events (F, B), final
        hidden values (B, L·H))."""
        B = len(views)
        block = self.block_frames * self.static.frame_shift
        state = self._mod.init_state(self.model, self.static, B)
        events = []
        for off in range(0, n_samples, block):
            audio = np.stack([v[off:off + block] for v in views])
            state, out = self._run(state, audio, True)
            events.append(np.asarray(out.events))
        return np.concatenate(events), self._hidden(state)


def _judge(checks: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct iff all within."""
    shown = {}
    ok = True
    for name, limit in limits["limits"].items():
        if name not in checks:
            raise RunError(f"no number {name!r} from the check")
        value = checks[name]
        shown[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, shown


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list, *, fault=None) -> dict:
    """Set up, measure, check; the result line's dict.  ``fault`` (tests
    only) is called with the session before the window to break it."""
    import jax
    marks = [("start", t_start), ("imports", time.perf_counter())]
    drv = spec.driver(cell)
    params = model.make_params(cell.config, seed)
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_slot_mesh
        mesh = make_slot_mesh(cell.chips)
    run = Run(cell=cell, seed=seed, seconds=seconds, params=params,
              mesh=mesh, marks=marks)
    run.mark("weights")
    prep = drv.prepare(run)
    if fault is not None:
        fault(prep.session)
    setup_s = time.perf_counter() - t_start
    phases = " ".join(f"{n}={t - t0:.3f}s" for (_, t0), (n, t)
                      in zip(run.marks, run.marks[1:]))

    logdir = cell.root / ".bench_out" / "trace" / cell.name
    counter = probes.CompileCounter.get()
    if trace:
        shutil.rmtree(logdir, ignore_errors=True)
        # Host spans are the benchmark's annotations and the runtime's
        # own; the Python tracer (an event per function call) would slow
        # the host loop it is meant to observe.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(logdir), profiler_options=options)
    counter.arm()
    try:
        with probes.annotation(probes.SPAN_WINDOW):
            served = drv.serve(prep, run)
    finally:
        compiles = counter.disarm()
        if trace:
            jax.profiler.stop_trace()
    if compiles:
        raise RunError(f"{len(compiles)} trace/compile events inside the "
                       f"window: {sorted(set(compiles))}")
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                          0))
                      for d in devices[:cell.chips])
    h_final = drv.final_state(prep)
    prep.session = None
    prep.probe = None
    gc.collect()

    ref = Reference(cell, params)
    checks = drv.verify(prep, served, h_final, ref, run)
    numbers, attempted, failed = checks
    correct, shown = _judge(numbers, cell.limits)

    metrics = {}
    line = {"correct": correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": memory_peak}}
    if not trace:
        e2e = {"setup_s": setup_s,
               "rt_streams": served["audio_s"] / served["window_s"],
               "decision_p99_ms": served["report"]["e2e_ms"]["p99"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        summary = trace_mod.reduce_dir(logdir, n_devices=cell.chips)
        record = RunRecord(cell=cell, served=served, trace=summary,
                           work=costs.StepWork.of(cell, served),
                           peaks=costs.peaks_for(devices[0].device_kind))
        for m in cell.per_layer:
            value = spec.reader(cell, m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["device"]["busy_s"] = summary.busy_s
        line["device"]["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = shown
    line["setup_phases"] = phases
    return line


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric's reader reads."""
    cell: spec.Cell
    served: dict                  # the driver's window record
    trace: "trace_mod.Summary"
    work: "costs.StepWork"
    peaks: dict
