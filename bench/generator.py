"""The one traffic generator: audio from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only; this
module turns them into audio.  Two kinds of source, named by ``source``:

  utterances  a pool of 1 s GSCD-style clips: each class a two-formant
              trajectory with syllable-rate amplitude modulation, band
              noise and a mic floor, "silence" and "unknown" among them;
  streams     a pool of continuous streams: keywords placed left to
              right with exponential gaps into a white-noise bed at a set
              SNR below the mean keyword RMS.

Both follow the formant synthesis of the repository's data modules
(``data/gscd.py`` ``synth_batch``, ``data/continuous.py`` ``make_stream``),
copied here so that the benchmark's inputs cannot change with them.  The
same seed gives the same audio; every seed gives the same sizes.
"""
from __future__ import annotations

import numpy as np

FS = 8000

# (f1 start, f1 end, f2 start, f2 end, band noise, AM rate) per keyword.
KEYWORDS = {
    "down": (600, 300, 1800, 900, 0.02, 3.0),
    "go": (400, 600, 1000, 1400, 0.02, 2.0),
    "left": (500, 450, 1700, 2100, 0.03, 4.0),
    "no": (450, 650, 1200, 900, 0.02, 2.5),
    "off": (550, 350, 900, 1200, 0.04, 3.5),
    "on": (500, 700, 950, 1250, 0.03, 2.2),
    "right": (400, 520, 1900, 1500, 0.03, 4.5),
    "stop": (650, 380, 1500, 1100, 0.05, 5.0),
    "up": (350, 800, 1100, 1700, 0.02, 2.8),
    "yes": (480, 420, 2100, 1700, 0.03, 3.8),
}
CLASSES = ("silence", "unknown") + tuple(KEYWORDS)


def _clip(rng, spec, n: int, start: float, dur: float, floor: bool):
    """A keyword inside an n-sample window, peak-normalized to 0.3–0.9."""
    f1s, f1e, f2s, f2e, noise, am = spec
    t = np.arange(n) / FS
    env = np.exp(-0.5 * ((t - start - dur / 2) / (dur / 2.5)) ** 2)
    env *= 0.5 * (1 + np.cos(2 * np.pi * am * (t - start))) ** 0.7
    jitter = rng.uniform(0.9, 1.1)
    f1 = (f1s + (f1e - f1s) * (t - start) / dur) * jitter
    f2 = (f2s + (f2e - f2s) * (t - start) / dur) * jitter
    sig = env * (0.6 * np.sin(2 * np.pi * np.cumsum(f1) / FS)
                 + 0.4 * np.sin(2 * np.pi * np.cumsum(f2) / FS))
    sig += noise * rng.standard_normal(n)
    if floor:
        sig += 0.005 * rng.standard_normal(n)
    peak = np.max(np.abs(sig)) + 1e-9
    return (sig / peak * rng.uniform(0.3, 0.9)).astype(np.float32)


def utterances(rng: np.random.Generator, n: int, samples: int = FS
               ) -> np.ndarray:
    """(n, samples) float32 clips, classes drawn uniformly from 12."""
    labels = rng.integers(0, len(CLASSES), n)
    out = np.empty((n, samples), np.float32)
    for i, lb in enumerate(labels):
        name = CLASSES[lb]
        if name == "silence":
            out[i] = 0.01 * rng.standard_normal(samples)
        elif name == "unknown":
            spec = (rng.uniform(300, 800), rng.uniform(300, 800),
                    rng.uniform(900, 2200), rng.uniform(900, 2200),
                    rng.uniform(0.02, 0.06), rng.uniform(1.5, 6.0))
            out[i] = _clip(rng, spec, samples, rng.uniform(0.05, 0.3),
                           rng.uniform(0.3, 0.55), True)
        else:
            out[i] = _clip(rng, KEYWORDS[name], samples,
                           rng.uniform(0.05, 0.3), rng.uniform(0.3, 0.55),
                           True)
    return out


def stream(rng: np.random.Generator, samples: int, snr_db: float,
           events_per_min: float, min_gap_s: float) -> np.ndarray:
    """(samples,) float32: keywords with exponential gaps in a white bed."""
    audio = np.zeros(samples, np.float32)
    names = tuple(KEYWORDS)
    mean_gap_s = max(60.0 / max(events_per_min, 1e-6) - 0.45, 0.05)
    pos = int(rng.exponential(mean_gap_s) * FS)
    rms = []
    while True:
        spec = KEYWORDS[names[rng.integers(len(names))]]
        dur = float(rng.uniform(0.3, 0.55))
        n = int(round(dur * FS))
        utt = _clip(rng, spec, n, 0.0, dur, False)
        if pos + n > samples:
            break
        audio[pos:pos + n] += utt
        rms.append(float(np.sqrt(np.mean(utt ** 2))))
        pos += n + int((min_gap_s + rng.exponential(mean_gap_s)) * FS)
    ref = float(np.mean(rms)) if rms else 0.05
    bed = rng.standard_normal(samples)
    bed /= np.sqrt(np.mean(bed ** 2))
    audio += (ref / 10.0 ** (snr_db / 20.0) * bed).astype(np.float32)
    np.clip(audio, -1.0, 1.0 - 2.0 ** -11, out=audio)
    return audio


def pool(traffic: dict, seed: int, samples: int | None = None) -> np.ndarray:
    """The traffic's pool of distinct audio, (pool, samples) float32.

    ``utterances`` pools hold ``utterance_samples`` per clip; ``streams``
    pools are ``samples`` long (the driver sizes them to the window)."""
    rng = np.random.default_rng(seed)
    if traffic["source"] == "utterances":
        return utterances(rng, int(traffic["pool"]),
                          int(traffic["utterance_samples"]))
    if traffic["source"] == "streams":
        return np.stack([stream(rng, int(samples), traffic["snr_db"],
                                traffic["events_per_min"],
                                traffic["min_gap_s"])
                         for _ in range(int(traffic["pool"]))])
    raise ValueError(f"unknown traffic source {traffic['source']!r}")
