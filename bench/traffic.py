"""The one traffic generator: audio pools and stream layouts from a mix file.

The pool is audio: one float32 row of ``hop_samples`` samples per hop.
What a stream sends of it is the mix's ``input``.

A mix (``bench/traffic/<name>.json``) is data only:

  mode            "open": every stream's hop is due at each ``hop_ms``
                  boundary, phase-aligned across streams, whether or not
                  the server kept up; "drain": a backlog of stored audio,
                  fed as fast as the server retires ticks.
  streams         fleet size (open streams, one slot each).
  input           "fv" (the default when the key is absent): every
                  stream uploads the 16-channel FV_Norm frame of each
                  hop, as edge devices with the analog FEx do, made by
                  the reference's frontend from the pool's audio; "audio":
                  every stream sends its raw hop and the server runs the
                  frontend.
  audio           "speech": every stream carries speech-like audio;
                  "quiet": low-level noise with 1 s utterances whose
                  onsets arrive in bursts.
  tracks, track_hops   the pool: ``tracks`` seeded tracks of
                  ``track_hops`` hops each. Stream s plays track
                  ``track[s]`` from hop ``offset[s]`` on, wrapping.
  speech, quiet   synthesis parameters (levels, pitch, syllables,
                  bursts).
  ingress         PipelinedIngress depth and window.
  trace_ticks     ticks in the profiled slice of a traced run.
  check_streams   streams whose every hop is compared with the reference.

The same seed gives the same pool and layout. Every seed gives the same
number of streams, ticks and utterances; only where they fall changes.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np
from scipy import signal

DIR = pathlib.Path(__file__).resolve().parent / "traffic"
FS = 16000.0


def load(name: str) -> dict:
    with open(DIR / f"{name}.json") as f:
        return json.load(f)


def _smooth_walk(rng, n_tracks, n, step, lo, hi):
    """Slowly varying values in [lo, hi], one control point per ``step``."""
    pts = rng.uniform(lo, hi, (n_tracks, n // step + 2))
    x = np.arange(n) / step
    i = x.astype(np.int64)
    f = x - i
    return pts[:, i] * (1.0 - f) + pts[:, i + 1] * f


def speech(rng, n_tracks: int, n: int, p: dict) -> np.ndarray:
    """(n_tracks, n) speech-like audio at 16 kHz.

    A glottal pulse train at a drifting pitch through three formant
    resonators, plus a fricative noise band, under syllable envelopes
    (voiced nuclei with short gaps) at a per-track level.
    """
    f0 = _smooth_walk(rng, n_tracks, n, 4000, p["f0_lo"], p["f0_hi"])
    phase = np.cumsum(f0 / FS, axis=1)
    pulses = np.diff(np.floor(phase), axis=1, prepend=0.0)
    voiced = np.zeros_like(pulses)
    for formant, bw in zip(p["formants_hz"], p["formant_bw_hz"]):
        r = np.exp(-np.pi * bw / FS)
        w = 2.0 * np.pi * formant / FS
        voiced += signal.lfilter([1.0 - r], [1.0, -2.0 * r * np.cos(w), r * r],
                                 pulses, axis=1)
    noise = signal.lfilter([1.0, -0.95], [1.0], rng.standard_normal((n_tracks, n)),
                           axis=1)
    env = np.zeros((n_tracks, n))
    fric = np.zeros((n_tracks, n))
    for t in range(n_tracks):
        pos = int(rng.integers(0, int(0.1 * FS)))
        while pos < n:
            dur = int(rng.uniform(*p["syllable_s"]) * FS)
            seg = np.hanning(dur)[: max(0, min(dur, n - pos))]
            env[t, pos: pos + seg.size] = seg
            fric[t, pos: pos + seg.size] = seg * rng.uniform(0.0, p["fricative"])
            pos += dur + int(rng.uniform(*p["gap_s"]) * FS)
    x = env * voiced / (np.abs(voiced).max(axis=1, keepdims=True) + 1e-9)
    x += fric * noise / (np.abs(noise).max(axis=1, keepdims=True) + 1e-9)
    level = rng.uniform(*p["level"], (n_tracks, 1))
    return (level * x / (np.abs(x).max(axis=1, keepdims=True) + 1e-9)
            ).astype(np.float32)


def quiet(rng, n_tracks: int, n_hops: int, hop: int, p: dict,
          sp: dict) -> np.ndarray:
    """(n_tracks, n_hops * hop) of low-level noise with 1 s utterances.

    Onsets come in bursts every ``burst_every_hops``: at each burst the
    same number of tracks, ``round(burst_share * n_tracks)``, start an
    utterance after a jitter of up to ``onset_jitter_hops`` hops.
    """
    n = n_hops * hop
    x = (p["noise_level"] * rng.standard_normal((n_tracks, n))).astype(
        np.float32)
    u_len = p["utterance_hops"] * hop
    per_burst = int(round(p["burst_share"] * n_tracks))
    n_bursts = n_hops // p["burst_every_hops"]
    bank = speech(rng, per_burst * n_bursts, u_len, sp)
    fade = np.minimum(1.0, np.minimum(np.arange(u_len), np.arange(u_len)[::-1])
                      / (0.02 * FS)).astype(np.float32)
    u = 0
    for b in range(n_bursts):
        for t in rng.permutation(n_tracks)[:per_burst]:
            start = (b * p["burst_every_hops"]
                     + int(rng.integers(0, p["onset_jitter_hops"] + 1))) * hop
            idx = (start + np.arange(u_len)) % n
            x[t, idx] += bank[u] * fade
            u += 1
    return x


class Traffic:
    """A seeded pool and the layout of streams over it.

    ``rows(t)`` gives, per stream, the pool row holding its hop of tick
    ``t``; the harness copies those rows into the staged slab.
    """

    def __init__(self, mix: dict, hop: int, seed: int):
        rng = np.random.default_rng([seed, 1])
        n_tr, n_hops = mix["tracks"], mix["track_hops"]
        if mix["audio"] == "speech":
            audio = speech(rng, n_tr, n_hops * hop, mix["speech"])
            offsets = rng.integers(0, n_hops, mix["streams"])
        elif mix["audio"] == "quiet":
            audio = quiet(rng, n_tr, n_hops, hop, mix["quiet"], mix["speech"])
            # whole burst periods, so that bursts stay aligned across streams
            every = mix["quiet"]["burst_every_hops"]
            offsets = every * rng.integers(0, n_hops // every, mix["streams"])
        else:
            raise ValueError(f"unknown audio kind {mix['audio']!r}")
        self.pool = np.ascontiguousarray(audio.reshape(n_tr * n_hops, hop))
        self.n_hops = n_hops
        self.streams = mix["streams"]
        self.base = (rng.permutation(mix["streams"]) % n_tr) * n_hops
        self.offsets = offsets.astype(np.int64)

    def rows(self, t: int, streams=None) -> np.ndarray:
        base, off = self.base, self.offsets
        if streams is not None:
            base, off = base[streams], off[streams]
        return base + (off + t) % self.n_hops
