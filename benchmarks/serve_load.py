"""Serving load generator: fused jitted tick vs the pre-refactor path.

Sweeps classifier backend x `max_streams` x occupancy x input kind over
the streaming KWS server and measures sustained tick throughput and
per-tick latency for:

  * ``fused``  — the current `StreamingKWSServer.step_batch`: one
    jit-compiled device program per tick (frontend + GRU + softmax +
    smoothing) over donated `ServerState` buffers, slab-in/slab-out;
  * ``legacy`` — a faithful copy of the pre-refactor `step`: separate
    jitted feature / GRU dispatches, host-side carry masking via
    tree_map, and a per-stream Python loop doing numpy softmax + score
    smoothing;
  * ``scan``   — the offline `run_batch` lax.scan replay (whole tick
    sequence as one device program; the replay returns to the host
    once, so there are NO per-tick latencies — scan rows report
    sustained throughput only, with ``p50_ms``/``p99_ms`` = null),
    swept for both kinds. The scan-fv point at 256 streams is what the
    headline claim below gates on;
  * ``pipelined`` — the live async ingress
    (`repro.serving.ingress.PipelinedIngress` over
    `step_batch_async`/`run_batch_async`): double-buffered host slab
    staging, non-blocking dispatch, deferred score fetch, and a
    ``window``-tick coalescing scan dispatch. Unlike scan this is a
    LIVE mode — every tick's submit-to-scores latency is measured
    (handle retirement timestamps), so its rows carry real p50/p99,
    and the SLO block below gates on them.

Input kinds: ``fv`` ticks carry precomputed FV_Norm frames (isolates
the serving-path overhead the fused tick removes); ``audio`` ticks
carry raw 16 ms hops (adds the frontend filter scan, identical compute
in both paths, so the ratio there is bounded by the shared filter cost
on CPU).

Classifier backends (``--classifier``, default sweeps qat + integer +
delta): ``qat`` is the fake-quant float tick; ``integer`` runs the
bit-exact int8/Q6.8 engine (`repro.core.gru_int`) — weight codes
resident, int32 GRU state leaves in `ServerState` — through the same
fused tick and scan drivers; ``delta`` / ``delta-int`` run the
temporal-sparsity ΔGRU engine (`repro.core.gru_delta`) at the
``--theta`` threshold, and their rows record the measured per-stream
effective-MAC fraction (``sparsity``, mean over active streams — the
`srv.sparsity` telemetry; dense backends record 1.0). ``legacy`` mode
is benched only for ``qat`` (the pre-refactor path had no integer or
delta engine), so the headline claim is unchanged; the other backends'
rows quantify the cost/benefit of code-domain and sparsity-aware
serving.

Cascade (``--cascade``, plus ``--wake-threshold``): serves every
non-legacy point with the stage-1 wake gate
(`repro.serving.cascade`, energy detector) in the tick; each row then
records the measured mean classifier duty cycle (``wake_rate``, the
`srv.wake_rate` telemetry over the point's active streams — the load
generator's noise traffic mostly sits below a real threshold, so the
gate holds the classifier asleep and the row measures the gated
tick's throughput). Cascaded sweeps skip the headline claim: the
legacy baseline has no gate, so fused-vs-legacy is not
apples-to-apples there — the default (no ``--cascade``) sweep keeps
the claim unchanged.

Every row records the jax backend it ran on (``jax_backend``), so
artifacts from different platforms stay comparable.

Devices (``--devices``, default "auto"): every row records the device
count it ran on. Counts > 1 build the server on a ``("stream",)`` mesh
(the slot axis sharded block-wise, params replicated — bit-identical to
the single-device tick, see tests/test_serve_sharded.py) and are swept
for the fused/scan modes at 256+ streams, quantifying stream-parallel
scaling. "auto" sweeps 1 plus every power-of-two count the platform
exposes; emulate a multi-device host on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the CI slow job
records a devices=2 row this way). The headline claim stays pinned to
devices=1 so it is comparable across platforms.

Writes ``BENCH_serve.json`` (fields documented in benchmarks/common.py)
and checks the claim: at 256 streams, full occupancy, FV_Norm ticks, the
fused tick body sustains >= 5x the legacy path's ticks/sec. The claimed
number is the *sustained* throughput of the fused tick — the scanned
replay driver, a serving mode that exists only because the tick is one
on-device function (the legacy path's per-tick numpy smoothing forces a
host round-trip every 16 ms, so it cannot be scanned at all). The live
per-call fused tick is reported alongside as ``speedup_live`` (it wins
by dispatch/host overhead only, since both paths pay the same GRU
compute per tick on CPU).

Alongside the claim the payload carries an SLO block ("slo") gating the
live async path the way a deployment would — latency, not throughput
alone: pipelined p99 <= the 16 ms tick budget at 256 streams (full
occupancy, fv, qat, devices=1) AND live pipelined throughput >= 0.5x
the scan ceiling on the same state at 64 and 256 streams.
``--fail-on-slo`` turns a violated gate into a non-zero exit for CI.

Observability (PR 10): the instrumented modes are built with
``metrics=True`` and CONSUME the server's own registry instead of
private perf_counter lists — fused rows read per-tick latency from the
``kws_serve_tick_ms`` histogram, pipelined rows read submit-to-scores
latency and throughput from the ingress's stage→commit→dispatch→retire
`TickTrace` spans (each pipelined row records the rolled-up ``spans``
percentiles; every instrumented row records its counted ``retraces``).
A ``metrics_overhead`` block measures metrics-on vs metrics-off fused
ticks on identical traffic and gates the difference < 5%
(``--fail-on-slo``), and the full registry snapshots of the
deployment-relevant points land in ``METRICS_serve.json`` next to the
BENCH artifact.

  PYTHONPATH=src python -m benchmarks.serve_load [--classifier all]
      [--devices auto|1|1,2,...] [--theta 0.25]
      [--cascade [--wake-threshold 0.15]] [--fail-on-slo]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, percentile_stats
from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.gru_delta import DeltaConfig
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.serving.cascade import CascadeConfig
from repro.serving.ingress import PipelinedIngress
from repro.serving.metrics import span_percentiles
from repro.serving.serve_loop import StreamingKWSServer

N_TICKS = 40 if QUICK else 200
WARMUP = 5
# async ingress shape for the pipelined rows: double buffering plus a
# 4-tick coalescing window — enough to amortize the fixed per-dispatch
# host cost below the per-tick device compute, while bounding the
# latency a tick spends waiting for its window at 3 ticks (well inside
# the 16 ms budget the SLO gates at 256 streams)
PIPELINE_DEPTH = 2
PIPELINE_WINDOW = 4
# the SLO gate (see run()): live pipelined p99 within the paper's tick
# budget, live pipelined throughput within 2x of the scan ceiling
SLO_P99_MS = 16.0
SLO_MIN_VS_SCAN = 0.5
# metrics-overhead gate (see _bench_metrics_overhead): a
# metrics-enabled fused tick may cost < 5% throughput over metrics-off
OVERHEAD_STREAMS = 256
OVERHEAD_BUDGET_FRAC = 0.05

# metrics snapshots captured per instrumented benched point, keyed
# (mode, classifier, kind, max_streams, occupancy, devices); run()
# writes the deployment-relevant ones to METRICS_serve.json
_SNAPSHOTS = {}


class _LegacyStreamingServer:
    """The pre-refactor per-stream serving path, kept verbatim as the
    benchmark baseline: per-tick Python dict loops, separate device
    dispatches, host-side carry masking, and numpy softmax + smoothing
    per stream. (It also carries the pre-refactor bug of advancing idle
    streams' GRU states on zero frames — harmless here because the load
    generator submits every active stream each tick.)
    """

    def __init__(self, pipeline, params, max_streams):
        self.pipeline = pipeline
        self.params = params
        self.max_streams = max_streams
        self.smoothing = 0.7
        self.frontend_state = pipeline.state
        self.states = pipeline.streaming_init(max_streams)
        self.feat_carry = pipeline.streaming_features_init(max_streams)
        self.active = {}
        self.scores = np.zeros(
            (max_streams, pipeline.config.gru.num_classes), np.float32
        )
        self._free = list(range(max_streams))[::-1]

    def open_stream(self, stream_id):
        slot = self._free.pop()
        self.active[stream_id] = slot

    def _features_tick(self, chunks):
        s = self.pipeline.chunk_samples
        audio = np.zeros((self.max_streams, s), np.float32)
        mask = np.zeros((self.max_streams,), bool)
        for sid, chunk in chunks.items():
            audio[self.active[sid]] = chunk
            mask[self.active[sid]] = True
        new_carry, fv = self.pipeline.streaming_features_step(
            self.feat_carry, jnp.asarray(audio), self.frontend_state
        )
        m = jnp.asarray(mask)[:, None]
        self.feat_carry = jax.tree_util.tree_map(
            lambda new, old: jnp.where(m, new, old),
            new_carry, self.feat_carry,
        )
        return np.asarray(fv)

    def step(self, frames):
        c = self.pipeline.config.fex.num_channels
        hop = self.pipeline.chunk_samples
        dim = next(iter(frames.values())).shape[-1]
        if dim == hop:
            fv_all = self._features_tick(frames)
            fv = np.zeros((self.max_streams, c), np.float32)
            for sid in frames:
                fv[self.active[sid]] = fv_all[self.active[sid]]
        else:
            fv = np.zeros((self.max_streams, c), np.float32)
            for sid, frame in frames.items():
                fv[self.active[sid]] = frame
        self.states, logits = self.pipeline.streaming_step(
            self.params, self.states, jnp.asarray(fv)
        )
        logits = np.asarray(logits)
        out = {}
        for sid in frames:
            slot = self.active[sid]
            p = np.exp(logits[slot] - logits[slot].max())
            p /= p.sum()
            self.scores[slot] = (
                self.smoothing * self.scores[slot]
                + (1 - self.smoothing) * p
            )
            out[sid] = {
                "probs": self.scores[slot].copy(),
                "top": int(self.scores[slot].argmax()),
            }
        return out


def _pipeline(classifier=None, theta=0.0, cascade=None):
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    stats = fit_norm_stats(quant.log_compress_lut(raw, 12, 10))
    delta = (
        DeltaConfig(theta_x=theta, theta_h=theta)
        if classifier in ("delta", "delta-int")
        else None
    )
    return KWSPipeline(
        KWSPipelineConfig(
            classifier=classifier, delta=delta, cascade=cascade
        ),
        norm_stats=stats,
    )


def _traffic(pipe, max_streams, n_active, kind, seed=0, n_variants=8):
    """Pre-built per-tick inputs (synthesis outside the timer): a list of
    (slab, mask) for the fused path and matching {sid: frame} dicts for
    the legacy path."""
    rng = np.random.default_rng(seed)
    dim = pipe.chunk_samples if kind == "audio" else \
        pipe.config.fex.num_channels
    slabs, dicts = [], []
    for _ in range(n_variants):
        slab = np.zeros((max_streams, dim), np.float32)
        mask = np.zeros((max_streams,), bool)
        frames = {}
        for sid in range(n_active):
            f = rng.standard_normal(dim).astype(np.float32) * 0.05
            slab[sid] = f
            mask[sid] = True
            frames[sid] = f
        slabs.append((slab, mask))
        dicts.append(frames)
    return slabs, dicts


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _bench_mode(mode, kind, pipe, params, max_streams, occupancy, n_ticks,
                devices=1):
    n_active = max(1, int(round(max_streams * occupancy)))
    slabs, dicts = _traffic(pipe, max_streams, n_active, kind)
    n_var = len(slabs)
    lat = []
    spans = None
    srv = None
    if mode == "legacy":
        assert devices == 1, "legacy path predates the serving mesh"
        srv = _LegacyStreamingServer(pipe, params, max_streams)
        for sid in range(n_active):
            srv.open_stream(sid)
        for t in range(WARMUP + n_ticks):
            frames = dicts[t % n_var]
            t0 = time.perf_counter()
            srv.step(frames)
            if t >= WARMUP:
                lat.append(time.perf_counter() - t0)
    elif mode == "fused":
        # metrics=True: the per-tick latencies below come from the
        # server's own kws_serve_tick_ms histogram (the registry IS
        # the bookkeeping; the benchmark keeps no private timer list).
        # The registry's cost is itself measured and gated by
        # _bench_metrics_overhead — < 5% of a fused tick.
        srv = StreamingKWSServer(
            pipe, params, max_streams=max_streams, devices=devices,
            metrics=True,
        )
        for sid in range(n_active):
            srv.open_stream(sid)
        for t in range(WARMUP + n_ticks):
            slab, mask = slabs[t % n_var]
            srv.step_batch(slab, mask)
        tick_hist = srv.metrics.histogram("kws_serve_tick_ms")
        lat = [s * 1e-3 for s in list(tick_hist.samples)[WARMUP:]]
        assert len(lat) == n_ticks
    elif mode == "pipelined":
        srv = StreamingKWSServer(
            pipe, params, max_streams=max_streams, devices=devices,
            metrics=True,
        )
        for sid in range(n_active):
            srv.open_stream(sid)
        dim = slabs[0][0].shape[-1]
        ing = PipelinedIngress(
            srv, dim, depth=PIPELINE_DEPTH, window=PIPELINE_WINDOW
        )
        for t in range(max(WARMUP, 2 * PIPELINE_WINDOW)):
            src_slab, src_mask = slabs[t % n_var]
            slab, mask = ing.stage()
            slab[:] = src_slab
            mask[:] = src_mask
            ing.commit()
        ing.drain()
        n0 = len(srv.metrics.traces)  # skip the warmup ticks' traces
        for t in range(n_ticks):
            src_slab, src_mask = slabs[t % n_var]
            slab, mask = ing.stage()
            slab[:] = src_slab
            mask[:] = src_mask
            ing.commit()
        ing.drain()
        # per-tick latency and throughput both come from the ingress's
        # TickTrace spans (the registry replaces the old meta=
        # perf_counter freight): submit-to-scores = commit -> retire
        # per tick (ticks of one coalesced window share a retirement
        # instant but not a commit instant), wall = first stage ->
        # last retire over the measured ticks
        traces = list(srv.metrics.traces)[n0:]
        lat = [
            tr.marks["retire"] - tr.marks["commit"] for tr in traces
        ]
        assert len(lat) == n_ticks
        wall = traces[-1].marks["retire"] - traces[0].marks["stage"]
        spans = span_percentiles(traces)
    elif mode == "scan":
        srv = StreamingKWSServer(
            pipe, params, max_streams=max_streams, devices=devices,
        )
        for sid in range(n_active):
            srv.open_stream(sid)
        slab = np.stack(
            [slabs[t % n_var][0] for t in range(n_ticks)], axis=0
        )
        mask = np.stack(
            [slabs[t % n_var][1] for t in range(n_ticks)], axis=0
        )
        srv.run_batch(slab, mask)  # warm the (n_ticks,)-shaped program
        # best of 3 timed replays: the amortized number is a property of
        # the compiled program, not of transient host load
        wall = min(
            _timed(lambda: srv.run_batch(slab, mask)) for _ in range(3)
        )
    else:
        raise ValueError(mode)
    if mode in ("legacy", "fused"):
        # blocking per-call modes: each tick's wall time is disjoint, so
        # throughput is the reciprocal mean latency
        stats = percentile_stats(lat)
        ticks_per_s = 1.0 / float(np.mean(lat))
    elif mode == "pipelined":
        # overlapped latencies: percentiles are real (per-tick submit-to-
        # scores), but throughput MUST come from the wall clock — ticks
        # are in flight concurrently, so 1/mean(lat) would undercount
        stats = percentile_stats(lat)
        ticks_per_s = n_ticks / wall
    else:
        # scan: one device program, one host round-trip — there is no
        # per-tick latency to report. Fabricating lat = [wall/n]*n here
        # used to make p50==p99==mean look like measured percentiles.
        stats = {
            "p50_ms": None,
            "p99_ms": None,
            "mean_ms": wall / n_ticks * 1e3,
        }
        ticks_per_s = n_ticks / wall
    # measured temporal sparsity of this point's traffic: mean
    # effective-MAC fraction over the active streams (srv.sparsity
    # telemetry; identically 1.0 for the dense backends, None for the
    # pre-telemetry legacy path)
    sparsity = None
    wake = None
    if isinstance(srv, StreamingKWSServer):
        slots = list(srv.active.values())
        sparsity = float(np.mean(srv.sparsity[slots]))
        # measured classifier duty cycle under the stage-1 gate (mean
        # srv.wake_rate over active streams; identically 1.0 when no
        # cascade is configured, None for the pre-telemetry legacy path)
        wake = float(np.mean(srv.wake_rate[slots]))
    delta_cfg = pipe.config.delta
    casc_cfg = pipe.config.cascade
    row = {
        "classifier": pipe.config.classifier_key,
        "mode": mode,
        "kind": kind,
        # the jax backend underneath, recorded per row so artifacts
        # from different platforms compare
        "jax_backend": jax.default_backend(),
        "devices": devices,
        "max_streams": max_streams,
        "occupancy": occupancy,
        "active_streams": n_active,
        "n_ticks": n_ticks,
        "ticks_per_s": ticks_per_s,
        "streams_per_s": ticks_per_s * n_active,
        "window": PIPELINE_WINDOW if mode == "pipelined" else None,
        "sparsity": sparsity,
        "theta": None if delta_cfg is None else delta_cfg.theta_x,
        "wake_rate": wake,
        "wake_threshold": (
            None if casc_cfg is None else casc_cfg.wake_threshold
        ),
        # counted (program, shape) retraces this row's server paid —
        # exact jit accounting from the observability layer (None for
        # the pre-telemetry legacy path)
        "retraces": (
            srv.retrace_count
            if isinstance(srv, StreamingKWSServer) else None
        ),
        # pipelined rows: stage->commit->dispatch->retire span
        # percentiles from the ingress's per-tick traces
        "spans": spans,
        **stats,
    }
    if isinstance(srv, StreamingKWSServer) and srv.metrics is not None:
        _SNAPSHOTS[
            (mode, pipe.config.classifier_key, kind, max_streams,
             occupancy, devices)
        ] = srv.metrics_snapshot()
    return row


def _bench_metrics_overhead(n_ticks):
    """Measured cost of `metrics=`: fused fv ticks, metrics-on vs -off.

    Two servers on identical pipeline/params/traffic at 256 streams
    full occupancy — one with a `MetricsRegistry`, one without — timed
    by the SAME external wall clock (so the measurement itself is
    symmetric), in interleaved rounds so transient host load hits both
    configs alike; best-of-3 round means per config. The observability
    contract gates ``overhead_frac`` (on/off - 1) < 5%: the registry
    is two host clock reads and a couple of dict/deque updates per
    tick, which must stay invisible next to a 256-stream device tick.
    The metrics-on server's full `metrics_snapshot()` is returned
    alongside and written to METRICS_serve.json.
    """
    pipe = _pipeline("qat")
    params = pipe.init_params(jax.random.PRNGKey(0))
    slabs, _ = _traffic(
        pipe, OVERHEAD_STREAMS, OVERHEAD_STREAMS, "fv"
    )
    n_var = len(slabs)
    servers = {
        "off": StreamingKWSServer(
            pipe, params, max_streams=OVERHEAD_STREAMS
        ),
        "on": StreamingKWSServer(
            pipe, params, max_streams=OVERHEAD_STREAMS, metrics=True
        ),
    }
    for srv in servers.values():
        for sid in range(OVERHEAD_STREAMS):
            srv.open_stream(sid)
        for t in range(WARMUP):
            srv.step_batch(*slabs[t % n_var])
    means = {"off": [], "on": []}
    for _round in range(3):
        for name, srv in servers.items():
            t0 = time.perf_counter()
            for t in range(n_ticks):
                srv.step_batch(*slabs[t % n_var])
            means[name].append(
                (time.perf_counter() - t0) / n_ticks
            )
    off = min(means["off"])
    on = min(means["on"])
    overhead = on / off - 1.0
    block = {
        "what": (
            f"metrics-enabled fused tick costs < "
            f"{OVERHEAD_BUDGET_FRAC:.0%} throughput over metrics-off "
            f"at {OVERHEAD_STREAMS} streams (fv, qat, occupancy 1.0, "
            f"devices=1; best-of-3 interleaved round means)"
        ),
        "streams": OVERHEAD_STREAMS,
        "n_ticks": n_ticks,
        "mean_ms_metrics_off": off * 1e3,
        "mean_ms_metrics_on": on * 1e3,
        "overhead_frac": overhead,
        "budget_frac": OVERHEAD_BUDGET_FRAC,
        "ok": overhead < OVERHEAD_BUDGET_FRAC,
    }
    return block, servers["on"].metrics_snapshot()


def _auto_devices():
    """[1] plus every power-of-two device count the platform exposes."""
    visible = len(jax.devices())
    counts = [1]
    d = 2
    while d <= visible:
        counts.append(d)
        d *= 2
    return counts


def run(classifiers=("qat", "integer", "delta"), devices=None, theta=0.25,
        cascade=False, wake_threshold=0.15, fail_on_slo=False):
    casc = (
        CascadeConfig(wake_threshold=wake_threshold) if cascade else None
    )
    if devices is None:
        devices = _auto_devices()
    sweep_streams = [64, 256] if QUICK else [64, 256, 1024]
    visible = len(jax.devices())
    bad = [d for d in devices if d < 1 or d > visible]
    if bad:
        # fail before any row is benched — a mid-sweep ValueError from
        # stream_mesh would throw away minutes of measurements
        raise ValueError(
            f"--devices {bad} invalid for this platform ({visible} "
            f"visible device(s); emulate more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    multi_sizes = [ms for ms in sweep_streams if ms >= 256]
    useless = [
        d for d in devices
        if d > 1 and not any(ms % d == 0 for ms in multi_sizes)
    ]
    if useless:
        # same fail-fast contract: a count that divides none of the
        # multi-device stream sizes would silently produce zero rows
        raise ValueError(
            f"--devices {useless} divide none of the multi-device "
            f"stream sizes {multi_sizes}; pick divisors of those"
        )
    occupancies = [0.5, 1.0]
    results = []
    frontend = None
    for clf in classifiers:
        pipe = _pipeline(clf, theta=theta, cascade=casc)
        frontend = pipe.config.frontend
        params = pipe.init_params(jax.random.PRNGKey(0))
        for kind in ("fv", "audio"):
            # the legacy baseline predates the classifier registry;
            # bench it only on the backend it historically ran (qat) —
            # and never under the cascade (it has no gate, so a gated
            # sweep drops it rather than bench an unlike-for-unlike
            # pair)
            modes = (
                ("fused", "pipelined", "scan", "legacy")
                if clf == "qat" and casc is None
                else ("fused", "pipelined", "scan")
            )
            for ms in sweep_streams:
                for occ in occupancies:
                    for mode in modes:
                        # multi-device rows: the sharded fused tick /
                        # scan at full occupancy and serving scale —
                        # the stream-parallel scaling axis; everything
                        # else stays on the devices=1 baseline, which
                        # is always benched (the claim and the scaling
                        # ratios are defined against it even when
                        # --devices omits 1)
                        devs = [1]
                        if mode != "legacy" and ms >= 256 and occ == 1.0:
                            devs = sorted(
                                {1, *(d for d in devices if ms % d == 0)}
                            )
                        for d in devs:
                            r = _bench_mode(
                                mode, kind, pipe, params, ms, occ,
                                N_TICKS, devices=d,
                            )
                            results.append(r)
                            sp = (
                                f"  eff-MAC {r['sparsity']:.3f}"
                                if r["theta"] is not None else ""
                            )
                            if r["wake_threshold"] is not None:
                                sp += f"  wake {r['wake_rate']:.3f}"
                            # scan rows have no per-tick latency
                            # (p50/p99 null — the replay is one device
                            # program); print throughput alone there
                            pct = (
                                f"p50 {r['p50_ms']:7.2f} ms  "
                                f"p99 {r['p99_ms']:7.2f} ms  "
                                if r["p99_ms"] is not None
                                else "(amortized; no percentiles)  "
                            )
                            print(
                                f"  {clf:9s} {kind:5s} {mode:9s} "
                                f"N={ms:5d} occ={occ:.1f} dev={d}: "
                                f"{r['ticks_per_s']:8.1f} ticks/s  "
                                f"{pct}"
                                f"({r['streams_per_s']:.0f} streams/s)"
                                f"{sp}"
                            )

    def _pick(mode, kind, clf="qat", devs=1, ms=256):
        return next(
            (r for r in results
             if r["mode"] == mode and r["kind"] == kind
             and r["classifier"] == clf and r["devices"] == devs
             and r["max_streams"] == ms and r["occupancy"] == 1.0),
            None,
        )

    # Headline: sustained ticks/sec of the fused tick body (the scanned
    # replay driver — a mode only the fused architecture admits, since
    # the pre-refactor path's per-tick numpy smoothing forces a host
    # round-trip every tick and cannot scan) vs the pre-refactor
    # per-stream path on the same traffic. The live per-call fused tick
    # is reported separately as speedup_live, not folded into the claim.
    # The claim gates on the qat backend; a sweep restricted to another
    # backend (--classifier integer) or run under --cascade (no legacy
    # rows to compare against) records results without a claim.
    claim = None
    if "qat" in classifiers and casc is None:
        fused_live = _pick("fused", "fv")
        fused_scan = _pick("scan", "fv")
        legacy = _pick("legacy", "fv")
        speedup_scan = fused_scan["ticks_per_s"] / legacy["ticks_per_s"]
        speedup_live = fused_live["ticks_per_s"] / legacy["ticks_per_s"]
        ok = speedup_scan >= 5.0
        audio_scan_speedup = (
            _pick("scan", "audio")["ticks_per_s"]
            / _pick("legacy", "audio")["ticks_per_s"]
        )
        claim = {
            "what": "sustained fused-tick throughput (scanned replay "
                    "driver) >= 5x legacy ticks/sec at 256 streams, "
                    "occupancy 1.0, FV_Norm ticks, qat classifier; live "
                    "per-call fused ticks reported as speedup_live",
            "fused_live_ticks_per_s": fused_live["ticks_per_s"],
            "fused_scan_ticks_per_s": fused_scan["ticks_per_s"],
            "legacy_ticks_per_s": legacy["ticks_per_s"],
            "speedup": speedup_scan,
            "speedup_live": speedup_live,
            "audio_scan_speedup": audio_scan_speedup,
            "ok": ok,
        }
        int_scan = _pick("scan", "fv", "integer")
        if int_scan is not None:
            claim["integer_scan_ticks_per_s"] = int_scan["ticks_per_s"]
            claim["integer_vs_qat_scan"] = (
                int_scan["ticks_per_s"] / fused_scan["ticks_per_s"]
            )
        delta_scan = _pick("scan", "fv", "delta") or _pick(
            "scan", "fv", "delta-int"
        )
        if delta_scan is not None:
            claim["delta_scan_ticks_per_s"] = delta_scan["ticks_per_s"]
            claim["delta_vs_qat_scan"] = (
                delta_scan["ticks_per_s"] / fused_scan["ticks_per_s"]
            )
            claim["delta_sparsity"] = delta_scan["sparsity"]
    # SLO gate for the live async path — latency AND throughput, the
    # way a deployment would gate it: pipelined p99 within the paper's
    # 16 ms tick budget at 256 streams, and live pipelined throughput
    # within 2x of the scan ceiling on the same state at 64 and 256
    # streams (all at full occupancy, fv kind, devices=1; gated on the
    # sweep's first classifier so cascaded / single-backend sweeps get
    # a gate too).
    slo = None
    slo_clf = classifiers[0]
    p99_row = _pick("pipelined", "fv", slo_clf)
    ratios = {}
    for ms in (64, 256):
        pr = _pick("pipelined", "fv", slo_clf, ms=ms)
        sr = _pick("scan", "fv", slo_clf, ms=ms)
        if pr is not None and sr is not None:
            ratios[ms] = pr["ticks_per_s"] / sr["ticks_per_s"]
    if p99_row is not None and ratios:
        p99_ok = p99_row["p99_ms"] <= SLO_P99_MS
        ratio_ok = all(v >= SLO_MIN_VS_SCAN for v in ratios.values())
        slo = {
            "what": (
                f"live pipelined (window={PIPELINE_WINDOW}, "
                f"depth={PIPELINE_DEPTH}) p99 <= {SLO_P99_MS} ms at "
                f"256 streams AND >= {SLO_MIN_VS_SCAN}x the scan "
                f"ceiling at 64/256 streams (fv, {slo_clf}, occupancy "
                f"1.0, devices=1)"
            ),
            "classifier": slo_clf,
            "p99_ms": p99_row["p99_ms"],
            "p99_budget_ms": SLO_P99_MS,
            "pipelined_vs_scan": {str(k): v for k, v in ratios.items()},
            "min_vs_scan": SLO_MIN_VS_SCAN,
            "p99_ok": p99_ok,
            "ratio_ok": ratio_ok,
            "ok": p99_ok and ratio_ok,
        }
    # stream-parallel scaling summary: sustained scan-fv throughput at
    # 256 streams per device count (vs the devices=1 row). On emulated
    # CPU meshes the "devices" share one physical socket, so the ratio
    # mostly measures SPMD overhead; on real multi-chip platforms it is
    # the scaling curve.
    scaling = []
    for d in sorted({1, *devices}):
        row = _pick("scan", "fv", classifiers[0], devs=d)
        base = _pick("scan", "fv", classifiers[0], devs=1)
        if row is None or base is None:
            continue
        scaling.append({
            "devices": d,
            "scan_fv_ticks_per_s": row["ticks_per_s"],
            "vs_single_device": row["ticks_per_s"] / base["ticks_per_s"],
        })
    # the observability layer's own claim: metrics cost < 5% of a
    # fused tick (measured, recorded, and gated with the SLO)
    metrics_overhead, overhead_snapshot = _bench_metrics_overhead(
        N_TICKS
    )
    payload = {
        "backend": jax.default_backend(),
        "frontend": frontend,
        "classifiers": list(classifiers),
        # ΔGRU threshold the delta rows ran at (per-row "theta" repeats
        # it; dense rows carry theta=None and sparsity=1.0)
        "theta": theta,
        # stage-1 cascade the sweep served under (per-row
        # "wake_threshold"/"wake_rate" repeat/record it; False -> every
        # row ran the ungated tick and wake_rate is identically 1.0)
        "cascade": cascade,
        "wake_threshold": wake_threshold if cascade else None,
        # counts that actually produced rows (a requested count that
        # divides none of the 256+ stream sizes is swept nowhere and
        # must not be claimed in the artifact)
        "devices": sorted({r["devices"] for r in results}),
        "quick": QUICK,
        "results": results,
        "scaling": scaling,
        "claim": claim,
        "slo": slo,
        "metrics_overhead": metrics_overhead,
    }
    with open("BENCH_serve.json", "w") as f:
        json.dump(payload, f, indent=2)
    # full registry snapshots of the deployment-relevant points, as an
    # artifact next to the BENCH rows (histogram buckets + percentiles,
    # journal events, per-span rollups — the CI slow job uploads this)
    snapshots = {"metrics_overhead_on": overhead_snapshot}
    slo_key = ("pipelined", classifiers[0], "fv", 256, 1.0, 1)
    if slo_key in _SNAPSHOTS:
        snapshots["pipelined_256"] = _SNAPSHOTS[slo_key]
    fused_key = ("fused", classifiers[0], "fv", 256, 1.0, 1)
    if fused_key in _SNAPSHOTS:
        snapshots["fused_256"] = _SNAPSHOTS[fused_key]
    with open("METRICS_serve.json", "w") as f:
        json.dump(snapshots, f, indent=2)
    for s in scaling:
        if s["devices"] > 1:
            print(
                f"serve_load: {s['devices']} devices sustain "
                f"{s['scan_fv_ticks_per_s']:.1f} scan ticks/s at 256 "
                f"streams ({s['vs_single_device']:.2f}x the single-"
                f"device program)"
            )
    if claim is not None:
        extra = (
            f", integer scan {claim['integer_vs_qat_scan']:.2f}x qat"
            if "integer_vs_qat_scan" in claim else ""
        )
        print(
            f"serve_load: fused scan "
            f"{claim['fused_scan_ticks_per_s']:.1f} / live "
            f"{claim['fused_live_ticks_per_s']:.1f} vs legacy "
            f"{claim['legacy_ticks_per_s']:.1f} ticks/s at 256 streams "
            f"(fv, qat) -> {claim['speedup']:.1f}x sustained, "
            f"{claim['speedup_live']:.1f}x live "
            f"(audio scan: {claim['audio_scan_speedup']:.1f}x{extra})  "
            f"[{'PASS' if claim['ok'] else 'FAIL'}] "
            f"(BENCH_serve.json written)"
        )
    else:
        why = (
            "cascaded sweep has no like-for-like legacy baseline"
            if cascade else "no qat baseline in sweep"
        )
        print(
            f"serve_load: swept classifiers {list(classifiers)} "
            f"({why} -> no claim); BENCH_serve.json written"
        )
    if slo is not None:
        rat = ", ".join(
            f"{k} streams {v:.2f}x"
            for k, v in sorted(slo["pipelined_vs_scan"].items(),
                               key=lambda kv: int(kv[0]))
        )
        print(
            f"serve_load SLO: pipelined p99 {slo['p99_ms']:.2f} ms "
            f"(budget {slo['p99_budget_ms']:.0f} ms) at 256 streams; "
            f"vs scan ceiling: {rat} (floor {slo['min_vs_scan']:.2f}x)"
            f"  [{'PASS' if slo['ok'] else 'FAIL'}]"
        )
    mo = metrics_overhead
    print(
        f"serve_load metrics-overhead: metrics-on fused tick "
        f"{mo['mean_ms_metrics_on']:.3f} ms vs off "
        f"{mo['mean_ms_metrics_off']:.3f} ms at {mo['streams']} "
        f"streams -> {mo['overhead_frac']:+.2%} "
        f"(budget {mo['budget_frac']:.0%})  "
        f"[{'PASS' if mo['ok'] else 'FAIL'}] "
        f"(METRICS_serve.json written)"
    )
    if fail_on_slo and (slo is None or not slo["ok"]):
        raise SystemExit(
            "serve_load: --fail-on-slo and the live-serving SLO gate "
            + ("produced no measurable rows" if slo is None
               else "failed (see the SLO line above)")
        )
    if fail_on_slo and not mo["ok"]:
        raise SystemExit(
            "serve_load: --fail-on-slo and the metrics-overhead gate "
            "failed (see the metrics-overhead line above)"
        )
    return claim


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--classifier", default="all",
        choices=["all", "qat", "integer", "float", "delta", "delta-int"],
        help="classifier backend(s) to sweep; "
             "'all' = qat + integer + delta",
    )
    ap.add_argument(
        "--devices", default="auto",
        help="device counts to sweep, e.g. '1,2' ('auto' = 1 plus "
             "every power-of-two count the platform exposes; emulate "
             "with XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--cascade", action="store_true",
        help="serve every non-legacy point with the stage-1 wake gate "
             "(repro.serving.cascade, energy detector at "
             "--wake-threshold); rows record the measured classifier "
             "duty cycle as 'wake_rate'; the fused-vs-legacy claim is "
             "skipped (the legacy path has no gate)",
    )
    ap.add_argument(
        "--wake-threshold", type=float, default=0.15,
        help="stage-1 energy-detector wake threshold for --cascade "
             "(mean rectified FV_Norm units; 0 = always-open, "
             "bit-identical to the ungated tick)",
    )
    ap.add_argument(
        "--fail-on-slo", action="store_true",
        help="exit non-zero when the live-serving SLO gate fails "
             "(pipelined p99 <= 16 ms at 256 streams AND >= 0.5x the "
             "scan ceiling at 64/256 streams) or when the metrics-"
             "overhead gate fails (metrics-on fused tick < 5% over "
             "metrics-off) — the CI slow job's regression tripwire "
             "for the async ingress and observability layers",
    )
    ap.add_argument(
        "--theta", type=float, default=0.25,
        help="ΔGRU delta threshold (Q6.8 value units, applied to both "
             "input and hidden deltas of every layer) for the "
             "delta/delta-int rows; their 'sparsity' fields record the "
             "measured effective-MAC fraction under this threshold",
    )
    args = ap.parse_args()
    run(
        ("qat", "integer", "delta") if args.classifier == "all"
        else (args.classifier,),
        devices=(
            None if args.devices == "auto"
            else [int(d) for d in args.devices.split(",")]
        ),
        theta=args.theta,
        cascade=args.cascade,
        wake_threshold=args.wake_threshold,
        fail_on_slo=args.fail_on_slo,
    )
