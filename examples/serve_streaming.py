"""Streaming KWS serving: N concurrent audio streams, one batched
weights-resident GRU step per 16 ms frame — the chip's deployment shape
(Fig. 4) scaled to a TPU serving binary.

The server consumes RAW 16 ms audio hops per stream: feature extraction
runs inside the tick through the pipeline's registered frontend
(--frontend software|hardware|hardware-pallas), with per-stream filter
and SRO-phase carry. The whole tick (frontend + GRU + softmax +
smoothing) is one fused jit over donated state buffers; --offline
replays each stream's full buffered audio through the server's
`lax.scan` driver instead of live per-tick calls.

`--pipelined` swaps the blocking live loop for the async ingress
(`repro.serving.ingress.PipelinedIngress`): double-buffered host
staging, non-blocking dispatch, deferred score fetch, and `--window`
ticks coalesced per device dispatch — same score trajectory
bit-identically, fewer host round-trips.

`--metrics` serves through a `repro.serving.metrics.MetricsRegistry`
(bit-identical — instrumentation is host-side only) and dumps the full
`srv.metrics_snapshot()` JSON on exit; `--autoscale` drives an
occupancy/SLO `Autoscaler` per tick and prints every capacity decision
WITH its reason (`auto.last_decision`: occupancy_watermark, rejection,
or an slo_veto hold).

  PYTHONPATH=src python examples/serve_streaming.py [--streams 32]
      [--frontend software] [--classifier qat|integer]
      [--cascade [--wake-threshold 0.1]] [--offline]
      [--pipelined [--window 4]] [--grow 64]
      [--metrics] [--autoscale]
"""

import argparse
import json
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.fex import FExConfig, FExNormStats, fex_frames
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.compile_cache import enable_compile_cache
from repro.data.gscd import CLASSES, make_dataset
from repro.serving.serve_loop import StreamingKWSServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=32)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--frontend", default="software",
                    choices=["software", "hardware", "hardware-pallas"])
    ap.add_argument("--classifier", default="qat",
                    choices=["float", "qat", "integer", "delta",
                             "delta-int"],
                    help="classifier backend; 'integer' serves the "
                         "bit-exact int8/Q6.8 code engine (the IC's "
                         "WMEM-resident arithmetic); 'delta'/"
                         "'delta-int' serve the temporal-sparsity ΔGRU "
                         "engine at --theta (θ=0 is bit-identical to "
                         "qat/integer) and report per-stream "
                         "effective-MAC fractions")
    ap.add_argument("--theta", type=float, default=0.0,
                    help="ΔGRU delta threshold (Q6.8 value units, "
                         "input and hidden deltas of every layer) for "
                         "--classifier delta/delta-int; 0 = exact "
                         "dense replay, larger skips more MACs")
    ap.add_argument("--cascade", action="store_true",
                    help="run the stage-1 always-on wake gate "
                         "(repro.serving.cascade, energy detector) "
                         "inside the tick: the classifier advances "
                         "only on ticks the gate wakes, and the "
                         "per-stream duty cycle (srv.wake_rate) is "
                         "printed next to the posterior trace")
    ap.add_argument("--wake-threshold", type=float, default=0.1,
                    help="energy-detector wake threshold for --cascade "
                         "(mean rectified FV_Norm units; 0 = "
                         "always-open, bit-identical to no cascade)")
    ap.add_argument("--offline", action="store_true",
                    help="replay buffered audio via the lax.scan driver "
                         "(server.run) instead of live per-tick step calls")
    ap.add_argument("--pipelined", action="store_true",
                    help="serve live ticks through the async ingress "
                         "(repro.serving.ingress.PipelinedIngress): "
                         "double-buffered staging, non-blocking "
                         "dispatch, scores fetched via deferred "
                         "TickHandles — bit-identical to the blocking "
                         "loop, fewer host round-trips")
    ap.add_argument("--window", type=int, default=4,
                    help="ticks coalesced into one scan dispatch by "
                         "--pipelined (the throughput/latency knob; "
                         "1 = one fused tick per dispatch)")
    ap.add_argument("--grow", type=int, default=None,
                    help="elastic-serving demo: live-resize the server "
                         "to this many slots halfway through the run "
                         "(must be a multiple of the device count; the "
                         "open streams' state moves bitwise, so the "
                         "score trajectories are unaffected). Only in "
                         "the live blocking mode")
    ap.add_argument("--metrics", action="store_true",
                    help="serve through a MetricsRegistry "
                         "(repro.serving.metrics — bit-identical, "
                         "host-side instrumentation only) and dump the "
                         "full metrics_snapshot() JSON on exit: tick "
                         "latency histograms, occupancy gauges, "
                         "retrace/compile counters, and the structured "
                         "event journal")
    ap.add_argument("--autoscale", action="store_true",
                    help="drive an occupancy/SLO Autoscaler "
                         "(repro.serving.autoscale) per live tick and "
                         "print every capacity decision with its "
                         "reason (auto.last_decision). Only in the "
                         "live blocking mode")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the stream-slot axis over the first N "
                         "visible devices (('stream',) mesh; default: "
                         "the largest visible count that divides "
                         "--streams — 1 device keeps the plain "
                         "single-device program). Emulate a mesh on "
                         "CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    args = ap.parse_args()
    enable_compile_cache()

    # corpus + norm stats + a model (random weights for the demo)
    data = make_dataset(6, seed=0)
    fcfg = FExConfig()
    frames = fex_frames(jnp.asarray(data["audio"][: args.streams]), fcfg)
    fv_raw = quant.quantize_unsigned(frames, 12, fcfg.quant_full_scale)
    fv_log = quant.log_compress_lut(fv_raw, 12, 10)
    stats = FExNormStats(
        mu=fv_log.reshape(-1, 16).mean(0),
        sigma=fv_log.reshape(-1, 16).std(0) + 1e-3,
    )
    delta = None
    if args.classifier in ("delta", "delta-int"):
        from repro.core.gru_delta import DeltaConfig

        delta = DeltaConfig(theta_x=args.theta, theta_h=args.theta)
    cascade = None
    if args.cascade:
        from repro.serving.cascade import CascadeConfig

        cascade = CascadeConfig(wake_threshold=args.wake_threshold)
    pipe = KWSPipeline(
        KWSPipelineConfig(
            frontend=args.frontend, classifier=args.classifier,
            delta=delta, cascade=cascade,
        ),
        norm_stats=stats,
    )
    # calibrated FrontendState (beta/alpha for the hardware paths; the
    # fitted norm stats are carried over automatically)
    pipe = pipe.with_state(pipe.init_frontend_state(mismatch=False))
    params = pipe.init_params(jax.random.PRNGKey(0))

    audio = np.asarray(data["audio"][: args.streams], np.float32)
    # devices=None shards over every visible device (single visible
    # device -> the plain single-device program, bit-identically)
    n_dev = args.devices
    if n_dev is None:
        n_dev = len(jax.devices())
        while args.streams % n_dev:
            n_dev -= 1  # largest visible count the slot axis divides
    srv = StreamingKWSServer(
        pipe, params, max_streams=args.streams, devices=n_dev,
        metrics=args.metrics,
    )
    for sid in range(args.streams):
        srv.open_stream(sid)
    auto = None
    if args.autoscale:
        from repro.serving.autoscale import Autoscaler, AutoscalePolicy

        auto = Autoscaler(
            srv,
            AutoscalePolicy(
                min_streams=srv.n_devices,
                max_streams=4 * args.streams,
                hysteresis_ticks=2, cooldown_ticks=4,
            ),
        )

    hop = pipe.chunk_samples  # 256 samples = 16 ms @ 16 kHz
    n_frames = min(audio.shape[1] // hop, int(args.seconds / 16e-3))
    if args.offline:
        mode = "offline lax.scan replay"
    elif args.pipelined:
        mode = f"live async ingress (depth 2, window {args.window})"
    else:
        mode = "live fused ticks"
    print(f"serving {args.streams} streams x {n_frames} raw-audio hops "
          f"({hop} samples / 16 ms each) via frontend "
          f"{args.frontend!r}, classifier {args.classifier!r} "
          f"on {srv.n_devices} device(s) [{mode}]...")
    t0 = time.time()
    detections = {}
    if args.offline:
        out = srv.run({sid: audio[sid, : n_frames * hop]
                       for sid in range(args.streams)})
        for sid, r in out.items():
            detections[sid] = r["top"]
    elif args.pipelined:
        from repro.serving.ingress import PipelinedIngress

        ing = PipelinedIngress(srv, dim=hop, window=args.window)
        slots = {sid: srv.active[sid] for sid in range(args.streams)}
        for t in range(n_frames):
            slab, mask = ing.stage()  # host staging overlaps the
            for sid, slot in slots.items():  # in-flight dispatch
                slab[slot] = audio[sid, t * hop:(t + 1) * hop]
                mask[slot] = True
            ing.commit(meta=t)  # non-blocking past the first `depth`
        # every score row is in some retired handle; the final tick's
        # top row lives in the last handle's last window row
        tops = ing.drain()[-1].top
        tops = tops[-1] if tops.ndim == 2 else tops  # window > 1
        for sid, slot in slots.items():
            detections[sid] = int(tops[slot])
    else:
        for t in range(n_frames):
            if args.grow is not None and t == n_frames // 2:
                # live grow: the ServerState pytree is re-laid onto the
                # larger slot axis bitwise, open streams keep serving
                srv.resize(args.grow)
                print(f"  [tick {t}] resized live to {srv.max_streams} "
                      f"slots ({len(srv.active)} open streams moved "
                      f"bitwise)")
            chunk = {sid: audio[sid, t * hop:(t + 1) * hop]
                     for sid in range(args.streams)}
            t_tick = time.perf_counter()
            out = srv.step(chunk)
            for sid, r in out.items():
                detections[sid] = r["top"]
            if auto is not None:
                # why did (or didn't) capacity change? last_decision
                # carries the reason for grows, shrinks, AND slo-veto
                # holds — print each decision as it lands
                before = auto.last_decision
                auto.observe(time.perf_counter() - t_tick)
                d = auto.last_decision
                if d is not None and d is not before:
                    print(f"  [tick {t}] autoscaler {d['action']}: "
                          f"{d['from']} -> {d['to']} slots "
                          f"(reason: {d['reason']})")
    wall = time.time() - t0
    per_frame = wall / n_frames * 1e3
    rt_streams = args.streams * (16.0 / per_frame)
    print(f"wall {wall:.2f}s -> {per_frame:.2f} ms per batched "
          f"audio-in tick (FEx + GRU); real-time capacity at this batch "
          f"~{rt_streams:.0f} streams/host "
          f"({jax.devices()[0].device_kind}, first tick's compile "
          f"included)")
    top_counts = {}
    for sid, cls in detections.items():
        top_counts[CLASSES[cls]] = top_counts.get(CLASSES[cls], 0) + 1
    print("final per-stream top classes (untrained weights -> arbitrary):",
          top_counts)
    if args.classifier in ("delta", "delta-int"):
        # per-stream effective-MAC fraction next to the posterior trace
        # (the srv.sparsity telemetry the ΔGRU state accumulates)
        frac = srv.sparsity
        per_stream = {
            sid: float(frac[srv.active[sid]]) for sid in sorted(detections)
        }
        shown = {s: round(f, 3) for s, f in list(per_stream.items())[:8]}
        vals = list(per_stream.values())
        print(f"ΔGRU θ={args.theta:g}: effective-MAC fraction "
              f"mean {np.mean(vals):.3f} "
              f"(min {np.min(vals):.3f} / max {np.max(vals):.3f}); "
              f"first streams: {shown}")
    if args.cascade:
        # per-stream classifier duty cycle next to the effective-MAC
        # fraction (the srv.wake_rate telemetry the stage-1 gate
        # accumulates; composes multiplicatively with the ΔGRU
        # sparsity in the IC energy model)
        wr = srv.wake_rate
        per_stream = {
            sid: float(wr[srv.active[sid]]) for sid in sorted(detections)
        }
        shown = {s: round(w, 3) for s, w in list(per_stream.items())[:8]}
        vals = list(per_stream.values())
        print(f"cascade thr={args.wake_threshold:g}: classifier duty "
              f"cycle (wake rate) mean {np.mean(vals):.3f} "
              f"(min {np.min(vals):.3f} / max {np.max(vals):.3f}); "
              f"first streams: {shown}")
    if args.metrics:
        # the full observability snapshot: tick histograms (with exact
        # percentiles), occupancy gauges, retrace/compile counters, and
        # the structured event journal (every resize / retrace /
        # autoscale decision with its reason, in order)
        print("metrics snapshot:")
        print(json.dumps(srv.metrics_snapshot(), indent=2))
    print("the IC serves 1 stream at 23 uW; TPU serving amortizes one "
          "weights-resident GRU across thousands of streams")


if __name__ == "__main__":
    main()
