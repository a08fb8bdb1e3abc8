"""Elastic serving under churn: open/close load, live resizes, and
injected shard loss.

Where benchmarks/serve_load.py measures steady-state throughput at a
fixed capacity, this generator drives the elastic serving stack the way
a deployment actually stresses it — a three-phase open/close schedule
(``ramp`` -> ``peak`` -> ``drain``) with Poisson arrivals and per-stream
departures, while the occupancy/SLO autoscaler
(`repro.serving.autoscale.Autoscaler`) watches every tick and calls
`StreamingKWSServer.resize` live:

  * ``ramp``  — arrivals push occupancy through the grow watermark;
    the autoscaler doubles capacity (possibly repeatedly). Arrivals
    that land while capacity lags the offered load are REJECTED at
    `open_stream` and fed back as `note_rejection()` — the immediate
    grow signal.
  * ``peak``  — steady churn at high occupancy. With ``--shard-loss``
    (and a multi-device server) one shard is lost mid-peak:
    `recover_shard_loss` shrink-reshards onto the survivors, reopens
    the lost shard's streams, and the bench VERIFIES in-line that every
    healthy stream's per-slot state is bit-unchanged through the move
    (the recovery contract of tests/test_serve_sharded.py, re-checked
    on the benchmark's own traffic).
  * ``drain`` — departures dominate; occupancy falls through the
    shrink watermark and the autoscaler halves capacity under
    hysteresis, SLO veto, and the open-streams block floor.

Tick latencies come from the server's own observability layer
(``metrics=True``): each blocking `step_batch` observes into the
``kws_serve_tick_ms`` histogram, and the bench reads ``.last`` off it.
A tick that traced+compiled a fresh program — the first tick ever, and
the first tick at any slot width this program set has not served yet —
is excluded from the steady-state percentiles EXACTLY, by comparing
`srv.retrace_count` around the call (the counted shape-keyed retraces
of the serving stack; the old next-tick-after-resize heuristic both
missed recompiles it didn't know about and excluded warm cache-hit
ticks after a resize back to a seen capacity). Compile ticks are
recorded separately (``resize.post_change_compile_ms``), as are the
in-band pauses of the `resize()` / `recover_shard_loss()` calls
themselves (``pause_ms`` / ``recovery_ms``); the full registry
snapshot (histograms, decision journal, gauges) lands in
``METRICS_churn.json`` next to the BENCH artifact.

Writes ``BENCH_churn.json`` (every field documented in
benchmarks/common.py, ``BENCH_CHURN_FIELDS``) and gates an SLO block:
steady-state peak p99 within the 16 ms tick budget, the rejection rate
within budget, and the elasticity smoke — the autoscaler actually grew
during ramp and shrank during drain, and injected shard loss left the
healthy streams bit-unchanged. ``--fail-on-slo`` turns a violated gate
into a non-zero exit for CI.

  PYTHONPATH=src python -m benchmarks.churn_load [--classifier qat]
      [--devices 1] [--shard-loss] [--seed 0] [--fail-on-slo]

Multi-device runs (``--devices 2``) need visible devices; emulate on
CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import QUICK
from benchmarks.serve_load import _pipeline
from repro.distributed.fault_tolerance import StragglerMonitor
from repro.serving.autoscale import Autoscaler, AutoscalePolicy, shard_of_slot
from repro.serving.serve_loop import StreamingKWSServer

# phase schedule: (name, n_ticks, arrival rate per tick, per-stream
# close probability, target open streams). Arrivals pause once the open
# count overshoots the target by 10% — the generator models offered
# load with backpressure, so rejections happen only while capacity lags
# a rising target (exactly the window the autoscaler is meant to close).
PHASES = (
    ("ramp", 40 if QUICK else 200, 3.0, 0.02, 48),
    ("peak", 60 if QUICK else 300, 2.0, 0.04, 48),
    ("drain", 40 if QUICK else 200, 0.0, 0.12, 4),
)
START_STREAMS = 12
START_CAPACITY = 16
MAX_CAPACITY = 64 if QUICK else 256

SLO_P99_MS = 16.0
SLO_MAX_REJECTION_RATE = 0.10


def _verify_survivors(srv, pre_by_sid):
    """Healthy streams' per-slot state must be bit-unchanged; returns
    (ok, n_checked)."""
    leaves = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(srv.state)]
    ok = True
    for sid, rows in pre_by_sid.items():
        slot = srv.active[sid]
        for row, leaf in zip(rows, leaves):
            if not np.array_equal(row, leaf[slot]):
                ok = False
    return ok, len(pre_by_sid)


def run(classifier="qat", devices=1, shard_loss=False, seed=0,
        fail_on_slo=False):
    visible = len(jax.devices())
    if devices < 1 or devices > visible:
        raise ValueError(
            f"--devices {devices} invalid for this platform ({visible} "
            f"visible device(s); emulate more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    if shard_loss and devices < 2:
        raise ValueError("--shard-loss needs --devices >= 2")
    pipe = _pipeline(classifier)
    params = pipe.init_params(jax.random.PRNGKey(0))
    srv = StreamingKWSServer(
        pipe, params, max_streams=START_CAPACITY, devices=devices,
        metrics=True,
    )
    tick_hist = srv.metrics.histogram("kws_serve_tick_ms")
    policy = AutoscalePolicy(
        min_streams=max(8, devices),
        max_streams=MAX_CAPACITY,
        grow_at=0.85,
        shrink_at=0.30,
        hysteresis_ticks=3,
        cooldown_ticks=4,
        factor=2,
    )
    auto = Autoscaler(
        srv, policy,
        monitor=StragglerMonitor(threshold=4.0, budget=8, warmup=1),
    )
    rng = np.random.default_rng(seed)
    dim = pipe.config.fex.num_channels
    next_sid = 0
    for _ in range(START_STREAMS):
        srv.open_stream(next_sid)
        next_sid += 1

    phase_rows = []
    pause_ms = []
    compile_ms = []
    loss_record = None
    totals = {"opens": START_STREAMS, "closes": 0, "rejections": 0,
              "arrivals": START_STREAMS, "stream_frames": 0}
    step = 0
    wall_t0 = time.perf_counter()
    for name, n_ticks, rate, p_close, target in PHASES:
        lat, opens, closes, rejections, active_sum = [], 0, 0, 0, 0
        loss_tick = n_ticks // 2 if (shard_loss and name == "peak") else None
        for t in range(n_ticks):
            # departures
            for sid in [s for s in list(srv.active)
                        if rng.random() < p_close]:
                srv.close_stream(sid)
                closes += 1
            # arrivals (offered load pauses past 110% of the target)
            n_arrive = (
                int(rng.poisson(rate))
                if len(srv.active) < target * 1.1 and rate > 0 else 0
            )
            for _ in range(n_arrive):
                totals["arrivals"] += 1
                try:
                    srv.open_stream(next_sid)
                    next_sid += 1
                    opens += 1
                except RuntimeError:
                    rejections += 1
                    auto.note_rejection()
            # injected shard loss: mid-peak, timed, verified in-line
            if loss_tick is not None and t == loss_tick and srv.n_devices > 1:
                lost = srv.n_devices - 1
                healthy = {
                    sid: slot for sid, slot in srv.active.items()
                    if shard_of_slot(
                        slot, srv.max_streams, srv.n_devices
                    ) != lost
                }
                leaves = [
                    np.asarray(leaf)
                    for leaf in jax.tree_util.tree_leaves(srv.state)
                ]
                pre = {
                    sid: [leaf[slot].copy() for leaf in leaves]
                    for sid, slot in healthy.items()
                }
                t0 = time.perf_counter()
                info = srv.recover_shard_loss(lost)
                recovery_s = time.perf_counter() - t0
                ok, n_checked = _verify_survivors(srv, pre)
                loss_record = {
                    "step": step,
                    "lost_shard": lost,
                    "recovery_ms": recovery_s * 1e3,
                    "reopened": len(info["reopened"]),
                    "survivors": len(info["survivors"]),
                    "survivors_checked": n_checked,
                    "healthy_bit_unchanged": ok,
                    "n_devices_after": srv.n_devices,
                    "max_streams_after": srv.max_streams,
                }
            # one fused tick over the current active set
            slab = np.zeros((srv.max_streams, dim), np.float32)
            mask = np.zeros((srv.max_streams,), bool)
            for sid, slot in srv.active.items():
                slab[slot] = rng.standard_normal(dim).astype(np.float32) * 0.05
                mask[slot] = True
            # tick latency comes from the server's own histogram; a
            # tick whose dispatch traced+compiled a new program is
            # identified EXACTLY by the retrace counter (no latency
            # heuristic — a resize back to an already-compiled width
            # is a warm tick and stays in the steady-state pool)
            r0 = srv.retrace_count
            srv.step_batch(slab, mask)
            dt = tick_hist.last * 1e-3
            if srv.retrace_count > r0:
                compile_ms.append(dt * 1e3)
            else:
                lat.append(dt)
            active_sum += len(srv.active)
            totals["stream_frames"] += len(srv.active)
            # autoscaler observes the measured tick; an action is a
            # capacity change — its in-band pause is the observe() time
            t0 = time.perf_counter()
            action = auto.observe(dt)
            if action is not None:
                pause_ms.append((time.perf_counter() - t0) * 1e3)
            step += 1
        lat_ms = np.asarray(lat, np.float64) * 1e3
        phase_rows.append({
            "phase": name,
            "ticks": n_ticks,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            "mean_ms": float(lat_ms.mean()),
            "ticks_per_s": 1e3 / float(lat_ms.mean()),
            "mean_active": active_sum / n_ticks,
            "capacity_end": srv.max_streams,
            "opens": opens,
            "closes": closes,
            "rejections": rejections,
        })
        totals["opens"] += opens
        totals["closes"] += closes
        totals["rejections"] += rejections
        print(
            f"  {name:5s} {n_ticks:4d} ticks: p50 "
            f"{phase_rows[-1]['p50_ms']:6.2f} ms  p99 "
            f"{phase_rows[-1]['p99_ms']:6.2f} ms  mean active "
            f"{phase_rows[-1]['mean_active']:5.1f}  capacity -> "
            f"{srv.max_streams:3d}  ({opens} opens, {closes} closes, "
            f"{rejections} rejections)"
        )
    wall_s = time.perf_counter() - wall_t0

    grew = any(e["action"] == "grow" for e in auto.events)
    shrank = any(e["action"] == "shrink" for e in auto.events)
    peak = next(r for r in phase_rows if r["phase"] == "peak")
    rejection_rate = totals["rejections"] / max(1, totals["arrivals"])
    p99_ok = peak["p99_ms"] <= SLO_P99_MS
    rejection_ok = rejection_rate <= SLO_MAX_REJECTION_RATE
    elastic_ok = grew and shrank and (
        loss_record is None or loss_record["healthy_bit_unchanged"]
    )
    slo = {
        "what": (
            f"steady-state peak p99 <= {SLO_P99_MS} ms, rejection rate "
            f"<= {SLO_MAX_REJECTION_RATE}, and the elasticity smoke: "
            f"the autoscaler grew during ramp AND shrank during drain"
            + (", and injected shard loss left every healthy stream's "
               "state bit-unchanged" if shard_loss else "")
        ),
        "p99_ms": peak["p99_ms"],
        "p99_budget_ms": SLO_P99_MS,
        "p99_ok": p99_ok,
        "rejection_rate": rejection_rate,
        "rejection_budget": SLO_MAX_REJECTION_RATE,
        "rejection_ok": rejection_ok,
        "grew": grew,
        "shrank": shrank,
        "elastic_ok": elastic_ok,
        "ok": p99_ok and rejection_ok and elastic_ok,
    }
    payload = {
        "backend": jax.default_backend(),
        "classifier": pipe.config.classifier_key,
        "devices_initial": devices,
        "devices_final": srv.n_devices,
        "seed": seed,
        "quick": QUICK,
        "policy": {
            "min_streams": policy.min_streams,
            "max_streams": policy.max_streams,
            "grow_at": policy.grow_at,
            "shrink_at": policy.shrink_at,
            "hysteresis_ticks": policy.hysteresis_ticks,
            "cooldown_ticks": policy.cooldown_ticks,
            "factor": policy.factor,
        },
        "phases": phase_rows,
        "resize": {
            "events": auto.events,
            "count": len(auto.events),
            "pause_ms": pause_ms,
            "max_pause_ms": max(pause_ms) if pause_ms else None,
            "post_change_compile_ms": compile_ms,
            # exact jit accounting from the observability layer: the
            # compile-tick exclusion above counted THESE retraces
            "retraces": srv.retrace_count,
            "compiles": srv.compile_count,
        },
        "shard_loss": loss_record,
        "totals": {
            **totals,
            "ticks": step,
            "wall_s": wall_s,
            "stream_frames_per_s": totals["stream_frames"] / wall_s,
        },
        "slo": slo,
    }
    with open("BENCH_churn.json", "w") as f:
        json.dump(payload, f, indent=2)
    # full registry snapshot — tick histograms, occupancy gauges, and
    # the journal of every autoscale / resize / retrace / shard-loss
    # event with its reason, in order (the CI slow job uploads this)
    with open("METRICS_churn.json", "w") as f:
        json.dump(srv.metrics_snapshot(), f, indent=2)
    sizes = " -> ".join(
        str(s) for s in
        [START_CAPACITY] + [e["to"] for e in auto.events]
    )
    print(
        f"churn_load: {step} ticks, {totals['opens']} opens / "
        f"{totals['closes']} closes / {totals['rejections']} rejections "
        f"({rejection_rate:.1%} of offered), capacity {sizes}, "
        f"{len(auto.events)} resize(s), max pause "
        f"{max(pause_ms) if pause_ms else 0.0:.1f} ms"
    )
    if loss_record is not None:
        print(
            f"churn_load shard-loss: shard {loss_record['lost_shard']} "
            f"lost at step {loss_record['step']}: recovered in "
            f"{loss_record['recovery_ms']:.0f} ms, "
            f"{loss_record['reopened']} stream(s) reopened, "
            f"{loss_record['survivors_checked']} healthy stream(s) "
            f"bit-unchanged="
            f"{'yes' if loss_record['healthy_bit_unchanged'] else 'NO'}"
        )
    print(
        f"churn_load SLO: peak p99 {slo['p99_ms']:.2f} ms (budget "
        f"{SLO_P99_MS:.0f} ms), rejections {rejection_rate:.1%} "
        f"(budget {SLO_MAX_REJECTION_RATE:.0%}), grew="
        f"{'yes' if grew else 'NO'} shrank={'yes' if shrank else 'NO'}"
        f"  [{'PASS' if slo['ok'] else 'FAIL'}] (BENCH_churn.json "
        f"written)"
    )
    if fail_on_slo and not slo["ok"]:
        raise SystemExit(
            "churn_load: --fail-on-slo and the churn SLO gate failed "
            "(see the SLO line above)"
        )
    return payload


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--classifier", default="qat",
        choices=["qat", "integer", "float", "delta", "delta-int"],
        help="classifier backend the churn traffic is served with",
    )
    ap.add_argument(
        "--devices", type=int, default=1,
        help="initial device count; > 1 builds the server on a "
             "('stream',) mesh (emulate on CPU with "
             "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--shard-loss", action="store_true",
        help="inject the loss of one shard mid-peak (needs "
             "--devices >= 2): times recover_shard_loss, counts the "
             "reopened streams, and bit-verifies the healthy ones",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--fail-on-slo", action="store_true",
        help="exit non-zero when the churn SLO gate fails (peak p99, "
             "rejection rate, or the elasticity smoke) — the CI slow "
             "job's regression tripwire for elastic serving",
    )
    args = ap.parse_args()
    run(
        classifier=args.classifier,
        devices=args.devices,
        shard_loss=args.shard_loss,
        seed=args.seed,
        fail_on_slo=args.fail_on_slo,
    )
