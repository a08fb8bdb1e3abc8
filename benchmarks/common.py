"""Shared benchmark machinery: dataset prep, feature recording, and the
QAT classifier training loop (the paper's recipe: AdamW 1e-3, wd 0.01,
ReduceLROnPlateau 0.8/3, floor 5e-4 — Section III-F)."""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.classifier import get_classifier, resolve_classifier_key
from repro.core.fex import FExConfig, FExNormStats, fex_frames
from repro.core.gru import (
    GRUConfig,
    gru_classifier_forward,
    init_gru_classifier,
)
from repro.data.gscd import make_dataset
from repro.training.optimizer import (
    AdamWConfig,
    ReduceLROnPlateau,
    adamw_update,
    init_opt_state,
)

QUICK = os.environ.get("BENCH_FULL", "0") != "1"

# quick mode: enough samples for the claims' *ordering* to be stable;
# BENCH_FULL=1 scales everything up.
N_TRAIN = 24 if QUICK else 120  # per class
N_TEST = 10 if QUICK else 40
EPOCHS = 60 if QUICK else 200


def datasets(seed: int = 0):
    train = make_dataset(N_TRAIN, seed=seed, unknown_split="train")
    test = make_dataset(N_TEST, seed=seed + 1, unknown_split="test")
    return train, test


@functools.partial(jax.jit, static_argnums=(1,))
def _frames_batch(audio, cfg: FExConfig):
    return fex_frames(audio, cfg)


def record_software_frames(audio: np.ndarray, cfg: FExConfig,
                           batch: int = 64) -> np.ndarray:
    outs = []
    for i in range(0, len(audio), batch):
        outs.append(np.asarray(_frames_batch(jnp.asarray(audio[i:i + batch]), cfg)))
    return np.concatenate(outs)


def frames_to_features(
    frames_or_raw: np.ndarray,
    cfg: FExConfig,
    use_log: bool,
    use_norm: bool,
    stats: Optional[FExNormStats] = None,
    already_raw: bool = False,
) -> Tuple[np.ndarray, Optional[FExNormStats]]:
    """Rectified frames (or recorded FV_Raw codes) -> classifier input."""
    if already_raw:
        fv_raw = jnp.asarray(frames_or_raw)
    else:
        fv_raw = quant.quantize_unsigned(
            jnp.asarray(frames_or_raw), cfg.quant_bits, cfg.quant_full_scale
        )
    x = fv_raw
    if use_log:
        x = quant.log_compress_lut(x, cfg.quant_bits, cfg.log_bits)
    if use_norm:
        if stats is None:
            flat = x.reshape(-1, x.shape[-1])
            stats = FExNormStats(
                mu=flat.mean(0), sigma=flat.std(0) + 1e-3
            )
        x = (x - stats.mu) / stats.sigma
    else:
        in_bits = cfg.log_bits if use_log else cfg.quant_bits
        x = x * 2.0 ** -(in_bits - 5)
    return np.asarray(quant.fake_quant(x, quant.ACT_Q6_8)), stats


def train_classifier(
    feats: np.ndarray,
    labels: np.ndarray,
    seed: int = 0,
    epochs: int = EPOCHS,
    batch: int = 64,
    verbose: bool = False,
) -> Dict:
    """QAT training of the 2x48 GRU-FC. Returns dict with params+curve."""
    gcfg = GRUConfig()
    params = init_gru_classifier(jax.random.PRNGKey(seed), gcfg)
    ocfg = AdamWConfig(lr=1e-3, weight_decay=0.01)
    opt = init_opt_state(params, ocfg)
    sched = ReduceLROnPlateau(1e-3, 0.8, 3, 5e-4)

    @jax.jit
    def step(params, opt, fv, y, lr):
        def loss_fn(p):
            logits = gru_classifier_forward(p, fv, gcfg)[:, -1, :]
            logz = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
            return jnp.mean(logz - gold)

        loss, g = jax.value_and_grad(loss_fn)(params)
        params, opt, _ = adamw_update(params, g, opt, ocfg, lr)
        return params, opt, loss

    n = len(labels)
    rng = np.random.default_rng(seed)
    lr = 1e-3
    history = []
    for epoch in range(epochs):
        order = rng.permutation(n)
        losses = []
        for i in range(0, n - n % batch, batch):
            sl = order[i:i + batch]
            params, opt, loss = step(
                params, opt, jnp.asarray(feats[sl]),
                jnp.asarray(labels[sl]), lr,
            )
            losses.append(float(loss))
        mean_loss = float(np.mean(losses))
        lr = sched.step(mean_loss)
        history.append(mean_loss)
        if verbose and epoch % 10 == 0:
            print(f"    epoch {epoch:3d} loss {mean_loss:.4f} lr {lr:.2e}")
    return {"params": params, "config": gcfg, "history": history}


def evaluate(model: Dict, feats: np.ndarray, labels: np.ndarray,
             batch: int = 128, classifier: Optional[str] = None):
    """Accuracy + confusion matrix through a registered classifier
    backend; ``classifier=None`` resolves from the model config (the
    QAT path), ``"integer"`` runs the bit-exact int8/Q6.8 engine. (The
    ΔGRU θ sweep needs per-example MAC fractions as well, so it drives
    `repro.core.gru_delta.delta_classifier_forward` directly — see
    benchmarks/fig_delta_tradeoff.py.)"""
    gcfg = model["config"]
    backend = get_classifier(resolve_classifier_key(classifier, gcfg))
    params = backend.prepare(model["params"], gcfg)

    @jax.jit
    def logits_fn(fv):
        return backend.forward(params, fv, gcfg)[:, -1, :]

    preds = []
    for i in range(0, len(labels), batch):
        preds.append(np.argmax(np.asarray(
            logits_fn(jnp.asarray(feats[i:i + batch]))), -1))
    preds = np.concatenate(preds)
    acc = float((preds == labels).mean())
    n_cls = int(labels.max()) + 1
    conf = np.zeros((n_cls, n_cls), np.int32)
    for t, p in zip(labels, preds):
        conf[t, p] += 1
    return acc, conf


def percentile_stats(latencies_s) -> Dict[str, float]:
    """Per-tick latency list (seconds) -> p50/p99/mean in milliseconds.

    This is the shared latency summary of the serving benchmarks; the
    field names match what `benchmarks/serve_load.py` writes to
    ``BENCH_serve.json``:

      backend        jax backend the sweep ran on ("cpu" / "tpu" / ...)
      frontend       registered FeatureFrontend of the benched pipeline
      classifiers    registered ClassifierBackend keys the sweep covered
      theta          ΔGRU threshold (Q6.8 value units) the delta rows
                     ran at (--theta; dense rows are unaffected)
      cascade        True when the sweep served every non-legacy point
                     with the stage-1 wake gate
                     (`repro.serving.cascade`; --cascade)
      wake_threshold energy-detector wake threshold the cascaded sweep
                     ran at (--wake-threshold; None when cascade is
                     False)
      devices        device counts the sweep covered (counts > 1 bench
                     the stream-parallel server on a ("stream",) mesh)
      quick          True when the quick (CI-sized) sweep ran
      results[]      one entry per (classifier, mode, kind, devices,
                     max_streams, occupancy):
        classifier     registered ClassifierBackend of the point: "qat"
                       (fake-quant float tick), "integer" (bit-exact
                       int8/Q6.8 engine, weight codes resident), or
                       "delta"/"delta-int" (temporal-sparsity ΔGRU at
                       the sweep's theta); "legacy" mode exists only
                       for "qat" (the pre-refactor path had no integer
                       or delta engine)
        mode           "fused" (one jitted tick per step_batch call),
                       "legacy" (pre-refactor per-stream path),
                       "pipelined" (live async ingress:
                       `repro.serving.ingress.PipelinedIngress` over
                       step_batch_async/run_batch_async — double-
                       buffered staging, non-blocking dispatch,
                       deferred fetch, window-tick coalescing), or
                       "scan" (run_batch lax.scan replay: ONE device
                       program and one host round-trip for the whole
                       tick sequence, so there is no per-tick latency
                       to measure)
        kind           tick payload: "fv" = precomputed FV_Norm frames
                       (isolates serving-path overhead), "audio" = raw
                       16 ms hops (adds the frontend filter scan, a
                       cost shared by every mode)
        jax_backend    jax backend the row ran on (repeated per row so
                       rows merged across artifacts stay attributable)
        devices        device count the row ran on; > 1 means the slot
                       axis was sharded over a ("stream",) mesh (bit-
                       identical to devices=1 — the row measures pure
                       throughput, tests/test_serve_sharded.py proves
                       the equality)
        max_streams    server slot capacity for the point
        occupancy      fraction of slots with an open, submitting stream
        active_streams occupancy * max_streams, rounded, >= 1
        n_ticks        measured ticks (after warmup)
        ticks_per_s    sustained tick throughput. For the blocking
                       per-call modes (fused/legacy) this is
                       1 / mean(latency); for scan and pipelined it is
                       n_ticks / wall-clock — pipelined ticks overlap,
                       so the reciprocal mean would overcount
        streams_per_s  ticks_per_s * active_streams (stream-frames/sec)
        window         pipelined rows only: ticks coalesced into one
                       scan dispatch by the ingress (the throughput/
                       latency knob); None for every other mode
        sparsity       measured effective-MAC fraction, mean over the
                       point's active streams (the `srv.sparsity`
                       telemetry): < 1.0 for the ΔGRU backends when
                       their traffic lets them skip, identically 1.0
                       for dense backends, None for the legacy path
                       (predates the telemetry)
        theta          ΔGRU threshold of the point's pipeline (None for
                       dense backends)
        wake_rate      measured classifier duty cycle, mean over the
                       point's active streams (the `srv.wake_rate`
                       telemetry): < 1.0 when the stage-1 cascade gate
                       held the classifier asleep for part of the
                       traffic, identically 1.0 for ungated sweeps,
                       None for the legacy path (predates the
                       telemetry)
        wake_threshold stage-1 wake threshold of the point's pipeline
                       (None when the sweep ran without --cascade)
        retraces       counted jit retraces the row's server paid: the
                       exact number of (program, operand-shape) keys
                       first-dispatched since construction
                       (`srv.retrace_count` — the counter
                       benchmarks/churn_load.py uses to exclude compile
                       ticks exactly). 1 for a steady-state row (the
                       warmup tick traces once); None for the legacy
                       path, which predates the counter
        spans          pipelined rows only: per-span duration rollups
                       of the row's `TickTrace` ring
                       (repro.serving.metrics.span_percentiles) — span
                       name ("stage_to_commit" / "commit_to_dispatch" /
                       "dispatch_to_retire" / "total") ->
                       {count, p50_ms, p99_ms, mean_ms}. The
                       dispatch_to_retire span is the device-side
                       residency; None for every other mode (the
                       blocking modes have no pipeline stages)
        p50_ms/p99_ms  per-tick wall latency percentiles. Null for scan
                       rows: the replay returns to the host once, so
                       per-tick percentiles do not exist there (they
                       used to be fabricated as wall/n_ticks repeated,
                       which made p50==p99==mean look measured).
                       Fused/legacy rows measure each blocking call;
                       pipelined rows measure real submit-to-scores
                       latency per tick (commit timestamp to handle
                       retirement — the SLO-relevant number)
        mean_ms        mean per-tick wall latency (scan rows: the
                       amortized wall/n_ticks, the only latency-like
                       number a single-program replay has)
      scaling[]      per device count: sustained scan-fv ticks/sec at
                     256 streams and the ratio vs the devices=1 row
                     (on emulated CPU meshes this measures SPMD
                     overhead, on real multi-chip platforms the
                     stream-parallel scaling curve)
      claim          the checked headline ("ok" bool): sustained
                     fused-tick throughput (scan driver) >= 5x legacy
                     ticks/sec at 256 streams, full occupancy, fv kind,
                     devices=1; "speedup_live" carries the per-call
                     fused ratio
      slo            the live-serving latency gate ("ok" bool, also
                     "p99_ok"/"ratio_ok"): pipelined p99 <= the 16 ms
                     tick budget at 256 streams AND pipelined
                     throughput >= 0.5x the scan ceiling at 64 and 256
                     streams ("pipelined_vs_scan", keyed by stream
                     count), all at full occupancy, fv kind, devices=1
                     on the sweep's first classifier;
                     `--fail-on-slo` exits non-zero when violated
      metrics_overhead
                     the observability cost gate ("ok" bool): a
                     metrics-enabled server's fused tick vs a
                     metrics-off twin at 256 streams, fv, full
                     occupancy, devices=1 (best-of-3 INTERLEAVED round
                     means, so platform drift hits both arms equally) —
                     mean_ms_metrics_off / mean_ms_metrics_on,
                     overhead_frac (on/off - 1), budget_frac (0.05),
                     ok = overhead_frac < budget_frac. `--fail-on-slo`
                     exits non-zero when violated. The full registry
                     snapshot of the metrics-on server (plus the
                     deployment-relevant 256-stream sweep points) is
                     written to ``METRICS_serve.json`` next to the
                     BENCH artifact
    """
    lat = np.asarray(latencies_s, np.float64) * 1e3
    return {
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "mean_ms": float(lat.mean()),
    }


BENCH_CHURN_FIELDS = """\
Field reference for ``BENCH_churn.json`` (written by
benchmarks/churn_load.py — elastic serving under open/close churn,
live autoscaler resizes, and injected shard loss):

  backend          jax backend the run executed on ("cpu"/"tpu"/...)
  classifier       registered ClassifierBackend the traffic was served
                   with (--classifier)
  devices_initial  device count the server was built on (--devices)
  devices_final    device count at exit — smaller than initial exactly
                   when --shard-loss shrank the mesh mid-run
  seed             traffic RNG seed (--seed)
  quick            True when the quick (CI-sized) schedule ran
  policy           the AutoscalePolicy the run was driven by:
                   min_streams / max_streams (capacity clamp),
                   grow_at / shrink_at (occupancy watermarks),
                   hysteresis_ticks (consecutive breaches before an
                   act), cooldown_ticks (dead time after an act), and
                   factor (the grow/shrink multiple)
  phases[]         one entry per schedule phase (ramp / peak / drain):
    phase            phase name
    ticks            ticks driven in the phase
    p50_ms/p99_ms/mean_ms
                     steady-state per-tick `step_batch` wall latency —
                     compile ticks are EXCLUDED here and recorded under
                     resize.post_change_compile_ms instead. A compile
                     tick is identified EXACTLY: the server's
                     shape-keyed retrace counter (`srv.retrace_count`)
                     incremented across the call. (The old heuristic —
                     "skip the first tick after any capacity change" —
                     missed recompiles it didn't predict and excluded
                     warm cache-hit ticks after a resize back to a
                     seen capacity)
    ticks_per_s      1e3 / mean_ms (blocking per-call cadence)
    mean_active      mean open-stream count over the phase's ticks
    capacity_end     server max_streams when the phase ended
    opens/closes     streams opened / closed during the phase
    rejections       open_stream calls refused at capacity (each one
                     also fed the autoscaler's note_rejection — the
                     immediate grow signal)
  resize           the elasticity trace:
    events[]         the Autoscaler event log, one entry per capacity
                     change: {step, action ("grow"/"shrink"), from, to}
    count            len(events)
    pause_ms[]       in-band wall time of each autoscaler-triggered
                     resize() call (state relay + re-placement; the
                     serving pause the tick loop actually felt)
    max_pause_ms     max(pause_ms), null when no resize fired
    post_change_compile_ms[]
                     wall time of each excluded compile tick (every
                     tick whose dispatch traced a fresh program:
                     first tick at a new slot width, plus the first
                     tick after shard-loss recovery, which rebuilds
                     the jitted programs on the shrunken mesh)
    retraces         `srv.retrace_count` at exit — the exact number of
                     (program, shape) first-dispatches the run paid;
                     len(post_change_compile_ms) equals the retraces
                     the tick loop itself triggered
    compiles         `srv.compile_count` at exit: program rebuilds
                     (construction + one per shard-loss recovery)
  shard_loss       null without --shard-loss, else the injected-loss
                   record:
    step               global tick index the loss was injected at
    lost_shard         mesh index of the lost shard
    recovery_ms        wall time of recover_shard_loss (host state
                       relay + mesh rebuild + program recompile +
                       reopening the lost streams)
    reopened           streams that lived on the lost shard, reopened
                       (same ids) on fresh zeroed slots
    survivors          streams on healthy shards
    survivors_checked  survivors bit-verified by the bench
    healthy_bit_unchanged
                       True when every survivor's per-slot state was
                       bitwise identical through the move (the
                       recovery contract, re-checked on the bench's
                       own traffic; gates slo.elastic_ok)
    n_devices_after / max_streams_after
                       mesh and capacity after recovery (capacity is
                       rounded UP to whole blocks of the surviving
                       device count)
  totals           run-wide counters: ticks, opens, closes, arrivals
                   (offered opens, accepted + rejected), rejections,
                   stream_frames (sum of active streams over ticks),
                   wall_s, stream_frames_per_s
  slo              the churn SLO gate ("ok" bool, and the per-clause
                   p99_ok / rejection_ok / elastic_ok): steady-state
                   PEAK-phase p99 <= the 16 ms tick budget, rejection
                   rate (rejections / arrivals) <= 10%, and the
                   elasticity smoke — the autoscaler grew during ramp
                   AND shrank during drain, and (when injected) shard
                   loss left every healthy stream bit-unchanged.
                   `--fail-on-slo` exits non-zero when violated

The run's full `srv.metrics_snapshot()` — tick histograms, occupancy
gauges, and the structured event journal (every autoscale / resize /
retrace / shard-loss event with its reason, in order) — is written to
``METRICS_churn.json`` next to the BENCH artifact.
"""


def timed(name):
    class _T:
        def __enter__(self):
            self.t0 = time.time()
            return self

        def __exit__(self, *a):
            print(f"  [{name}: {time.time() - self.t0:.1f}s]")

    return _T()
