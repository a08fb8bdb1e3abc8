"""Chip smoke test: the streaming KWS server on a TPU, checked against the CPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded ("stream",) mesh only

One chip: for the qat, integer, delta (θ=0.15) and a cascaded integer
pipeline at the paper's width (16-channel FEx, 2×GRU(48), 12 classes),
`StreamingKWSServer` opens 1024 streams with its default kernel dispatch
and serves raw 16 ms audio hops: 8 ticks through
`step_batch`, one `run_batch` window and one `PipelinedIngress` window.
The same ticks are replayed in this process through the same tick on
the host CPU device and compared within the tolerances below, each with
its reason. Each pipeline is then served FV_Norm frames made by the CPU
frontend from the same audio, on the TPU and on the CPU: there every
state leaf (codes, counters, latches, Q6.8 activations) and every
tick's `top` must be equal as arrays.

Four chips: a `devices=4` server against the one-device server on the
same traffic (bit-identical scores and state), the device set of every
state leaf, one live `resize` and one `recover_shard_loss`.

Runs in one process and starts no other. Exits non-zero, printing no
result, when JAX finds no TPU, when any phase fails, or when a kernel
that should run compiled resolves to `interpret` or `reference`. The
last line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the CPU replay needs the host backend next to the TPU
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import quant  # noqa: E402
from repro.core.fex import FExNormStats, fit_norm_stats  # noqa: E402
from repro.core.gru_delta import DeltaConfig  # noqa: E402
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig  # noqa: E402
from repro.data.gscd import make_dataset  # noqa: E402
from repro.kernels.dispatch import force_dispatch  # noqa: E402
from repro.kernels.intgemm.ops import resolve_intgemm_dispatch  # noqa: E402
from repro.serving.cascade import CascadeConfig  # noqa: E402
from repro.serving.ingress import PipelinedIngress  # noqa: E402
from repro.serving.serve_loop import StreamingKWSServer  # noqa: E402

N_STREAMS = 1024
N_LIVE = 8  # step_batch ticks before the windows
WINDOW = 4  # ticks in the run_batch window and in the ingress window
N_TICKS = N_LIVE + 2 * WINDOW
THETA = 0.15
# Where the TPU and the CPU may differ, and by how much. Streams are
# independent, so the comparison is per stream: a stream "differs" when
# any of its state leaves (codes, counters, latches, Q6.8 activations)
# is unequal after the last tick or its posteriors differ by more than
# softmax rounding at any tick. On every other stream `top` must be
# equal at every tick. The number of differing streams is bounded:
#  * on identical FV_Norm frames, 0: every backend computes its
#    classifier dots at float32 precision and reads sigmoid/tanh from
#    the host-built ROMs, so the codes are the CPU's;
#  * on raw audio the frontend's biquad carry rounds in float32 on two
#    backends (9.4e-7 measured over 16 ticks on a v5e), and a 12-bit
#    FV_Raw code flips where the two filter outputs straddle a rounding
#    edge (3 of 262144 codes measured, on 3 or 4 streams): that stream
#    differs from then on. The bound is twice the measured count.
FV_MAX_STREAMS = 0
RAW_MAX_STREAMS = 8
CARRY_ATOL = 1e-5
# Posteriors are a softmax of equal logits on streams that agree; the
# backends' exp and divide differ in the last bits only.
SOFTMAX_ATOL = 1e-6
# On a differing stream a flipped code moves a posterior by a few Q6.8
# steps (1/256); the bound is 4 of them, and `top` may change there only
# where the CPU's top-1 lead is within twice this bound.
SCORE_ATOL = 1 / 64

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Counts backend compiles and their seconds, and cache hits."""
    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg):
    print(msg, flush=True)


def norm_stats():
    """FV_Log mean/std over synthetic keyword audio, as host arrays."""
    audio = make_dataset(2, seed=1)["audio"]
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(jnp.asarray(audio, jnp.float32))
    st = fit_norm_stats(quant.log_compress_lut(raw, 12, 10))
    return np.asarray(st.mu), np.asarray(st.sigma)


def traffic(hop, seed=0):
    """(N_TICKS, N, hop) raw audio and (N_TICKS, N) submit masks.

    Each stream plays a synthetic keyword clip from a random offset at
    a gain from -40 dB to 0 dB; every 16th stream is near-silent, and
    each stream skips about one tick in eleven.
    """
    rng = np.random.default_rng(seed)
    clips = make_dataset(2, seed=seed)["audio"].astype(np.float32)
    n_hops = clips.shape[1] // hop
    slab = np.zeros((N_TICKS, N_STREAMS, hop), np.float32)
    for s in range(N_STREAMS):
        clip = clips[s % len(clips)]
        gain = 0.0 if s % 16 == 0 else 10.0 ** rng.uniform(-2.0, 0.0)
        start = int(rng.integers(0, n_hops - N_TICKS))
        seg = clip[start * hop:(start + N_TICKS) * hop].reshape(N_TICKS, hop)
        slab[:, s] = gain * seg
    slab += rng.standard_normal(slab.shape).astype(np.float32) * 1e-3
    t = np.arange(N_TICKS)[:, None]
    s = np.arange(N_STREAMS)[None, :]
    mask = (s + 3 * t) % 11 != 0
    return slab, mask


def make_pipeline(kind, mu, sigma):
    classifier, cascade = {
        "qat": ("qat", None),
        "integer": ("integer", None),
        "delta": ("delta", None),
        "cascade-integer": ("integer", CascadeConfig(wake_threshold=0.1)),
    }[kind]
    cfg = KWSPipelineConfig(
        classifier=classifier, delta=DeltaConfig(THETA, THETA),
        cascade=cascade,
    )
    return KWSPipeline(cfg, norm_stats=FExNormStats(mu=mu, sigma=sigma))


def drive(srv, slab, mask, compiles):
    """Serve the traffic through the three ingress paths.

    Returns per-tick scores and tops, the seconds of the first call of
    each path, and the number of compiles on any later call."""
    first = {}
    for sid in range(srv.max_streams):
        srv.open_stream(sid)
    scores, tops = [], []
    late = 0
    for t in range(N_LIVE):
        c0, t0 = compiles.n, time.perf_counter()
        s, k = srv.step_batch(slab[t], mask[t])
        if t == 0:
            first["step_batch"] = time.perf_counter() - t0
        else:
            late += compiles.n - c0
        scores.append(s)
        tops.append(k)
    w = slice(N_LIVE, N_LIVE + WINDOW)
    t0 = time.perf_counter()
    s, k = srv.run_batch(slab[w], mask[w])
    first["run_batch"] = time.perf_counter() - t0
    scores.extend(s)
    tops.extend(k)
    c0 = compiles.n
    ing = PipelinedIngress(srv, dim=slab.shape[-1], window=WINDOW)
    for t in range(N_LIVE + WINDOW, N_TICKS):
        buf, m = ing.stage()
        buf[...] = slab[t]
        m[...] = mask[t]
        ing.commit(meta=t)
    (h,) = ing.drain()
    scores.extend(h.scores)
    tops.extend(h.top)
    late += compiles.n - c0
    return np.stack(scores), np.stack(tops), first, late


def serve_on_chip(tag, pipe, params, slab, mask, compiles, errors):
    """Default server on the TPU; logs what it resolved and compiled."""
    srv = StreamingKWSServer(pipe, params, max_streams=N_STREAMS)
    intgemm = None  # the float-domain backends call no intgemm
    if pipe.config.classifier_key in ("integer", "delta-int"):
        intgemm = resolve_intgemm_dispatch()
        if intgemm != "pallas":
            errors.append(f"{tag}: intgemm resolved to {intgemm!r} on TPU")
    c0, s0 = compiles.n, compiles.seconds
    sc, top, first, late = drive(srv, slab, mask, compiles)
    state = jax.device_get(srv.state)
    log(f"  [{tag}] intgemm={intgemm} "
        f"compile_count={srv.compile_count} retrace_count="
        f"{srv.retrace_count} compiles={compiles.n - c0} compile_s="
        f"{compiles.seconds - s0:.2f} first_call_s="
        + json.dumps({k: round(v, 3) for k, v in first.items()})
        + f" compiles_after_warmup={late}")
    if srv.retrace_count != 2:  # one tick and one window program
        errors.append(f"{tag}: retrace_count {srv.retrace_count} != 2")
    if late:
        errors.append(f"{tag}: {late} compiles after warm-up")
    if not np.all(np.isfinite(sc)):
        errors.append(f"{tag}: non-finite scores")
    return srv, (sc, top, state)


def replay_on_cpu(name, mu, sigma, params, slab, mask, compiles):
    """The same ticks through the reference kernels on the host CPU
    device."""
    with jax.default_device(jax.devices("cpu")[0]), \
            force_dispatch("reference"):
        pipe = make_pipeline(name, mu, sigma)
        ref = StreamingKWSServer(pipe, jax.tree.map(np.asarray, params),
                                 max_streams=N_STREAMS)
        sc, top, _, _ = drive(ref, slab, mask, compiles)
        return sc, top, jax.device_get(ref.state)


def cpu_features(name, mu, sigma, slab, mask):
    """FV_Norm frames of the traffic from the CPU frontend, with each
    stream's filter carry advancing only on ticks it submitted."""

    with jax.default_device(jax.devices("cpu")[0]):
        pipe = make_pipeline(name, mu, sigma)
        carry = pipe.streaming_features_init(N_STREAMS)
        frames = []
        for t in range(N_TICKS):
            new, fv = pipe.streaming_features_step(carry, slab[t])
            m = mask[t][:, None]
            carry = jax.tree.map(lambda a, b: np.where(m, a, b), new, carry)
            frames.append(np.asarray(fv))
    return np.stack(frames)


def _leaves(state):
    return [(jax.tree_util.keystr(p), x)
            for p, x in jax.tree_util.tree_leaves_with_path(state)]


def compare(tag, tpu, cpu, max_streams, errors):
    """Per-stream TPU vs CPU comparison (see the limits above)."""
    (sc_t, top_t, st_t), (sc_c, top_c, st_c) = tpu, cpu
    d_sc = np.abs(sc_t - sc_c).max(axis=(0, 2))  # per stream
    differ = d_sc > SOFTMAX_ATOL
    info, d_carry = {}, 0.0
    for (key, a), (_, b) in zip(_leaves(st_t), _leaves(st_c)):
        a, b = np.asarray(a), np.asarray(b)
        if ".carry" in key:
            d_carry = max(d_carry, float(np.abs(a - b).max()))
        elif key != ".scores":
            ne = (a != b).reshape(N_STREAMS, -1)
            info[key] = int(ne.sum())
            differ |= ne.any(axis=1)
    n_differ = int(differ.sum())
    if n_differ > max_streams:
        errors.append(f"{tag}: {n_differ} streams differ > {max_streams}")
    if d_carry > CARRY_ATOL:
        errors.append(f"{tag}: carry max |diff| {d_carry:.3g} > "
                      f"{CARRY_ATOL:.3g}")
    flips = top_t != top_c  # (ticks, streams)
    n_same = int(flips[:, ~differ].sum())
    if n_same:
        errors.append(f"{tag}: top differs in {n_same} stream-ticks of "
                      f"streams that agree")
    srt = np.sort(sc_c, axis=-1)
    clear = srt[..., -1] - srt[..., -2] > 2 * SCORE_ATOL
    n_clear = int((flips & clear).sum())
    if n_clear:
        errors.append(f"{tag}: top differs in {n_clear} stream-ticks "
                      f"with a clear lead")
    d_max = float(d_sc.max())
    if d_max > SCORE_ATOL:
        errors.append(f"{tag}: scores max |diff| {d_max:.3g} > "
                      f"{SCORE_ATOL:.3g}")
    log(f"  [{tag}] tpu vs cpu: {n_differ} of {N_STREAMS} streams differ "
        f"(limit {max_streams}); top differs in {int(flips.sum())} of "
        f"{flips.size} stream-ticks, all on differing streams; clear "
        f"top-1 lead in {float(clear.mean()):.3f} of stream-ticks; scores "
        f"max |diff| {d_max:.3g} (agreeing streams "
        f"{float(d_sc[~differ].max(initial=0.0)):.3g}); carry max |diff| "
        f"{d_carry:.3g}; state entries differing {json.dumps(info)}")


def phase_one_chip(name, mu, sigma, slab, mask, compiles):
    """Raw audio, then the CPU frontend's FV_Norm frames of the same
    audio, each on the TPU and replayed on the CPU."""
    errors = []
    pipe = make_pipeline(name, mu, sigma)
    params = pipe.init_params(jax.random.PRNGKey(0))
    srv, tpu = serve_on_chip(name, pipe, params, slab, mask, compiles,
                             errors)
    cpu = replay_on_cpu(name, mu, sigma, params, slab, mask, compiles)
    compare(name, tpu, cpu, RAW_MAX_STREAMS, errors)
    if srv.state.det is not None:
        log(f"  [{name}] mean wake rate "
            f"{float(np.mean(srv.wake_rate)):.3f}")
    if pipe.config.classifier_key.startswith("delta"):
        log(f"  [{name}] mean effective-MAC fraction "
            f"{float(np.mean(srv.sparsity)):.3f}")
    fv = cpu_features(name, mu, sigma, slab, mask)
    tag = f"{name}/fv"
    _, tpu = serve_on_chip(tag, make_pipeline(name, mu, sigma), params,
                           fv, mask, compiles, errors)
    cpu = replay_on_cpu(name, mu, sigma, params, fv, mask, compiles)
    compare(tag, tpu, cpu, FV_MAX_STREAMS, errors)
    return errors


def _by_stream(srv, ids):
    """Every state leaf's rows for stream ids, as host arrays."""
    slots = [srv.active[i] for i in ids]
    return [np.asarray(x)[slots] for x in jax.tree_util.tree_leaves(srv.state)]


def _device_sets(srv):
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(srv.state):
        out[jax.tree_util.keystr(path)] = sorted(d.id for d in x.devices())
    return out


def phase_four_chips(mu, sigma, slab, mask):
    """devices=4 vs one device, then resize and shard-loss recovery."""
    errors = []
    ids = list(range(N_STREAMS))
    for name in ("integer", "qat"):
        pipe = make_pipeline(name, mu, sigma)
        params = pipe.init_params(jax.random.PRNGKey(0))
        one = StreamingKWSServer(pipe, params, max_streams=N_STREAMS)
        four = StreamingKWSServer(pipe, params, max_streams=N_STREAMS,
                                  devices=4)
        for srv in (one, four):
            for sid in ids:
                srv.open_stream(sid)
        sets = _device_sets(four)
        log(f"  [{name}] leaf device sets: {json.dumps(sets)}")
        spread = [k for k, v in sets.items() if len(v) != 4]
        if spread:
            errors.append(f"{name}: leaves not on 4 devices: {spread}")
        per_dev = {
            k: sorted({s.data.shape[0] for s in x.addressable_shards})
            for k, x in zip(sets, jax.tree_util.tree_leaves(four.state))
        }
        if any(v != [N_STREAMS // 4] for v in per_dev.values()):
            errors.append(f"{name}: shard rows {per_dev}")

        def tick(t):
            a = one.step_batch(
                _slab_for(one, slab[t], ids), _slab_for(one, mask[t], ids)
            )
            b = four.step_batch(
                _slab_for(four, slab[t], ids), _slab_for(four, mask[t], ids)
            )
            return a, b

        def same(tag, subset):
            for x, y in zip(_by_stream(one, subset), _by_stream(four, subset)):
                if not np.array_equal(x, y):
                    errors.append(f"{name}: state differs {tag}")
                    return

        for t in range(N_LIVE):
            (sa, ta), (sb, tb) = tick(t)
            rows_a = sa[[one.active[i] for i in ids]]
            rows_b = sb[[four.active[i] for i in ids]]
            if not np.array_equal(rows_a, rows_b):
                errors.append(f"{name}: scores differ at tick {t}")
        same("after live ticks", ids)
        # live resize: both servers grow; survivors move bitwise
        for srv in (one, four):
            srv.resize(2 * N_STREAMS)
        same("after resize", ids)
        for t in range(N_LIVE, N_LIVE + 2):
            tick(t)
        same("after ticks at the new capacity", ids)
        # shard loss: survivors keep their state, the lost ones reopen
        info = four.recover_shard_loss(1)
        survivors = info["survivors"]
        same("of survivors after shard loss", survivors)
        for t in range(N_LIVE + 2, N_LIVE + 4):
            tick(t)
        same("of survivors after post-recovery ticks", survivors)
        log(f"  [{name}] devices=4 == one device over {N_LIVE} ticks; "
            f"resize to {2 * N_STREAMS}; shard loss -> "
            f"{info['n_devices']} devices, {info['max_streams']} slots, "
            f"{len(survivors)} survivors, {len(info['reopened'])} "
            f"reopened; post-recovery device sets "
            f"{sorted(set(map(tuple, _device_sets(four).values())))}")
    return errors


def _slab_for(srv, rows, ids):
    """Scatter per-stream rows (in id order) into a slot-major slab."""
    out = np.zeros((srv.max_streams,) + rows.shape[1:], rows.dtype)
    out[[srv.active[i] for i in ids]] = rows[: len(ids)]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform!r})", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1


    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")
    compiles = Compiles()
    mu, sigma = norm_stats()
    hop = KWSPipeline(KWSPipelineConfig()).chunk_samples
    slab, mask = traffic(hop)
    errors = []
    t0 = time.perf_counter()
    if args.chips == 4:
        errors += phase_four_chips(mu, sigma, slab, mask)
    else:
        for name in ("qat", "integer", "delta", "cascade-integer"):
            errors += phase_one_chip(name, mu, sigma, slab, mask, compiles)
    log(f"total: {compiles.n} compiles, {compiles.seconds:.2f} s compiling, "
        f"{compiles.cache_hits} persistent-cache hits, "
        f"{time.perf_counter() - t0:.1f} s")
    if errors:
        for e in errors:
            print(f"FAIL {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
