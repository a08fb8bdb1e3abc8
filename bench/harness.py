"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell needs is found by name: the cell in BENCHMARK.json,
its configuration in ``configs/``, its traffic mix in ``traffic/``, each
per-layer metric's reader in ``metrics/<name>.py`` and the plain
reference in ``bench/<reference>.py``, which the configuration may name
(default ``reference``).

What a stream sends is the mix's ``input``: "fv" (the default), the
16-channel FV_Norm frame of each hop, made by the reference's frontend
so that the server's frontend is bypassed; or "audio", the raw hop of
``hop_samples`` samples, so that the server runs its own frontend on the
device (`StreamingKWSServer` picks the program by the slab's width). The
server is built from the configuration's keys, the optional ``delta``
(ΔGRU thresholds, `DeltaConfig`) and ``tdfex`` (the time-domain
frontend's parameters, `TDFExConfig`) among them.

A traced run profiles the window's first ``trace_ticks`` ticks and hands
each per-layer reader one context: the configuration and mix, the trace's
device summary (`bench.trace.summarize`), the slice's host timings and
``spans``, the readings of the program's own spans and device scopes
(`bench.spans.readings`, ``scope_ms`` holding every ``kws_*`` scope's
device ms per tick), made with the tick program the window ran, compiled
again once the window has closed. An untraced run reads no per-layer
metric.

Open loop ("open" mixes): tick k is due at t0 + k * hop_ms for every
stream. The main thread waits for each due time, stages the tick into
`PipelinedIngress` and commits it; a second thread fetches each tick's
scores as soon as the device has them. A hop's latency runs from its due
time to the moment its scores are on the host, so a stall delays every
later hop too. Drain mixes commit as fast as the ingress takes ticks.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import importlib
import importlib.util
import json
import math
import pathlib
import queue
import re
import shutil
import sys
import tempfile
import threading
import time
import types

import numpy as np

from bench import check, model, ops
from bench import spans as spans_lib
from bench import trace as trace_lib
from bench import traffic as traffic_lib

ROOT = pathlib.Path(__file__).resolve().parents[1]
METRICS = pathlib.Path(__file__).resolve().parent / "metrics"
LATE_CAP_S = 60.0  # how long past the window's close a due hop may come
KERNEL = "intgemm"  # substring of the classifier GEMM kernel's op names

clock = time.perf_counter


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def spec(workload: str):
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    return bench, cells[workload]


class Fetcher:
    """Fetches tick handles in order on its own thread and stamps the
    moment each tick's scores are on the host."""

    def __init__(self, sample_slots, span):
        self.q: queue.SimpleQueue = queue.SimpleQueue()
        self.sample_slots = sample_slots
        self.span = span
        self.done_at: dict = {}
        self.scores: dict = {}
        self.top: dict = {}
        self.error = None
        self.n_done = 0
        self.cv = threading.Condition()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            k, handle = item
            try:
                with self.span("fetch"):
                    scores, top = handle.result()
                t = clock()
                self.scores[k] = scores[self.sample_slots]
                self.top[k] = top[self.sample_slots]
            except Exception as e:  # a failed tick: its hops never come
                self.error = repr(e)
                t = None
            with self.cv:
                self.done_at[k] = t
                self.n_done += 1
                self.cv.notify_all()

    def wait_for(self, n: int, timeout=None) -> bool:
        with self.cv:
            return self.cv.wait_for(lambda: self.n_done >= n, timeout)

    def close(self):
        self.q.put(None)
        self.thread.join()


def nearest_rank(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return math.inf
    i = max(0, math.ceil(q * len(sorted_vals)) - 1)
    return sorted_vals[i]


def sample_streams(n: int, k: int, seed: int) -> np.ndarray:
    """The streams whose every hop the check compares, drawn from the seed."""
    rng = np.random.default_rng([seed, 4])
    return np.sort(rng.choice(n, min(k, n), replace=False))


def build_server(cfg, floats, norm, n_streams, chips):
    import jax.numpy as jnp

    from repro.core.fex import FExConfig, FExNormStats
    from repro.core.gru import GRUConfig
    from repro.core.gru_delta import DeltaConfig
    from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
    from repro.core.tdfex import TDFExConfig
    from repro.serving.cascade import CascadeConfig
    from repro.serving.serve_loop import StreamingKWSServer

    fex = FExConfig(
        num_channels=cfg["num_channels"], fs_audio=float(cfg["fs_audio"]),
        oversample=cfg["oversample"],
        frame_shift_ms=1000.0 * cfg["hop_samples"] / cfg["fs_audio"],
        f_lo=cfg["f_lo"], f_hi=cfg["f_hi"], q=cfg["q"],
        quant_bits=cfg["quant_bits"], log_bits=cfg["log_bits"],
        quant_full_scale=cfg["quant_full_scale"])
    gru = GRUConfig(input_dim=cfg["num_channels"],
                    hidden_dim=cfg["hidden_dim"],
                    num_layers=cfg["num_layers"],
                    num_classes=cfg["num_classes"])
    casc, delta, tdfex = (cfg.get(k) for k in ("cascade", "delta", "tdfex"))
    pcfg = KWSPipelineConfig(
        frontend=cfg["frontend"], fex=fex, gru=gru,
        classifier=cfg["classifier"],
        cascade=None if not casc else CascadeConfig(**casc),
        delta=None if delta is None else DeltaConfig(**delta),
        tdfex=None if tdfex is None else TDFExConfig(fex=fex, **tdfex))
    pipe = KWSPipeline(pcfg, norm_stats=FExNormStats(
        mu=jnp.asarray(norm["mu"]), sigma=jnp.asarray(norm["sigma"])))
    if pipe.chunk_samples != cfg["hop_samples"]:
        raise ValueError("configuration's hop does not match the server's")
    params = {"gru": [{k: jnp.asarray(v) for k, v in layer.items()}
                      for layer in floats["gru"]],
              "fc": {k: jnp.asarray(v) for k, v in floats["fc"].items()}}
    return StreamingKWSServer(
        pipe, params, max_streams=n_streams, smoothing=cfg["smoothing"],
        devices=chips if chips > 1 else None)


def _readers(bench, cell, e2e_names):
    """(name, unit, read) of the cell's per-layer metrics."""
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            if m["moves"] not in e2e_names:
                continue
        elif cell["name"] not in cells:
            continue
        path = METRICS / f"{m['name']}.py"
        sp = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        out.append((m["name"], m["unit"], mod.read))
    return out


def reference_module(cfg):
    """The plain reference the configuration names: ``bench/<name>.py``."""
    name = cfg.get("reference", "reference")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise ValueError(f"reference {name!r} is not a module name")
    return importlib.import_module(f"bench.{name}")


def input_kind(mix) -> str:
    """What each stream sends: "fv" frames (the default) or "audio"."""
    kind = mix.get("input", "fv")
    if kind not in ("fv", "audio"):
        raise ValueError(f"unknown input {kind!r}")
    return kind


def fv_pool(ref, cfg, norm, traffic) -> np.ndarray:
    """The pool's audio as FV_Norm frames, one row per hop, made by the
    reference's frontend over each track from its start."""
    n_tr = traffic.pool.shape[0] // traffic.n_hops
    audio = traffic.pool.reshape(n_tr, traffic.n_hops, -1)
    codes, _ = ref.frontend(
        cfg, norm, lambda a, b: audio[:, a:b].transpose(1, 0, 2),
        traffic.n_hops, n_tr)
    return np.ascontiguousarray(
        codes.transpose(1, 0, 2).reshape(n_tr * traffic.n_hops, -1)
        / np.float32(256.0), np.float32)


def replay(ref, cfg, norm, weights, traffic, mix, streams, n_ticks: int,
           dtype="float32"):
    """What the reference serves ``streams`` over their first ``n_ticks``
    ticks, and its frontend's final filter state (None for "fv" input).

    Raw audio goes through the reference's frontend from tick 0, each
    stream's hops read from the pool by the same `Traffic.rows` layout
    the window staged; uploaded frames go straight to the classifier.
    ``dtype`` is the frontend's precision (the control's bfloat16).
    """
    rows = [traffic.rows(t, streams) for t in range(n_ticks)]
    if input_kind(mix) == "audio":
        fv, carry = ref.frontend(
            cfg, norm, lambda a, b: np.stack([traffic.pool[r]
                                              for r in rows[a:b]]),
            n_ticks, len(streams), dtype=dtype)
    else:
        fv = np.stack([np.round(traffic.pool[r] * 256).astype(np.int64)
                       for r in rows])
        carry = None
    return ref.classifier(cfg, weights, fv), carry


def tick_program(srv, slab, mask) -> dict:
    """`spans.tick_map` of the tick program the window ran, for the slab's
    input kind; compiled from the persistent cache after warm-up."""
    fn = srv._tick_audio if srv._is_raw(slab.shape[-1]) else srv._tick_fv
    lowered = fn.lower(srv.params, srv.state, slab, mask,
                       srv.frontend_state, srv.smoothing)
    return spans_lib.tick_map(lowered.compile().as_text())


def find_chips(chips: int):
    """The devices JAX found; exits, printing no result, when they are not
    TPUs or fewer than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices "
                         "only")
    if len(devices) < chips:
        raise SystemExit(f"cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, mix_override: dict | None = None,
             keep_trace: str | None = None):
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.serving.ingress import PipelinedIngress

    bench, cell = spec(workload)
    cfg = model.load(cell["config"])
    ref = reference_module(cfg)
    mix = dict(traffic_lib.load(cell["traffic"]), **(mix_override or {}))
    chips = cell["chips"]
    seed = int(seed) % 2 ** 63
    enable_compile_cache()

    devices = find_chips(chips)
    platform, kind = devices[0].platform, devices[0].device_kind
    used = devices[:chips]

    # ---- set-up: data from the seed, the server, every shape warmed up
    phases = {}
    t_ph = clock()

    def phase(name):
        nonlocal t_ph
        phases[name] = clock() - t_ph
        t_ph = clock()

    hop = cfg["hop_samples"]
    codes, floats = model.weights(cfg, seed)
    norm = model.norm_stats(cfg, seed)
    traffic = traffic_lib.Traffic(mix, hop, seed)
    phase("audio")
    if input_kind(mix) == "fv":
        # the fleet uploads features: the server's frontend is bypassed
        traffic.pool = fv_pool(ref, cfg, norm, traffic)
        phase("features")
    dim = traffic.pool.shape[1]
    n = mix["streams"]
    srv = build_server(cfg, floats, norm, n, chips)
    phase("server")
    for sid in range(n):
        srv.open_stream(sid)
    phase("open")
    slot_of = np.array([srv.active[s] for s in range(n)])
    stream_at = np.empty(n, np.int64)
    stream_at[slot_of] = np.arange(n)
    base_slot = traffic.base[stream_at]
    off_slot = traffic.offsets[stream_at]
    sample = sample_streams(n, mix["check_streams"], seed)
    sample_slots = slot_of[sample]
    ing = mix["ingress"]
    if ing["window"] != 1:
        raise ValueError("the harness times one tick per dispatch")
    depth = ing["depth"]
    ingress = PipelinedIngress(srv, dim=dim, depth=depth, window=1)
    for _ in range(depth + 1):  # compiles or loads every program once
        slab, mask = ingress.stage()
        slab[:] = 0.0
        ingress.commit(None)
    ingress.drain()
    retraces0 = srv.retrace_count
    phase("warmup")
    log(f"set-up phases (s): { {k: round(v, 3) for k, v in phases.items()} }")
    # what set-up built lives for the whole run: keep the collector off it,
    # so that no full collection of the program's objects stalls the loop
    gc.collect()
    gc.freeze()

    span = jax.profiler.TraceAnnotation if trace else (
        lambda name: contextlib.nullcontext())
    fetcher = Fetcher(sample_slots, span)
    trace_dir = tempfile.mkdtemp(prefix="kws-trace-") if trace else None
    slice_ticks = mix["trace_ticks"] if trace else 0
    if trace:
        jax.profiler.start_trace(trace_dir)
    pool = traffic.pool
    hop_s = mix["hop_ms"] / 1000.0
    open_loop = mix["mode"] == "open"
    if not open_loop and mix["mode"] != "drain":
        raise ValueError(f"unknown mode {mix['mode']!r}")
    n_due = int(round(seconds / hop_s)) if open_loop else None
    dispatched_at, commit_s, late = {}, {}, []
    slice_span = None

    # ---- the measured window
    t0 = clock() + (0.005 if open_loop else 0.0)
    setup_s = t0 - t_start
    t_end = t0 + seconds
    k = 0
    while True:
        if open_loop:
            if k >= n_due or clock() > t_end + LATE_CAP_S:
                break
            due = t0 + k * hop_s
            now = clock()
            if now < due:
                with span("wait"):
                    time.sleep(due - now)
                late.append(clock() - due)
        elif clock() >= t_end:
            break
        if trace and k == 0:
            slice_span = span(trace_lib.SLICE)
            slice_span.__enter__()
        with span("stage"):
            # the ingress reuses the buffers of tick k - depth: its scores
            # must be on the host before they are overwritten
            fetcher.wait_for(k - depth + 1)
            slab, mask = ingress.stage()
            np.take(pool, base_slot + (off_slot + k) % traffic.n_hops,
                    axis=0, out=slab)
            mask[:] = True
        with span("dispatch"):
            ts = clock()
            handle = ingress.commit(k)
            commit_s[k] = clock() - ts
        dispatched_at[k] = ts
        fetcher.q.put((k, handle))
        k += 1
        if trace and k == slice_ticks:
            fetcher.wait_for(slice_ticks)
            slice_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    n_sent = k
    if trace and n_sent < slice_ticks:
        fetcher.wait_for(n_sent)
        slice_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    # drain: every dispatched tick is fetched (the FIFO retires in order)
    fetcher.wait_for(n_sent, timeout=LATE_CAP_S + max(0.0, t_end - clock()))
    ingress.drain()
    fetcher.close()
    retraces = srv.retrace_count - retraces0

    # ---- end-to-end numbers
    done = fetcher.done_at
    served_ticks = 0
    while served_ticks < n_sent and done.get(served_ticks) is not None:
        served_ticks += 1
    e2e = {"setup_s": setup_s}
    if open_loop:
        lat = [(done[i] - (t0 + i * hop_s)) if done.get(i) is not None
               else math.inf for i in range(n_due)]
        attempted = n * n_due
        missing = n * sum(1 for v in lat if math.isinf(v))
        lat_sorted = sorted(lat)
        e2e["hop_p99_ms"] = nearest_rank(lat_sorted, 0.99) * 1e3
        e2e["hop_p50_ms"] = nearest_rank(lat_sorted, 0.50) * 1e3
        met = sum(1 for v in lat if v <= hop_s) / len(lat)
        in_window = sum(1 for i in range(n_due)
                        if done.get(i) is not None and done[i] <= t_end)
        due_in_window = n_due
        log(f"generator lateness: p99 {nearest_rank(sorted(late), 0.99)*1e3:.3f}"
            f" ms, max {max(late, default=0.0)*1e3:.3f} ms over {len(late)} "
            f"on-time ticks")
        log(f"hops meeting {mix['hop_ms']} ms: {met*100:.2f}%; hop p99 "
            f"{e2e['hop_p99_ms']:.3f} ms, p50 {e2e['hop_p50_ms']:.3f} ms")
    else:
        in_window = sum(1 for i in range(n_sent)
                        if done.get(i) is not None and done[i] <= t_end)
        attempted = n * n_sent
        missing = n * (n_sent - served_ticks)
        e2e["hops_per_s"] = n * in_window / seconds
        due_in_window = n_sent
    log(f"backlog at the window's close: {due_in_window - in_window} ticks "
        f"({n_sent} dispatched, {in_window} served in the window)")
    log(f"compiles inside the window: {retraces}")
    if retraces:
        raise RuntimeError(f"{retraces} programs compiled inside the window")

    mem_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    served = {
        "scores": np.stack([fetcher.scores[i] for i in range(served_ticks)]),
        "top": np.stack([fetcher.top[i] for i in range(served_ticks)]),
        # a ΔGRU layer keeps its hidden codes as the dict's "h" leaf
        "h": [np.asarray(g["h"] if isinstance(g, dict) else g)[sample_slots]
              for g in srv.state.gru],
        "det": ({key: np.asarray(v)[sample_slots]
                 for key, v in srv.state.det.items()}
                if srv.state.det is not None else {}),
        "carry": {key: np.asarray(v)[sample_slots]
                  for key, v in srv.state.carry.items()},
    }
    if srv.state.det is not None:
        log(f"measured wake share: {float(np.mean(srv.wake_rate))*100:.2f}%")
    tick = tick_program(srv, slab, mask) if trace else None
    del srv, ingress, handle, slab, mask
    gc.collect()

    # ---- the check against the plain reference
    t_ref = clock()
    want, carry = replay(ref, cfg, norm, codes, traffic, mix, sample,
                         served_ticks)
    nums = check.numbers(served, want, missing, carry)
    correct, rows = check.verdict(nums, cfg["limits"])
    log(f"reference: {clock() - t_ref:.2f} s over {served_ticks} ticks x "
        f"{len(sample)} streams")

    # ---- per-layer metrics from the traced slice
    result_metrics, breakdown, device_extra = {}, None, {}
    if trace:
        path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        t_tr = clock()
        tr = trace_lib.from_xplane(path)
        summary = trace_lib.summarize(tr, KERNEL)
        sl = range(min(slice_ticks, served_ticks))
        lo, hi = trace_lib.slice_bounds(tr)
        program = spans_lib.readings(spans_lib.from_xplane(path, tick),
                                     lo, hi, len(sl))
        log(f"trace: {pathlib.Path(path).stat().st_size} bytes, reduced in "
            f"{clock() - t_tr:.2f} s")
        if keep_trace:
            trace_lib.to_json(tr, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            cfg=cfg, mix=mix, chips=chips, streams=n,
            peaks=ops.peaks(kind),
            summary=summary, slice_ticks=len(sl),
            commit_s=[commit_s[i] for i in sl],
            tick_s=[done[i] - dispatched_at[i] for i in sl],
            window_s=seconds, hops_in_window=n * in_window,
            spans=program)
        e2e_names = [m["name"] for m in bench["end_to_end"]
                     if cell["name"] in m.get("workloads", [cell["name"]])]
        for name, unit, read in _readers(bench, cell, e2e_names):
            v = read(ctx)
            if v is not None:
                result_metrics[name] = {"value": v, "unit": unit}
        busy = list(summary["busy_s"].values())
        device_extra = {"busy_s": sum(busy) / max(len(busy), 1),
                        "window_s": summary["slice_s"]}
        breakdown = {"device_ops": [[a, b] for a, b in summary["device_ops"]],
                     "idle_gaps": [[a, b] for a, b in summary["idle_gaps"]]}
        log(f"idle by host phase (s): {summary['idle_by_phase']}")
        log(f"{KERNEL} events per device: {summary['kernel_n']}")
    else:
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}

    for name, v, lim in rows:
        log(f"check {name}: {v!r} (limit {lim!r})")
    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(missing),
        "metrics": result_metrics,
        "device": dict({"platform": platform, "kind": kind,
                        "count": len(devices),
                        "memory_peak_bytes": int(mem_peak)}, **device_extra),
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"ticks_sent": n_sent, "ticks_in_window": in_window,
                        "backlog_ticks": due_in_window - in_window,
                        "generator_late_p99_ms":
                            nearest_rank(sorted(late), 0.99) * 1e3
                            if late else None,
                        "retraces": retraces,
                        "hop_p99_ms": e2e.get("hop_p99_ms"),
                        "hop_p50_ms": e2e.get("hop_p50_ms")}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    if fetcher.error:
        log(f"a tick failed: {fetcher.error}")
    return result
