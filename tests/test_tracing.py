"""The serving path's spans and the tick's device scopes, as a profiler
records them.

`repro.serving.metrics.span` writes every host interval of the serving
path into the profiler's trace (``kws.ingress.*``, ``kws.server.*``,
``kws.handle.*``), each carrying ``tick=<the server's dispatch
number>``; the tick program names its stages with `jax.named_scope`
(``kws_*``). This suite records CPU profiles of a pipelined server and
checks what a benchmark reduction relies on:

  * one ``kws.server.tick_call``, ``kws.server.prefetch`` and
    ``kws.handle.fetch`` per dispatched tick, each with the right
    ``tick`` stat, nested as documented, with ``kws.server.compile``
    only on a dispatch that traced a new (program, shape) — with
    metrics off, since tracing does not depend on a registry;
  * with a registry, the dispatch and fetch histograms observe the
    very intervals of the matching spans, and
    ``kws_serve_d2h_transfers_total`` counts one transfer per dispatch,
    tick or window;
  * the compiled tick's HLO carries every ``kws_*`` scope for the
    integer, cascaded and mesh-sharded servers, and the persistent
    compilation cache never hands back a program with another
    checkout's scopes.
"""

import collections
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile_cache import enable_compile_cache
from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.serving.cascade import CascadeConfig
from repro.serving.ingress import PipelinedIngress
from repro.serving.serve_loop import StreamingKWSServer

MAX_STREAMS = 8
N_TICKS = 5
Span = collections.namedtuple("Span", "name start end tick line")


@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    _, raw = KWSPipeline(KWSPipelineConfig(use_norm=False)).features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


def _pipe(norm_stats, cascade=None):
    return KWSPipeline(
        KWSPipelineConfig(classifier="integer", cascade=cascade),
        norm_stats=norm_stats,
    )


@pytest.fixture(scope="module")
def params(norm_stats):
    return _pipe(norm_stats).init_params(jax.random.PRNGKey(0))


def _server(norm_stats, params, **kw):
    srv = StreamingKWSServer(_pipe(norm_stats), params,
                             max_streams=MAX_STREAMS, **kw)
    for sid in range(MAX_STREAMS):
        srv.open_stream(sid)
    return srv


def _drive(srv, n_ticks=N_TICKS, seed=0, window=1):
    """n_ticks FV_Norm ticks through a depth-2 pipelined ingress."""
    dim = srv.pipeline.config.fex.num_channels
    rng = np.random.default_rng(seed)
    ing = PipelinedIngress(srv, dim, depth=2, window=window)
    for _ in range(n_ticks):
        slab, mask = ing.stage()
        slab[:] = rng.standard_normal(slab.shape) * 0.05
        mask[:] = rng.random(mask.shape) > 0.25
        ing.commit()
    ing.drain()


def _profiled_spans(tmp_path, fn):
    """Run fn under the profiler; the ``kws.`` host spans it recorded."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("kws."):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats).get("tick"),
                                    (plane.name, line.name)))
    return sorted(out, key=lambda s: s.start)


def _by_tick(spans, name):
    out = collections.defaultdict(list)
    for s in spans:
        if s.name == name:
            out[s.tick].append(s)
    return out


def _inside(child, parent):
    return (child.line == parent.line and parent.start <= child.start
            and child.end <= parent.end)


def test_spans_per_dispatched_tick(tmp_path, norm_stats, params):
    srv = _server(norm_stats, params)  # metrics off
    spans = _profiled_spans(tmp_path, lambda: _drive(srv))
    ticks = list(range(N_TICKS))
    assert srv.dispatch_seq == N_TICKS
    for name in ("kws.ingress.stage", "kws.ingress.commit",
                 "kws.server.dispatch", "kws.server.tick_call",
                 "kws.server.prefetch", "kws.handle.fetch",
                 "kws.handle.wait", "kws.handle.d2h"):
        got = _by_tick(spans, name)
        assert sorted(got) == ticks, name
        assert all(len(v) == 1 for v in got.values()), name
    # the first dispatch traced and compiled the tick; no later one did
    assert sorted(_by_tick(spans, "kws.server.compile")) == [0]
    # depth 2: staging tick k forced tick k - 2 off the FIFO front
    assert sorted(_by_tick(spans, "kws.ingress.reuse_wait")) == ticks[2:]
    parent = {
        "kws.ingress.reuse_wait": "kws.ingress.stage",
        "kws.server.dispatch": "kws.ingress.commit",
        "kws.server.tick_call": "kws.server.dispatch",
        "kws.server.prefetch": "kws.server.dispatch",
        "kws.server.compile": "kws.server.dispatch",
        "kws.handle.wait": "kws.handle.fetch",
        "kws.handle.d2h": "kws.handle.fetch",
    }
    for child_name, parent_name in parent.items():
        parents = _by_tick(spans, parent_name)
        for k, (child,) in _by_tick(spans, child_name).items():
            assert _inside(child, parents[k][0]), (child_name, k)
    (compile_span,) = _by_tick(spans, "kws.server.compile")[0]
    assert _inside(_by_tick(spans, "kws.server.tick_call")[0][0],
                   compile_span)
    # a tick is staged, committed and dispatched before it is fetched
    for k in ticks:
        assert (_by_tick(spans, "kws.ingress.stage")[k][0].end
                <= _by_tick(spans, "kws.server.tick_call")[k][0].start
                <= _by_tick(spans, "kws.handle.fetch")[k][0].start)


def test_histograms_observe_the_spans(tmp_path, norm_stats, params):
    srv = _server(norm_stats, params, metrics=True)
    _drive(srv, n_ticks=1)  # compile outside the profile
    spans = _profiled_spans(tmp_path, lambda: _drive(srv, seed=1))
    for hist_name, span_name in (
        ("kws_serve_tick_dispatch_ms", "kws.server.dispatch"),
        ("kws_serve_tick_fetch_ms", "kws.handle.fetch"),
    ):
        observed = list(srv.metrics.histogram(hist_name).samples)[1:]
        traced = [s for s in spans if s.name == span_name]
        traced.sort(key=lambda s: s.tick)
        assert [s.tick for s in traced] == list(range(1, 1 + N_TICKS))
        assert len(observed) == len(traced)
        for ms, s in zip(observed, traced):
            # the span's own clock reads sit just inside the profiler's
            # interval: same interval, up to the reads' own cost
            dur_ms = (s.end - s.start) * 1e-6
            assert 0.0 <= dur_ms - ms < 0.25, (hist_name, s.tick)
    # the ingress's per-tick marks come from the same span boundaries
    tr = list(srv.metrics.traces)[-1]
    assert list(tr.marks) == ["stage", "commit", "dispatch", "retire"]


@pytest.mark.parametrize("window", [1, 3], ids=["tick", "window"])
def test_one_transfer_per_dispatch(norm_stats, params, window):
    """Each dispatch, a tick or a scanned window, is fetched in exactly
    one device-to-host transfer: its packed result."""
    srv = _server(norm_stats, params, metrics=True)
    transfers = srv.metrics.counter("kws_serve_d2h_transfers_total")
    for n_ticks in (1, N_TICKS):
        before, seq = transfers.value, srv.dispatch_seq
        _drive(srv, n_ticks=n_ticks, window=window)
        dispatches = srv.dispatch_seq - seq
        assert dispatches == -(-n_ticks // window)
        assert transfers.value - before == dispatches


_SCOPES = ("kws_classifier", "kws_gru0_gemm", "kws_gru0_gates",
           "kws_gru0_update", "kws_gru1_gemm", "kws_gru1_gates",
           "kws_gru1_update", "kws_head", "kws_smooth", "kws_pack")


@pytest.mark.parametrize("kind", ["integer", "cascade", "mesh", "audio"])
def test_compiled_tick_carries_every_scope(norm_stats, params, kind):
    cascade = CascadeConfig() if kind == "cascade" else None
    devices = 4 if kind == "mesh" else None
    if devices and len(jax.devices()) < devices:
        pytest.skip("needs 4 emulated devices")
    srv = StreamingKWSServer(_pipe(norm_stats, cascade), params,
                             max_streams=MAX_STREAMS, devices=devices)
    dim = srv.pipeline.config.fex.num_channels
    slab = np.zeros((MAX_STREAMS, dim), np.float32)
    mask = np.ones((MAX_STREAMS,), bool)
    if kind == "audio":
        # the raw-audio tick compiled: its frontend's ops, and the log ROM
        # read within it, carry their scopes
        text = srv._tick_audio.lower(
            srv.params, srv.state,
            np.zeros((MAX_STREAMS, srv.pipeline.chunk_samples), np.float32),
            mask, srv.frontend_state, srv.smoothing,
        ).compile().as_text()
        scopes = {"/".join(p for p in op.split("/") if p.startswith("kws_"))
                  for op in re.findall(r'op_name="([^"]*)"', text)}
        assert {"kws_frontend", "kws_frontend/kws_log_rom"} <= scopes
        assert set(re.findall(r"kws_\w+", text)) == (
            set(_SCOPES) | {"kws_frontend", "kws_log_rom"})
        return
    text = srv._tick_fv.lower(
        srv.params, srv.state, slab, mask, srv.frontend_state,
        srv.smoothing,
    ).compile().as_text()
    found = set(re.findall(r"kws_\w+", text))
    want = set(_SCOPES) | ({"kws_cascade"} if cascade else set())
    assert found == want  # no kws_frontend: FV_Norm ticks bypass it
    audio = srv._tick_audio.lower(
        srv.params, srv.state,
        np.zeros((MAX_STREAMS, srv.pipeline.chunk_samples), np.float32),
        mask, srv.frontend_state, srv.smoothing,
    ).as_text(debug_info=True)
    assert "kws_frontend" in audio


_CACHE_OPTIONS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
                  "jax_compilation_cache_max_size",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cached_programs_keep_their_own_scopes(fresh_cache):
    """Two programs that differ only in a named scope are two cache
    entries: a profile never shows op names from a program that another
    checkout compiled."""
    assert enable_compile_cache() == str(fresh_cache)

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return x * 2.0 + 1.0
        return jax.jit(f)

    x = jnp.ones((8,), jnp.float32)
    scoped("kws_first").lower(x).compile()
    text = scoped("kws_second").lower(x).compile().as_text()
    assert "kws_second" in text and "kws_first" not in text
