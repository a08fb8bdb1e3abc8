"""Compile the serving programs for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each program for one
device of a described ``v5e:2x2`` topology and raises what the chip's
compiler would raise. Covered at the serving width of 1024 streams:

  * the serving tick for all five classifier backends, on raw audio
    and on FV_Norm, with the intgemm kernel at the tier "auto" resolves
    to on TPU ("pallas"), plus the scanned window, the cascaded tick,
    and the sharded tick of a ``devices=4`` server on the 2x2 mesh;
  * the Pallas kernels reachable on TPU at serving width: intgemm's
    three classifier GEMMs, and tdc, which Mosaic refuses (so "auto"
    resolves it to the reference on TPU).

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off while these compiles run.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.gru_delta import DeltaConfig
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.core.tdfex import TDFExConfig
from repro.kernels.dispatch import force_dispatch
from repro.kernels.intgemm import intgemm
from repro.kernels.intgemm.ops import resolve_intgemm_dispatch
from repro.kernels.tdc import tdc_counts
from repro.kernels.tdc.ops import resolve_tdc_dispatch
from repro.serving.cascade import CascadeConfig
from repro.serving.serve_loop import StreamingKWSServer

N_STREAMS = 1024
WINDOW = 4
CLASSIFIERS = ("float", "qat", "integer", "delta", "delta-int")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e device, with the persistent cache off (its
    entries cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    _, raw = KWSPipeline(KWSPipelineConfig(use_norm=False)).features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


@pytest.fixture(scope="module")
def params():
    return KWSPipeline(KWSPipelineConfig()).init_params(
        jax.random.PRNGKey(0)
    )


def _pipe(norm_stats, classifier, cascade=None):
    return KWSPipeline(
        KWSPipelineConfig(classifier=classifier,
                          delta=DeltaConfig(0.15, 0.15), cascade=cascade),
        norm_stats=norm_stats,
    )


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding),
        tree,
    )


def _operands(srv, raw_audio, sharding, window=None):
    """Described operands of a tick (or of a scanned window)."""
    pipe = srv.pipeline
    dim = pipe.chunk_samples if raw_audio else pipe.config.fex.num_channels
    lead = () if window is None else (window,)
    return (
        _shapes(srv.params, sharding),
        _shapes(srv.state, sharding),
        jax.ShapeDtypeStruct(lead + (N_STREAMS, dim), jnp.float32,
                             sharding=sharding),
        jax.ShapeDtypeStruct(lead + (N_STREAMS,), jnp.bool_,
                             sharding=sharding),
        _shapes(srv.frontend_state, sharding),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding),
    )


def _kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _n_gemm(classifier) -> int:
    """intgemm kernels one tick lowers to: 2 layers x (W_i, W_h) + FC
    for the integer backends; the float and qat backends call none."""
    return 5 if classifier in ("integer", "delta-int") else 0


@pytest.mark.parametrize("raw_audio", [True, False], ids=["audio", "fv"])
@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_auto_tick_compiles_for_v5e(one_chip, norm_stats, params,
                                    classifier, raw_audio):
    srv = StreamingKWSServer(_pipe(norm_stats, classifier), params,
                             max_streams=N_STREAMS)
    tick = srv._tick_audio if raw_audio else srv._tick_fv
    run = srv._run_audio if raw_audio else srv._run_fv
    # the tiers "auto" resolves to on a TPU
    with force_dispatch("pallas"):
        compiled = tick.lower(*_operands(srv, raw_audio, one_chip)).compile()
        scanned = run.lower(
            *_operands(srv, raw_audio, one_chip, window=WINDOW)
        ).compile()
    assert _kernels(compiled) == _n_gemm(classifier)
    assert _kernels(scanned) == _n_gemm(classifier)


def test_tick_ops_carry_stable_names_on_v5e(one_chip, norm_stats, params):
    """The names a profile of the integer tick is read by: the five GEMM
    kernels are instructions named ``kws_intgemm`` (holding the
    ``intgemm`` the roofline reduction matches by), and each layer's
    sigmoid/tanh step counts are fusions under its ``kws_gru{l}_gates``
    scope. No ROM gather is left in the program."""
    srv = StreamingKWSServer(_pipe(norm_stats, "integer"), params,
                             max_streams=N_STREAMS)
    with force_dispatch("pallas"):
        text = srv._tick_fv.lower(
            *_operands(srv, False, one_chip)
        ).compile().as_text()
    entry = text[text.index("\nENTRY"):].splitlines()
    kernels = [ln.split()[0] for ln in entry
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 5
    assert all(k.startswith("%kws_intgemm") for k in kernels)
    assert not re.search(r"\sgather\(", text)
    gates = re.findall(
        r'op_name="[^"]*/(kws_gru\d_gates)/',
        "\n".join(ln for ln in entry if " fusion(" in ln),
    )
    assert set(gates) == {"kws_gru0_gates", "kws_gru1_gates"}


def test_audio_tick_reads_the_log_rom_without_gather_on_v5e(one_chip,
                                                            norm_stats,
                                                            params):
    """The raw-audio tick's 12 -> 10-bit log ROM is read as a count of its
    steps: a fusion under ``kws_frontend/kws_log_rom``, and no gather
    anywhere in the program."""
    srv = StreamingKWSServer(_pipe(norm_stats, "integer"), params,
                             max_streams=N_STREAMS)
    with force_dispatch("pallas"):
        text = srv._tick_audio.lower(
            *_operands(srv, True, one_chip)
        ).compile().as_text()
    assert not re.search(r"\sgather\(", text)
    entry = text[text.index("\nENTRY"):].splitlines()
    rom = [ln for ln in entry if " fusion(" in ln and re.search(
        r'op_name="[^"]*/kws_frontend/kws_log_rom/', ln)]
    assert rom


@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_cascaded_auto_tick_compiles_for_v5e(one_chip, norm_stats, params,
                                             classifier):
    srv = StreamingKWSServer(
        _pipe(norm_stats, classifier, CascadeConfig()), params,
        max_streams=N_STREAMS,
    )
    with force_dispatch("pallas"):
        compiled = srv._tick_audio.lower(
            *_operands(srv, True, one_chip)
        ).compile()
    assert _kernels(compiled) == _n_gemm(classifier)


@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_sharded_auto_tick_compiles_for_v5e_2x2(topo, one_chip, norm_stats,
                                                params, classifier):
    """The devices=4 tick: one program over a 4-chip ("stream",) mesh.
    Each intgemm call must stay shard-local (GSPMD cannot partition a
    Mosaic kernel), and the tick needs no collective."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (
        replicated_shardings,
        stream_shardings,
    )
    from repro.serving.serve_loop import _tick

    mesh = Mesh(np.asarray(topo.devices), ("stream",))
    pipe = _pipe(norm_stats, classifier)
    srv = StreamingKWSServer(pipe, params, max_streams=N_STREAMS)
    rep = lambda t: replicated_shardings(t, mesh)  # noqa: E731
    st_sh = stream_shardings(srv.state, mesh)
    row = NamedSharding(mesh, P("stream", None))
    vec = NamedSharding(mesh, P("stream"))
    scalar = NamedSharding(mesh, P())
    in_sh = (rep(srv.params), st_sh, row, vec, rep(srv.frontend_state),
             scalar)

    def described(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                              sharding=s),
            tree, shardings,
        )

    operands = (
        described(srv.params, in_sh[0]), described(srv.state, st_sh),
        jax.ShapeDtypeStruct((N_STREAMS, pipe.chunk_samples), jnp.float32,
                             sharding=row),
        jax.ShapeDtypeStruct((N_STREAMS,), jnp.bool_, sharding=vec),
        described(srv.frontend_state, in_sh[4]),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar),
    )
    tick = jax.jit(
        functools.partial(_tick, pipe, True, mesh=mesh),
        donate_argnums=(1,), in_shardings=in_sh,
        out_shardings=(st_sh, NamedSharding(mesh, P(None, "stream"))),
    )
    with force_dispatch("pallas"):
        text = tick.lower(*operands).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        _n_gemm(classifier))
    assert "all-gather" not in text and "all-reduce" not in text


def test_packed_result_is_stream_minor_on_v5e(one_chip, norm_stats, params):
    """The one output a fetch copies to the host, the packed (K + 1, N)
    int32 result, is laid out with the stream axis minor — N streams
    across the lanes, not 12 classes padded to 128 — for the frame and
    raw-audio ticks and the scanned window."""
    srv = StreamingKWSServer(_pipe(norm_stats, "integer"), params,
                             max_streams=N_STREAMS)
    k = srv.pipeline.config.gru.num_classes
    for fn, raw_audio, window in ((srv._tick_fv, False, None),
                                  (srv._tick_audio, True, None),
                                  (srv._run_fv, False, WINDOW)):
        with force_dispatch("pallas"):
            lowered = fn.lower(
                *_operands(srv, raw_audio, one_chip, window=window)
            )
            compiled = lowered.compile()
        shape = lowered.out_info[1].shape
        assert shape[-2:] == (k + 1, N_STREAMS)
        minor = compiled.output_formats[1].layout.major_to_minor[-1]
        assert minor == len(shape) - 1, compiled.output_formats[1]


@pytest.mark.parametrize("k,n", [(16, 144), (48, 144), (48, 12)],
                         ids=["layer0", "layer1", "fc"])
def test_intgemm_compiles_for_v5e(one_chip, k, n):
    x = jax.ShapeDtypeStruct((N_STREAMS, k), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: intgemm(a, b, dispatch="pallas")
    ).lower(x, w).compile()
    assert _kernels(compiled) == 1


def test_tdc_kernel_refused_on_v5e(one_chip):
    cfg = TDFExConfig()
    spf = cfg.decimation // cfg.tdc_oversample
    u = jax.ShapeDtypeStruct((N_STREAMS, spf, 16), jnp.float32,
                             sharding=one_chip)
    with pytest.raises(Exception):  # noqa: B017 - VMEM exhaustion
        jax.jit(
            lambda a: tdc_counts(a, cfg, dispatch="pallas")
        ).lower(u).compile()


def test_auto_tiers_on_tpu(monkeypatch):
    """What "auto" resolves to with a TPU backend: the compiled intgemm,
    and the tdc reference (the kernel above does not compile)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_intgemm_dispatch() == "pallas"
    assert resolve_tdc_dispatch(N_STREAMS) == "reference"
    assert resolve_tdc_dispatch(4) == "reference"
