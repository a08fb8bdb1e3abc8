"""One-kernel serving tick: megakernel == XLA tick, bit for bit.

`repro.kernels.tick_fused` runs the ENTIRE 16 ms serving tick
(frontend feature frame, cascade wake gate, GRU layers, FC head,
softmax, smoothing, masked state advance) as one `pallas_call` over
stream blocks. This suite pins the whole contract down to array
equality (`np.testing.assert_array_equal`, never allclose) on the CPU
interpret tier, which executes the same kernel body — block slicing,
operand encoding, the ΔGRU gather path — as the compiled TPU tier:

  * fused-interpret == xla for every classifier backend ("float" /
    "qat" / "integer" / "delta" / "delta-int", the delta pair at a
    real θ>0 where the gather path actually skips columns), across
    live ticks (raw audio and FV_Norm slabs, rotating partial masks,
    an all-idle tick), late-fetched async handles, the `lax.scan`
    replay, a cascaded pipeline, and the 8-emulated-device stream
    mesh;
  * the gather-compacted Δ·W building blocks equal their dense
    counterparts exactly on the fixed-point grids (float domain vs
    ``d @ w``, code domain vs `intgemm_ref` incl. the int24 clip),
    and the wake-mask row zeroing touches ONLY rows the tick's
    `masked_select` discards;
  * kernel geometry edges (hypothesis): odd max_streams that leave a
    block remainder, hidden_dim % lane != 0, single-stream slabs,
    all-idle ticks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.gru import GRUConfig
from repro.core.gru_delta import DeltaConfig
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.kernels.intgemm import intgemm_ref
from repro.kernels.tick_fused import (
    gather_delta_intgemm,
    gather_delta_matmul,
    make_sparse_step,
    resolve_tick_dispatch,
)
from repro.serving.cascade import CascadeConfig
from repro.serving.serve_loop import StreamingKWSServer

from _hypothesis_compat import given, settings, st

N_DEV = len(jax.devices())
CLASSIFIERS = ("float", "qat", "integer", "delta", "delta-int")
THETA = 0.15  # real sparsity: the gather path must actually skip work


@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


@pytest.fixture(scope="module")
def shared_params():
    return KWSPipeline(KWSPipelineConfig()).init_params(
        jax.random.PRNGKey(7)
    )


def _pipe(norm_stats, classifier, cascade=None, gru=None):
    kw = dict(classifier=classifier, delta=DeltaConfig(THETA, THETA))
    if cascade is not None:
        kw["cascade"] = cascade
    if gru is not None:
        kw["gru"] = gru
    return KWSPipeline(KWSPipelineConfig(**kw), norm_stats=norm_stats)


def _pair(norm_stats, params, classifier, max_streams=5, cascade=None,
          gru=None, devices=None):
    """(xla, fused-interpret) servers on identical params/config."""
    mk = lambda impl, dev: StreamingKWSServer(  # noqa: E731
        _pipe(norm_stats, classifier, cascade, gru), params,
        max_streams=max_streams, tick_impl=impl, devices=dev,
    )
    return mk("xla", None), mk("fused-interpret", devices)


def _assert_servers_identical(a, b):
    for la, lb in zip(
        jax.tree_util.tree_leaves(a.state),
        jax.tree_util.tree_leaves(b.state),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _drive_live(a, b, rng, ticks=5, open_ids=(0, 1, 2)):
    """Raw-audio ticks with rotating partial masks + one all-idle tick,
    asserting scores/top equality every tick and state equality after."""
    for srv in (a, b):
        for sid in open_ids:
            srv.open_stream(sid)
    hop = a.pipeline.chunk_samples
    n = a.max_streams
    for t in range(ticks):
        slab = np.zeros((n, hop), np.float32)
        mask = np.zeros((n,), bool)
        for sid in open_ids:
            if (t + sid) % 3 != 0:
                slab[a.active[sid]] = (
                    rng.standard_normal(hop).astype(np.float32) * 0.05
                )
                mask[a.active[sid]] = True
        s_a, t_a = a.step_batch(slab, mask)
        s_b, t_b = b.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    # all-idle tick: zero firing columns, empty gather loop
    idle = np.zeros((n, hop), np.float32), np.zeros((n,), bool)
    np.testing.assert_array_equal(a.step_batch(*idle)[0],
                                  b.step_batch(*idle)[0])
    _assert_servers_identical(a, b)


# --------------------------------------------------------------------------
# serving API surface
# --------------------------------------------------------------------------

def test_tick_impl_validation_and_resolution(norm_stats, shared_params):
    pipe = _pipe(norm_stats, "qat")
    with pytest.raises(ValueError, match="tick_impl"):
        StreamingKWSServer(pipe, shared_params, max_streams=4,
                           tick_impl="pallas")  # kernel-tier name, not an impl
    srv = StreamingKWSServer(pipe, shared_params, max_streams=4,
                             tick_impl="fused-interpret")
    assert srv.tick_impl == "fused-interpret"
    assert srv.tick_dispatch == "interpret"
    auto = StreamingKWSServer(pipe, shared_params, max_streams=4)
    assert auto.tick_impl == "xla"  # on every platform
    assert auto.tick_dispatch == "xla"


@pytest.mark.parametrize("classifier,cascade,match", [
    ("qat", None, "shape cast"),
    ("integer", None, "shape cast"),
    ("delta-int", None, "cumsum"),
    ("delta", None, "cumsum"),
    ("qat", CascadeConfig(), "truncation"),
], ids=["qat-None-gather", "integer-None-gather", "delta-int-None-gather",
        "delta-None-cumsum", "qat-cascade4-truncation"])
def test_fused_pallas_rejects_what_mosaic_cannot_lower(
    norm_stats, shared_params, classifier, cascade, match
):
    """The compiled megakernel is refused, naming the primitive, at
    construction — before anything is traced or compiled. (The ids keep
    the names the cases had when the ROM lookup was a gather.)"""
    with pytest.raises(ValueError, match=match):
        StreamingKWSServer(
            _pipe(norm_stats, classifier, cascade), shared_params,
            max_streams=4, tick_impl="fused-pallas",
        )


def test_fused_pallas_rejects_raw_audio_before_any_state_change(
    norm_stats, shared_params
):
    """float constructs (FV_Norm compiles), but a raw-audio tick is
    refused at trace time and leaves the server's state untouched."""
    srv = StreamingKWSServer(_pipe(norm_stats, "float"), shared_params,
                             max_streams=4, tick_impl="fused-pallas")
    srv.open_stream(0)
    before = [np.asarray(x) for x in jax.tree_util.tree_leaves(srv.state)]
    slab = np.zeros((4, srv.pipeline.chunk_samples), np.float32)
    with pytest.raises(ValueError, match="scan"):
        srv.step_batch(slab, np.ones((4,), bool))
    for a, b in zip(before, jax.tree_util.tree_leaves(srv.state)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_resolve_tick_dispatch_off_tpu():
    if jax.default_backend() == "tpu":  # pragma: no cover - TPU runner
        assert resolve_tick_dispatch() == "pallas"
    else:
        assert resolve_tick_dispatch() == "reference"
    assert resolve_tick_dispatch("interpret") == "interpret"
    assert resolve_tick_dispatch(interpret=True) == "interpret"


def test_make_sparse_step_only_for_delta(norm_stats):
    assert make_sparse_step(_pipe(norm_stats, "qat")) is None
    assert make_sparse_step(_pipe(norm_stats, "integer")) is None
    assert make_sparse_step(_pipe(norm_stats, "delta")) is not None
    assert make_sparse_step(_pipe(norm_stats, "delta-int")) is not None


# --------------------------------------------------------------------------
# gather-compacted Δ·W building blocks
# --------------------------------------------------------------------------

def _grid_delta(rng, b, i, fire_frac):
    """A thresholded-Δ block on the Q6.8 grid with dead columns."""
    d = quant.fake_quant(
        jnp.asarray(rng.standard_normal((b, i)).astype(np.float32)),
        quant.ACT_Q6_8,
    )
    cols = rng.random(i) < fire_frac
    return jnp.where(jnp.asarray(cols)[None, :], d, 0.0)


@pytest.mark.parametrize("fire_frac", [0.0, 0.3, 1.0])
def test_gather_matmul_matches_dense(fire_frac):
    rng = np.random.default_rng(3)
    d = _grid_delta(rng, 4, 48, fire_frac)
    w = quant.fake_quant(
        jnp.asarray(rng.standard_normal((48, 36)).astype(np.float32)),
        quant.WEIGHT_INT8,
    )
    np.testing.assert_array_equal(
        np.asarray(gather_delta_matmul(d, w)), np.asarray(d @ w)
    )


@pytest.mark.parametrize("fire_frac", [0.0, 0.3, 1.0])
def test_gather_intgemm_matches_ref(fire_frac):
    rng = np.random.default_rng(4)
    d = jnp.asarray(
        rng.integers(-4096, 4096, (4, 48)).astype(np.int32)
        * (rng.random((4, 48)) < fire_frac)
    ).astype(jnp.int16)
    w = jnp.asarray(rng.integers(-128, 128, (48, 36)), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(gather_delta_intgemm(d, w)),
        np.asarray(intgemm_ref(d, w)),
    )


def test_gather_intgemm_saturates_like_ref():
    """int24 clip applied to the whole contribution, like intgemm_ref."""
    d = jnp.full((2, 48), 32767, jnp.int16)
    w = jnp.full((48, 8), 127, jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(gather_delta_intgemm(d, w)),
        np.asarray(intgemm_ref(d, w)),
    )


def test_gather_row_mask_touches_only_masked_rows():
    rng = np.random.default_rng(5)
    d = _grid_delta(rng, 4, 48, 0.5)
    w = quant.fake_quant(
        jnp.asarray(rng.standard_normal((48, 36)).astype(np.float32)),
        quant.WEIGHT_INT8,
    )
    keep = jnp.asarray([True, False, True, False])
    out = np.asarray(gather_delta_matmul(d, w, row_mask=keep))
    dense = np.asarray(d @ w)
    np.testing.assert_array_equal(out[np.asarray(keep)],
                                  dense[np.asarray(keep)])


# --------------------------------------------------------------------------
# megakernel == XLA tick, end to end
# --------------------------------------------------------------------------

@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_fused_interpret_bit_identical_live(
    norm_stats, shared_params, classifier
):
    a, b = _pair(norm_stats, shared_params, classifier)
    _drive_live(a, b, np.random.default_rng(10))


@pytest.mark.parametrize("classifier", ("qat", "integer", "delta",
                                        "delta-int"))
def test_fused_interpret_bit_identical_scan(
    norm_stats, shared_params, classifier
):
    a, b = _pair(norm_stats, shared_params, classifier)
    for srv in (a, b):
        for sid in range(3):
            srv.open_stream(sid)
    hop = a.pipeline.chunk_samples
    rng = np.random.default_rng(11)
    slab = rng.standard_normal((6, 5, hop)).astype(np.float32) * 0.05
    mask = rng.random((6, 5)) < 0.6
    mask[:, 3:] = False  # never-opened slots stay idle
    seq_a, tops_a = a.run_batch(slab, mask)
    seq_b, tops_b = b.run_batch(slab, mask)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(tops_a, tops_b)
    _assert_servers_identical(a, b)


def test_fused_interpret_async_handles_survive_later_ticks(
    norm_stats, shared_params
):
    a, b = _pair(norm_stats, shared_params, "delta")
    for srv in (a, b):
        srv.open_stream(0)
    hop = a.pipeline.chunk_samples
    rng = np.random.default_rng(12)
    slabs = [rng.standard_normal((5, hop)).astype(np.float32) * 0.05
             for _ in range(3)]
    mask = np.zeros((5,), bool)
    mask[0] = True
    ha = [a.step_batch_async(s, mask) for s in slabs]
    hb = [b.step_batch_async(s, mask) for s in slabs]
    for x, y in zip(ha, hb):  # fetched AFTER later ticks donated state
        sa, ta = x.result()
        sb, tb = y.result()
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(ta, tb)


@pytest.mark.parametrize("classifier", ("qat", "delta", "delta-int"))
def test_fused_interpret_bit_identical_cascaded(
    norm_stats, shared_params, classifier
):
    """Real wake threshold: gated streams' frozen state + score decay
    must survive the block-sliced kernel unchanged."""
    casc = CascadeConfig()
    a, b = _pair(norm_stats, shared_params, classifier, cascade=casc)
    _drive_live(a, b, np.random.default_rng(13))
    np.testing.assert_array_equal(a.wake_rate, b.wake_rate)
    np.testing.assert_array_equal(a.sparsity, b.sparsity)


@pytest.mark.skipif(N_DEV < 2, reason="needs the emulated multi-device "
                    "platform (tests/conftest.py)")
@pytest.mark.parametrize("classifier", ("qat", "delta", "delta-int"))
def test_fused_interpret_bit_identical_sharded(
    norm_stats, shared_params, classifier
):
    """shard_map'd megakernel (one kernel per shard-local slab) == the
    single-device XLA tick."""
    mesh_dev = max(d for d in (2, 4, 8) if d <= min(8, N_DEV))
    a, b = _pair(norm_stats, shared_params, classifier, max_streams=8,
                 devices=mesh_dev)
    _drive_live(a, b, np.random.default_rng(14))
    np.testing.assert_array_equal(a.sparsity, b.sparsity)


# --------------------------------------------------------------------------
# kernel geometry edges
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "max_streams,hidden,classifier",
    [
        (1, 48, "qat"),        # single-stream slab, 7-row block pad
        (5, 20, "delta-int"),  # odd remainder + lane-misaligned hidden
        (13, 20, "delta"),     # two blocks + remainder, gather path
    ],
)
def test_geometry_edges_deterministic(
    norm_stats, max_streams, hidden, classifier
):
    """Pinned geometry-edge cases (the hypothesis sweep below widens
    the net when the extra is installed)."""
    gru = GRUConfig(hidden_dim=hidden)
    params = _pipe(norm_stats, classifier, gru=gru).init_params(
        jax.random.PRNGKey(21)
    )
    a, b = _pair(norm_stats, params, classifier,
                 max_streams=max_streams, gru=gru)
    open_ids = tuple(range(min(3, max_streams)))
    _drive_live(a, b, np.random.default_rng(21), ticks=3,
                open_ids=open_ids)

@settings(max_examples=5, deadline=None)
@given(
    max_streams=st.sampled_from([1, 5, 7, 13]),
    hidden=st.sampled_from([20, 48]),  # 20: hidden % lane width != 0
    classifier=st.sampled_from(["qat", "delta-int"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_geometry_edges_bit_identical(
    norm_stats, max_streams, hidden, classifier, seed
):
    """Odd stream counts (block remainders incl. a single-stream slab),
    lane-misaligned hidden dims, and all-idle ticks: the padded block
    grid must stay exact."""
    gru = GRUConfig(hidden_dim=hidden)
    params = _pipe(norm_stats, classifier, gru=gru).init_params(
        jax.random.PRNGKey(seed % 1000)
    )
    a, b = _pair(norm_stats, params, classifier,
                 max_streams=max_streams, gru=gru)
    open_ids = tuple(range(min(3, max_streams)))
    _drive_live(a, b, np.random.default_rng(seed), ticks=3,
                open_ids=open_ids)
