"""Sharded multi-device serving, proven bit-identical on an emulated mesh.

tests/conftest.py forces an 8-device emulated CPU host platform (before
the first jax import, guarded against a user-set flag), so this suite
runs on any plain CPU runner. It proves the stream-parallel
`StreamingKWSServer` (slot axis sharded over a 1-D ``("stream",)``
mesh) is BIT-identical — `np.testing.assert_array_equal`, never
allclose — to the single-device server for every classifier backend
("float" / "qat" / "integer" / "delta" / "delta-int"), across live
ticks (`step` / `step_batch`), the scanned replay (`run_batch`),
idle-stream isolation, and slot-reuse hygiene across shard boundaries.
The ΔGRU backends additionally get a cross-backend check: a θ=0 delta
server sharded over the mesh must bit-match its dense base backend's
single-device server (the temporal-sparsity engine survives
partitioning), with the sparsity telemetry consistent across shards.
The cascade subsystem (`repro.serving.cascade`) gets the same
treatment: an always-open cascaded server sharded over the mesh must
bit-match the plain single-device server for every backend, and at a
real wake threshold the per-stream `srv.wake_rate` telemetry must be
placement-independent. A hypothesis property
test drives random open/close/submit schedules against a pure-Python
lifecycle oracle: a stream's scores depend only on its own submitted
frames, never on other streams' traffic or its device placement — a
cascaded variant additionally asserts `wake_rate` resets on
open_stream, freezes while idle, and is placement-independent. The
donation-hazard regression (step twice without fetching scores in
between) runs here for the sharded path and in
tests/test_pipeline_serving.py for the single-device path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.distributed.sharding import STREAM_AXIS, stream_mesh
from repro.serving.autoscale import StreamRouter, shard_of_slot
from repro.serving.cascade import CascadeConfig
from repro.serving.serve_loop import StreamingKWSServer

from _hypothesis_compat import given, settings, st

N_DEV = len(jax.devices())
pytestmark = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs a multi-device platform (conftest forces 8 emulated "
    "CPU devices unless XLA_FLAGS overrides it)",
)

MAX_STREAMS = 16
# largest power-of-two mesh (<= 8 devices) the slot axis divides, so a
# user-forced odd device count (the conftest guard allows e.g. =6)
# degrades to a smaller mesh instead of erroring the whole suite
MESH_DEV = max(d for d in (2, 4, 8) if d <= min(8, N_DEV)) if N_DEV >= 2 else 1

CLASSIFIERS = ("float", "qat", "integer", "delta", "delta-int")


@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


@pytest.fixture(scope="module", params=CLASSIFIERS)
def backend(request, norm_stats):
    """(pipeline, params) per classifier backend, built once."""
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier=request.param), norm_stats=norm_stats
    )
    return pipe, pipe.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def server_pair(backend):
    """Matched (single-device, sharded) servers on the same params."""
    pipe, params = backend
    single = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    sharded = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    return single, sharded


def _reset_pair(pair):
    """Close every open stream on both servers (fixtures are
    module-scoped; open_stream zeroes the reused slot, so close+open is
    a full per-example reset)."""
    for srv in pair:
        for sid in list(srv.active):
            srv.close_stream(sid)


def _state_leaves(srv):
    return [
        np.asarray(leaf).copy()
        for leaf in jax.tree_util.tree_leaves(srv.state)
    ]


def _assert_states_identical(a, b):
    for la, lb in zip(_state_leaves(a), _state_leaves(b)):
        np.testing.assert_array_equal(la, lb)


def _slot_slice(srv, sid):
    slot = srv.active[sid]
    return jax.tree_util.tree_map(
        lambda t: np.asarray(t[slot]).copy(), srv.state
    )


# --------------------------------------------------------------------------
# mesh construction + fallback
# --------------------------------------------------------------------------

def test_sharded_server_places_state_on_mesh(server_pair):
    _, sharded = server_pair
    assert sharded.n_devices == MESH_DEV
    assert sharded.mesh is not None
    assert sharded.mesh.axis_names == (STREAM_AXIS,)
    for leaf in jax.tree_util.tree_leaves(sharded.state):
        spec = leaf.sharding.spec
        assert spec and spec[0] == STREAM_AXIS, spec
        assert len(leaf.devices()) == MESH_DEV
    # params replicate: every leaf lives whole on every device
    for leaf in jax.tree_util.tree_leaves(sharded.params):
        assert leaf.sharding.is_fully_replicated


def test_single_visible_device_falls_back(backend):
    pipe, params = backend
    srv = StreamingKWSServer(pipe, params, max_streams=4, devices=1)
    assert srv.mesh is None and srv.n_devices == 1
    # a size-1 mesh also falls back to the plain single-device program
    srv1 = StreamingKWSServer(
        pipe, params, max_streams=4, mesh=stream_mesh(1)
    )
    assert srv1.mesh is None and srv1.n_devices == 1


def test_constructor_validation(backend):
    pipe, params = backend
    with pytest.raises(ValueError, match="divide over"):
        StreamingKWSServer(
            pipe, params, max_streams=9, devices=MESH_DEV
        )
    with pytest.raises(ValueError, match="not both"):
        StreamingKWSServer(
            pipe, params, max_streams=8, mesh=stream_mesh(2), devices=2
        )
    with pytest.raises(ValueError, match="visible"):
        StreamingKWSServer(
            pipe, params, max_streams=8, devices=N_DEV + 1
        )


# --------------------------------------------------------------------------
# bit-identity: sharded == single-device, all backends, all entry points
# --------------------------------------------------------------------------

def test_step_batch_bit_identical(server_pair):
    """Live fused ticks (raw-audio and FV_Norm slabs, partial masks):
    scores, argmax, and the full ServerState match bit for bit."""
    single, sharded = server_pair
    _reset_pair(server_pair)
    pipe = single.pipeline
    for srv in (single, sharded):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    rng = np.random.default_rng(1)
    hop = pipe.chunk_samples
    for t in range(3):  # raw-audio ticks, rotating partial masks
        slab = rng.standard_normal((MAX_STREAMS, hop)).astype(np.float32)
        slab *= 0.05
        mask = np.ones(MAX_STREAMS, bool)
        mask[t::3] = False
        s_a, t_a = single.step_batch(slab, mask)
        s_b, t_b = sharded.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    fv = rng.standard_normal((MAX_STREAMS, 16)).astype(np.float32)
    s_a, t_a = single.step_batch(fv, np.ones(MAX_STREAMS, bool))
    s_b, t_b = sharded.step_batch(fv, np.ones(MAX_STREAMS, bool))
    np.testing.assert_array_equal(s_a, s_b)
    np.testing.assert_array_equal(t_a, t_b)
    _assert_states_identical(single, sharded)


def test_run_batch_bit_identical(server_pair):
    """The lax.scan replay lowers to one SPMD program whose whole
    (n_ticks, N, K) trajectory matches the single-device scan."""
    single, sharded = server_pair
    _reset_pair(server_pair)
    pipe = single.pipeline
    for srv in (single, sharded):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    rng = np.random.default_rng(2)
    hop = pipe.chunk_samples
    slab = rng.standard_normal((4, MAX_STREAMS, hop)).astype(np.float32)
    slab *= 0.05
    mask = rng.random((4, MAX_STREAMS)) < 0.7
    seq_a, tops_a = single.run_batch(slab, mask)
    seq_b, tops_b = sharded.run_batch(slab, mask)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(tops_a, tops_b)
    _assert_states_identical(single, sharded)


def test_fused_tick_sharded_bit_identical(backend):
    """tick_impl="fused-interpret" on the mesh: the megakernel runs
    once per shard-local slab under `shard_map` (GSPMD cannot partition
    a pallas_call), and the result still matches the single-device
    xla-tick server bit for bit — for every backend."""
    pipe, params = backend
    single = StreamingKWSServer(
        pipe, params, max_streams=8, tick_impl="xla"
    )
    sharded = StreamingKWSServer(
        pipe, params, max_streams=8, devices=MESH_DEV,
        tick_impl="fused-interpret",
    )
    assert sharded.tick_dispatch == "interpret"
    for srv in (single, sharded):
        for sid in range(8):
            srv.open_stream(sid)
    rng = np.random.default_rng(6)
    hop = pipe.chunk_samples
    for t in range(3):
        slab = rng.standard_normal((8, hop)).astype(np.float32) * 0.05
        mask = np.ones(8, bool)
        mask[t::3] = False
        s_a, t_a = single.step_batch(slab, mask)
        s_b, t_b = sharded.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    _assert_states_identical(single, sharded)


def test_dict_step_bit_identical_across_placements(server_pair):
    """`step` with {sid: frame} dicts: the sharded router places the
    same stream ids on different slots/shards than the single-device
    free list, yet every stream's posteriors match bit for bit —
    placement independence."""
    single, sharded = server_pair
    _reset_pair(server_pair)
    for srv in (single, sharded):
        for sid in range(6):
            srv.open_stream(sid)
    # same ids, different slots (round-robin vs first-free)
    assert single.active != sharded.active
    rng = np.random.default_rng(3)
    for _ in range(3):
        frames = {
            sid: rng.standard_normal(16).astype(np.float32)
            for sid in range(6)
        }
        out_a = single.step(frames)
        out_b = sharded.step(frames)
        for sid in frames:
            np.testing.assert_array_equal(
                out_a[sid]["probs"], out_b[sid]["probs"]
            )
            assert out_a[sid]["top"] == out_b[sid]["top"]


# --------------------------------------------------------------------------
# isolation + slot hygiene across shard boundaries
# --------------------------------------------------------------------------

def test_idle_stream_isolation_across_shards(server_pair):
    """A stream idling on one shard is bit-identical across ticks that
    only touch streams on OTHER shards (the temporal-sparsity contract
    survives partitioning)."""
    _, sharded = server_pair
    _reset_pair(server_pair)
    # round-robin: sids 0..MESH_DEV-1 land one per shard
    for sid in range(MESH_DEV):
        sharded.open_stream(sid)
    shards = {
        sid: shard_of_slot(sharded.active[sid], MAX_STREAMS, MESH_DEV)
        for sid in range(MESH_DEV)
    }
    assert sorted(shards.values()) == list(range(MESH_DEV))
    rng = np.random.default_rng(4)
    fv = rng.standard_normal(16).astype(np.float32)
    sharded.step({sid: fv for sid in range(MESH_DEV)})
    idle_before = _slot_slice(sharded, 0)
    for _ in range(3):  # stream 0 (shard 0) idles; every other shard ticks
        sharded.step({
            sid: rng.standard_normal(16).astype(np.float32)
            for sid in range(1, MESH_DEV)
        })
    idle_after = _slot_slice(sharded, 0)
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, idle_before, idle_after
    )


def test_slot_reuse_hygiene_across_shards(server_pair):
    """close -> reopen on a non-zero shard hands out a fully zeroed
    slot while every other slot (on every shard) is untouched."""
    _, sharded = server_pair
    _reset_pair(server_pair)
    for sid in range(MAX_STREAMS):
        sharded.open_stream(sid)
    rng = np.random.default_rng(5)
    fv = rng.standard_normal((MAX_STREAMS, 16)).astype(np.float32)
    sharded.step_batch(fv, np.ones(MAX_STREAMS, bool))
    victim = next(
        sid for sid in sharded.active
        if shard_of_slot(sharded.active[sid], MAX_STREAMS, MESH_DEV)
        == MESH_DEV - 1
    )
    victim_slot = sharded.active[victim]
    before = _state_leaves(sharded)
    sharded.close_stream(victim)
    sharded.open_stream(999)  # only free slot -> must reuse it
    assert sharded.active[999] == victim_slot
    reused = _slot_slice(sharded, 999)
    jax.tree_util.tree_map(
        lambda t: np.testing.assert_array_equal(t, np.zeros_like(t)),
        reused,
    )
    after = _state_leaves(sharded)
    for la, lb in zip(before, after):
        la[victim_slot] = 0  # the reused slot is the ONLY change
        np.testing.assert_array_equal(la, lb)


def test_router_round_robin_balance():
    """Slot allocation keeps shard loads within 1 at every point of an
    open/close sequence, and placement matches the block mapping."""
    r = StreamRouter(MAX_STREAMS, MESH_DEV)
    slots = []
    for _ in range(MAX_STREAMS):
        slot = r.acquire()
        slots.append(slot)
        loads = r.shard_loads()
        assert max(loads) - min(loads) <= 1, loads
        p = r.placement(slot)
        assert p.shard == shard_of_slot(slot, MAX_STREAMS, MESH_DEV)
        assert p.slot == slot
    assert sorted(slots) == list(range(MAX_STREAMS))
    with pytest.raises(RuntimeError, match="capacity"):
        r.acquire()
    # releases rebalance: freeing two slots on one shard makes it the
    # next two allocation targets
    shard0 = [s for s in slots if shard_of_slot(s, MAX_STREAMS, MESH_DEV) == 0]
    for s in shard0[:2]:
        r.release(s)
    got = [r.acquire(), r.acquire()]
    assert sorted(got) == sorted(shard0[:2])
    # single-shard router preserves the pre-sharding lowest-first order
    r1 = StreamRouter(4, 1)
    assert [r1.acquire() for _ in range(4)] == [0, 1, 2, 3]


# --------------------------------------------------------------------------
# donation hazard (sharded path; single-device twin lives in
# tests/test_pipeline_serving.py)
# --------------------------------------------------------------------------

def test_step_twice_keeps_first_scores_sharded(server_pair):
    """Two ticks back-to-back without fetching `scores` in between: the
    first tick's returned arrays must be views of one host array that
    owns its memory, and stay intact (a zero-copy view of a device
    buffer could alias a buffer donated to tick 2)."""
    _, sharded = server_pair
    _reset_pair(server_pair)
    for sid in range(MAX_STREAMS):
        sharded.open_stream(sid)
    rng = np.random.default_rng(6)
    mask = np.ones(MAX_STREAMS, bool)
    fv1 = rng.standard_normal((MAX_STREAMS, 16)).astype(np.float32)
    fv2 = rng.standard_normal((MAX_STREAMS, 16)).astype(np.float32)
    s1, t1 = sharded.step_batch(fv1, mask)
    assert s1.base is t1.base and s1.base.flags["OWNDATA"]
    snap_s, snap_t = s1.copy(), t1.copy()
    view = sharded.scores
    assert view.flags["OWNDATA"]
    sharded.step_batch(fv2, mask)
    sharded.step_batch(fv1, mask)
    np.testing.assert_array_equal(s1, snap_s)
    np.testing.assert_array_equal(t1, snap_t)
    np.testing.assert_array_equal(view, snap_s)


# --------------------------------------------------------------------------
# ΔGRU: θ=0 sharded delta server == single-device dense base server
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "delta_key,base_key", [("delta", "qat"), ("delta-int", "integer")]
)
def test_sharded_delta_matches_dense_base(norm_stats, delta_key, base_key):
    """Cross-backend AND cross-placement: the θ=0 ΔGRU server sharded
    over the emulated mesh bit-matches the dense base backend's
    single-device server — scores, argmax, and the hidden-state
    trajectory — for live slab ticks and the scanned replay. The
    per-stream sparsity telemetry survives partitioning (counters are
    just more sharded state leaves)."""
    pipe_delta = KWSPipeline(
        KWSPipelineConfig(classifier=delta_key), norm_stats=norm_stats
    )
    pipe_base = KWSPipeline(
        KWSPipelineConfig(classifier=base_key), norm_stats=norm_stats
    )
    params = pipe_base.init_params(jax.random.PRNGKey(13))
    dense = StreamingKWSServer(pipe_base, params, max_streams=MAX_STREAMS)
    sharded = StreamingKWSServer(
        pipe_delta, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    for srv in (dense, sharded):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    hop = pipe_base.chunk_samples
    rng = np.random.default_rng(14)
    for t in range(3):
        slab = rng.standard_normal((MAX_STREAMS, hop)).astype(np.float32)
        slab *= 0.05
        mask = np.ones(MAX_STREAMS, bool)
        mask[t::3] = False
        s_a, t_a = dense.step_batch(slab, mask)
        s_b, t_b = sharded.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    slab = rng.standard_normal((4, MAX_STREAMS, hop)).astype(np.float32)
    slab *= 0.05
    mask = rng.random((4, MAX_STREAMS)) < 0.7
    seq_a, tops_a = dense.run_batch(slab, mask)
    seq_b, tops_b = sharded.run_batch(slab, mask)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(tops_a, tops_b)
    # the delta server's true hidden state tracks the dense server's
    for hb, std in zip(dense.state.gru, sharded.state.gru):
        np.testing.assert_array_equal(
            np.asarray(hb), np.asarray(std["h"])
        )
    # telemetry: dense base reports all-ones, the sharded delta server
    # a valid fraction per slot (θ=0 still skips exactly-repeated
    # components), gathered transparently from the sharded counters
    np.testing.assert_array_equal(
        dense.sparsity, np.ones(MAX_STREAMS, np.float32)
    )
    frac = sharded.sparsity
    assert frac.shape == (MAX_STREAMS,)
    assert ((frac >= 0.0) & (frac <= 1.0)).all()


def test_sharded_delta_sparsity_matches_single_device(norm_stats):
    """The measured per-stream effective-MAC fraction is placement-
    independent: a θ>0 sharded delta server reports bit-identical
    sparsity to its single-device twin on the same traffic."""
    from repro.core.gru_delta import DeltaConfig

    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier="delta",
            delta=DeltaConfig(theta_x=0.25, theta_h=0.25),
        ),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(15))
    single = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    sharded = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    for srv in (single, sharded):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    hop = pipe.chunk_samples
    rng = np.random.default_rng(16)
    base = rng.standard_normal((MAX_STREAMS, hop)).astype(np.float32) * 0.05
    for t in range(4):
        slab = base + rng.standard_normal(
            (MAX_STREAMS, hop)
        ).astype(np.float32) * 0.002
        mask = np.ones(MAX_STREAMS, bool)
        single.step_batch(slab, mask)
        sharded.step_batch(slab, mask)
    np.testing.assert_array_equal(single.sparsity, sharded.sparsity)
    assert (sharded.sparsity < 1.0).all()  # near-static traffic skips


# --------------------------------------------------------------------------
# cascade: always-open sharded server == plain single-device server
# --------------------------------------------------------------------------

def test_sharded_cascade_always_open_matches_plain(backend):
    """Cross-config AND cross-placement: an always-open cascaded server
    sharded over the emulated mesh bit-matches the NON-cascaded
    single-device server — scores, argmax, hidden states — for live
    slab ticks and the scanned replay, for every backend. The gate
    mask degenerates to the submitted mask, so the extra detector
    leaves in `ServerState` change nothing downstream."""
    pipe, params = backend
    import dataclasses as _dc

    pipe_casc = KWSPipeline(
        _dc.replace(pipe.config, cascade=CascadeConfig.always_on()),
        norm_stats=pipe.norm_stats,
    )
    plain = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    sharded = StreamingKWSServer(
        pipe_casc, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    for srv in (plain, sharded):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    hop = pipe.chunk_samples
    rng = np.random.default_rng(17)
    for t in range(3):
        slab = rng.standard_normal((MAX_STREAMS, hop)).astype(np.float32)
        slab *= 0.05
        mask = np.ones(MAX_STREAMS, bool)
        mask[t::3] = False
        s_a, t_a = plain.step_batch(slab, mask)
        s_b, t_b = sharded.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    slab = rng.standard_normal((4, MAX_STREAMS, hop)).astype(np.float32)
    slab *= 0.05
    mask = rng.random((4, MAX_STREAMS)) < 0.7
    seq_a, tops_a = plain.run_batch(slab, mask)
    seq_b, tops_b = sharded.run_batch(slab, mask)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(tops_a, tops_b)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        list(plain.state.gru),
        list(sharded.state.gru),
    )
    np.testing.assert_array_equal(plain.scores, sharded.scores)
    # every submitted tick woke the classifier, on every shard
    np.testing.assert_array_equal(
        sharded.wake_rate, np.ones(MAX_STREAMS, np.float32)
    )
    # detector leaves are sharded over the stream axis like the rest
    for leaf in jax.tree_util.tree_leaves(sharded.state.det):
        spec = leaf.sharding.spec
        assert spec and spec[0] == STREAM_AXIS, spec


def test_sharded_cascade_wake_rate_matches_single_device(norm_stats):
    """The measured per-stream wake rate is placement-independent: a
    gated sharded server reports bit-identical `wake_rate` (and
    scores) to its single-device twin on the same mixed loud/quiet
    traffic."""
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier="qat",
            cascade=CascadeConfig(wake_threshold=0.3, hangover_frames=1),
        ),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(18))
    single = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    sharded = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    for srv in (single, sharded):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    rng = np.random.default_rng(19)
    for _ in range(6):
        # half the slots get speech-loud frames, half near-silence
        scale = np.where(rng.random(MAX_STREAMS) < 0.5, 3.0, 0.02)
        fv = (
            rng.standard_normal((MAX_STREAMS, 16)) * scale[:, None]
        ).astype(np.float32)
        mask = rng.random(MAX_STREAMS) < 0.8
        s_a, _ = single.step_batch(fv, mask)
        s_b, _ = sharded.step_batch(fv, mask)
        np.testing.assert_array_equal(s_a, s_b)
    np.testing.assert_array_equal(single.wake_rate, sharded.wake_rate)
    wr = sharded.wake_rate
    assert (wr < 1.0).any() and (wr > 0.0).any()  # the gate really gated


# --------------------------------------------------------------------------
# property test: random lifecycles vs a pure-Python oracle
# --------------------------------------------------------------------------

class LifecycleOracle:
    """Pure-Python model of the sharded server's stream lifecycles.

    Tracks, with no device code: which streams are open, every frame
    each stream submitted since it was (re)opened, and the slot each
    stream must occupy (an independent reimplementation of the
    round-robin placement). The expected posteriors for a stream are
    then whatever the single-device engine produces for that stream's
    OWN frame sequence alone — by construction independent of every
    other stream's traffic and of device placement.
    """

    def __init__(self, max_streams, n_shards):
        self.max_streams = max_streams
        self.n_shards = n_shards
        self.per_shard = max_streams // n_shards
        self.free = [
            sorted(range(s * self.per_shard, (s + 1) * self.per_shard))
            for s in range(n_shards)
        ]
        self.slot_of = {}
        self.frames = {}

    def _place(self, sid):
        loads = [self.per_shard - len(f) for f in self.free]
        shard = min(
            (ld, s) for s, ld in enumerate(loads) if self.free[s]
        )[1]
        self.slot_of[sid] = self.free[shard].pop(0)

    def open(self, sid):
        self._place(sid)
        self.frames[sid] = []

    def resize(self, new_max):
        """Model of `StreamingKWSServer.resize`'s router remap: fresh
        free lists at the new capacity, survivors re-placed in
        ascending OLD-slot order through the same least-loaded rule.
        Frames are untouched — a resize must never change what a
        stream has seen."""
        order = sorted(self.slot_of, key=self.slot_of.get)
        self.max_streams = new_max
        self.per_shard = new_max // self.n_shards
        self.free = [
            sorted(range(s * self.per_shard, (s + 1) * self.per_shard))
            for s in range(self.n_shards)
        ]
        self.slot_of = {}
        for sid in order:
            self._place(sid)

    def close(self, sid):
        slot = self.slot_of.pop(sid)
        shard = slot // self.per_shard
        self.free[shard].append(slot)
        self.free[shard].sort()
        del self.frames[sid]

    def submit(self, sid, frame):
        self.frames[sid].append(frame)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    events=st.lists(
        st.tuples(
            st.booleans(),  # open a new stream before this tick?
            st.booleans(),  # close the oldest open stream first?
            st.integers(min_value=0, max_value=255),  # submit bitmask
        ),
        min_size=2,
        max_size=6,
    ),
)
def test_random_schedule_matches_lifecycle_oracle(
    oracle_servers, seed, events
):
    """Random open/close/submit schedules: each open stream's scores
    bit-match a single-device replay of its own recorded frames —
    independent of other streams' traffic and of shard placement."""
    sharded, reference = oracle_servers
    for srv in (sharded, reference):
        for sid in list(srv.active):
            srv.close_stream(sid)
    oracle = LifecycleOracle(sharded.max_streams, sharded.n_devices)
    rng = np.random.default_rng(seed)
    next_sid = 0

    def do_open():
        nonlocal next_sid
        sharded.open_stream(next_sid)
        oracle.open(next_sid)
        next_sid += 1

    do_open()
    for want_open, want_close, submit_bits in events:
        if want_close and len(oracle.slot_of) > 1:
            victim = min(oracle.slot_of)
            sharded.close_stream(victim)
            oracle.close(victim)
        if want_open and len(oracle.slot_of) < sharded.max_streams:
            do_open()
        open_sids = sorted(oracle.slot_of)
        frames = {}
        for i, sid in enumerate(open_sids):
            if submit_bits >> (i % 8) & 1:
                f = rng.standard_normal(16).astype(np.float32)
                frames[sid] = f
                oracle.submit(sid, f)
        out = sharded.step(frames)
        del out
        # placement must match the oracle's independent reimplementation
        assert {s: oracle.slot_of[s] for s in open_sids} == {
            s: sharded.active[s] for s in open_sids
        }
    # every open stream's scores == single-device replay of its own frames
    for sid in sorted(oracle.slot_of):
        reference.open_stream(sid)
        expected = np.zeros_like(
            np.asarray(reference.state.scores[0])
        )
        for f in oracle.frames[sid]:
            out = reference.step({sid: f})
            expected = out[sid]["probs"]
        got = sharded.scores[sharded.active[sid]]
        np.testing.assert_array_equal(got, expected)
        reference.close_stream(sid)


@pytest.fixture(scope="module")
def oracle_servers(norm_stats):
    """(sharded 8-slot server, single-device 1-slot reference) on shared
    qat params — module-scoped so hypothesis examples reuse the
    compiled tick programs."""
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier="qat"), norm_stats=norm_stats
    )
    params = pipe.init_params(jax.random.PRNGKey(7))
    sharded = StreamingKWSServer(
        pipe, params, max_streams=8, devices=MESH_DEV
    )
    reference = StreamingKWSServer(pipe, params, max_streams=1)
    return sharded, reference


@pytest.fixture(scope="module")
def cascade_oracle_servers(norm_stats):
    """Cascaded twin of `oracle_servers`: a real wake threshold with
    hangover, so random schedules exercise gated AND woken ticks."""
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier="qat",
            cascade=CascadeConfig(wake_threshold=0.3, hangover_frames=1),
        ),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(7))
    sharded = StreamingKWSServer(
        pipe, params, max_streams=8, devices=MESH_DEV
    )
    reference = StreamingKWSServer(pipe, params, max_streams=1)
    return sharded, reference


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    events=st.lists(
        st.tuples(
            st.booleans(),  # open a new stream before this tick?
            st.booleans(),  # close the oldest open stream first?
            st.integers(min_value=0, max_value=255),  # submit bitmask
        ),
        min_size=2,
        max_size=6,
    ),
)
def test_random_schedule_cascade_wake_rate_oracle(
    cascade_oracle_servers, seed, events
):
    """Random open/close/submit schedules on a GATED cascaded server:
    each open stream's scores AND wake rate bit-match a single-device
    replay of its own recorded frames. This pins every telemetry
    clause at once: `wake_rate` resets on open_stream (the reference
    starts at 1.0 and the replay reproduces it from scratch), freezes
    while idle (ticks the stream skipped leave no trace), and is
    independent of shard placement and other streams' traffic."""
    sharded, reference = cascade_oracle_servers
    for srv in (sharded, reference):
        for sid in list(srv.active):
            srv.close_stream(sid)
    oracle = LifecycleOracle(sharded.max_streams, sharded.n_devices)
    rng = np.random.default_rng(seed)
    next_sid = 0

    def do_open():
        nonlocal next_sid
        sharded.open_stream(next_sid)
        oracle.open(next_sid)
        # a freshly opened slot reads unity wake rate (reset contract)
        assert sharded.wake_rate[sharded.active[next_sid]] == 1.0
        next_sid += 1

    do_open()
    for want_open, want_close, submit_bits in events:
        if want_close and len(oracle.slot_of) > 1:
            victim = min(oracle.slot_of)
            sharded.close_stream(victim)
            oracle.close(victim)
        if want_open and len(oracle.slot_of) < sharded.max_streams:
            do_open()
        open_sids = sorted(oracle.slot_of)
        frames = {}
        for i, sid in enumerate(open_sids):
            if submit_bits >> (i % 8) & 1:
                # mixed traffic: loud frames wake the gate, quiet ones
                # leave it (or its hangover) to gate the classifier
                scale = 3.0 if rng.random() < 0.5 else 0.02
                f = (rng.standard_normal(16) * scale).astype(np.float32)
                frames[sid] = f
                oracle.submit(sid, f)
        sharded.step(frames)
    # every open stream's scores and wake rate == single-device replay
    # of its own frames alone
    for sid in sorted(oracle.slot_of):
        reference.open_stream(sid)
        assert reference.wake_rate[0] == 1.0
        expected = np.zeros_like(np.asarray(reference.state.scores[0]))
        for f in oracle.frames[sid]:
            out = reference.step({sid: f})
            expected = out[sid]["probs"]
        slot = sharded.active[sid]
        np.testing.assert_array_equal(sharded.scores[slot], expected)
        np.testing.assert_array_equal(
            sharded.wake_rate[slot], reference.wake_rate[0]
        )
        reference.close_stream(sid)


# --------------------------------------------------------------------------
# elastic capacity: live resize (grow / shrink) & shard-loss recovery
# --------------------------------------------------------------------------

GROWN = MAX_STREAMS * 2


def test_resize_grow_shrink_bit_identical(server_pair):
    """Grow then shrink back, live, against an UN-resized single-device
    server: every surviving stream's posteriors and full per-slot state
    (GRU/delta/carry/scores leaves) stay bit-identical through both
    moves — for every classifier backend."""
    single, sharded = server_pair
    _reset_pair(server_pair)
    for srv in (single, sharded):
        for sid in range(10):
            srv.open_stream(sid)
    rng = np.random.default_rng(30)

    def tick(n):
        for _ in range(n):
            frames = {
                sid: rng.standard_normal(16).astype(np.float32)
                for sid in sorted(sharded.active)
            }
            out_a = single.step(frames)
            out_b = sharded.step(frames)
            for sid in frames:
                np.testing.assert_array_equal(
                    out_a[sid]["probs"], out_b[sid]["probs"]
                )
                assert out_a[sid]["top"] == out_b[sid]["top"]

    try:
        tick(2)
        sharded.resize(GROWN)
        assert sharded.max_streams == GROWN
        assert sharded.router.max_streams == GROWN
        tick(2)
        # grown capacity is genuinely usable: open past the old limit
        for sid in range(100, 100 + MAX_STREAMS):
            sharded.open_stream(sid)
        assert len(sharded.active) > MAX_STREAMS
        for sid in range(100, 100 + MAX_STREAMS):
            sharded.close_stream(sid)
        sharded.resize(MAX_STREAMS)
        tick(2)
        # full per-slot state, not just scores, survived both moves
        for sid in sorted(single.active):
            jax.tree_util.tree_map(
                np.testing.assert_array_equal,
                _slot_slice(single, sid),
                _slot_slice(sharded, sid),
            )
    finally:
        if sharded.max_streams != MAX_STREAMS:
            sharded.resize(MAX_STREAMS)  # module-scoped fixture


def test_resize_keeps_mesh_layout(server_pair):
    """After a grow every state leaf is still block-sharded over the
    SAME ("stream",) mesh at the new capacity, params stay replicated,
    and the router's placement stays balanced — no program rebuild, no
    layout drift."""
    _, sharded = server_pair
    _reset_pair(server_pair)
    for sid in range(MAX_STREAMS):
        sharded.open_stream(sid)
    mesh_before = sharded.mesh
    tick_before = sharded._tick_fv
    try:
        sharded.resize(GROWN)
        assert sharded.mesh is mesh_before
        # resize must NOT rebuild the jitted programs (shape-agnostic
        # NamedShardings; jax's own cache handles the retrace)
        assert sharded._tick_fv is tick_before
        for leaf in jax.tree_util.tree_leaves(sharded.state):
            assert leaf.shape[0] == GROWN
            spec = leaf.sharding.spec
            assert spec and spec[0] == STREAM_AXIS, spec
        for leaf in jax.tree_util.tree_leaves(sharded.params):
            assert leaf.sharding.is_fully_replicated
        loads = sharded.router.shard_loads()
        assert max(loads) - min(loads) <= 1
        assert sum(loads) == len(sharded.active)
    finally:
        sharded.resize(MAX_STREAMS)


def test_resize_validation(server_pair):
    _, sharded = server_pair
    _reset_pair(server_pair)
    for sid in range(10):
        sharded.open_stream(sid)
    with pytest.raises(ValueError, match="divide over"):
        sharded.resize(MAX_STREAMS + 1)
    with pytest.raises(ValueError, match=">= 1"):
        sharded.resize(0)
    with pytest.raises(RuntimeError, match="open"):
        sharded.resize(MESH_DEV)  # 10 open streams never fit
    # same capacity is a no-op: same state object, nothing re-laid
    state_before = sharded.state
    sharded.resize(MAX_STREAMS)
    assert sharded.state is state_before


def test_resize_with_async_handle_in_flight(backend):
    """A TickHandle dispatched BEFORE a resize stays valid after it:
    the handle owns device-side copies, so its scores bit-match the
    synchronous un-resized twin however late it is fetched. The twin
    is a second SHARDED server (identical router placement — slot-
    major `step_batch` comparisons are only meaningful between servers
    that place the same stream on the same slot)."""
    pipe, params = backend
    sharded = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    twin = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    for srv in (sharded, twin):
        for sid in range(MAX_STREAMS):
            srv.open_stream(sid)
    assert sharded.active == twin.active  # identical placement
    rng = np.random.default_rng(31)
    mask = np.ones(MAX_STREAMS, bool)
    fv1 = rng.standard_normal((MAX_STREAMS, 16)).astype(np.float32)
    fv2 = rng.standard_normal((MAX_STREAMS, 16)).astype(np.float32)
    s1, t1 = twin.step_batch(fv1, mask)
    handle = sharded.step_batch_async(fv1, mask)
    sharded.resize(GROWN)  # before the handle is fetched
    s_b, t_b = handle.result()
    np.testing.assert_array_equal(s1, s_b)
    np.testing.assert_array_equal(t1, t_b)
    # and the resized server keeps serving bit-identically, per sid
    out_a = twin.step({sid: fv2[sid] for sid in range(MAX_STREAMS)})
    out_b = sharded.step({sid: fv2[sid] for sid in range(MAX_STREAMS)})
    for sid in range(MAX_STREAMS):
        np.testing.assert_array_equal(
            out_a[sid]["probs"], out_b[sid]["probs"]
        )


def test_resize_cascaded_bit_identical(norm_stats):
    """A GATED cascaded server (real wake threshold + hangover) grown
    and shrunk mid-traffic: per-stream scores AND the wake-rate
    telemetry bit-match the un-resized single-device twin — detector
    state (awake latch, hangover countdown, woken/ticks counters) is
    carried bitwise like every other leaf."""
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier="qat",
            cascade=CascadeConfig(wake_threshold=0.3, hangover_frames=1),
        ),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(33))
    single = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    sharded = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    for srv in (single, sharded):
        for sid in range(12):
            srv.open_stream(sid)
    rng = np.random.default_rng(34)

    def tick(n):
        for _ in range(n):
            frames = {}
            for sid in sorted(sharded.active):
                scale = 3.0 if rng.random() < 0.5 else 0.02
                frames[sid] = (
                    rng.standard_normal(16) * scale
                ).astype(np.float32)
            out_a = single.step(frames)
            out_b = sharded.step(frames)
            for sid in frames:
                np.testing.assert_array_equal(
                    out_a[sid]["probs"], out_b[sid]["probs"]
                )

    tick(3)
    sharded.resize(GROWN)
    tick(3)
    sharded.resize(MAX_STREAMS)
    tick(3)
    wr_a, wr_b = single.wake_rate, sharded.wake_rate
    for sid in sorted(single.active):
        np.testing.assert_array_equal(
            wr_a[single.active[sid]], wr_b[sharded.active[sid]]
        )
    assert (wr_b[: len(sharded.active)] < 1.0).any() or (
        wr_a < 1.0
    ).any()  # the gate really gated through the moves


def test_shard_loss_recovery(backend):
    """Simulated loss of one shard: recovery shrink-reshards onto the
    surviving devices, healthy streams' per-slot state is BIT-unchanged
    through the move, the lost shard's streams reopen (same ids) on
    fresh zeroed slots, and post-recovery serving bit-matches a
    single-device replay of each stream's surviving history — for
    every classifier backend.

    The reference is width-matched (same max_streams, one device):
    XLA vectorizes the float classifier differently at different batch
    widths, so bit-identity only holds at a fixed slot-axis width —
    which is exactly what recovery preserves (16 slots before and
    after losing a shard here), while the device count shrinks."""
    pipe, params = backend
    sharded = StreamingKWSServer(
        pipe, params, max_streams=MAX_STREAMS, devices=MESH_DEV
    )
    reference = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    rng = np.random.default_rng(35)
    history = {sid: [] for sid in range(12)}
    for sid in range(12):
        sharded.open_stream(sid)

    def tick(n):
        for _ in range(n):
            frames = {
                sid: rng.standard_normal(16).astype(np.float32)
                for sid in sorted(sharded.active)
            }
            sharded.step(frames)
            for sid, f in frames.items():
                history[sid].append(f)

    tick(3)
    lost = 1
    pre = {sid: _slot_slice(sharded, sid) for sid in sharded.active}
    lost_sids = {
        sid for sid, slot in sharded.active.items()
        if shard_of_slot(slot, MAX_STREAMS, MESH_DEV) == lost
    }
    assert lost_sids  # 12 streams round-robin over <= 8 shards
    info = sharded.recover_shard_loss(lost)
    assert set(info["reopened"]) == lost_sids
    assert set(info["survivors"]) == set(range(12)) - lost_sids
    assert sharded.n_devices < MESH_DEV
    assert sharded.max_streams % sharded.n_devices == 0
    assert set(sharded.active) == set(range(12))  # same ids throughout
    # healthy shards' per-stream state: bit-unchanged through the move
    for sid in info["survivors"]:
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            pre[sid],
            _slot_slice(sharded, sid),
        )
    # the lost shard's streams: fresh zeroed slots, history restarted
    for sid in info["reopened"]:
        jax.tree_util.tree_map(
            lambda t: np.testing.assert_array_equal(t, np.zeros_like(t)),
            _slot_slice(sharded, sid),
        )
        history[sid] = []
    # the state leaves live on the SMALLER mesh now
    if sharded.mesh is not None:
        for leaf in jax.tree_util.tree_leaves(sharded.state):
            assert len(leaf.devices()) == sharded.n_devices
    tick(2)
    # every stream bit-matches a single-device replay of the frames its
    # surviving state has seen
    for sid in sorted(sharded.active):
        reference.open_stream(sid)
        expected = np.zeros_like(np.asarray(reference.state.scores[0]))
        for f in history[sid]:
            out = reference.step({sid: f})
            expected = out[sid]["probs"]
        np.testing.assert_array_equal(
            sharded.scores[sharded.active[sid]], expected
        )
        reference.close_stream(sid)


def test_shard_loss_validation(server_pair, backend):
    _, sharded = server_pair
    with pytest.raises(ValueError, match="outside"):
        sharded.recover_shard_loss(sharded.n_devices)
    pipe, params = backend
    single = StreamingKWSServer(pipe, params, max_streams=4)
    with pytest.raises(ValueError, match="no shards"):
        single.recover_shard_loss(0)


# --------------------------------------------------------------------------
# router: churn at capacity boundaries + remap
# --------------------------------------------------------------------------

def test_shard_of_slot_validates_divisibility():
    """Regression: max_streams=10 over 4 shards used to silently
    truncate to 2-slot blocks, reporting slot 9 on 'shard 4' — an
    index past the mesh. Uneven geometry is now an error at the
    function itself, not only in StreamRouter.__init__."""
    with pytest.raises(ValueError, match="divide evenly"):
        shard_of_slot(9, 10, 4)
    with pytest.raises(ValueError, match="n_shards"):
        shard_of_slot(0, 8, 0)
    with pytest.raises(ValueError, match="outside"):
        shard_of_slot(8, 8, 4)
    assert shard_of_slot(5, 8, 4) == 2


def test_router_churn_at_capacity_boundaries():
    """Random release/acquire interleavings: every acquire targets a
    least-loaded shard (with free capacity), double-release raises,
    acquire-at-full raises, and one release reopens exactly one
    slot."""
    rng = np.random.default_rng(40)
    r = StreamRouter(MAX_STREAMS, MESH_DEV)
    held = []
    for _ in range(300):
        if held and (rng.random() < 0.45 or r.free_count == 0):
            s = held.pop(int(rng.integers(len(held))))
            r.release(s)
            with pytest.raises(ValueError, match="already free"):
                r.release(s)
        else:
            loads_before = r.shard_loads()
            slot = r.acquire()
            shard = shard_of_slot(slot, MAX_STREAMS, MESH_DEV)
            eligible = [
                ld for ld in loads_before if ld < r.slots_per_shard
            ]
            assert loads_before[shard] == min(eligible)
            held.append(slot)
    while r.free_count:
        held.append(r.acquire())
    with pytest.raises(RuntimeError, match="capacity"):
        r.acquire()
    r.release(held.pop())
    assert r.free_count == 1
    held.append(r.acquire())
    with pytest.raises(RuntimeError, match="capacity"):
        r.acquire()


def test_router_remap_survives_resize():
    """Placements survive a resize remap: deterministic mapping in
    ascending old-slot order, balanced on the new geometry, further
    acquires continue the round-robin fill, and impossible remaps
    (overflow, duplicates) are rejected before any state would move."""
    r = StreamRouter(MAX_STREAMS, MESH_DEV)
    slots = [r.acquire() for _ in range(MAX_STREAMS)]
    kept = [s for i, s in enumerate(slots) if i % 3]  # scattered subset
    # grow remap
    r2, mapping = StreamRouter.remap(kept, GROWN, MESH_DEV)
    assert sorted(mapping) == sorted(kept)
    assert len(set(mapping.values())) == len(kept)
    assert all(0 <= v < GROWN for v in mapping.values())
    loads = r2.shard_loads()
    assert max(loads) - min(loads) <= 1
    assert sum(loads) == len(kept)
    # deterministic: identical inputs -> identical mapping
    _, mapping2 = StreamRouter.remap(kept, GROWN, MESH_DEV)
    assert mapping2 == mapping
    # the remapped router keeps allocating balanced
    extra = r2.acquire()
    assert extra not in set(mapping.values())
    loads = r2.shard_loads()
    assert max(loads) - min(loads) <= 1
    # shrink remap down to the exact occupied count still fits
    n_kept = len(kept)
    target = -(-n_kept // MESH_DEV) * MESH_DEV
    r3, m3 = StreamRouter.remap(kept, target, MESH_DEV)
    assert sorted(m3.values()) == list(range(n_kept)) or len(
        set(m3.values())
    ) == n_kept
    assert r3.free_count == target - n_kept
    # rejected remaps
    with pytest.raises(ValueError, match="cannot remap"):
        StreamRouter.remap(list(range(MESH_DEV + 1)), MESH_DEV, MESH_DEV)
    with pytest.raises(ValueError, match="unique"):
        StreamRouter.remap([1, 1], MAX_STREAMS, MESH_DEV)


# --------------------------------------------------------------------------
# property test: random lifecycles WITH live resizes vs the oracle
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def resize_oracle_servers(norm_stats):
    """(elastic sharded server, single-device 1-slot reference) —
    capacity starts at 8 and toggles among {8, 16, 32} across
    examples, so jax's shape-keyed jit cache amortizes the retraces."""
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier="qat"), norm_stats=norm_stats
    )
    params = pipe.init_params(jax.random.PRNGKey(9))
    sharded = StreamingKWSServer(
        pipe, params, max_streams=8, devices=MESH_DEV
    )
    reference = StreamingKWSServer(pipe, params, max_streams=1)
    return sharded, reference


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    events=st.lists(
        st.tuples(
            st.booleans(),  # open a new stream before this tick?
            st.booleans(),  # close the oldest open stream first?
            st.integers(min_value=0, max_value=255),  # submit bitmask
            st.sampled_from(("none", "grow", "shrink")),  # resize after
        ),
        min_size=2,
        max_size=6,
    ),
)
def test_random_schedule_with_resize_matches_oracle(
    resize_oracle_servers, seed, events
):
    """The lifecycle-oracle harness extended with live resizes: random
    open/close/submit/grow/shrink schedules, placements matching the
    oracle's independent remap model after every event, and each open
    stream's final scores bit-matching a single-device replay of its
    own frames — a resize is invisible to every surviving stream."""
    sharded, reference = resize_oracle_servers
    for sid in list(sharded.active):
        sharded.close_stream(sid)
    sharded.resize(8)
    oracle = LifecycleOracle(8, sharded.n_devices)
    rng = np.random.default_rng(seed)
    next_sid = 0

    def do_open():
        nonlocal next_sid
        sharded.open_stream(next_sid)
        oracle.open(next_sid)
        next_sid += 1

    do_open()
    for want_open, want_close, submit_bits, action in events:
        if want_close and len(oracle.slot_of) > 1:
            victim = min(oracle.slot_of)
            sharded.close_stream(victim)
            oracle.close(victim)
        if want_open and len(oracle.slot_of) < sharded.max_streams:
            do_open()
        open_sids = sorted(oracle.slot_of)
        frames = {}
        for i, sid in enumerate(open_sids):
            if submit_bits >> (i % 8) & 1:
                f = rng.standard_normal(16).astype(np.float32)
                frames[sid] = f
                oracle.submit(sid, f)
        sharded.step(frames)
        new_max = None
        if action == "grow" and sharded.max_streams < 32:
            new_max = sharded.max_streams * 2
        elif action == "shrink" and sharded.max_streams > 8:
            half = sharded.max_streams // 2
            if half >= len(sharded.active):
                new_max = half
        if new_max is not None:
            sharded.resize(new_max)
            oracle.resize(new_max)
        # placement matches the oracle's independent remap model
        assert oracle.slot_of == dict(sharded.active)
    # every open stream's scores == single-device replay of its frames
    for sid in sorted(oracle.slot_of):
        reference.open_stream(sid)
        expected = np.zeros_like(
            np.asarray(reference.state.scores[0])
        )
        for f in oracle.frames[sid]:
            out = reference.step({sid: f})
            expected = out[sid]["probs"]
        got = sharded.scores[sharded.active[sid]]
        np.testing.assert_array_equal(got, expected)
        reference.close_stream(sid)
