"""The serving tick is stream-separable (repro.serving.tick).

`serve_tick` has no cross-stream term anywhere: frontend carry, wake
gate, GRU layers, FC head, softmax and smoothing all act row by row,
and an idle row keeps its old value. So a stream served in a slab of N
slots must see exactly what a one-slot server fed that stream alone
sees, whatever the other rows carry and whichever ticks it skips. This
suite checks that at the edges of the tick's geometry: a single-stream
slab, odd slot counts, and a hidden width (20) that is no multiple of
the TPU lane width, for every classifier backend (the ΔGRU pair at a
real θ, so columns are skipped), under rotating partial masks and one
all-idle tick.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.gru import GRUConfig
from repro.core.gru_delta import DeltaConfig
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.serving.serve_loop import StreamingKWSServer

THETA = 0.15
TICKS = 4


@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


def _slot(srv, slot):
    return [np.asarray(leaf)[slot]
            for leaf in jax.tree_util.tree_leaves(srv.state)]


@pytest.mark.parametrize("max_streams,hidden,classifier", [
    (1, 48, "qat"),
    (5, 20, "delta-int"),
    (13, 20, "delta"),
    (7, 48, "integer"),
    (13, 48, "float"),
])
def test_tick_is_stream_separable(norm_stats, max_streams, hidden,
                                  classifier):
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier=classifier,
                          gru=GRUConfig(hidden_dim=hidden),
                          delta=DeltaConfig(THETA, THETA)),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(21))
    if classifier == "float":
        # The float backend's hidden state and scores are unquantized
        # f32. XLA:CPU sums an N-row dot and a 1-row dot in different
        # orders, so they differ in the last bits (measured: <= 9e-8 on
        # hidden values, <= 4e-9 on scores after five ticks); every
        # other backend keeps its values on a fixed-point grid where
        # the sums are exact, and is held to array equality.
        same = functools.partial(np.testing.assert_allclose, rtol=0,
                                 atol=1e-6)
    else:
        same = np.testing.assert_array_equal
    n, hop = max_streams, pipe.chunk_samples
    rng = np.random.default_rng(21)
    audio = rng.standard_normal((TICKS, n, hop)).astype(np.float32) * 0.05
    # rotating partial masks, then one tick where every stream is idle
    masks = [np.array([(t + s) % 3 != 0 for s in range(n)])
             for t in range(TICKS)] + [np.zeros(n, bool)]
    slabs = list(audio) + [np.zeros((n, hop), np.float32)]

    full = StreamingKWSServer(pipe, params, max_streams=n)
    for sid in range(n):
        full.open_stream(sid)
    got = []
    for slab, mask in zip(slabs, masks):
        rows = np.zeros_like(slab)
        m = np.zeros(n, bool)
        for sid, slot in full.active.items():
            rows[slot], m[slot] = slab[sid], mask[sid]
        got.append(full.step_batch(rows, m))

    solo = StreamingKWSServer(pipe, params, max_streams=1)
    for sid in range(n):
        solo.open_stream(sid)
        slot = full.active[sid]
        for (scores, top), slab, mask in zip(got, slabs, masks):
            s1, t1 = solo.step_batch(slab[sid][None], mask[sid][None])
            assert int(t1[0]) == int(top[slot])
            same(s1[0], scores[slot])
        for a, b in zip(_slot(solo, 0), _slot(full, slot)):
            same(a, b)
        solo.close_stream(sid)
