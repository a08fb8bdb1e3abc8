"""The check passes a sound run and fails what it must: the control, and
a run whose timed path is broken underneath. Runs the harness on the
CPU at a tiny fleet; conftest.py skips its look for a chip.
"""

import functools
import time

import jax.numpy as jnp
import pytest

from bench import control, harness

SMALL = {"streams": 32, "tracks": 4, "track_hops": 100, "check_streams": 16,
         "trace_ticks": 8}


def run(workload="int8-fv-rt", seconds=0.5):
    return harness.run_cell(workload, 987654321012, seconds, False,
                            time.perf_counter(), mix_override=SMALL)


@pytest.fixture
def plant(monkeypatch):
    from repro.serving import serve_loop

    orig = serve_loop._fused_tick

    def install(fault):
        @functools.wraps(orig)
        def tick(pipeline, raw_audio, params, state, inp, mask, *a, **kw):
            return fault(orig, pipeline, raw_audio, params, state, inp, mask,
                         *a, **kw)
        monkeypatch.setattr(serve_loop, "_fused_tick", tick)
    return install


@pytest.mark.parametrize("workload", ["int8-fv-rt", "cascade-fv-quiet-rt",
                                      "int8-fv-drain"])
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0


def _state_unchanged(orig, pipeline, raw, params, state, inp, mask, *a, **kw):
    _, scores, top = orig(pipeline, raw, params, state, inp, mask, *a, **kw)
    return state, scores, top


def _half_batch(orig, pipeline, raw, params, state, inp, mask, *a, **kw):
    keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
    return orig(pipeline, raw, params, state, inp, mask & keep, *a, **kw)


def _answer_altered(orig, pipeline, raw, params, state, inp, mask, *a, **kw):
    new, scores, top = orig(pipeline, raw, params, state, inp, mask, *a, **kw)
    return new, scores.at[:, 0].add(1e-3), top


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_is_not_correct(plant, fault):
    plant(fault)
    r = run()
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_is_not_correct(seed):
    r = control.readings("int8-fv-rt", seed, 0.5, mix_override=SMALL)
    assert not r["correct"], r
    assert r["h1_mismatch"] > 0.5 and r["score_mismatch"] > 0.5
