"""The trace reduction on a hand-made trace and on a slice recorded on a
TPU v5e (``fixtures/trace_rt.json.gz``)."""

import pathlib

import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_rt.json.gz"


def hand_trace():
    return {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 0, 10), ("intgemm_call", 5, 15),
            ("fusion.2", 20, 30), ("late", 45, 50)]},
        "host": [("slice", 0, 40), ("wait", 14, 21), ("stage", 30, 40),
                 ("fetch", 29, 31)],
    }


def test_busy_is_the_union_of_ops_inside_the_slice():
    s = trace.summarize(hand_trace(), "intgemm")
    # [0, 15] and [20, 30]; the op after the slice does not count
    assert s["busy_s"]["/device:TPU:0"] == pytest.approx(25e-9)
    assert s["slice_s"] == pytest.approx(40e-9)


def test_kernel_events_match_by_name():
    s = trace.summarize(hand_trace(), "intgemm")
    assert s["kernel_n"] == {"/device:TPU:0": 1}
    assert s["kernel_s"]["/device:TPU:0"] == pytest.approx(10e-9)


def test_idle_gaps_are_named_by_the_host_phase_overlapping_most():
    s = trace.summarize(hand_trace(), "intgemm")
    assert s["idle_gaps"] == [("stage", pytest.approx(10e-9)),
                              ("wait", pytest.approx(5e-9))]
    assert s["idle_by_phase"] == {"stage": pytest.approx(10e-9),
                                  "wait": pytest.approx(5e-9)}


def test_union_merges_overlaps_and_clips():
    ops = [("a", -5, 3), ("b", 2, 6), ("c", 8, 12)]
    assert trace.union(ops, 0, 10) == [(0, 6), (8, 10)]
    assert trace.gaps([(0, 6), (8, 10)], 0, 12) == [(6, 8), (10, 12)]


@pytest.fixture(scope="module")
def chip_trace():
    if not FIXTURE.exists():
        pytest.fail(f"missing fixture {FIXTURE}")
    return trace.from_json(str(FIXTURE))


def test_recorded_slice_busy_and_idle_are_consistent(chip_trace):
    s = trace.summarize(chip_trace, "intgemm")
    assert len(s["busy_s"]) == 1
    busy = next(iter(s["busy_s"].values()))
    assert 0 < busy < s["slice_s"]
    idle = sum(g for g in s["idle_by_phase"].values())
    assert busy + idle == pytest.approx(s["slice_s"], rel=1e-6)


def test_recorded_slice_has_five_intgemm_calls_per_tick(chip_trace):
    s = trace.summarize(chip_trace, "intgemm")
    ticks = sum(1 for name, _, _ in chip_trace["host"] if name == "dispatch")
    assert s["kernel_n"] == {k: 5 * ticks for k in s["kernel_n"]}
    assert all(v > 0 for v in s["kernel_s"].values())


def test_recorded_slice_idle_gaps_are_attributed(chip_trace):
    s = trace.summarize(chip_trace, "intgemm")
    assert set(s["idle_by_phase"]) <= {"wait", "stage", "dispatch", "fetch",
                                       "none"}
    assert s["idle_gaps"][0][1] >= s["idle_gaps"][-1][1]
