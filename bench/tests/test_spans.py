"""The program-span reduction (`bench/spans.py`) on a hand-made trace, on a
host profile recorded on the CPU, and on a slice recorded on a TPU v5e
(``fixtures/spans_rt.json.gz``: `int8-fv-rt`, 3072 streams, 64 ticks, seed
14102: `from_xplane` of the run's profile with `tick_map` of its compiled
tick, cut to the traced slice and written as gzipped JSON)."""

import glob
import importlib.util
import math
import pathlib
import types

import pytest

from bench import spans, trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
TICK, OWN = "jit__tick", "jit__own_copies"


def hand_trace():
    """Dispatches 7 and 8, 100 ns apart, on one device; tick 6's device
    work ends just inside the slice."""
    program = []
    for t, base in ((7, 0), (8, 100)):
        program += [
            ("kws.ingress.stage", base + 0, base + 4, t),
            ("kws.ingress.commit", base + 4, base + 9, t),
            ("kws.server.dispatch", base + 5, base + 9, t),
            ("kws.server.tick_call", base + 5, base + 8, t),
            ("kws.server.own_copy", base + 8, base + 9, t),
        ]
    program += [("kws.handle.fetch", 30, 45, 7),
                ("kws.handle.fetch", 130, 150, 8)]
    modules = [(OWN, 1, 2),  # tick 6's copies: no tick run precedes it
               (TICK, 10, 40), (OWN, 41, 43),
               (TICK, 112, 140), (OWN, 140, 141),
               (TICK, 170, 180)]  # after the slice
    scoped = [
        (OWN, 1, 2),
        ("kws_classifier/kws_gru0_gemm", 10, 12),
        ("kws_classifier/kws_gru0_gates", 12, 22),
        ("kws_classifier/kws_gru1_gates", 22, 32), ("kws_smooth", 32, 40),
        (OWN, 41, 43),
        ("kws_classifier/kws_gru0_gemm", 112, 114),
        ("kws_classifier/kws_gru0_gates", 114, 124), ("", 124, 125),
        ("kws_classifier/kws_gru1_gates", 125, 135), ("kws_smooth", 135, 140),
        (OWN, 140, 141),
        ("kws_classifier/kws_gru0_gates", 170, 180),
    ]
    return {"program": sorted(program, key=lambda s: s[1]),
            "modules": {"/device:TPU:0": modules},
            "scoped": {"/device:TPU:0": scoped}, "tick_module": TICK}


def test_readings_of_a_hand_made_trace():
    r = spans.readings(hand_trace(), 0, 160, ticks=2)
    # (10 + 10) + (10 + 10) ns of gates over 2 ticks
    assert r["gate_rom_ms"] == pytest.approx(20e-6)
    assert r["tick_call_ms"] == pytest.approx(3e-6)
    # device work 10 - call 5, 112 - 105: 5 and 7 ns
    assert r["launch_lag_ms"] == pytest.approx(6e-6)
    # fetch end 45 - copies' end 43, 150 - 141: 2 and 9 ns
    assert r["scores_lag_ms"] == pytest.approx(5.5e-6)
    assert r["scope_ms"][""] == pytest.approx(0.5e-6)
    assert r["scope_ms"]["kws_smooth"] == pytest.approx(6.5e-6)
    assert r["scope_ms"][OWN] == pytest.approx(2e-6)


def test_dispatches_pair_with_device_runs_from_the_end():
    """Pairing is by order, not by clock: a device clock read early
    (work seeming to start before its call) moves the lags, never which
    tick the work is counted to."""
    tr = hand_trace()
    assert spans.dispatch_runs(tr["modules"]["/device:TPU:0"], TICK,
                               0, 160) == [(10, 43), (112, 141)]
    early = dict(tr, modules={"/device:TPU:0": [
        (n, s - 8, e - 8) for n, s, e in tr["modules"]["/device:TPU:0"]
        if s >= 8]})
    launch, scores = spans.tick_lags(early, 0, 160)
    assert launch == pytest.approx([-3e-9, -1e-9])
    assert scores == pytest.approx([10e-9, 17e-9])


def test_the_latest_device_sets_the_lags():
    tr = hand_trace()
    late = [(n, s + 3, e + 3) for n, s, e in tr["modules"]["/device:TPU:0"]]
    tr["modules"]["/device:TPU:1"] = late
    launch, scores = spans.tick_lags(tr, 0, 160)
    assert launch == pytest.approx([8e-9, 10e-9])
    assert scores == pytest.approx([-1e-9, 6e-9])


def test_a_trace_without_program_marks_reads_nothing():
    """A program without spans or scopes (an older checkout) gives no
    reading, and raises nothing."""
    tr = {"program": [], "modules": {"/device:TPU:0": [("jit_x", 0, 10)]},
          "scoped": {"/device:TPU:0": [("", 0, 10)]}, "tick_module": "jit_x"}
    r = spans.readings(tr, 0, 20, ticks=1)
    assert r["gate_rom_ms"] is None and r["tick_call_ms"] is None
    assert r["launch_lag_ms"] is None and r["scores_lag_ms"] is None


HLO = """HloModule jit__tick, is_scheduled=true

%fused_computation (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %g = s32[8]{0} negate(%p), metadata={op_name="jit(f)/kws_inner"}
}

ENTRY %main.1 (x.1: s32[8]) -> s32[8] {
  %x.1 = s32[8]{0} parameter(0), metadata={op_name="x"}
  %copy-start = (s32[8]{0}, u32[]) copy-start(%x.1)
  %fusion.6 = s32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(<unknown>)/kws_classifier/kws_gru0_gates/jit(_take)/gather" stack_frame_id=66}
  ROOT %kws_intgemm.5 = s32[8]{0} custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(<unknown>)/kws_classifier/kws_head/jit(intgemm_pallas)/kws_intgemm/pallas_call"}
}
"""


def test_tick_map_names_entry_instructions_by_scope():
    m = spans.tick_map(HLO)
    assert m["module"] == "jit__tick"
    assert m["scopes"] == {
        "x.1": "", "copy-start": "",
        "fusion.6": "kws_classifier/kws_gru0_gates",
        "kws_intgemm.5": "kws_classifier/kws_head/kws_intgemm",
    }


def test_host_spans_from_a_cpu_profile(tmp_path):
    import jax
    import numpy as np

    from repro.serving.metrics import span

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for k in range(3):
            with span("kws.server.tick_call", tick=k):
                np.ones(4).sum()
        with span("other"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    reduced = spans.from_xplane(path, spans.tick_map(HLO))
    assert [(n, t) for n, _, _, t in reduced["program"]] == [
        ("kws.server.tick_call", k) for k in range(3)]
    assert reduced["scoped"] == {}


@pytest.fixture(scope="module")
def chip_slice():
    return spans.from_json(str(FIXTURES / "spans_rt.json.gz"))


def test_recorded_slice_readings(chip_slice):
    lo, hi = 0, math.inf  # the fixture holds the slice only
    r = spans.readings(chip_slice, lo, hi, ticks=64)
    (ops,) = chip_slice["scoped"].values()
    busy = sum(e - s for s, e in trace.union(ops, lo, hi)) * 1e-6 / 64
    # the six ROM gathers are most of the tick, and never more than it
    assert 0.9 * busy < r["gate_rom_ms"] <= busy
    named = sum(v for k, v in r["scope_ms"].items() if k.startswith("kws_"))
    assert named >= 0.95 * busy
    assert r["tick_call_ms"] > 0
    launch, scores = spans.tick_lags(chip_slice, lo, hi)
    assert len(launch) == len(scores) == 64


def test_recorded_slice_spans_per_dispatch(chip_slice):
    """Every dispatch of the slice left one of each span, tagged with its
    number, and the lags with the device work tile call start to fetch
    end."""
    per = spans.span_seconds(chip_slice, 0, math.inf)
    for name in ("kws.ingress.stage", "kws.ingress.commit",
                 "kws.server.dispatch", "kws.server.tick_call",
                 "kws.server.own_copy", "kws.handle.fetch",
                 "kws.handle.wait", "kws.handle.d2h"):
        assert len(per[name]) == 64, name
    calls = {t: s for n, s, _, t in chip_slice["program"]
             if n == "kws.server.tick_call"}
    fetch_end = {t: e for n, _, e, t in chip_slice["program"]
                 if n == "kws.handle.fetch"}
    assert sorted(calls) == sorted(fetch_end)
    assert sorted(calls) == list(range(min(calls), min(calls) + 64))
    launch, scores = spans.tick_lags(chip_slice, 0, math.inf)
    (mods,) = chip_slice["modules"].values()
    runs = spans.dispatch_runs(mods, chip_slice["tick_module"], 0, math.inf)
    device = sum(e - s for s, e in runs[-64:]) * 1e-9
    total = sum(fetch_end[t] - calls[t] for t in calls) * 1e-9
    assert sum(launch) + device + sum(scores) == pytest.approx(total)


def test_the_existing_reduction_reads_as_before():
    """The older recorded slice (``trace_rt.json.gz``) reads as it always
    did: the program's spans add a reduction beside `bench/trace.py` and
    change nothing in it."""
    tr = trace.from_json(str(FIXTURES / "trace_rt.json.gz"))
    s = trace.summarize(tr, "intgemm")
    assert s["busy_s"] == {"/device:TPU:0": pytest.approx(0.013406521)}
    assert s["kernel_n"] == {"/device:TPU:0": 30}
    assert s["device_ops"][0] == ("%fusion.10", pytest.approx(0.002105403))
    assert s["idle_by_phase"]["wait"] == pytest.approx(0.065403265)


# ---- the per-layer readers of these readings (bench/metrics/)

READERS = ["gate_rom_ms.rt", "gate_rom_ms.drain", "tick_call_ms.rt",
           "tick_call_ms.drain", "return_ms.rt"]


def reader(name):
    sp = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def expected(name, r):
    if name == "return_ms.rt":  # the two lags' clock offsets cancel
        return r["launch_lag_ms"] + r["scores_lag_ms"]
    return r[name.split(".")[0]]


@pytest.mark.parametrize("name", READERS)
def test_readers_of_a_hand_made_trace(name):
    r = spans.readings(hand_trace(), 0, 160, ticks=2)
    got = reader(name)(types.SimpleNamespace(spans=r))
    assert got == pytest.approx(expected(name, r))
    assert got > 0


def test_return_ms_of_a_hand_made_trace():
    r = spans.readings(hand_trace(), 0, 160, ticks=2)
    # launch lags 5 and 7 ns, scores lags 2 and 9 ns: means 6 + 5.5 ns
    assert reader("return_ms.rt")(types.SimpleNamespace(spans=r)) == (
        pytest.approx(11.5e-6))


@pytest.mark.parametrize("name", READERS)
def test_readers_of_the_recorded_slice(name, chip_slice):
    r = spans.readings(chip_slice, 0, math.inf, ticks=64)
    got = reader(name)(types.SimpleNamespace(spans=r))
    assert got == pytest.approx(expected(name, r))
    assert got > 0


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_spans(name):
    """An untraced context, or one from a checkout whose harness hands no
    span readings, gives no value."""
    assert reader(name)(types.SimpleNamespace(summary={})) is None


@pytest.mark.parametrize("lag", ["launch_lag_ms", "scores_lag_ms"])
def test_return_ms_needs_both_lags(lag):
    r = dict(spans.readings(hand_trace(), 0, 160, ticks=2), **{lag: None})
    assert reader("return_ms.rt")(types.SimpleNamespace(spans=r)) is None
