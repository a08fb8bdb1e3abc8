"""The raw-audio cell against the plain reference, on the CPU.

`int8-audio-rt` sends each stream's raw 16 ms hop, so the server runs
the paper's whole chain on the device: 2x oversampling, the biquad bank,
the rectified frame mean, the 12-bit quantizer, the 12 -> 10-bit log ROM
and the normalizer, then the integer classifier. The benchmark's harness
runs the committed configuration and mix at a small fleet and compares
every hop of a sample of streams, and their final GRU and filter state,
with `bench/reference.py`, which reads the log table it builds in float64
itself. No part of the program is replaced: a log ROM that rounded any
code the other way than its table fails the check.
"""

import json
import pathlib
import sys
import time

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, ops  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

SMALL = {"streams": 32, "tracks": 4, "track_hops": 100, "check_streams": 16,
         "trace_ticks": 8}  # bench/tests/test_check.py's fleet
NEW_READERS = ("frontend_ms.audio", "log_rom_ms.audio",
               "frontend_roofline.audio", "tick_mfu_pct.audio")
_CACHE_OPTIONS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
                  "jax_compilation_cache_max_size",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def cpu_cell(tmp_path, monkeypatch):
    """The harness on the CPU: its look for a TPU hands over the CPU
    devices, and the compile cache it turns on lives in ``tmp_path`` and
    is put back as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(harness, "find_chips", lambda chips: jax.devices())
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compilation_cache.reset_cache()
    assert enable_compile_cache() == str(tmp_path)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _stand_in_device(from_xplane):
    """A CPU profile has no device line, so the scope readers would find
    nothing. This lays every instruction of the compiled tick's entry
    computation (the `bench.spans.tick_map` the harness made from the
    program the window ran, with that program's own scopes) as a 1 us op
    inside one run of the tick's module in the traced slice, on a
    stand-in device: the readers then read the real program's scope
    names. Their values say nothing of any device's speed."""
    def reduce(path, tick):
        reduced = from_xplane(path, tick)
        lo, hi = trace_lib.slice_bounds(trace_lib.from_xplane(path))
        names = sorted(tick["scopes"])
        step = (hi - lo) / (len(names) + 2)
        ops_ = [(n, lo + (i + 1) * step, lo + (i + 1) * step + 1e3)
                for i, n in enumerate(names)]
        run = [(tick["module"], lo + step / 2, hi - step / 2)]
        reduced["modules"]["/device:stand-in"] = run
        reduced["scoped"]["/device:stand-in"] = [
            (tick["scopes"][n], s, e) for n, s, e in ops_]
        return reduced
    return reduce


@pytest.mark.parametrize("seed,trace", [(1700000000017, False),
                                        (2900000000029, True)],
                         ids=["untraced", "traced"])
def test_raw_audio_cell_matches_the_reference(cpu_cell, monkeypatch, seed,
                                              trace):
    if trace:
        # the chip's peaks stand in for the CPU's, which the table lacks:
        # the readers' values here only show that they find what to read
        with open(ops.PEAKS) as f:
            v5e = json.load(f)["TPU v5 lite"]
        monkeypatch.setattr(ops, "peaks", lambda kind: v5e)
        monkeypatch.setattr(harness.spans_lib, "from_xplane",
                            _stand_in_device(harness.spans_lib.from_xplane))
    r = harness.run_cell("int8-audio-rt", seed, 0.5, trace,
                         time.perf_counter(), mix_override=SMALL)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["checks"]["carry_err"]["value"] <= 1e-5
    assert r["checks"]["carry_err"]["limit"] == 1e-5
    if trace:
        for name in NEW_READERS:
            assert r["metrics"][name]["value"] is not None, name
            assert r["metrics"][name]["value"] > 0, name
