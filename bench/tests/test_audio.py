"""Raw-audio mixes, the frontend's check and what a configuration names.

A mix with ``input: audio`` sends each stream's raw hop, so the server
runs its own frontend; the check replays the reference's frontend over
the same audio and compares the filter state (``carry_err``) besides the
classifier's numbers. The committed configurations give ``carry_err`` no
limit, so the tests load them with one (``CARRY_LIMIT``, the limit a
raw-audio configuration gives it). Runs the harness on the CPU at
`test_check.py`'s fleet; conftest.py skips its look for a chip.

The program's log compression (`repro.core.quant.log_compress_lut`)
evaluates its closed form in float32, and under jit it rounds some
FV_Raw codes the other way than its table does, on the CPU as on a TPU
v5e: FV_Raw code 63 (1023 * log2(64) / 12 = 511.5 exactly) among them.
That is a fault of the program, and the check fails it. A sound server
for these tests reads the table instead (`exact_log_rom`), as the chip's
ROM does.
"""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, model, ops

SMALL = {"streams": 32, "tracks": 4, "track_hops": 100, "check_streams": 16,
         "trace_ticks": 8}  # test_check.py's fleet
AUDIO = dict(SMALL, input="audio")
CARRY_LIMIT = 1e-5
SEED = 987654321012


@pytest.fixture
def edit_config(monkeypatch):
    """Installs ``edit(cfg) -> cfg`` on every configuration the harness
    and the control load; the committed files stay as they are."""
    orig = model.load

    def install(edit):
        monkeypatch.setattr(model, "load", lambda name: edit(orig(name)))
    return install


@pytest.fixture
def exact_log_rom(monkeypatch):
    """The program's log compression as a read of the 12 -> 10-bit table,
    built in float64. Returns the table, which a test may edit before the
    server is built."""
    from repro.core import quant

    rom = {"table": np.round(1023.0 * np.log2(1.0 + np.arange(4096.0))
                             / 12.0)}

    def lookup(codes, in_bits=12, out_bits=10):
        table = jnp.asarray(rom["table"], jnp.float32)
        return table[jnp.clip(codes, 0, 4095).astype(jnp.int32)]
    monkeypatch.setattr(quant, "log_compress_lut", lookup)
    return rom


def carry_limit(cfg):
    return dict(cfg, limits=dict(cfg["limits"], carry_err=CARRY_LIMIT))


def run(workload="int8-fv-rt", mix=AUDIO, seconds=0.5, trace=False):
    return harness.run_cell(workload, SEED, seconds, trace,
                            time.perf_counter(), mix_override=mix)


@pytest.mark.parametrize("workload", ["int8-fv-rt", "cascade-fv-quiet-rt"])
def test_sound_audio_run_is_correct(edit_config, exact_log_rom, workload):
    edit_config(carry_limit)
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["checks"]["carry_err"]["value"] <= CARRY_LIMIT


def test_audio_run_without_a_carry_limit_is_not_correct():
    """A number without a limit fails: the committed FV configurations
    cannot pass a raw-audio run."""
    r = run()
    assert not r["correct"]
    assert r["checks"]["carry_err"]["limit"] is None


def test_fv_run_has_no_carry_number():
    assert "carry_err" not in run(mix=SMALL)["checks"]


@pytest.fixture
def plant_tick(monkeypatch):
    from repro.serving import serve_loop

    orig = serve_loop._fused_tick

    def install(fault):
        @functools.wraps(orig)
        def tick(*a, **kw):
            return fault(orig, *a, **kw)
        monkeypatch.setattr(serve_loop, "_fused_tick", tick)
    return install


def _carry_nudged(orig, *a, **kw):
    new, scores, top = orig(*a, **kw)
    carry = jax.tree.map(lambda v: v + 1e-3, new.carry)
    return dataclasses.replace(new, carry=carry), scores, top


def test_nudged_carry_is_not_correct(edit_config, plant_tick):
    edit_config(carry_limit)
    plant_tick(_carry_nudged)
    r = run()
    assert not r["correct"], r["checks"]
    assert r["checks"]["carry_err"]["value"] > CARRY_LIMIT


def test_one_log_code_off_by_one_is_not_correct(edit_config, exact_log_rom):
    """The shape of the v5e fault: the device's log compression gives
    another code than its table for one FV_Raw code, here 3 (the tie
    1023 * log2(4) / 12 = 170.5), which about 2% of this traffic's
    frame values take. The filter state is untouched, so the classifier's
    numbers have to catch it."""
    exact_log_rom["table"][3] += 1
    edit_config(carry_limit)
    r = run()
    assert not r["correct"], r["checks"]
    checks = r["checks"]
    assert checks["carry_err"]["value"] <= CARRY_LIMIT
    assert checks["h0_mismatch"]["value"] > 0
    assert checks["score_mismatch"]["value"] > 0


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bfloat16_frontend_control_is_not_correct(edit_config, seed):
    edit_config(carry_limit)
    """The filter in bfloat16: its lowest band's poles round to outside the
    unit circle, so the state diverges (``carry_err`` reads inf) and the
    codes it makes are not the reference's."""
    r = control.readings("int8-fv-rt", seed, 0.5, mix_override=AUDIO)
    assert not r["correct"], r
    assert r["carry_err"] == np.inf
    assert r["h1_mismatch"] > 0.5 and r["score_mismatch"] > 0.5


def delta_int(theta):
    def edit(cfg):
        return dict(cfg, classifier="delta-int",
                    delta={"theta_x": theta, "theta_h": theta})
    return edit


def test_delta_int_at_theta_zero_matches_the_integer_reference(edit_config):
    """The ΔGRU keeps each layer's state as a dict; its ``h`` leaf is what
    the check compares, and at θ = 0 it is the integer GRU's."""
    edit_config(delta_int(0.0))
    r = run(mix=SMALL)
    assert r["correct"], r["checks"]


def test_delta_threshold_reaches_the_server(edit_config):
    """θ > 0 skips updates, so the integer reference no longer holds: the
    configuration's ``delta`` reached the server."""
    edit_config(delta_int(0.15))
    r = run(mix=SMALL)
    assert not r["correct"]
    assert r["checks"]["h1_mismatch"]["value"] > 0


def test_named_default_reference_gives_the_same_numbers(edit_config):
    plain = run(mix=SMALL)["checks"]
    edit_config(lambda cfg: dict(cfg, reference="reference"))
    assert run(mix=SMALL)["checks"] == plain


def test_reference_name_is_a_module_name():
    with pytest.raises(ValueError, match="module name"):
        harness.reference_module({"reference": "../run"})


@pytest.mark.parametrize("mix", [SMALL, AUDIO], ids=["fv", "audio"])
def test_traced_run_hands_readers_the_program_spans(edit_config,
                                                    exact_log_rom,
                                                    monkeypatch, mix):
    edit_config(carry_limit)
    seen = []

    def probe(ctx):
        seen.append(ctx)

    monkeypatch.setattr(harness, "_readers",
                        lambda bench, cell, e2e: [("probe", "ms", probe)])
    monkeypatch.setattr(ops, "peaks", lambda kind: {})  # no CPU row
    r = run(mix=mix, trace=True)
    assert r["correct"], r["checks"]
    (ctx,) = seen
    assert "scope_ms" in ctx.spans
    assert ctx.spans["tick_call_ms"] > 0
