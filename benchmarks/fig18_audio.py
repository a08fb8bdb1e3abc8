"""Fig. 18 reproduction: FEx response to a "yes" keyword — low channels
light up for the voiced /ye/, high channels for the fricative /s/."""

import jax
import numpy as np

from repro.core.calibration import calibrate_chip
from repro.core.tdfex import TDFExConfig, counts_to_fv_raw, draw_chip, tdfex_raw_counts
from repro.data.gscd import GSCDSynthConfig, _TEMPLATES, synth_keyword


def run(seed: int = 0):
    print('== Fig. 18: FEx audio response to "yes" ==')
    cfg = TDFExConfig()
    chip = draw_chip(jax.random.PRNGKey(seed), cfg)
    beta, alpha = calibrate_chip(cfg, chip)

    rng = np.random.default_rng(seed)
    scfg = GSCDSynthConfig(amplitude=0.127)  # ~254 mVpp, like the paper
    audio = synth_keyword(rng, _TEMPLATES["yes"], scfg)[None, :]
    counts = tdfex_raw_counts(
        jax_arr(audio), cfg, chip
    )
    fv = np.asarray(counts_to_fv_raw(counts, cfg, beta, alpha))[0]
    # normalize per Fig. 18 (offset/std of the clip)
    fvn = (fv - fv.mean(0)) / (fv.std(0) + 1e-6)

    # voiced segment = frames with most low-channel energy;
    # fricative = frames with most high-channel energy
    energy = fv.sum(-1)
    active = energy > energy.mean()
    low = fvn[:, :6].mean(-1)
    high = fvn[:, 11:].mean(-1)
    voiced_frames = np.argsort(low)[-8:]
    fric_frames = np.argsort(high)[-8:]
    lo_ratio = fvn[voiced_frames][:, :6].mean() - fvn[voiced_frames][:, 11:].mean()
    hi_ratio = fvn[fric_frames][:, 11:].mean() - fvn[fric_frames][:, :6].mean()
    print(f"  voiced /ye/ frames: low-high channel contrast {lo_ratio:+.2f}")
    print(f"  fricative /s/ frames: high-low channel contrast {hi_ratio:+.2f}")
    ok = lo_ratio > 0.3 and hi_ratio > 0.3 and bool(active.any())
    print(f"  claim (formant vs fricative bands separate): "
          f"{'PASS' if ok else 'FAIL'}")
    return {"lo_contrast": float(lo_ratio), "hi_contrast": float(hi_ratio),
            "ok": ok}


def jax_arr(x):
    import jax.numpy as jnp

    return jnp.asarray(x)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
