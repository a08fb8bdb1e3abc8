"""Fig. 17(c) reproduction: output spectrum of the DeltaSigma TDC shows
first-order noise shaping (20 dB/dec) for both zero and sine inputs."""

import numpy as np

from repro.core.tdfex import TDFExConfig, sro_tdc


def _slope_db_per_decade(freqs, psd, f_lo, f_hi):
    m = (freqs > f_lo) & (freqs < f_hi)
    x = np.log10(freqs[m])
    y = 10 * np.log10(psd[m] + 1e-30)
    a, _b = np.polyfit(x, y, 1)
    return a


def run(seed: int = 0):
    print("== Fig. 17c: DeltaSigma TDC noise shaping ==")
    cfg = TDFExConfig()
    fs_tdc = cfg.f_tdc
    n_frames = 16
    spf = cfg.decimation // cfg.tdc_oversample

    rng = np.random.default_rng(seed)
    results = {}
    for name, u in [
        ("zero input", np.full((1, spf * n_frames, 1), 0.08, np.float32)),
        ("sine input", (0.08 + 0.05 * np.sin(
            2 * np.pi * 100.0 * np.arange(spf * n_frames)
            / cfg.fex.fs_internal))[None, :, None].astype(np.float32)),
    ]:
        _, diff = sro_tdc(jnp_u(u), cfg, return_diff_stream=True)
        d = np.asarray(diff)[0, :, 0]
        d = d - d.mean()
        win = np.hanning(len(d))
        psd = np.abs(np.fft.rfft(d * win)) ** 2
        freqs = np.fft.rfftfreq(len(d), 1 / fs_tdc)
        slope = _slope_db_per_decade(freqs, psd, fs_tdc / 2000, fs_tdc / 4)
        results[name] = slope
        print(f"  {name:11s}: quantization-noise slope "
              f"{slope:+5.1f} dB/dec (ideal 1st-order: +20)")
    ok = all(10.0 < s < 32.0 for s in results.values())
    print(f"  claim (first-order shaping): {'PASS' if ok else 'FAIL'}")
    return {"slopes": results, "ok": ok}


def jnp_u(u):
    import jax.numpy as jnp

    return jnp.asarray(u)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
