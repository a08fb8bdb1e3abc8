"""Fig. 19 reproduction: 12-class KWS accuracy with the HARDWARE-SIM
feature extractor (mismatch + noise + calibration), confusion matrix,
and per-class true-positive rates.

Paper: 86.03% on chip vs 91.35% software; silence easiest (100%),
"unknown" hardest. We validate those *relations* on the synthetic corpus
and report the hw-vs-sw gap measured the same way. The trained model is
additionally evaluated through the bit-exact integer classifier backend
("integer" — int8 weights / Q6.8 activations on codes), which must
reproduce the QAT confusion matrix exactly."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    datasets,
    evaluate,
    frames_to_features,
    train_classifier,
)
from repro.core.calibration import calibrate_state
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.core.tdfex import TDFExConfig, draw_chip
from repro.data.gscd import CLASSES
from repro.core.fex import FExConfig


def run(seed: int = 0):
    print("== Fig. 19: 12-class accuracy, hardware-sim FEx ==")
    import dataclasses

    # noise-calibrated chip model: SRO accumulated jitter set to the
    # chip's measured 248 uV_RMS floor (same calibration as Table I) —
    # this is the "increased noise floor" the paper blames for the
    # 86% (chip) vs 91% (software) gap
    tdcfg = dataclasses.replace(TDFExConfig(), phase_noise_rms=1.4)
    chip = draw_chip(jax.random.PRNGKey(seed), tdcfg)
    state = calibrate_state(tdcfg, chip)
    pipe_hw = KWSPipeline(
        KWSPipelineConfig(frontend="hardware", tdfex=tdcfg), state=state
    )
    train, test = datasets(seed)

    # record FV_Raw from the "chip" for train + test (Section III-F flow)
    key = jax.random.PRNGKey(seed + 99)
    k1, k2 = jax.random.split(key)
    raw_tr = pipe_hw.record_features(train["audio"], key=k1)
    raw_te = pipe_hw.record_features(test["audio"], key=k2)
    cfg = tdcfg.fex
    ftr, stats = frames_to_features(
        raw_tr, cfg, True, True, already_raw=True
    )
    fte, _ = frames_to_features(
        raw_te, cfg, True, True, stats=stats, already_raw=True
    )
    model = train_classifier(ftr, train["label"], seed=seed)
    acc, conf = evaluate(model, fte, test["label"])
    print(f"  hardware-sim accuracy: {acc:6.2%} (paper chip: 86.03%)")

    # deployment check: the bit-exact integer engine (int8 weight codes,
    # Q6.8 activations, 24-bit accumulators — what the IC actually runs)
    # must reproduce the QAT evaluation decision-for-decision
    acc_int, conf_int = evaluate(
        model, fte, test["label"], classifier="integer"
    )
    int_ok = bool(np.array_equal(conf, conf_int))
    print(
        f"  integer-engine accuracy: {acc_int:6.2%} "
        f"(bit-exact vs QAT: {'PASS' if int_ok else 'FAIL'})"
    )

    # software-model comparison on the same data/split — the same
    # pipeline call sites with frontend="software"
    pipe_sw = KWSPipeline(KWSPipelineConfig(frontend="software"))
    raw_sw_tr = pipe_sw.record_features(train["audio"])
    raw_sw_te = pipe_sw.record_features(test["audio"])
    str_, stats_sw = frames_to_features(
        raw_sw_tr, cfg, True, True, already_raw=True
    )
    ste, _ = frames_to_features(
        raw_sw_te, cfg, True, True, stats=stats_sw, already_raw=True
    )
    model_sw = train_classifier(str_, train["label"], seed=seed)
    acc_sw, _ = evaluate(model_sw, ste, test["label"])
    print(f"  software-model accuracy: {acc_sw:6.2%} (paper: 91.35%)")
    print(f"  hw-sw gap: {acc_sw - acc:+.2%} (paper: +5.3pp)")

    tpr = np.diag(conf) / np.maximum(conf.sum(1), 1)
    order = np.argsort(tpr)
    print("  per-class TPR (worst -> best):")
    for i in order:
        print(f"    {CLASSES[i]:8s} {tpr[i]:6.2%}")
    print("  confusion matrix (rows=true):")
    for i, row in enumerate(conf):
        print(f"    {CLASSES[i]:8s} " + " ".join(f"{v:3d}" for v in row))
    ok = acc > 2.0 / 12.0 and acc_sw >= acc - 0.03 and int_ok
    print(f"  claim (noisy hw <= sw within tolerance, both >> chance, "
          f"integer == QAT): {'PASS' if ok else 'FAIL'}")
    return {"acc_hw": acc, "acc_sw": acc_sw, "acc_int": acc_int,
            "integer_matches_qat": int_ok, "tpr": tpr.tolist(), "ok": ok}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
