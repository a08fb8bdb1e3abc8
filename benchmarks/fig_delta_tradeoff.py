"""DeltaKWS-style accuracy vs effective-MACs tradeoff for the ΔGRU.

Trains the paper's QAT GRU-FC on the synthetic GSCD (the
benchmarks.common recipe), then sweeps the ΔGRU threshold θ
(`repro.core.gru_delta`, input and hidden deltas alike) and measures,
per θ:

  * 12-class accuracy through the delta engine (θ=0 must reproduce the
    QAT predictions EXACTLY — the bit-identity contract);
  * the measured effective-MAC fraction (executed / offered, dense FC
    included — the same accounting as the serving telemetry
    `srv.sparsity`);
  * predicted IC latency and power at that sparsity, via
    `repro.core.energy.AcceleratorModel(effective_mac_fraction=...)` —
    dynamic MAC energy scales with the executed work, leakage does not
    (the DeltaKWS split).

Claim checked (the DeltaKWS result, transposed to our corpus): some θ
achieves >= 2x MAC reduction (effective fraction <= 0.5) within 1
accuracy point of the dense QAT baseline. Writes ``BENCH_delta.json``.

  PYTHONPATH=src python -m benchmarks.fig_delta_tradeoff
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    datasets,
    frames_to_features,
    record_software_frames,
    timed,
    train_classifier,
)
from repro.core.energy import AcceleratorModel, ICPowerModel
from repro.core.fex import FExConfig
from repro.core.gru_delta import (
    DeltaConfig,
    delta_classifier_forward,
    effective_mac_fraction,
)

THETAS = (0.0, 0.05, 0.1, 0.15, 0.25, 0.4, 0.6, 1.0)


def run(seed: int = 0):
    print("== ΔGRU accuracy vs effective-MACs tradeoff (DeltaKWS-style) ==")
    train, test = datasets(seed)
    cfg = FExConfig()
    with timed("features"):
        ftr, stats = frames_to_features(
            record_software_frames(train["audio"], cfg), cfg, True, True
        )
        fte, _ = frames_to_features(
            record_software_frames(test["audio"], cfg), cfg, True, True,
            stats=stats,
        )
    with timed("train"):
        model = train_classifier(ftr, train["label"], seed=seed)
    gcfg = model["config"]
    labels = np.asarray(test["label"])

    # dense QAT baseline: ONE forward pass yields both the per-example
    # predictions (the θ=0 gate compares decisions, not aggregate
    # accuracy — compensating flips must not pass) and the accuracy
    @jax.jit
    def qat_preds_fn(fv):
        from repro.core.gru import gru_classifier_forward

        return jnp.argmax(
            gru_classifier_forward(model["params"], fv, gcfg)[:, -1, :],
            axis=-1,
        )

    base_preds = np.concatenate([
        np.asarray(qat_preds_fn(jnp.asarray(fte[i : i + 128])))
        for i in range(0, len(labels), 128)
    ])
    base_acc = float((base_preds == labels).mean())
    print(f"  dense QAT baseline: {base_acc:6.2%}")

    @functools.partial(jax.jit, static_argnums=(1,))
    def delta_eval(fv, thetas):
        logits, states = delta_classifier_forward(
            model["params"], fv, gcfg, thetas, return_states=True
        )
        return (
            jnp.argmax(logits[:, -1, :], axis=-1),
            effective_mac_fraction(states, gcfg),
        )

    pm_dense = ICPowerModel()
    dense_lat_ms = pm_dense.accel.latency_s(gcfg) * 1e3
    dense_uw = pm_dense.total_power_w(gcfg) * 1e6

    rows = []
    theta0_exact = None
    for theta in THETAS:
        thetas = DeltaConfig(
            theta_x=theta, theta_h=theta
        ).code_thresholds(gcfg.num_layers)
        preds, fracs = [], []
        for i in range(0, len(labels), 128):
            p, f = delta_eval(jnp.asarray(fte[i : i + 128]), thetas)
            preds.append(np.asarray(p))
            fracs.append(np.asarray(f))
        preds = np.concatenate(preds)
        frac = float(np.concatenate(fracs).mean())
        acc = float((preds == labels).mean())
        if theta == 0.0:
            theta0_exact = bool(np.array_equal(preds, base_preds))
        # predicted IC numbers at this measured sparsity
        accel = AcceleratorModel(effective_mac_fraction=frac)
        pm = ICPowerModel(accel=accel)
        row = {
            "theta": theta,
            "accuracy": acc,
            "effective_mac_fraction": frac,
            "mac_reduction": 1.0 / max(frac, 1e-9),
            "accuracy_drop_pts": (base_acc - acc) * 100.0,
            "pred_latency_ms": accel.latency_s(gcfg) * 1e3,
            "pred_accel_uw": pm.accelerator_power_w(gcfg) * 1e6,
            "pred_total_uw": pm.total_power_w(gcfg) * 1e6,
        }
        rows.append(row)
        print(
            f"  θ={theta:4.2f}: acc {acc:6.2%} "
            f"(Δ {row['accuracy_drop_pts']:+5.2f} pts)  "
            f"eff-MAC {frac:5.3f} ({row['mac_reduction']:4.1f}x)  "
            f"-> {row['pred_latency_ms']:5.2f} ms, "
            f"{row['pred_total_uw']:5.2f} µW"
        )

    # θ=0 is the bit-identity point: the delta engine reproduced the
    # dense QAT predictions decision-for-decision (array equality of
    # per-example argmaxes, set inside the sweep above)
    # DeltaKWS claim: >= 2x MAC reduction within 1 accuracy point
    good = [
        r for r in rows
        if r["effective_mac_fraction"] <= 0.5
        and r["accuracy_drop_pts"] <= 1.0
    ]
    best = max(good, key=lambda r: r["mac_reduction"], default=None)
    ok = theta0_exact and best is not None
    claim = {
        "what": "ΔGRU: some θ achieves >= 2x MAC reduction (effective "
                "fraction <= 0.5) within 1 accuracy point of dense QAT "
                "on the synthetic GSCD; θ=0 reproduces QAT exactly",
        "dense_accuracy": base_acc,
        "dense_latency_ms": dense_lat_ms,
        "dense_total_uw": dense_uw,
        "theta0_exact": theta0_exact,
        "best": best,
        "ok": ok,
    }
    with open("BENCH_delta.json", "w") as f:
        json.dump({"rows": rows, "claim": claim}, f, indent=2)
    if best is not None:
        print(
            f"fig_delta_tradeoff: θ={best['theta']:.2f} gives "
            f"{best['mac_reduction']:.1f}x fewer MACs at "
            f"{best['accuracy_drop_pts']:+.2f} pts "
            f"({best['pred_total_uw']:.1f} µW predicted vs "
            f"{dense_uw:.1f} µW dense), θ=0 exact: {theta0_exact}  "
            f"[{'PASS' if ok else 'FAIL'}] (BENCH_delta.json written)"
        )
    else:
        print(
            f"fig_delta_tradeoff: no θ reached 2x within 1 pt "
            f"(θ=0 exact: {theta0_exact})  [FAIL] "
            f"(BENCH_delta.json written)"
        )
    return claim


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
