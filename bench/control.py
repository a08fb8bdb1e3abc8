"""The check's control: the reference, one precision down, in the
server's place.

    python3 -m bench.control --workload int8-fv-rt --seeds 1 2 3 --seconds 20

For each seed it builds the cell's traffic, weights and norm statistics
exactly as a run does, replays the sampled streams over an open-loop
window through the configuration's reference one precision below what
the configuration states, and compares that with the reference by the
run's own numbers. Where the streams upload FV_Norm frames the step down
is the classifier's: int4 weights (the int8 codes rounded to multiples
of 16) instead of int8. Where they send raw audio it is the frontend's:
the filter in bfloat16 instead of float32, before the int8 classifier.
Each line printed is one seed's readings; a sound check fails every one
of them. Needs no chip, and runs the reference frontend on whatever
device JAX offers.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

import bench.run  # noqa: F401  (platforms and import paths)


def int4(codes: dict) -> dict:
    """int8 weight codes rounded to the int4 grid (multiples of 16)."""
    q = lambda w: np.clip(np.round(w / 16.0), -8, 7) * 16.0  # noqa: E731
    return {"gru": [dict(layer, w_i=q(layer["w_i"]), w_h=q(layer["w_h"]))
                    for layer in codes["gru"]],
            "fc_w": q(codes["fc_w"]), "fc_b": codes["fc_b"]}


def readings(workload: str, seed: int, seconds: float,
             mix_override: dict | None = None) -> dict:
    from bench import check, harness, model, traffic as tl

    _, cell = harness.spec(workload)
    cfg = model.load(cell["config"])
    ref = harness.reference_module(cfg)
    mix = dict(tl.load(cell["traffic"]), **(mix_override or {}))
    seed = int(seed) % 2 ** 63
    codes, _ = model.weights(cfg, seed)
    norm = model.norm_stats(cfg, seed)
    traffic = tl.Traffic(mix, cfg["hop_samples"], seed)
    sample = harness.sample_streams(mix["streams"], mix["check_streams"],
                                    seed)
    n_ticks = int(round(seconds * 1000.0 / mix["hop_ms"]))
    audio = harness.input_kind(mix) == "audio"
    if not audio:
        traffic.pool = harness.fv_pool(ref, cfg, norm, traffic)

    def serve(weights, dtype="float32"):
        return harness.replay(ref, cfg, norm, weights, traffic, mix, sample,
                              n_ticks, dtype)

    want, carry = serve(codes)
    ctl, ctl_carry = serve(codes, "bfloat16") if audio else serve(int4(codes))
    served = {"scores": ctl.scores.astype("float32"), "top": ctl.top,
              "h": list(ctl.h), "det": ctl.det, "carry": ctl_carry}
    nums = check.numbers(served, want, 0, carry)
    correct, _ = check.verdict(nums, cfg["limits"])
    return {"seed": seed, "correct": correct, **nums}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds)),
              flush=True)


if __name__ == "__main__":
    main()
