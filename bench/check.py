"""The comparison that decides ``correct``.

What the timed path produced for a seeded sample of streams (every hop
they were served, and their state when the run ended) against the plain
reference (`bench.reference`) replayed over the same audio. Each number
is held to the limit the configuration file gives it:

  missing_hops      hops due in the window that were never served.
  h<l>_mismatch     share of layer l's final hidden-state codes that
                    differ from the reference's.
  score_mismatch    share of served hops whose smoothed posterior
                    differs from the reference's by more than 1e-4 in
                    any class (a differing logit code moves it by far
                    more; float rounding of softmax by far less).
  top_mismatch      share of served hops whose top-1 differs, among hops
                    where the reference's top-1 leads by 1e-4 or more.
  det_mismatch      (cascade) share of streams whose gate state (latch,
                    hangover, woken and tick counters) differs at the end.
  carry_err         (raw-audio mixes) the largest absolute difference,
                    over the sampled streams, their channels and both
                    registers of each biquad, between the server's
                    frontend filter state at the end and the reference's;
                    a state that is not finite on either side reads inf.
                    A configuration without a limit for it fails every
                    raw-audio run (`verdict`).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

SCORE_TOL = 1e-4


def numbers(served: dict, ref, missing_hops: int,
            carry: Optional[dict] = None) -> Dict[str, float]:
    """The check's numbers. ``carry`` is the reference frontend's final
    filter state where the streams sent raw audio; ``served["carry"]``
    then holds the server's at the sampled streams."""
    out = {"missing_hops": float(missing_hops)}
    for i, (hp, hr) in enumerate(zip(served["h"], ref.h)):
        out[f"h{i}_mismatch"] = float(np.mean(hp != hr))
    gap = np.abs(served["scores"].astype(np.float64) - ref.scores).max(axis=2)
    out["score_mismatch"] = float(np.mean(gap > SCORE_TOL))
    decided = ref.lead >= SCORE_TOL
    out["top_mismatch"] = float(
        np.mean(served["top"][decided] != ref.top[decided])
        if decided.any() else 0.0)
    if ref.det:
        differs = np.zeros(len(ref.det["awake"]), bool)
        for key, val in ref.det.items():
            differs |= np.asarray(served["det"][key]).astype(np.int64) != val
        out["det_mismatch"] = float(np.mean(differs))
    if carry is not None:
        gaps = [np.abs(np.asarray(served["carry"][key], np.float64)
                       - np.asarray(val, np.float64))
                for key, val in carry.items()]
        out["carry_err"] = max(float(np.max(np.nan_to_num(g, nan=np.inf)))
                               for g in gaps)
    return out


def verdict(nums: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit), ...]); a number without a limit
    fails, so a new number cannot pass unset."""
    rows = [(k, v, limits.get(k)) for k, v in nums.items()]
    ok = all(lim is not None and v <= lim for _, v, lim in rows)
    return ok, rows
