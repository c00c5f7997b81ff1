"""The comparisons that decide ``correct``. Tolerances and their reasons.

Generative (the rule of chip_smoke.py, PR 23): greedy token IDENTITY with a
reference is a float32-arithmetic contract; the chip's default float32
matmul is bf16 passes, and programs of different shape (chunk ladder, paged
gather, cold vs prefix-hit) round differently, so a near-tie argmax may
flip. What must hold: teacher-forced along the SERVED tokens, the
reference's exact ("highest") logit of each served token trails the
reference's best logit by at most 2 x delta, where delta is the chip's own
rounding noise for this model on these sequences: the largest difference
between the reference at default precision and at "highest". A wrong page,
position or mask moves logits by O(1), far outside it; serving in a lower
precision than stated raises the gap past a delta measured at the stated
one. On the CPU backend delta is ~0 and the rule is identity.

Graph: served bf16 probabilities against the float32 "highest" forward of
the same weights: 12 layers of 8-bit-mantissa activations leave a 2-class
probability a few bf16 ulps (2^-8 each) off: 0.03, stated by PR 23 before
its first chip run and not fitted since.
"""

from __future__ import annotations

import numpy as np

BF16_PROB_TOL = 0.03
NOISE_FACTOR = 2.0
# float32 sums in another order differ in the last digits even at "highest"
# (and on the CPU backend, where delta is 0): logits of O(1) agree to ~1e-5
ABS_FLOOR = 1e-3


def judge_generated(served: list, exact: np.ndarray, noisy: np.ndarray, first: int) -> dict:
    """``served``: token id lists, prompt then generated; ``exact`` /
    ``noisy``: reference logits [b, n, vocab] whose row j predicts position
    ``first + j + 1``. Judges every generated token."""
    delta = float(np.abs(noisy - exact).max())
    worst, agree, total = 0.0, 0, 0
    for r, ids in enumerate(served):
        for pos in range(first + 1, len(ids)):
            row = exact[r, pos - 1 - first]
            worst = max(worst, float(row.max() - row[ids[pos]]))
            agree += int(int(np.argmax(row)) == int(ids[pos]))
            total += 1
    tol = max(NOISE_FACTOR * delta, ABS_FLOOR)
    return {
        "ok": bool(total > 0 and worst <= tol),
        "tokens_judged": total,
        "argmax_agreement": f"{agree}/{total}",
        "worst_logit_gap": worst,
        "rounding_delta": delta,
        "tolerance": tol,
    }


def judge_probabilities(served: np.ndarray, reference: np.ndarray) -> dict:
    diff = float(np.abs(np.asarray(served, np.float64) - np.asarray(reference, np.float64)).max())
    well_formed = bool(
        np.all(np.isfinite(served)) and np.allclose(np.sum(served, axis=-1), 1.0, atol=2e-2)
    )
    return {
        "ok": bool(well_formed and diff <= BF16_PROB_TOL),
        "rows_judged": int(np.asarray(served).shape[0]),
        "max_prob_diff": diff,
        "tolerance": BF16_PROB_TOL,
    }


def compared(**verdicts: dict) -> dict:
    """{name: {"value", "limit"}} of every number a run's verdicts compared,
    for the result's line and the run's last lines on standard error."""
    out = {}
    for when, v in verdicts.items():
        for key in ("worst_logit_gap", "max_prob_diff"):
            if key in v:
                out[f"{key}.{when}"] = {"value": v[key], "limit": v["tolerance"]}
    return out
