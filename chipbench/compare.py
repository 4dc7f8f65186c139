"""The comparison that decides ``correct``.

Every request due in the window is answered (the backlog is drained once
the window closes) and every answer is compared with the plain
reference's logits for the image whose frame the request sent.  The
numbers compared, each against the limit in the configuration's file
(``limits``):

* ``unanswered``: requests due in the window that never got an answer.
  Limit 0.
* ``logit_gap``: the widest gap, over all answered requests, between a
  served logit and the reference's, as a share of the largest reference
  logit of that image: ``max_j |served_j - ref_j| / max_j |ref_j|``.
  The frames' head and encode kernel, framing and parse, the decode
  kernel, slot batching and the tail all lie between the image and the
  served logits, so a fault in any of them moves this number; an answer
  put in another request's slot reads about 1.
"""
from __future__ import annotations

import numpy as np


def logit_gaps(answers: dict, pick: np.ndarray, ref_logits: np.ndarray) -> np.ndarray:
    """Per request: the relative widest logit gap, NaN where unanswered."""
    ref = np.asarray(ref_logits, np.float64)
    scale = np.max(np.abs(ref), axis=-1)
    out = np.full(len(pick), np.nan)
    for rid, got in answers.items():
        want = ref[pick[rid]]
        got = np.asarray(got, np.float64).reshape(want.shape)
        out[rid] = np.max(np.abs(got - want)) / scale[pick[rid]]
    return out


def judge(answers: dict, pick: np.ndarray, ref_logits: np.ndarray,
          limits: dict) -> dict:
    """``{"correct", "failed", "checks"}`` for one run.  ``failed`` counts
    requests that never got an answer or whose answer is over the limit;
    ``checks`` maps each compared number to its value and limit."""
    gaps = logit_gaps(answers, pick, ref_logits)
    missing = int(np.count_nonzero(np.isnan(gaps)))
    over = gaps[~np.isnan(gaps)] > limits["logit_gap"]
    widest = float(np.nanmax(gaps)) if missing < len(gaps) else float("nan")
    checks = {
        "unanswered": {"value": missing, "limit": limits["unanswered"]},
        "logit_gap": {"value": widest, "limit": limits["logit_gap"]},
    }
    correct = (missing <= limits["unanswered"]
               and bool(widest <= limits["logit_gap"]))
    return {"correct": bool(correct),
            "failed": missing + int(np.count_nonzero(over)),
            "checks": checks}
