"""The comparison that decides ``correct``.

Once the window has closed, a sample of the answered requests (drawn from
the seed) is compared with the plain reference (``reference.py``):

* ``score_gap`` -- the widest relative gap between a served score and the
  reference's MaxSim of the same query and doc in float64,
  ``|served - exact| / max(|exact|, 1)``, over every served (doc, score)
  of the sample.  It holds the rerank to the precision the configuration
  states and every served id to the query it answers;
* ``selection_miss`` -- the share of the exact top-k among the program's
  own k' candidates (its public ``candidates()`` for the same queries)
  that the served top-k leaves out.  It holds the rerank's selection: a
  rerank over part of the candidates, or a top-k that keeps the wrong
  ones, reads high while every served score is exact;
* ``malformed`` -- sampled answers that say something impossible: an id
  outside the corpus, an id twice, scores out of order, or a real id
  after a pad (-1).  Limit 0;
* ``unanswered`` -- requests of the window that never got an answer, or
  got an error.  Limit 0.

A late answer is late, not wrong: the latency counts the wait.
The served top-10's recall against the reference's top-10 is reported,
not compared: on this corpus the learned first stage finds the exact
top-10 only a little above chance, and no floor separates a sound run
from one that probes half the lists (readings in PERF.md).

The limits live in the configuration file's ``check`` group, each a
ceiling, with the readings it was set from in PERF.md.
"""
from __future__ import annotations

import numpy as np


def sample_requests(timed: list, n: int, rng: np.random.Generator) -> list:
    answered = [r for r in timed if r.result is not None]
    if len(answered) <= n:
        return answered
    pick = np.sort(rng.choice(len(answered), n, replace=False))
    return [answered[i] for i in pick]


def malformed(scores: np.ndarray, ids: np.ndarray, m: int) -> bool:
    real = ids >= 0
    if np.any(ids >= m) or np.any(ids < -1):
        return True
    if real.sum() != np.unique(ids[real]).size:
        return True
    if np.any(real[1:] & ~real[:-1]):           # a real id after a pad
        return True
    s = scores[real]
    return bool(np.any(s[1:] > s[:-1]))


def score_gap(served: np.ndarray, ids: np.ndarray,
              exact: np.ndarray) -> float:
    """served, ids: (N, k); exact: (N, k) reference scores of those ids."""
    real = ids >= 0
    if not real.any():
        return float("inf")
    gap = np.abs(served - exact) / np.maximum(np.abs(exact), 1.0)
    return float(np.max(gap[real]))


def recall_at(served_ids: np.ndarray, truth: np.ndarray) -> float:
    """Mean share of each row's true ids found among its served ids."""
    hits = [len(set(a[a >= 0].tolist()) & set(b.tolist())) / len(b)
            for a, b in zip(served_ids, truth)]
    return float(np.mean(hits)) if hits else float("nan")


def selection_miss(served_ids: np.ndarray, cands: np.ndarray,
                   exact: np.ndarray) -> float:
    """served_ids (N, k), cands (N, k'), exact (N, m) reference scores ->
    mean share of each row's exact top-k among its real candidates that
    its served ids leave out."""
    misses = []
    for got, c, ex in zip(served_ids, cands, exact):
        c = c[c >= 0]
        k = min(got.shape[0], c.size)
        if k == 0:
            continue
        want = c[np.argsort(-ex[c], kind="stable")[:k]]
        misses.append(np.setdiff1d(want, got).size / k)
    return float(np.mean(misses)) if misses else float("inf")


def compare(numbers: dict, check_group: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit", "holds"}}): every number is
    held at or under its ceiling (``limits``); a number that is not finite
    fails."""
    out = {k: {"value": numbers[k], "limit": lim, "holds": "<="}
           for k, lim in check_group["limits"].items()}
    return all(bool(np.isfinite(c["value"])) and c["value"] <= c["limit"]
               for c in out.values()), out
