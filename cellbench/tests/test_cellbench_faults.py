"""A whole run at a tiny size on the CPU (the chip check skipped), once
sound and once with each fault the cells can have planted in the timed
path underneath the server: ``correct`` must come out true, then false.

One chip, no training state: the faults are half of each micro-batch
answered with other rows' answers, an answer altered where it is
produced, a search that returns its previous answer unchanged, and a
rerank over only the first-stage's better half of the k' candidates
(every served score exact, the selection wrong).
"""
import copy
import dataclasses
import time

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import cell as cellmod, corpus, spec
from harness.peaks import peaks


def _tiny_cell():
    c = spec.load_cell("sq8.closed-64")
    cfg = copy.deepcopy(c.config)
    cfg["corpus"].update(docs=256, centroids=1024, topics=256)
    cfg["lemur"].update(d_prime=32, m_pretrain=64, n_train=1024, n_ols=256,
                        epochs=1, k=10, k_prime=32)
    cfg["lemur"]["ivf"]["nprobe"] = 16     # more real entries than k'
    cfg["check"]["sample"] = 12
    tr = dict(c.traffic, clients=8, ramp_s=0.1, pool_qps=300)
    return dataclasses.replace(c, config=cfg, traffic=tr)


def _half_batch(s, ids, state):
    h = s.shape[0] // 2
    s[h:], ids[h:] = s[:s.shape[0] - h], ids[:s.shape[0] - h]


def _altered(s, ids, state):
    ids[:, 0] = (ids[:, 0] + 1) % state["m"]


def _stale(s, ids, state):
    prev = state.get(s.shape)
    state[s.shape] = (s.copy(), ids.copy())
    if prev is not None:
        s[:], ids[:] = prev


def _half_candidates(monkeypatch):
    """The rerank sees only the first half of each row's candidates."""
    import jax.numpy as jnp
    from repro.kernels import ops

    rerank = ops.fused_rerank_paged

    def broken(q, qm, cand, *args, **kw):
        keep = jnp.arange(cand.shape[-1]) < cand.shape[-1] // 2
        return rerank(q, qm, jnp.where(keep, cand, -1), *args, **kw)

    monkeypatch.setattr(ops, "fused_rerank_paged", broken)


@pytest.mark.parametrize(
    "fault", [None, _half_batch, _altered, _stale, _half_candidates],
    ids=["sound", "half_batch", "altered", "stale", "half_candidates"])
def test_correct_catches_each_fault(fault, monkeypatch):
    from repro.retriever import LemurRetriever

    monkeypatch.setattr(corpus, "BLOCK_DOCS", 256)
    monkeypatch.setattr(cellmod, "peaks_of", lambda kind: peaks("TPU v5 lite"))
    cell = _tiny_cell()
    if fault is _half_candidates:
        fault(monkeypatch)
    elif fault is not None:
        search = LemurRetriever.search
        state = {"m": cell.config["corpus"]["docs"]}

        def broken(self, q, qm=None, params=None):
            s, ids = (np.array(a) for a in search(self, q, qm, params))
            fault(s, ids, state)
            return s, ids

        monkeypatch.setattr(LemurRetriever, "search", broken)
    out = cellmod.run_cell(cell, 2**31 + 17, 1.0, False,
                           t_process=time.perf_counter())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
