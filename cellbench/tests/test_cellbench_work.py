"""Work counts against hand counts: only real list entries and real
candidate tokens of real queries count; pads count zero."""
import types

import jax
import numpy as np

import _paths  # noqa: F401
from harness import readers, traffic, work

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_ivf_scan_hand_count():
    w = work.ivf_scan(10, 2, 2048, sq8=True)
    assert w.flops == 2 * 2048 * 10
    assert w.bytes == 10 * (2048 + 4 + 4) + 2 * 2048 * 4
    w = work.ivf_scan(10, 2, 2048, sq8=False)
    assert w.bytes == 10 * (4 * 2048 + 4) + 2 * 2048 * 4


def test_rerank_hand_count():
    w = work.rerank(100, 2, 32, 128)
    assert w.flops == 2 * 32 * 128 * 100
    assert w.bytes == 100 * 512 + 2 * 32 * 128 * 4


def test_least_time_and_share():
    w = work.Work(flops=197e12, bytes=819e9 * 2)     # 1 s of FLOPs, 2 s of bytes
    assert w.least_s(PEAKS) == 2.0
    assert work.share_pct(w, 4.0, PEAKS) == 50.0
    assert work.share_pct(w, 0.0, PEAKS) is None


def _ctx(requests, probes, cands, list_counts, n_tokens, sq8=True):
    rec = traffic.WindowRecord(0.0, 10.0, requests)
    return types.SimpleNamespace(
        record=rec,
        config={"lemur": {"d": 128, "d_prime": 2048, "ivf": {"sq8": sq8}},
                "corpus": {"query_tokens": 32}},
        observed={"qids": [r.qid for r in requests], "probes": probes,
                  "cands": cands, "list_counts": np.asarray(list_counts),
                  "n_tokens": np.asarray(n_tokens), "nlist": 4})


def _req(qid, t_done=1.0):
    r = traffic.Request(qid=qid, t_due=0.5, t_submit=0.5, t_done=t_done)
    r.result = (np.zeros(3), np.zeros(3, np.int32))
    return r


def test_readers_count_real_entries_and_tokens_only():
    # lists hold 3, 0, 5 and 2 real entries (their pad slots are not
    # counted); two real queries -- the micro-batch's pad rows are not
    # requests and add nothing; one answered after the window adds nothing
    reqs = [_req(0), _req(1), _req(2, t_done=11.0)]
    probes = np.array([[0, 1], [2, 3], [0, 2]])
    cands = np.array([[0, 1, -1], [2, -1, -1], [0, 1, 2]])
    ctx = _ctx(reqs, probes, cands, [3, 0, 5, 2], [16, 5, 80])
    assert readers.ivf_work(ctx) == work.ivf_scan(3 + 0 + 5 + 2, 2, 2048,
                                                  sq8=True)
    assert readers.rerank_work(ctx) == work.rerank(16 + 5 + 80, 2, 32, 128)


def test_list_counts_are_real_entries_of_the_program_ivf():
    from repro.anns.ivf import build_ivf

    v = jax.random.normal(jax.random.PRNGKey(0), (300, 16))
    ivf = build_ivf(jax.random.PRNGKey(1), v, nlist=16, sq8=True)
    ids = np.asarray(ivf.ids)
    np.testing.assert_array_equal((ids >= 0).sum(1), np.asarray(ivf.counts))
    assert int(np.asarray(ivf.counts).sum()) == 300
