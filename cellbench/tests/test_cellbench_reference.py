"""The reference against the program at a tiny size, and its control.

* the generator's structure at the published width d = 128: exact MaxSim
  ranks each query's source doc first;
* ``LemurRetriever.search`` (the configuration cut to a tiny size) agrees
  with the float64 reference within the configuration's ``score_gap``
  limit;
* the controls -- the reference in the program's place, its products
  formed in three bf16 passes (``Precision.HIGH``, the control for fp32
  at ``HIGHEST``) or in one -- fail the sq8 limit.
"""
import copy

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from harness import check, corpus, reference, spec
from harness.cell import lemur_config


def _params(config, **cut):
    g = dict(config["corpus"], **cut)
    return corpus.CorpusParams.from_config({"corpus": g})


@pytest.fixture(scope="module")
def sq8():
    return spec.load_cell("sq8.closed-64").config


def test_source_doc_ranks_first_at_d128(sq8, monkeypatch):
    monkeypatch.setattr(corpus, "BLOCK_DOCS", 1024)
    p = _params(sq8, docs=2048)
    corp = corpus.make_corpus(3, p)
    q, src = corpus.make_queries(corp, 48, 3, p)
    exact = reference.exact_scores(q, corp.doc_tokens, corp.doc_mask)
    np.testing.assert_array_equal(np.argmax(exact, axis=1), src)


def test_exact_scores_match_a_loop(sq8, monkeypatch):
    monkeypatch.setattr(corpus, "BLOCK_DOCS", 64)
    p = _params(sq8, docs=64, centroids=256, topics=64)
    corp = corpus.make_corpus(5, p)
    q, _ = corpus.make_queries(corp, 3, 5, p)
    got = reference.exact_scores(q, corp.doc_tokens, corp.doc_mask)
    for i in range(3):
        for j in range(0, 64, 7):
            toks = corp.doc_tokens[j][corp.doc_mask[j]].astype(np.float64)
            want = (q[i].astype(np.float64) @ toks.T).max(1).sum()
            assert abs(got[i, j] - want) < 1e-4
    ids = np.array([[3, 9, -1]])
    s = reference.scores_of(q[:1], ids, corp.doc_tokens, corp.doc_mask)
    np.testing.assert_allclose(s[0, :2], got[0, [3, 9]], rtol=1e-6)
    assert np.isnan(s[0, 2])
    ids = np.array([[3, 9, -1], [5, 0, 2]])
    s64 = reference.served_scores64(q[:2], ids, corp.doc_tokens,
                                    corp.doc_mask)
    for i, row in enumerate(ids):
        for c, j in enumerate(row):
            if j < 0:
                assert np.isnan(s64[i, c])
                continue
            toks = corp.doc_tokens[j][corp.doc_mask[j]].astype(np.float64)
            want = (q[i].astype(np.float64) @ toks.T).max(1).sum()
            assert s64[i, c] == pytest.approx(want, rel=1e-12)
    top = reference.top_ids(got, 5)
    np.testing.assert_array_equal(top, np.argsort(-got, 1)[:, :5])


def _tiny_search(config, monkeypatch):
    from repro.retriever import LemurRetriever

    cfg = copy.deepcopy(config)
    cfg["lemur"].update(d_prime=64, m_pretrain=128, n_train=2048, n_ols=512,
                        epochs=2, k=10, k_prime=64)
    cfg["lemur"]["ivf"]["nprobe"] = 8
    monkeypatch.setattr(corpus, "BLOCK_DOCS", 256)
    p = _params(cfg, docs=512, centroids=1024, topics=256)
    corp = corpus.make_corpus(9, p)
    r = LemurRetriever.build(corp, lemur_config(cfg),
                             key=jax.random.PRNGKey(9))
    q, _ = corpus.make_queries(corp, 16, 9, p)
    s, ids = (np.asarray(a) for a in r.search(q))
    exact = reference.exact_scores(q, corp.doc_tokens, corp.doc_mask)
    return cfg, corp, q, s, ids, exact


def test_program_agrees_and_bf16_control_fails_sq8(sq8, monkeypatch):
    cfg, corp, q, s, ids, exact = _tiny_search(sq8, monkeypatch)
    limit = cfg["check"]["limits"]["score_gap"]
    at = reference.served_scores64(q, ids, corp.doc_tokens, corp.doc_mask)
    assert check.score_gap(s, ids, at) <= limit
    assert not any(check.malformed(a, b, corp.m) for a, b in zip(s, ids))
    ctrl = reference.scores_of(q, ids, corp.doc_tokens, corp.doc_mask,
                               mode="bf16")
    assert check.score_gap(ctrl, ids, at) > limit


def test_three_pass_control_fails_sq8(sq8, monkeypatch):
    """The control for fp32 at HIGHEST (the three-pass product, as
    Precision.HIGH forms it) fails the score_gap limit over every doc of
    a small corpus, and its split is bf16 rounding to nearest."""
    monkeypatch.setattr(corpus, "BLOCK_DOCS", 256)
    p = _params(sq8, docs=512, centroids=1024, topics=256)
    corp = corpus.make_corpus(10, p)
    q, _ = corpus.make_queries(corp, 16, 10, p)
    ids = np.tile(np.arange(512), (16, 1))
    at = reference.served_scores64(q, ids, corp.doc_tokens, corp.doc_mask)
    ctrl = reference.scores_of(q, ids, corp.doc_tokens, corp.doc_mask,
                               mode="bf16_3x")
    assert check.score_gap(ctrl, ids, at) > sq8["check"]["limits"][
        "score_gap"]
    x = jax.numpy.asarray(q.reshape(-1))
    hi, lo = reference._split(x)
    np.testing.assert_array_equal(hi, x.astype(jax.numpy.bfloat16))
    rest = np.abs(np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
                  - np.asarray(x, np.float64))
    assert np.all(rest <= 2.0 ** -16 * np.abs(np.asarray(x)))
