"""The generators are deterministic per seed and differ across seeds."""
import numpy as np

import _paths  # noqa: F401
from harness import corpus, traffic

TINY = corpus.CorpusParams(
    docs=300, d=128, avg_tokens=67.5, min_tokens=8, max_tokens=80,
    centroids=1024, residual_ratio=0.5, topics=256, topic_words=128,
    topics_per_doc=2, common_share=0.3, zipf_s=1.0, query_tokens=32,
    query_noise_ratio=0.5)


def test_open_arrivals_fixed_count_and_seeded():
    a = traffic.open_arrivals(300.0, 2.0, corpus.host_rng(7, 4))
    b = traffic.open_arrivals(300.0, 2.0, corpus.host_rng(7, 4))
    c = traffic.open_arrivals(300.0, 2.0, corpus.host_rng(8, 4))
    assert len(a) == len(c) == 600
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 2.0


def test_pool_sizes():
    assert traffic.pool_size({"loop": "open", "rate_qps": 300}, 20) == 6000
    assert traffic.pool_size({"loop": "closed", "pool_qps": 100,
                              "ramp_s": 1.0}, 9) == 1000


def test_large_seeds_key_distinctly():
    keys = [np.asarray(corpus.base_key(s)) for s in
            (5, 2**32 + 5, 2**31 + 5, 2**40 + 5)]
    assert len({k.tobytes() for k in keys}) == len(keys)


def test_corpus_and_queries_repeat_per_seed(monkeypatch):
    monkeypatch.setattr(corpus, "BLOCK_DOCS", 128)
    a = corpus.make_corpus(2**31 + 3, TINY)
    b = corpus.make_corpus(2**31 + 3, TINY)
    c = corpus.make_corpus(11, TINY)
    np.testing.assert_array_equal(a.doc_tokens, b.doc_tokens)
    assert not np.array_equal(a.doc_tokens, c.doc_tokens)
    n = a.n_tokens
    assert n.min() >= TINY.min_tokens and n.max() <= TINY.max_tokens
    norms = np.linalg.norm(a.doc_tokens[a.doc_mask], axis=-1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    assert not a.doc_tokens[~a.doc_mask].any()
    qa, sa = corpus.make_queries(a, 40, 9, TINY)
    qb, sb = corpus.make_queries(a, 40, 9, TINY)
    qc, _ = corpus.make_queries(a, 40, 10, TINY)
    np.testing.assert_array_equal(qa, qb)
    np.testing.assert_array_equal(sa, sb)
    assert not np.array_equal(qa, qc)
    assert qa.shape == (40, 32, 128) and len(set(sa.tolist())) == 40
