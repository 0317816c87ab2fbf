"""ANNS layer: brute force, IVF, SQ8, MUVERA, token pruning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.anns import (
    MuveraConfig,
    build_ivf,
    build_token_pruning,
    doc_fde,
    mips_topk,
    query_fde,
    search_ivf,
    search_token_pruning,
    sq8_dequant,
    sq8_quant,
)

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")


def test_mips_topk_exact(rng):
    q = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    corpus = jnp.asarray(rng.standard_normal((200, 16)), jnp.float32)
    s, ids = mips_topk(q, corpus, 7, block=64)
    full = np.asarray(q @ corpus.T)
    want = np.argsort(-full, axis=1)[:, :7]
    assert (np.asarray(ids) == want).all()
    np.testing.assert_allclose(np.asarray(s), np.take_along_axis(full, want, 1), rtol=1e-5)


@given(seed=st.integers(0, 2**31 - 1))
def test_sq8_roundtrip_error_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((10, 32)) * rng.random() * 5, jnp.float32)
    q, s = sq8_quant(x)
    err = jnp.abs(sq8_dequant(q, s) - x)
    # symmetric scalar quantization: |err| <= scale/2 per element
    assert float(jnp.max(err - s[:, None] / 2)) <= 1e-6


def test_ivf_full_probe_matches_bruteforce(rng):
    corpus = jnp.asarray(rng.standard_normal((500, 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    idx = build_ivf(jax.random.PRNGKey(0), corpus, nlist=16, sq8=False)
    s, ids = search_ivf(idx, q, nprobe=16, k=10)
    _, want = mips_topk(q, corpus, 10)
    # same set (scores may tie-break differently)
    got = np.sort(np.asarray(ids), axis=1)
    exp = np.sort(np.asarray(want), axis=1)
    assert (got == exp).mean() > 0.98


def test_ivf_sq8_close_to_exact(rng):
    corpus = jnp.asarray(rng.standard_normal((400, 24)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((6, 24)), jnp.float32)
    idx = build_ivf(jax.random.PRNGKey(0), corpus, nlist=16, sq8=True)
    s, ids = search_ivf(idx, q, nprobe=16, k=10)
    _, want = mips_topk(q, corpus, 10)
    hits = (np.asarray(ids)[:, :, None] == np.asarray(want)[:, None, :]).any(1).mean()
    assert hits > 0.9  # int8 quantization may flip near-ties only


def test_ivf_all_ids_valid(rng):
    corpus = jnp.asarray(rng.standard_normal((100, 8)), jnp.float32)
    idx = build_ivf(jax.random.PRNGKey(0), corpus, nlist=8)
    q = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    _, ids = search_ivf(idx, q, nprobe=8, k=20)
    assert int(ids.min()) >= 0 and int(ids.max()) < 100
    # each row: no duplicate ids among valid entries
    for row in np.asarray(ids):
        assert len(set(row.tolist())) == len(row)


@pytest.mark.parametrize("sq8", [False, True])
def test_ivf_padded_lists_answer_as_before(rng, sq8):
    """``pad_lists`` widens every list with -1 slots: the same answers
    from a wider shape, and a cap below the lists' is refused."""
    from repro.anns.ivf import pad_lists

    corpus = jnp.asarray(rng.standard_normal((300, 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    idx = build_ivf(jax.random.PRNGKey(0), corpus, nlist=16, sq8=sq8)
    wide = pad_lists(idx, 2 * idx.capacity)
    assert wide.capacity == 2 * idx.capacity
    assert (np.asarray(wide.ids)[:, idx.capacity:] == -1).all()
    for fused in (False, True):
        s0, i0 = search_ivf(idx, q, nprobe=4, k=12, use_fused_gather=fused)
        s1, i1 = search_ivf(wide, q, nprobe=4, k=12, use_fused_gather=fused)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    assert pad_lists(idx, idx.capacity) is idx
    with pytest.raises(ValueError):
        pad_lists(idx, idx.capacity // 2)


def _ivf_producer(name, corpus):
    """One of the ways the program makes an ``IVFIndex`` on one chip."""
    from repro.anns import registry
    from repro.anns.ivf import extend_ivf, pad_lists

    build = lambda **kw: build_ivf(jax.random.PRNGKey(0), corpus[:200],
                                   nlist=16, **kw)
    be = registry.get_backend("ivf")
    return {
        "build_ivf-fp32": lambda: build(),
        "build_ivf-sq8": lambda: build(sq8=True),
        "build_ivf-res4": lambda: build(residual_bits=4),
        "extend_ivf": lambda: extend_ivf(build(sq8=True), corpus[200:]),
        "pad_lists": lambda: (lambda i: pad_lists(i, 4 * i.capacity))(
            build(sq8=True)),
        "pack_state-unpack_state": lambda: be.unpack_state(
            *be.pack_state(build(sq8=True))),
    }[name]()


@pytest.mark.parametrize("producer", ["build_ivf-fp32", "build_ivf-sq8",
                                      "build_ivf-res4", "extend_ivf",
                                      "pad_lists", "pack_state-unpack_state"])
def test_ivf_lists_hold_their_entries_as_a_prefix(rng, producer):
    """The probe scan streams only the slots [0, count) of each list, so
    every producer of an index keeps list c's entries there and -1 pads
    after: ``ids[c, :counts[c]] >= 0`` and ``ids[c, counts[c]:] == -1``."""
    corpus = jnp.asarray(rng.standard_normal((300, 16)), jnp.float32)
    idx = _ivf_producer(producer, corpus)
    ids, counts = np.asarray(idx.ids), np.asarray(idx.counts)
    assert counts.shape == (idx.nlist,)
    slot = np.arange(idx.capacity)[None, :]
    real = slot < counts[:, None]
    assert (ids[real] >= 0).all()
    assert (ids[~real] == -1).all()
    assert counts.sum() == len(np.unique(ids[real]))


def test_muvera_fde_better_than_random(tiny_corpus):
    """FDE inner products correlate with MaxSim (Jayaram et al. Thm 2.1)."""
    from repro.core import maxsim
    from repro.data import synthetic

    cfg = MuveraConfig(r_reps=8, k_sim=3, final_dim=512)
    docs = jnp.asarray(tiny_corpus.doc_tokens[:100])
    mask = jnp.asarray(tiny_corpus.doc_mask[:100])
    q = jnp.asarray(synthetic.queries_from_corpus_query(tiny_corpus, 8, 4))
    qm = jnp.ones(q.shape[:2], bool)
    dfde = doc_fde(docs, mask, cfg)
    qfde = query_fde(q, qm, cfg)
    approx = qfde @ dfde.T
    _, truth = maxsim.true_topk(q, qm, docs, mask, 10)
    _, got = jax.lax.top_k(approx, 30)
    rec = (np.asarray(got)[:, :, None] == np.asarray(truth)[:, None, :]).any(1).mean()
    assert rec > 0.35  # far better than 30/100 random... at least signal


def test_token_pruning_candidates(tiny_corpus):
    from repro.core import maxsim
    from repro.data import synthetic

    docs = jnp.asarray(tiny_corpus.doc_tokens[:150])
    mask = jnp.asarray(tiny_corpus.doc_mask[:150])
    idx = build_token_pruning(jax.random.PRNGKey(0), docs, mask, nlist=32)
    q = jnp.asarray(synthetic.queries_from_corpus_query(tiny_corpus, 4, 4))
    qm = jnp.ones(q.shape[:2], bool)
    s, cand = search_token_pruning(idx, q, qm, nprobe=8, k_prime=50, m=150)
    _, truth = maxsim.true_topk(q, qm, docs, mask, 10)
    rec = (np.asarray(cand)[:, :, None] == np.asarray(truth)[:, None, :]).any(1).mean()
    assert rec > 0.3


def test_kmeans_decreases_quantization_error(rng):
    from repro.anns.kmeans import kmeans

    x = jnp.asarray(rng.standard_normal((400, 8)), jnp.float32)
    c1, a1 = kmeans(jax.random.PRNGKey(0), x, 16, iters=1)
    c10, a10 = kmeans(jax.random.PRNGKey(0), x, 16, iters=10)
    e1 = float(jnp.mean(jnp.sum(jnp.square(x - c1[a1]), -1)))
    e10 = float(jnp.mean(jnp.sum(jnp.square(x - c10[a10]), -1)))
    assert e10 <= e1 + 1e-5


def test_dessert_lsh_baseline(tiny_corpus):
    """DESSERT-style LSH set-sketch retrieves real candidates (§5.1 family)."""
    import jax.numpy as jnp

    from repro.anns.dessert import DessertConfig, build_dessert, search_dessert
    from repro.core import maxsim
    from repro.data import synthetic

    docs = jnp.asarray(tiny_corpus.doc_tokens[:200])
    mask = jnp.asarray(tiny_corpus.doc_mask[:200])
    q = jnp.asarray(synthetic.queries_from_corpus_query(tiny_corpus, 8, 4, seed=3))
    qm = jnp.ones(q.shape[:2], bool)
    _, truth = maxsim.true_topk(q, qm, docs, mask, 10)
    idx = build_dessert(docs, mask, DessertConfig(n_tables=32, n_bits=5))
    _, cand = search_dessert(idx, q, qm, k_prime=60)
    import numpy as np

    rec = (np.asarray(cand)[:, :, None] == np.asarray(truth)[:, None, :]).any(1).mean()
    assert rec > 0.3
