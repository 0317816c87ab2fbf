"""Sharded-vs-local parity for the facade's multi-device serving path.

``LemurRetriever.shard(mesh)`` must be a pure distribution transform: the
same top-k ids AND scores as the single-device facade, bit for bit, on any
mesh — each test runs via the shared ``run_forced8`` conftest fixture (a
subprocess with 8 forced XLA host devices; the main process keeps its
single device under any pytest ordering) and compares a 1-device and an
8-device mesh against the local reference.

The corpora deliberately do NOT divide the device count (m=90, 8 devices)
so the pad-row masking path is always exercised.
"""
import textwrap


# shared preamble: tiny retriever whose k' covers the whole corpus, so the
# two-stage pipeline degenerates to exact MaxSim and parity must be EXACT
_BUILD = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import LemurConfig
from repro.data import synthetic
from repro.retriever import LemurRetriever, SearchParams, ShardedLemurRetriever

def build(m=90, k=5):
    corpus = synthetic.make_corpus(m=m, d=16, avg_tokens=8, max_tokens=8,
                                   n_centers=16, seed=0)
    cfg = LemurConfig(d=16, d_prime=32, m_pretrain=64, n_train=512, n_ols=256,
                      epochs=3, k=k, k_prime=m, anns="bruteforce")
    r = LemurRetriever.build(corpus, cfg, key=jax.random.PRNGKey(0))
    q = jnp.asarray(synthetic.queries_from_corpus_query(corpus, 4, 4, seed=5))
    qm = jnp.ones(q.shape[:2], bool)
    return r, q, qm

MESH1 = jax.make_mesh((1,), ("model",))
MESH8 = jax.make_mesh((2, 4), ("data", "model"))
"""


def test_sharded_search_matches_facade_fp32(run_forced8):
    """fp32 sharded search == single-device facade, bit-identical, on 1 and
    8 host devices; exactly one jit trace per (params, batch shape)."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    r, q, qm = build()
    params = SearchParams(use_ann=False)
    want_s, want_i = r.search(q, qm, params)
    for mesh in (MESH1, MESH8):
        sr = r.shard(mesh, sq8=False)
        got_s, got_i = sr.search(q, qm, params)
        assert np.array_equal(np.asarray(got_i), np.asarray(want_i)), mesh
        assert np.array_equal(np.asarray(got_s), np.asarray(want_s)), mesh
        sr.search(q, qm, params)          # same params + shape: no retrace
        assert sr.trace_count() == 1
        assert sr.trace_count(params) == 1
    print("OK")
    """))
    assert "OK" in out


def test_sharded_search_sq8_matches_single_device(run_forced8):
    """SQ8 state: scores are exact w.r.t. the quantized representation, so
    8-device serving must still be bit-identical to the 1-device mesh."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    r, q, qm = build()
    params = SearchParams(use_ann=False)
    s1, i1 = r.shard(MESH1, sq8=True).search(q, qm, params)
    s8, i8 = r.shard(MESH8, sq8=True).search(q, qm, params)
    assert np.array_equal(np.asarray(i1), np.asarray(i8))
    assert np.array_equal(np.asarray(s1), np.asarray(s8))
    ids = np.asarray(i8)
    assert ids.min() >= 0 and ids.max() < r.m      # pads never surface
    # quantized top-k stays close to the fp32 ranking on this easy corpus
    _, fp_i = r.search(q, qm, params)
    overlap = np.mean([len(set(a) & set(b)) / len(a)
                       for a, b in zip(ids, np.asarray(fp_i))])
    assert overlap >= 0.8, overlap
    print("OK")
    """))
    assert "OK" in out


def test_sharded_fused_gather_matches_legacy(run_forced8):
    """The fused (gather-at-source) per-shard rerank — the default — and the
    legacy gather-then-contract path return identical results on 8 devices,
    for both the fp32 and SQ8 states; the toggle gets its own jit trace."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    r, q, qm = build()
    fused = SearchParams(use_ann=False)                    # resolved default: fused
    legacy = SearchParams(use_ann=False, use_fused_gather=False)
    for sq8 in (False, True):
        sr = r.shard(MESH8, sq8=sq8)
        fs, fi = sr.search(q, qm, fused)
        ls, li = sr.search(q, qm, legacy)
        assert np.array_equal(np.asarray(fi), np.asarray(li)), sq8
        assert np.array_equal(np.asarray(fs), np.asarray(ls)), sq8
        assert sr.trace_count(fused) == 1 and sr.trace_count(legacy) == 1
    # fp32 fused sharded == local facade, bit for bit
    sr = r.shard(MESH8, sq8=False)
    want_s, want_i = r.search(q, qm, fused)
    got_s, got_i = sr.search(q, qm, fused)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    print("OK")
    """))
    assert "OK" in out


def test_sharded_one_launch_matches_legacy(run_forced8):
    """The one-launch per-shard first stage (fused dense scan + in-kernel
    top-k dispatch) returns the same candidate ids as the legacy
    scan → mask → top_k composition on 8 devices — including the pad-row
    masking path (m=90 does not divide 8) — with its own jit trace."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    r, q, qm = build()
    legacy = SearchParams(use_ann=False)
    one = SearchParams(use_ann=False, use_one_launch=True)
    for sq8 in (False, True):
        sr = r.shard(MESH8, sq8=sq8)
        ls, li = sr.search(q, qm, legacy)
        os_, oi = sr.search(q, qm, one)
        assert np.array_equal(np.asarray(oi), np.asarray(li)), sq8
        assert np.array_equal(np.asarray(os_), np.asarray(ls)), sq8
        assert sr.trace_count(legacy) == 1 and sr.trace_count(one) == 1
    # fp32 one-launch sharded == local facade legacy path, bit for bit
    sr = r.shard(MESH8, sq8=False)
    want_s, want_i = r.search(q, qm, legacy)
    got_s, got_i = sr.search(q, qm, one)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    print("OK")
    """))
    assert "OK" in out


def test_sharded_add_matches_facade(run_forced8):
    """Shard-balanced growth: after add(), sharded search still matches the
    (identically grown) facade bit for bit, and every shard holds the same
    row count."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    import repro.dist as dist
    r, q, qm = build()
    sr = r.shard(MESH8, sq8=False)
    extra = synthetic.make_corpus(m=21, d=16, avg_tokens=8, max_tokens=8,
                                  n_centers=16, seed=9)
    sr.add(extra.doc_tokens, extra.doc_mask)      # grows the shared base too
    assert sr.m == r.m == 111
    assert sr.state.W.shape[0] % dist.n_corpus_shards(MESH8) == 0
    params = SearchParams(k_prime=r.m, use_ann=False)  # full coverage again
    want_s, want_i = r.search(q, qm, params)
    got_s, got_i = sr.search(q, qm, params)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    print("OK")
    """))
    assert "OK" in out


def test_sharded_mutation_matches_facade(run_forced8):
    """Interleaved add/delete/update on the slot-pool sharded facade: an
    in-capacity mutation is an in-place row write (ZERO new traces for the
    already-compiled serve step), tombstoned ids never surface, and the
    mutated 8-device search stays bit-identical to an identically mutated
    single-device facade."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    r, q, qm = build()
    rl = r.clone()                    # independent local twin (shared solver
    sr = r.shard(MESH8, sq8=False)    # => bit-identical fitted W rows)
    params = SearchParams(use_ann=False)
    sr.search(q, qm, params)
    assert sr.trace_count() == 1
    extra = synthetic.make_corpus(m=12, d=16, avg_tokens=8, max_tokens=8,
                                  n_centers=16, seed=9)
    for t in (sr, rl):
        t.add(extra.doc_tokens, extra.doc_mask)
        t.delete(t.last_added_ids[:6])
        t.update([3, 7], extra.doc_tokens[6:8], extra.doc_mask[6:8])
    assert sr.m == rl.m == 104 and sr.n_alive == rl.n_alive == 96
    assert sr.version == rl.version == 3     # update bumps ONCE
    # pool had free rows + token width fits => in-place writes, no retrace
    _, ids = sr.search(q, qm, params)
    assert sr.trace_count() == 1, "in-capacity mutation retraced the serve step"
    gone = set(range(90, 96)) | {3, 7}
    assert not (set(np.asarray(ids).ravel().tolist()) & gone)
    # full-coverage exact parity vs the identically mutated local facade
    full = SearchParams(use_ann=False, k_prime=sr.m)
    want_s, want_i = rl.search(q, qm, full)
    got_s, got_i = sr.search(q, qm, full)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    print("OK")
    """))
    assert "OK" in out


def test_sharded_mutation_sq8_single_vs_8dev(run_forced8):
    """The same churn under SQ8: both meshes quantize the in-place row
    writes identically, so 1-device and 8-device search stay bit-identical
    and deleted ids never surface from the quantized scan either."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    r, q, qm = build()
    extra = synthetic.make_corpus(m=12, d=16, avg_tokens=8, max_tokens=8,
                                  n_centers=16, seed=9)
    res = []
    for mesh in (MESH1, MESH8):
        sr = r.clone().shard(mesh, sq8=True)
        sr.add(extra.doc_tokens, extra.doc_mask)
        sr.delete(sr.last_added_ids[:6])
        sr.update([3, 7], extra.doc_tokens[6:8], extra.doc_mask[6:8])
        res.append(sr.search(q, qm, SearchParams(use_ann=False,
                                                 k_prime=sr.m)))
    (s1, i1), (s8, i8) = res
    assert np.array_equal(np.asarray(i1), np.asarray(i8))
    assert np.array_equal(np.asarray(s1), np.asarray(s8))
    gone = set(range(90, 96)) | {3, 7}
    assert not (set(np.asarray(i8).ravel().tolist()) & gone)
    print("OK")
    """))
    assert "OK" in out


def test_sharded_k_exceeds_corpus_pads_to_k(run_forced8):
    """k > m on a corpus smaller than the device count: search must keep
    the facade's (B, k) shape, padding with (NEG, -1) — not return the
    merge's narrower width."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    corpus = synthetic.make_corpus(m=6, d=16, avg_tokens=6, max_tokens=6,
                                   n_centers=4, seed=0)
    cfg = LemurConfig(d=16, d_prime=16, m_pretrain=6, n_train=128, n_ols=64,
                      epochs=2, batch_size=64, k=10, k_prime=6,
                      anns="bruteforce")
    r = LemurRetriever.build(corpus, cfg, key=jax.random.PRNGKey(0))
    q = jnp.asarray(synthetic.queries_from_corpus_query(corpus, 2, 3, seed=1))
    qm = jnp.ones(q.shape[:2], bool)
    sr = r.shard(MESH8, sq8=False)
    s, i = sr.search(q, qm, SearchParams(k=10))
    ids = np.asarray(i)
    assert s.shape == (2, 10) and i.shape == (2, 10)
    assert (ids[:, 6:] == -1).all()
    assert (np.sort(ids[:, :6], axis=1) == np.arange(6)).all()
    print("OK")
    """))
    assert "OK" in out


def test_sharded_save_load_roundtrip(run_forced8):
    """save() persists the mesh-free index; load(directory, mesh) reproduces
    sharded search ids/scores bit-identically."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    import tempfile
    r, q, qm = build()
    params = SearchParams(use_ann=False)
    want_s, want_i = r.shard(MESH8, sq8=False).search(q, qm, params)
    with tempfile.TemporaryDirectory() as d:
        r.shard(MESH8).save(d)
        sr = ShardedLemurRetriever.load(d, MESH8, sq8=False)
        got_s, got_i = sr.search(q, qm, params)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    print("OK")
    """))
    assert "OK" in out


def test_sharded_index_step_matches_local_ols(run_forced8):
    """The zero-comms distributed OLS index step reproduces the local
    solve over an 8-way sharded corpus."""
    out = run_forced8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import LemurConfig, indexer
    from repro.core.model import init_psi
    from repro.data import synthetic
    from repro.dist import make_index_step

    corpus = synthetic.make_corpus(m=96, d=16, avg_tokens=8, max_tokens=8, seed=0)
    cfg = LemurConfig(d=16, d_prime=32, ridge=1e-4, n_ols=128)
    psi = init_psi(jax.random.PRNGKey(0), 16, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
    docs = jnp.asarray(corpus.doc_tokens); mask = jnp.asarray(corpus.doc_mask)
    W_ref = indexer.fit_output_layer_ols(psi, x, docs, mask, cfg)

    chol, feats = indexer.gram_factor(psi, x, cfg.ridge)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    step = make_index_step(mesh, cfg, doc_block=12)
    W = jax.jit(step)(chol[0], feats, x, docs, mask, jnp.zeros(()), jnp.ones(()))
    err = float(jnp.max(jnp.abs(W - W_ref)))
    assert err < 1e-3, err
    print("OK")
    """)
    assert "OK" in out


def test_online_server_sharded_parity(run_forced8):
    """The online serving runtime over an 8-device ShardedLemurRetriever:
    ragged bucketed micro-batches return the same top-k ids as direct
    sharded search (scores to reduction tolerance), streaming add() lands
    between micro-batches and post-add queries see the new docs, and the
    compiled-step count stays within the bucket-ladder bound."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    from repro.serving import BucketLadder, RetrieverServer

    r, q, qm = build()
    sr = r.shard(MESH8, sq8=False)
    params = SearchParams(use_ann=False)
    ladder = BucketLadder((4, 8), max_batch=4)
    rng = np.random.default_rng(3)
    with RetrieverServer(sr, ladder=ladder, max_wait_us=500,
                         default_params=params) as srv:
        futs = []
        for i in range(12):
            tq = int(rng.integers(1, 9))
            qi = np.asarray(q[i % q.shape[0], :tq])
            futs.append((qi, srv.submit(qi)))
        for qi, fut in futs:
            s, ids = fut.result(timeout=120)
            want_s, want_i = sr.search(qi[None],
                                       np.ones((1, len(qi)), bool), params)
            assert np.array_equal(ids, np.asarray(want_i)[0])
            np.testing.assert_allclose(s, np.asarray(want_s)[0],
                                       rtol=1e-5, atol=1e-6)
        assert srv.trace_count() <= ladder.compile_bound(1)
        # streaming add: applied between micro-batches, later queries see it
        extra = synthetic.make_corpus(m=7, d=16, avg_tokens=8, max_tokens=8,
                                      n_centers=16, seed=11)
        assert srv.add(extra.doc_tokens, extra.doc_mask).result(timeout=300) == 97
        grown = SearchParams(use_ann=False, k_prime=97)
        target = extra.doc_tokens[2][extra.doc_mask[2]]
        s, ids = srv.search(np.asarray(target), params=grown, timeout=300)
        assert ids[0] == 92, ids     # new doc id = 90 + 2, visible post-add
    print("OK")
    """))
    assert "OK" in out


def test_sharded_warm_swap_parity_and_barrier(run_forced8):
    """Lifecycle warm swap on 8 devices: ``build_refresh`` from the sharded
    snapshot is bit-identical to an identically mutated local twin's, the
    install lands through the RetrieverServer FIFO barrier with searches in
    flight (earlier futures stamped with the pre-swap version and answered
    by the old snapshot, later ones by the refit index), and the post-swap
    8-device search matches the locally refreshed facade bit for bit."""
    out = run_forced8(_BUILD + textwrap.dedent("""
    from repro.lifecycle import build_refresh
    from repro.serving import BucketLadder, RetrieverServer

    r, q, qm = build()
    rl = r.clone()                    # independent local twin
    sr = r.shard(MESH8, sq8=False)
    extra = synthetic.make_corpus(m=14, d=16, avg_tokens=8, max_tokens=8,
                                  n_centers=16, seed=9)
    for t in (sr, rl):
        t.add(extra.doc_tokens, extra.doc_mask)
        t.delete([1, 5, 90])
    # same snapshot + same seed => bit-identical refresh artifacts
    res_s = build_refresh(sr, seed=7)
    res_l = build_refresh(rl, seed=7)
    assert res_s.m0 == res_l.m0 == 104
    assert np.array_equal(np.asarray(res_s.W), np.asarray(res_l.W))
    params = SearchParams(use_ann=False, k_prime=sr.m)
    qs = [np.asarray(q[i, :4]) for i in range(3)]
    ones = np.ones((1, 4), bool)
    pre = [sr.search(qi[None], ones, params) for qi in qs]
    rl.install_refresh(res_l)
    post = [rl.search(qi[None], ones, params) for qi in qs]
    v0 = sr.version
    with RetrieverServer(sr, ladder=BucketLadder((4,), max_batch=2),
                         max_wait_us=200, default_params=params) as srv:
        srv.pause()                   # freeze the worker: strict FIFO order
        bef = [srv.submit(qi) for qi in qs]
        swap = srv.apply(lambda t, res=res_s: t.install_refresh(res))
        aft = [srv.submit(qi) for qi in qs]
        srv.resume()
        for fut, (ws, wi) in zip(bef, pre):
            s, ids = fut.result(timeout=300)
            assert fut.snapshot_version == v0
            assert np.array_equal(ids, np.asarray(wi)[0])
            np.testing.assert_allclose(s, np.asarray(ws)[0],
                                       rtol=1e-5, atol=1e-6)
        swap.result(timeout=300)
        assert swap.snapshot_version == v0 + 1
        for fut, (ws, wi) in zip(aft, post):
            s, ids = fut.result(timeout=300)
            assert fut.snapshot_version == v0 + 1
            assert np.array_equal(ids, np.asarray(wi)[0])
            np.testing.assert_allclose(s, np.asarray(ws)[0],
                                       rtol=1e-5, atol=1e-6)
    assert sr.version == rl.version == v0 + 1
    # full-coverage exact parity vs the locally refreshed facade
    want_s, want_i = rl.search(q, qm, params)
    got_s, got_i = sr.search(q, qm, params)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    print("OK")
    """))
    assert "OK" in out
