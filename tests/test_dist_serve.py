"""Sharded-vs-local parity for the facade's multi-device serving path.

``LemurRetriever.shard(mesh)`` must be a pure distribution transform: the
same top-k ids AND scores as the single-device facade, bit for bit, on any
mesh — each test runs via the shared ``run_forced8`` conftest fixture (a
subprocess with 8 forced XLA host devices; the main process keeps its
single device under any pytest ordering) and compares a 1-device and an
8-device mesh against the local reference.

The corpora deliberately do NOT divide the device count (m=90, 8 devices)
so the pad-row masking path is always exercised.

``LemurRetriever.build(mesh=)`` (the tests after the slot pool's) is held
to a plain reference on 4 forced host devices: a loop over the shards that
builds a one-chip index from the same ψ, W rows and key over each shard's
docs, runs the one-chip pipeline on it and merges in numpy, and exact
MaxSim in float64.
"""
import json
import textwrap

import pytest


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


# --------------------------------------------------------------------------
# LemurRetriever.build(mesh=): one one-chip index per shard
# --------------------------------------------------------------------------

# one subprocess on 4 forced host devices builds the deployment once and
# records what every test below checks; m=302 does not divide 4, so the
# shards hold 75, 76, 75 and 76 docs
_MESH_SCRIPT = """
import dataclasses, json, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro import dist
from repro.anns import ivf, registry
from repro.anns.base import CorpusView
from repro.core import LemurConfig, maxsim, pages
from repro.core.index import LemurIndex
from repro.core.model import TargetStats
from repro.data import synthetic
from repro.kernels import ops
from repro.retriever import (IVFBackendConfig, IVFSearchParams,
                             LemurRetriever, MeshLemurRetriever, SearchParams)
from repro.retriever.facade import search_pipeline

out = {}
M = 302
corpus = synthetic.make_corpus(m=M, d=16, avg_tokens=8, max_tokens=12,
                               n_centers=24, seed=0)
cfg = LemurConfig(d=16, d_prime=32, m_pretrain=64, n_train=512, n_ols=256,
                  epochs=2, k=5, k_prime=64, anns="ivf",
                  ivf=IVFBackendConfig(nprobe=8, sq8=True))
key = jax.random.PRNGKey(0)
mesh = jax.make_mesh((2, 2), ("data", "model"))
devs = dist.shard_devices(mesh)
mr = LemurRetriever.build(corpus, cfg, key=key, mesh=mesh)
one = LemurRetriever.build(corpus, cfg, key=key)
q = synthetic.queries_from_corpus_query(corpus, 8, 4, seed=5)
qm = np.ones(q.shape[:2], bool)
bounds = [s * M // 4 for s in range(5)]
nlist = ivf.default_nlist(max(np.diff(bounds)))
exact64 = np.stack([
    [(qi.astype(np.float64) @ corpus.doc_tokens[j][corpus.doc_mask[j]]
      .astype(np.float64).T).max(1).sum() for j in range(M)] for qi in q])
out["nlist"] = [int(mr.state.ann.centroids.shape[0] // 4), int(nlist)]

# (a) psi bit for bit; W rows within fp32 tolerance
host = jax.device_get(mr.state)
out["psi_bit_equal"] = all(
    np.array_equal(a, b) for a, b in zip(jax.tree.leaves(host.psi),
                                         jax.tree.leaves(jax.device_get(
                                             one.index.psi))))
C = host.store.W.shape[0] // 4
W_mesh = np.concatenate([host.store.W[s * C:s * C + bounds[s + 1] - bounds[s]]
                         for s in range(4)])
W_one = np.asarray(one.index.W)
out["W_rel_err"] = float(np.abs(W_mesh - W_one).max() / np.abs(W_one).max())

# (b) the plain reference: a one-chip index per shard from the same psi, W
# rows and key, the one-chip pipeline on each, the merge in numpy
keys = jax.random.split(key, 4)
stats = TargetStats(*host.stats)

def plain_reference(params):
    local = dataclasses.replace(mr.resolve(params),
                                k_prime=mr.k_prime_local(params))
    run = jax.jit(lambda psi, stats, store, ann, q, qm: search_pipeline(
        LemurIndex(cfg, psi, stats, store, "ivf", ann), q, qm, local))
    ss, ii = [], []
    for s in range(4):
        lo, hi = bounds[s], bounds[s + 1]
        W = W_mesh[lo:hi]
        toks, mask = corpus.doc_tokens[lo:hi], corpus.doc_mask[lo:hi]
        ann = registry.get_backend("ivf").build(
            jax.random.fold_in(keys[3], s), CorpusView(jnp.asarray(W), toks,
                                                       mask),
            cfg.ivf.replace(nlist=nlist))
        store, _ = pages.from_dense(W, toks, mask)
        sc, ids = (np.asarray(a) for a in run(host.psi, stats, store, ann,
                                              q, qm))
        ss.append(sc)
        ii.append(np.where(ids >= 0, ids + lo, -1))
    ss, ii = np.concatenate(ss, 1), np.concatenate(ii, 1)
    order = np.argsort(-ss, axis=1, kind="stable")[:, :local.k]
    return (np.take_along_axis(ss, order, 1),
            np.take_along_axis(ii, order, 1))

want_s, want_i = plain_reference(None)
got_s, got_i = (np.asarray(a) for a in mr.search(q, qm))
out["served_bit_equal"] = bool(np.array_equal(got_i, want_i)
                               and np.array_equal(got_s, want_s))
out["served_ids"] = got_i.tolist()
out["served_shape"] = list(got_i.shape)

# (c) nprobe = nlist and k'_local covering every shard: the exact top-k
full = SearchParams(k_prime=M, backend=IVFSearchParams(nprobe=nlist))
out["k_prime_local_full"] = mr.k_prime_local(full)

def exact_gap(r):
    s, i = (np.asarray(a) for a in r.search(q, qm, full))
    truth = np.argsort(-exact64, axis=1, kind="stable")[:, :cfg.k]
    same = all(set(a) == set(b) for a, b in zip(i.tolist(), truth.tolist()))
    ex = np.take_along_axis(exact64, i, 1)
    return same, float(np.max(np.abs(s - ex) / np.maximum(np.abs(ex), 1)))

out["exact_top_k"], out["exact_gap"] = exact_gap(mr)
rerank = ops.fused_rerank_paged

def bf16_rerank(q, qm, cand, tok_pages, *a, **kw):
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return rerank(r(q), qm, cand, r(tok_pages), *a, **kw)

ops.fused_rerank_paged = bf16_rerank
out["bf16_top_k"], out["bf16_gap"] = exact_gap(
    MeshLemurRetriever(mr.cfg, mr.mesh, mr.state, mr.m))
ops.fused_rerank_paged = rerank

# (d) candidates: every shard's k'_local, global ids, pads -1
cand = np.asarray(mr.candidates(q, qm))
kl = mr.k_prime_local()
out["cands_width"] = [int(cand.shape[1]), 4 * kl]
out["cands_in_own_shard"] = all(
    ((blk == -1) | ((blk >= bounds[s]) & (blk < bounds[s + 1]))).all()
    for s, blk in enumerate(np.split(cand, 4, axis=1)))
out["cands_pad"] = bool((cand == -1).any())
out["cands_fewer_than_docs"] = bool(((cand >= 0).sum(1) < M).all())
out["served_in_cands"] = all(
    set(s[s >= 0].tolist()) <= set(c[c >= 0].tolist())
    for s, c in zip(got_i, cand))
cand_full = np.asarray(mr.candidates(q, qm, full))
truth = np.argsort(-exact64, axis=1, kind="stable")[:, :cfg.k]
out["exact_top_k_in_cands"] = all(
    set(t.tolist()) <= set(c.tolist()) for t, c in zip(truth, cand_full))

# (e) each device's block of every per-shard leaf is its own shard's
placed, own_docs, own_lists = True, True, True
for leaf in jax.tree.leaves((mr.state.store, mr.state.ann, mr.state.base)):
    blk = leaf.shape[0] // 4
    for sh in leaf.addressable_shards:
        s = (sh.index[0].start or 0) // blk
        placed &= sh.device == devs[s] and sh.data.shape[0] == blk
for s, dev in enumerate(devs):
    mine = lambda t: jax.tree.map(lambda a: next(
        sh.data for sh in a.addressable_shards if sh.device == dev), t)
    st, ann = mine(mr.state.store), mine(mr.state.ann)
    lo, hi = bounds[s], bounds[s + 1]
    toks, mask = pages.gather_docs(st, jnp.arange(hi - lo))
    toks, mask = np.asarray(toks), np.asarray(mask)
    w = corpus.doc_tokens.shape[1]
    own_docs &= (int(st.n_docs[0]) == hi - lo
                 and np.array_equal(mask[:, :w], corpus.doc_mask[lo:hi])
                 and np.array_equal(toks[:, :w] * mask[:, :w, None],
                                    corpus.doc_tokens[lo:hi]
                                    * corpus.doc_mask[lo:hi, :, None]))
    ids = np.asarray(ann.ids)
    own_lists &= sorted(ids[ids >= 0].tolist()) == list(range(hi - lo))
out["placed"], out["own_docs"], out["own_lists"] = (
    bool(placed), bool(own_docs), bool(own_lists))

# (f) one trace per batch shape
fresh = MeshLemurRetriever(mr.cfg, mr.mesh, mr.state, mr.m)
for b in (8, 8, 3, 8, 3):
    fresh.search(q[:b], qm[:b])
out["traces"] = [fresh.trace_count(), fresh.trace_count(SearchParams())]
out["trace_shapes"] = sorted([list(k), v]
                             for k, v in fresh.trace_shapes().items())

# (g) add raises
try:
    mr.add(corpus.doc_tokens[:2], corpus.doc_mask[:2])
    out["add"] = "returned"
except NotImplementedError as e:
    out["add"] = str(e)

# build_per_shard: build() without a mesh trains and stops; shard(mesh)
# builds what build(mesh=) builds, here with a pinned per-shard budget
trained = LemurRetriever.build(corpus, cfg.replace(build_per_shard=True),
                               key=key)
out["trained"] = type(trained).__name__
pinned = trained.shard(mesh, k_prime_local=kl)
out["deferred_same_state"] = all(
    np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(jax.device_get(pinned.state)), jax.tree.leaves(host)))
s3, i3 = (np.asarray(a) for a in pinned.search(q, qm))
out["deferred_same_answers"] = bool(np.array_equal(s3, got_s)
                                    and np.array_equal(i3, got_i))
out["rows_per_shard"] = pinned.rows_per_shard
# k past each shard's k': every shard answers all its candidates, the
# merge drops none, and the columns past every candidate are pads
wide = SearchParams(k=4 * kl, use_fused_gather=False)
out["k_prime_local_wide"] = [pinned.k_prime_local(wide),
                             mr.k_prime_local(wide), kl]
ws, wi = (np.asarray(a) for a in pinned.search(q, qm, wide))
out["wide_shape"] = list(wi.shape)
out["wide_is_candidates"] = all(
    sorted(a[a >= 0].tolist()) == sorted(c[c >= 0].tolist())
    for a, c in zip(wi, cand))
out["wide_pads"] = bool(((wi == -1) == (ws == maxsim.NEG)).all()
                        and (wi == -1).any())
wider = SearchParams(k=4 * kl + 3, use_fused_gather=False)
ws, wi = (np.asarray(a) for a in pinned.search(q, qm, wider))
out["wider_tail"] = [list(wi.shape), bool((wi[:, -3:] == -1).all()
                                          and (ws[:, -3:] == maxsim.NEG).all())]

# build(mesh=) with k' covering every shard answers as build().shard(mesh)
s1, i1 = (np.asarray(a) for a in mr.search(q, qm, full))
s2, i2 = (np.asarray(a) for a in one.shard(mesh, sq8=False).search(
    q, qm, SearchParams(k_prime=M, use_ann=False)))
out["answers_as_shard"] = [bool(np.array_equal(i1, i2)),
                           float(np.abs(s1 - s2).max())]

# spans: the step's ops carry the stage scopes; search opens lemur.search
hlo = fresh._compiled_fn(fresh.resolve(None)).lower(
    mr.state, jnp.asarray(q), jnp.asarray(qm)).compile().as_text()
out["scopes"] = sorted({sc for sc in ("first_stage/", "rerank/", "merge/")
                        if sc in hlo})
with tempfile.TemporaryDirectory() as d:
    jax.profiler.start_trace(d)
    jax.block_until_ready(mr.search(q, qm))
    jax.profiler.stop_trace()
    import pathlib
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(d).glob("plugins/profile/*/*.xplane.pb"))[-1]
    out["host_spans"] = sorted({e.name for p in ProfileData.from_file(
        str(path)).planes for line in p.lines for e in line.events
        if e.name.startswith("lemur.")})
print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def on_mesh(run_forced8):
    """What ``_MESH_SCRIPT`` found on 4 forced host devices."""
    out = run_forced8(_MESH_SCRIPT, n_devices=4)
    return json.loads(out.split("JSON", 1)[1])


def test_mesh_build_trains_psi_and_w_as_one_chip(on_mesh):
    """(a) ψ is the one-chip build's bit for bit (trained once, same keys);
    each shard's W rows are the one-chip rows within 1e-3 of the largest
    entry.  Why not bit for bit: the OLS right-hand side Ψᵀg sums the same
    n' products, but in another order when a block of docs has another
    width (1 block of 302 against 4 of 75-76), and the solve against the
    ridge Gram (condition number ~1e5 here) multiplies that fp32 rounding;
    κ · 2^-24 ≈ 7e-3 bounds it, and it reads ~6e-5."""
    assert on_mesh["psi_bit_equal"]
    assert on_mesh["W_rel_err"] < 1e-3, on_mesh["W_rel_err"]
    # nlist by the paper's rule on the shard's m, the same on every shard
    assert on_mesh["nlist"][0] == on_mesh["nlist"][1]


def test_mesh_search_equals_the_plain_per_shard_reference(on_mesh):
    """(b) served ids and scores equal, bit for bit on fp32, a loop over
    the shards that builds a one-chip index from the same ψ, W rows and
    key, runs ``search_pipeline`` on it and merges the results in numpy."""
    assert on_mesh["served_shape"] == [8, 5]
    assert on_mesh["served_bit_equal"]


def test_mesh_full_probe_is_the_exact_top_k_and_bf16_is_not(on_mesh):
    """(c) with nprobe = nlist and k'_local covering every shard the served
    top-k is the whole corpus's exact top-k, scores within 1e-5 of float64
    MaxSim (relative to max(|exact|, 1)).  The limit: each fp32 token dot
    sums d=16 products and a score sums Tq=4 token maxima of at most 1, so
    rounding moves a score by at most (16 + 4) · 2^-24 · 4 ≈ 4.8e-6.  The
    control, the rerank's inputs rounded to bf16 (2^-8), fails it."""
    assert on_mesh["k_prime_local_full"] >= 76          # the largest shard
    assert on_mesh["exact_top_k"]
    assert on_mesh["exact_gap"] < 1e-5, on_mesh["exact_gap"]
    assert on_mesh["bf16_gap"] > 1e-5, on_mesh["bf16_gap"]


def test_mesh_candidates_are_every_shards_first_stage(on_mesh):
    """(d) ``candidates()`` is (B, shards × k'_local): shard s's global ids
    in the s-th block of columns, pads -1, every served id among them."""
    width, want = on_mesh["cands_width"]
    assert width == want
    assert on_mesh["cands_in_own_shard"]
    assert on_mesh["cands_pad"]
    assert on_mesh["served_in_cands"]


def test_mesh_leaves_hold_only_their_own_shard(on_mesh):
    """(e) every per-shard leaf (store, IVF lists, base) is block-sharded:
    device s holds one block, and that block is shard s's docs -- its pages
    gather back to exactly the corpus rows [s·m/n, (s+1)·m/n), its IVF
    lists hold each of those docs once."""
    assert on_mesh["placed"]
    assert on_mesh["own_docs"]
    assert on_mesh["own_lists"]


def test_mesh_search_traces_once_per_batch_shape(on_mesh):
    """(f) five searches over two batch shapes trace twice."""
    assert on_mesh["traces"] == [2, 2]
    assert on_mesh["trace_shapes"] == [[[3, 4, 16], 1], [[8, 4, 16], 1]]


def test_mesh_retriever_refuses_add(on_mesh):
    """(g) the mesh-built deployment is read-only, and says so."""
    assert "read-only" in on_mesh["add"]


def test_build_per_shard_defers_the_index_to_shard(on_mesh):
    """With ``cfg.build_per_shard`` and no mesh, ``build`` trains ψ and the
    OLS solver and returns a ``TrainedLemur``; its ``shard(mesh)`` builds,
    leaf for leaf, the state ``build(mesh=)`` builds and answers as it
    does."""
    assert on_mesh["trained"] == "TrainedLemur"
    assert on_mesh["deferred_same_state"]
    assert on_mesh["deferred_same_answers"]
    assert on_mesh["rows_per_shard"] == 76


def test_pinned_k_prime_local_holds_for_every_search(on_mesh):
    """A budget pinned at ``shard(mesh, k_prime_local=)`` is each shard's
    for every search; unpinned, a k past k' widens it."""
    pinned, default, kl = on_mesh["k_prime_local_wide"]
    assert pinned == kl
    assert default == 4 * kl


def test_mesh_search_past_every_shards_k_prime_is_its_candidates(on_mesh):
    """At k = shards x k'_local, on the rerank that gathers pages, the
    answer's ids are every shard's candidates; a pad id scores NEG; past
    that, the columns are pads."""
    kl = on_mesh["k_prime_local_wide"][2]
    assert on_mesh["wide_shape"] == [8, 4 * kl]
    assert on_mesh["wide_is_candidates"]
    assert on_mesh["wide_pads"]
    shape, tail_pads = on_mesh["wider_tail"]
    assert shape == [8, 4 * kl + 3]
    assert tail_pads


def test_mesh_build_answers_as_build_then_shard(on_mesh):
    """Where the first stage keeps every doc (nprobe = nlist, k'_local
    covering each shard), ``build(mesh=)`` answers what the slot pool of
    ``build().shard(mesh)`` answers with its exact scan: the same ids, the
    same scores."""
    same_ids, gap = on_mesh["answers_as_shard"]
    assert same_ids
    assert gap == 0.0


def test_mesh_candidates_hold_every_served_id(on_mesh):
    width, want = on_mesh["cands_width"]
    assert width == want
    assert on_mesh["cands_fewer_than_docs"]
    assert on_mesh["served_in_cands"]


def test_mesh_candidates_covering_a_shard_hold_the_exact_top_k(on_mesh):
    assert on_mesh["exact_top_k_in_cands"]


def test_mesh_step_carries_stage_scopes_and_search_span(on_mesh):
    """The compiled sharded step's op names carry ``first_stage/`` and
    ``rerank/`` (the one-chip pipeline on each shard) and ``merge/``; the
    sharded ``search`` opens a ``lemur.search`` host span."""
    assert on_mesh["scopes"] == ["first_stage/", "merge/", "rerank/"]
    assert "lemur.search" in on_mesh["host_spans"]
