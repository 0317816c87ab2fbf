"""Retriever API v1: facade lifecycle, typed SearchParams, per-backend
config namespaces, save/load persistence, and the one-trace-per-params
compilation contract."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.anns import registry
from repro.core import LemurConfig
from repro.retriever import (
    IVFBackendConfig,
    IVFSearchParams,
    LemurRetriever,
    NoSearchParams,
    SearchParams,
    TokenPruningSearchParams,
)

BACKENDS = registry.list_backends()


@pytest.fixture(scope="module")
def retriever(tiny_corpus):
    cfg = LemurConfig(d=16, d_prime=64, m_pretrain=128, n_train=1024, n_ols=512,
                      epochs=5, k=10, k_prime=60, anns="bruteforce")
    return LemurRetriever.build(tiny_corpus, cfg, key=jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def queries(tiny_corpus):
    from repro.data import synthetic

    q = jnp.asarray(synthetic.queries_from_corpus_query(tiny_corpus, 8, 4, seed=3))
    return q, jnp.ones(q.shape[:2], bool)


# --------------------------------------------------------------------------
# persistence: build -> save -> load -> search must be bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", BACKENDS)
def test_save_load_roundtrip_bit_identical(name, retriever, queries, tmp_path):
    q, qm = queries
    r = retriever.with_backend(name, key=jax.random.PRNGKey(1))
    params = SearchParams(k=10)
    s, ids = r.search(q, qm, params)
    r.save(tmp_path / name)
    r2 = LemurRetriever.load(tmp_path / name)
    assert r2.backend == name and r2.cfg == r.cfg and r2.m == r.m
    s2, ids2 = r2.search(q, qm, params)
    np.testing.assert_array_equal(np.asarray(ids2), np.asarray(ids))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))


def test_load_rejects_foreign_checkpoints(tmp_path):
    from repro.checkpoint import save as ckpt_save

    ckpt_save(tmp_path, 0, {"w": jnp.zeros(3)}, extra={"format": "other"})
    with pytest.raises(ValueError, match="lemur-retriever-v1"):
        LemurRetriever.load(tmp_path)
    with pytest.raises(FileNotFoundError):
        LemurRetriever.load(tmp_path / "empty")


def test_saved_retriever_add_is_deterministic(retriever, tiny_corpus, tmp_path,
                                              queries):
    """add() after load reuses the persisted OLS tokens — two loads grow to
    bit-identical W; and an explicit seed governs the no-solver fallback."""
    q, qm = queries
    retriever.save(tmp_path / "det")
    extra_t = tiny_corpus.doc_tokens[:25]
    extra_m = tiny_corpus.doc_mask[:25]
    r1 = LemurRetriever.load(tmp_path / "det").add(extra_t, extra_m)
    r2 = LemurRetriever.load(tmp_path / "det").add(extra_t, extra_m)
    np.testing.assert_array_equal(np.asarray(r1.index.W), np.asarray(r2.index.W))
    # build-time solver state is reused: growing the ORIGINAL retriever gives
    # the same rows as growing its save/load clone
    r0 = retriever.with_backend("bruteforce")
    r0.add(extra_t, extra_m)
    np.testing.assert_allclose(np.asarray(r0.index.W), np.asarray(r1.index.W),
                               rtol=1e-5, atol=1e-6)
    _, ids = r1.search(q, qm, SearchParams(k=10))
    assert int(jnp.max(ids)) < r1.m


def test_add_fallback_seed_is_explicit(retriever, tiny_corpus):
    """Wrapping a bare index (no solver, no persisted tokens) falls back to
    corpus sampling, which must be driven by the explicit seed."""
    idx = retriever.with_backend("bruteforce").index
    extra_t, extra_m = tiny_corpus.doc_tokens[:10], tiny_corpus.doc_mask[:10]
    g1 = LemurRetriever(idx).add(extra_t, extra_m, seed=7).index.W
    g2 = LemurRetriever(idx).add(extra_t, extra_m, seed=7).index.W
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


# --------------------------------------------------------------------------
# compilation contract: one jit trace per (backend, SearchParams, shape)
# --------------------------------------------------------------------------

def test_one_trace_per_search_params(retriever, queries):
    q, qm = queries
    r = retriever.with_backend("ivf", key=jax.random.PRNGKey(1))
    params = SearchParams(k=5)
    for _ in range(4):
        r.search(q, qm, params)
    assert r.trace_count(params) == 1, "repeated search() retraced"
    # equivalent spellings of the same resolved params share the compile
    r.search(q, qm, SearchParams(k=5, k_prime=r.cfg.k_prime))
    assert r.trace_count(params) == 1
    # a different SearchParams compiles exactly one more fn
    p2 = SearchParams(k=5, backend=IVFSearchParams(nprobe=4))
    r.search(q, qm, p2)
    r.search(q, qm, p2)
    assert r.trace_count(p2) == 1 and r.trace_count() == 2
    # a new batch shape retraces the same params once
    r.search(q[:3], qm[:3], params)
    assert r.trace_count(params) == 2


def test_add_preserves_compiled_fns(retriever, queries, tiny_corpus):
    """The streaming-add bugfix contract: compiled query fns take the
    mutable state (paged store + backend state) as jit ARGUMENTS, so an add
    that fits the pre-grown pool changes no shapes and issues ZERO new
    traces — the compile cache SURVIVES the mutation."""
    q, qm = queries
    exact = SearchParams(k=5, use_ann=False)
    for name in ("bruteforce", "ivf"):
        r = retriever.with_backend(name, key=jax.random.PRNGKey(1))
        r.search(q, qm, exact)
        m0 = r.m
        assert r.trace_count(exact) == 1
        r.add(tiny_corpus.doc_tokens[:15], tiny_corpus.doc_mask[:15])
        assert r.m == m0 + 15
        _, ids = r.search(q, qm, exact)  # grown corpus, SAME compiled fn
        assert r.trace_count(exact) == 1, "in-capacity add retraced"
        assert int(jnp.max(ids)) < r.m
    # the ANN path survives too: IVF cluster-list capacity is pow2-bucketed
    # with a never-shrink floor, so an in-capacity add keeps list shapes
    r = retriever.with_backend("ivf", key=jax.random.PRNGKey(1))
    ann = SearchParams(k=5)
    r.search(q, qm, ann)
    r.add(tiny_corpus.doc_tokens[:15], tiny_corpus.doc_mask[:15])
    _, ids = r.search(q, qm, ann)
    assert r.trace_count(ann) == 1, "in-capacity add retraced the IVF path"
    assert int(jnp.max(ids)) < r.m


def test_delete_update_lifecycle(retriever, queries, tiny_corpus):
    """delete() tombstones (stable surviving ids, deleted ids never
    surface), update() replaces under ONE version bump with NEW ids."""
    q, qm = queries
    r = retriever.with_backend("bruteforce")
    params = SearchParams(k=10, use_ann=False)
    m0, v0 = r.m, r.version
    r.add(tiny_corpus.doc_tokens[:8], tiny_corpus.doc_mask[:8])
    added = r.last_added_ids
    np.testing.assert_array_equal(added, np.arange(m0, m0 + 8))
    r.delete(added)
    assert r.m == m0 + 8 and r.n_alive == m0  # slots never reused
    assert r.version == v0 + 2
    _, ids = r.search(q, qm, params)
    assert not np.isin(np.asarray(ids), np.asarray(added)).any()
    # unknown / double deletes are typed errors
    with pytest.raises(ValueError):
        r.delete(added[:1])
    with pytest.raises(ValueError):
        r.delete([r.m + 5])
    new_ids = r.update([0, 1], tiny_corpus.doc_tokens[:2],
                       tiny_corpus.doc_mask[:2])
    assert r.version == v0 + 3  # ONE bump for delete+add
    np.testing.assert_array_equal(new_ids, np.arange(m0 + 8, m0 + 10))
    _, ids = r.search(q, qm, params)
    assert not np.isin(np.asarray(ids), [0, 1]).any()


# --------------------------------------------------------------------------
# paged corpus: doc-id stability, tombstone masking, rebuild parity
# --------------------------------------------------------------------------

def _churn(r, corpus):
    """One interleaved add/delete/update round; returns the set of ids that
    must never surface again."""
    m0 = r.m
    r.add(corpus.doc_tokens[:12], corpus.doc_mask[:12])
    added = r.last_added_ids
    np.testing.assert_array_equal(added, np.arange(m0, m0 + 12))
    r.delete(added[:6])
    upd = [3, 9, int(added[6])]
    r.update(upd, corpus.doc_tokens[20:23], corpus.doc_mask[20:23])
    return set(added[:6].tolist()) | set(upd)


@pytest.mark.parametrize("name", BACKENDS)
def test_tombstones_never_surface_any_backend(name, retriever, queries,
                                              tiny_corpus):
    """Backends are never rebuilt on delete — their stale candidates are
    masked after every first stage — so across all five backends, both
    gather paths (fused page-fed kernel dispatch and legacy materialize-
    from-pages), and both the ANN and exact-scan routes, a deleted or
    replaced doc id can never surface."""
    q, qm = queries
    r = retriever.with_backend(name, key=jax.random.PRNGKey(7))
    dead = _churn(r, tiny_corpus)
    for fused in (True, False):
        for params in (SearchParams(k=10, use_fused_gather=fused),
                       SearchParams(k=10, use_ann=False, k_prime=r.m,
                                    use_fused_gather=fused)):
            _, ids = r.search(q, qm, params)
            ids = np.asarray(ids)
            hit = set(ids.ravel().tolist()) & dead
            assert not hit, f"tombstoned ids surfaced (fused={fused}): {hit}"
            assert ids.max() < r.m


def test_tombstones_never_surface_sq8(tiny_corpus, queries):
    """Same contract under the SQ8 first-stage tier (cfg.ivf.sq8)."""
    q, qm = queries
    cfg = LemurConfig(d=16, d_prime=64, m_pretrain=128, n_train=1024,
                      n_ols=512, epochs=4, k=10, k_prime=60, anns="ivf",
                      ivf=IVFBackendConfig(sq8=True, nprobe=32))
    r = LemurRetriever.build(tiny_corpus, cfg, key=jax.random.PRNGKey(0))
    dead = _churn(r, tiny_corpus)
    for fused in (True, False):
        _, ids = r.search(q, qm, SearchParams(k=10, use_fused_gather=fused))
        hit = set(np.asarray(ids).ravel().tolist()) & dead
        assert not hit, f"tombstoned ids surfaced (sq8, fused={fused}): {hit}"


def test_ids_refer_to_same_documents_across_churn(retriever, tiny_corpus):
    """Stable external ids: a doc keeps answering to the SAME id across
    unrelated add/delete/update churn (slots are never reused)."""
    r = retriever.with_backend("bruteforce")

    def top1(doc_id):
        toks = tiny_corpus.doc_tokens[doc_id][tiny_corpus.doc_mask[doc_id]]
        params = SearchParams(k=1, use_ann=False, k_prime=r.m)
        _, ids = r.search(toks[None], np.ones((1, len(toks)), bool), params)
        return int(np.asarray(ids)[0, 0])

    probes = [5, 17, 40]
    assert [top1(i) for i in probes] == probes
    _churn(r, tiny_corpus)          # touches ids 3/9 + its own adds, not 5/17/40
    assert [top1(i) for i in probes] == probes


def test_surviving_ids_bit_identical_to_rebuild(retriever, queries,
                                                tiny_corpus):
    """The acceptance criterion: after interleaved add/delete/update, the
    exact-scan search over the mutated paged store returns bit-identical
    scores — and ids referring to the same documents — as a from-scratch
    dense rebuild over only the surviving docs (same ψ/stats/W rows, ids
    mapped through the survivor order)."""
    from repro.core import pages
    from repro.core.index import LemurIndex

    q, qm = queries
    r = retriever.with_backend("bruteforce")
    _churn(r, tiny_corpus)
    st = r.index.store
    alive = np.flatnonzero(np.asarray(st.alive)[: r.m])
    toks, mask = pages.gather_docs(st, jnp.asarray(alive))
    idx2 = LemurIndex.from_dense(r.cfg, r.index.psi, r.index.stats,
                                 jnp.take(st.W, jnp.asarray(alive), axis=0),
                                 toks, mask, "bruteforce", None)
    r2 = LemurRetriever(idx2)
    assert r2.m == r.n_alive
    s1, i1 = r.search(q, qm, SearchParams(k=10, use_ann=False, k_prime=r.m))
    s2, i2 = r2.search(q, qm, SearchParams(k=10, use_ann=False,
                                           k_prime=r2.m))
    np.testing.assert_array_equal(alive[np.asarray(i2)], np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s1))


def test_save_load_preserves_tombstones(retriever, queries, tiny_corpus,
                                        tmp_path):
    """The persisted ``alive`` mask is load-bearing: a reloaded retriever
    keeps its tombstones (deleted docs never resurface as zero-score rows),
    its slot high-water mark, and its stable id numbering for further
    growth."""
    q, qm = queries
    r = retriever.with_backend("bruteforce")
    dead = _churn(r, tiny_corpus)
    r.save(tmp_path / "mutated")
    r2 = LemurRetriever.load(tmp_path / "mutated")
    assert r2.m == r.m and r2.n_alive == r.n_alive
    params = SearchParams(k=10, use_ann=False, k_prime=r.m)
    s1, i1 = r.search(q, qm, params)
    s2, i2 = r2.search(q, qm, params)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s1))
    assert not (set(np.asarray(i2).ravel().tolist()) & dead)
    # growth after reload continues the stable numbering (slots, not holes)
    r2.add(tiny_corpus.doc_tokens[:2], tiny_corpus.doc_mask[:2])
    np.testing.assert_array_equal(r2.last_added_ids,
                                  np.arange(r.m, r.m + 2))


# --------------------------------------------------------------------------
# typed SearchParams + per-backend config namespaces
# --------------------------------------------------------------------------

def test_search_params_hashable_and_resolved(retriever):
    p = SearchParams(k=5, backend=IVFSearchParams(nprobe=8))
    assert hash(p) == hash(SearchParams(k=5, backend=IVFSearchParams(nprobe=8)))
    r = retriever.with_backend("ivf")
    resolved = r.resolve(SearchParams())
    assert resolved.k == r.cfg.k and resolved.k_prime == r.cfg.k_prime
    assert resolved.backend == IVFSearchParams(
        nprobe=r.cfg.ivf.nprobe,
        use_fused_gather=r.cfg.ivf.use_fused_gather,
        use_one_launch=r.cfg.ivf.use_one_launch)
    assert resolved.use_fused_gather == r.cfg.use_fused_gather
    assert resolved.use_one_launch == r.cfg.use_one_launch
    # exact-scan params carry no backend knobs (cache key collapses)
    assert r.resolve(SearchParams(use_ann=False)).backend is None


def test_partial_backend_params_fill_from_config(retriever):
    """An explicit-but-empty params instance means 'cfg defaults', not
    'hardcoded backend defaults' — and collapses to the same cache key."""
    r = retriever.with_backend("ivf", cfg=retriever.cfg.replace(
        anns="ivf", ivf=IVFBackendConfig(nprobe=48)))
    a = r.resolve(SearchParams(backend=IVFSearchParams()))
    b = r.resolve(SearchParams())
    assert a.backend == IVFSearchParams(nprobe=48, use_fused_gather=True,
                                        use_one_launch=False)
    assert a == b


def test_from_dict_folds_v0_flat_knobs():
    """A v0-era config dict (flat knobs at top level) must not silently
    lose settings on load."""
    d = LemurConfig(d=16).to_dict()
    del d["ivf"], d["token_pruning"]
    d |= {"sq8": False, "ivf_nprobe": 64, "tp_nprobe": 2}
    with pytest.warns(DeprecationWarning):
        cfg = LemurConfig.from_dict(d)
    assert cfg.ivf == IVFBackendConfig(nprobe=64, sq8=False)
    assert cfg.token_pruning.nprobe == 2


def test_search_params_backend_type_mismatch(retriever, queries):
    q, qm = queries
    r = retriever.with_backend("muvera", key=jax.random.PRNGKey(1))
    with pytest.raises(TypeError, match="NoSearchParams"):
        r.search(q, qm, SearchParams(backend=IVFSearchParams(nprobe=4)))


def test_registry_exposes_config_and_params_types():
    assert registry.get_config_cls("ivf") is IVFBackendConfig
    assert registry.get_params_cls("ivf") is IVFSearchParams
    assert registry.get_params_cls("muvera") is NoSearchParams
    assert registry.get_params_cls("token_pruning") is TokenPruningSearchParams
    assert registry.get_config_cls("exact").__name__ == "BruteforceBackendConfig"
    for name in BACKENDS:
        be = registry.get_backend(name)
        assert isinstance(be.default_params(be.config_cls()), be.params_cls)


def test_config_namespaces_and_dotted_overrides():
    cfg = LemurConfig(d=16, anns="ivf", ivf=IVFBackendConfig(nprobe=48, sq8=False))
    assert cfg.backend_config() == cfg.ivf
    assert cfg.backend_config("token_pruning").nprobe == 8
    cfg2 = cfg.override({"ivf.nprobe": 16, "muvera.r_reps": 7})
    assert cfg2.ivf.nprobe == 16 and cfg2.muvera.r_reps == 7
    # dict round-trip preserves the nested namespaces
    assert LemurConfig.from_dict(cfg2.to_dict()) == cfg2
    assert hash(LemurConfig.from_dict(cfg2.to_dict())) == hash(cfg2)


def test_legacy_flat_knobs_deprecated_but_working():
    with pytest.warns(DeprecationWarning, match="ivf_nprobe -> ivf.nprobe"):
        cfg = LemurConfig(d=16, ivf_nprobe=48, sq8=False)
    assert cfg.ivf == IVFBackendConfig(nprobe=48, sq8=False)
    with pytest.warns(DeprecationWarning, match="tp_nprobe"):
        cfg = cfg.replace(tp_nprobe=2)
    assert cfg.token_pruning.nprobe == 2
    assert cfg.ivf.nprobe == 48  # replace() preserved the folded namespace
    with pytest.warns(DeprecationWarning, match="read cfg.ivf.nprobe"):
        assert cfg.ivf_nprobe == 48
    with pytest.raises(AttributeError):
        cfg.no_such_knob


def test_legacy_free_functions_are_facade_shims(retriever, queries):
    """v0 query()/candidates() and the facade produce identical results."""
    from repro.core.index import candidates, query

    q, qm = queries
    r = retriever.with_backend("ivf", key=jax.random.PRNGKey(1))
    s_new, ids_new = r.search(q, qm, SearchParams(k=10,
                                                  backend=IVFSearchParams(nprobe=4)))
    s_old, ids_old = query(r.index, q, qm, k=10, nprobe=4)
    np.testing.assert_array_equal(np.asarray(ids_old), np.asarray(ids_new))
    cand_new = r.candidates(q, qm, SearchParams(k_prime=20, use_ann=False))
    cand_old = candidates(r.index, q, qm, k_prime=20)
    np.testing.assert_array_equal(np.asarray(cand_old), np.asarray(cand_new))


def test_with_backend_shares_reduction(retriever):
    r2 = retriever.with_backend("dessert", key=jax.random.PRNGKey(2))
    assert r2.backend == "dessert" and r2.cfg.anns == "dessert"
    assert r2.index.store is retriever.index.store  # ψ/W never re-trained
    assert retriever.backend == "bruteforce"  # original untouched


def test_backend_params_ride_jit_static(retriever, queries):
    """SearchParams fields must all be hashable (jit-static) types."""
    for p in (SearchParams(), SearchParams(k=3, k_prime=7, use_ann=False),
              SearchParams(backend=TokenPruningSearchParams(nprobe=2))):
        assert isinstance(hash(p), int)
        assert dataclasses.is_dataclass(p) and p.__dataclass_params__.frozen


def test_no_stray_deprecation_warnings_on_new_api(tiny_corpus):
    """The facade itself must never touch the legacy alias path."""
    cfg = LemurConfig(d=16, d_prime=32, m_pretrain=64, n_train=256, n_ols=128,
                      epochs=2, batch_size=64, k=5, k_prime=30, anns="ivf")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        r = LemurRetriever.build(tiny_corpus, cfg, key=jax.random.PRNGKey(0))
        q = jnp.asarray(tiny_corpus.doc_tokens[:4, :4])
        r.search(q, params=SearchParams(k=5))


def test_paged_store_shape_floors_keep_every_doc(tiny_corpus):
    """``from_dense``'s floors widen the slot capacity, the page pool and
    the page table (so several shards can share one shape) and change no
    doc: each gathers back exactly as from the unfloored store.
    ``pages_needed`` is the layout the unfloored store takes."""
    from repro.core import pages

    toks, mask = tiny_corpus.doc_tokens[:50], tiny_corpus.doc_mask[:50]
    W = np.random.default_rng(0).standard_normal((50, 8)).astype(np.float32)
    base, _ = pages.from_dense(W, toks, mask)
    need, pmax = pages.pages_needed(mask)
    assert base.pages_per_doc == pmax
    assert base.n_pages == pages.next_pow2(need)
    wide, _ = pages.from_dense(W, toks, mask, min_capacity=128,
                               min_pages=4 * base.n_pages, min_pmax=pmax + 2)
    assert (wide.capacity, wide.n_pages, wide.pages_per_doc) == (
        128, 4 * base.n_pages, pmax + 2)
    ids = jnp.arange(50)
    t0, m0 = (np.asarray(a) for a in pages.gather_docs(base, ids))
    t1, m1 = (np.asarray(a) for a in pages.gather_docs(wide, ids))
    width = m0.shape[1]
    np.testing.assert_array_equal(m1[:, :width], m0)
    assert not m1[:, width:].any()
    np.testing.assert_array_equal(t1[:, :width], t0)
    np.testing.assert_array_equal(np.asarray(wide.W)[:50], W)
    assert int(np.asarray(wide.alive).sum()) == 50
