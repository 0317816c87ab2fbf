"""LemurRetriever: the stable Retriever API v1 facade.

One object owns the full lifecycle of a LEMUR index (Fig. 1):

    r = LemurRetriever.build(corpus, cfg, key=jax.random.PRNGKey(0))
    scores, ids = r.search(q_tokens, q_mask, SearchParams(k=10))
    r.add(new_doc_tokens, new_doc_mask)          # incremental growth (§4.3)
    r.delete(r.last_added_ids)                   # tombstone + page free
    r.update([3, 7], new_tokens, new_mask)       # delete+add, ONE version
    r2 = r.with_backend("muvera")                # same reduction, new stage
    sr = r.shard(mesh)                           # multi-device serving
    mr = LemurRetriever.build(corpus, cfg, mesh=mesh)  # corpus > one chip
    r.save("my_index/"); r = LemurRetriever.load("my_index/")

Design points:

* **Paged corpus, surviving compile caches.**  The corpus lives in a
  :class:`repro.core.pages.PagedStore` (fixed-size token pages + per-doc
  page table + tombstones; stable slot ids).  Compiled query fns take the
  WHOLE mutable state (ψ, stats, store, backend state) as jit ARGUMENTS —
  never baked in as closure constants — so a mutation that fits the
  pre-grown pool changes no shapes and issues ZERO new traces; only a
  power-of-two capacity-bucket growth retraces.  ``_compiled`` is never
  cleared on mutation.

* **Build-time vs query-time split.**  ``LemurConfig`` (with its per-backend
  namespaces) is fixed at ``build()``; every query-time knob travels in a
  frozen :class:`SearchParams`.  ``search()`` resolves the params against
  the config once, then caches exactly one ``jax.jit``-compiled query fn
  per (backend, resolved params) — jit itself specializes per batch shape,
  so compilation count is one per (backend, params, batch-shape), observable
  via :meth:`trace_count`.

* **Deterministic growth.**  ``build()`` retains the OLS solver state
  (Gram factor + the n' training tokens), so ``add()`` fits new W rows with
  the exact build-time solver.  When the solver is gone (e.g. a legacy
  index wrapped directly), the corpus-sampling fallback takes an explicit
  ``seed`` instead of the v0 hidden ``default_rng(0)``.

* **Observability.**  :meth:`trace_count` counts Python traces of the
  query fns; :func:`xla_compile_count` counts what XLA did for them and
  every other jitted function of the process (backend compiles and
  persistent-cache loads), so a compile no retrace announced is seen too.
  ``search`` is one ``lemur.search`` host span in a profiler trace
  (``jax.profiler.TraceAnnotation``; nothing is recorded without an active
  trace), and the device program names its two stages with
  ``jax.named_scope``: every op of the first stage under ``first_stage/``,
  every op of the rerank and the final top-k under ``rerank/``.

* **Persistence.**  ``save()``/``load()`` use ``checkpoint/manager.py``'s
  atomic manifest+shards format: cfg, ψ, W, doc tokens, the backend name
  and its opaque packed state (plus the OLS tokens, so ``add()`` stays
  deterministic after a reload).  Round-trip reproduces search ids
  bit-identically.

The v0 free functions (``core.index.build_index`` / ``attach_backend`` /
``add_docs`` / ``query`` / ``candidates``) are thin shims over this module.
"""
from __future__ import annotations

import bisect
import json
import pathlib
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns import ivf as _ivf
from repro.anns import registry
from repro.anns.base import CorpusView, QueryBatch, pad_topk
from repro.anns.bruteforce import mips_topk
from repro.checkpoint import manager as ckpt
from repro.core import indexer, maxsim, pages
from repro.core.config import LemurConfig
from repro.kernels import ops
from repro.core.index import LemurIndex
from repro.core.model import TargetStats, pool_queries, train_phi
from repro.retriever.params import SearchParams

FORMAT = "lemur-retriever-v1"


class CorruptIndexError(ValueError):
    """A rebuilt index failed install-time validation in
    :meth:`LemurRetriever.install_refresh` — the last-good snapshot is left
    fully installed.  Serving layers treat this as ``SwapAborted``, never as
    a torn state.  ``preserves_replica_state`` tells the fleet write barrier
    this is a typed rejection with the replica intact, not a replica
    failure — no quarantine."""

    preserves_replica_state = True


# --------------------------------------------------------------------------
# process-wide XLA compile accounting
# --------------------------------------------------------------------------

# jax brackets every backend compile request in this duration event, whether
# XLA compiles or the persistent cache hands back the executable (the cache
# hit records an event of its own inside it, which would count it twice)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles_lock = threading.Lock()
_compile_times: list[float] = []   # perf_counter at each request's end


def _on_backend_compile(event: str, duration_secs: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        with _compiles_lock:
            _compile_times.append(time.perf_counter())


jax.monitoring.register_event_duration_secs_listener(_on_backend_compile)


def xla_compile_count(since: float | None = None) -> int:
    """XLA executables this process has made ready: backend compiles and
    persistent-cache loads alike.  Counts every jitted function, not only
    the query fns (:meth:`LemurRetriever.trace_count` counts Python
    traces, which a compile need not follow).  ``since`` (a
    ``time.perf_counter()`` reading) counts only those finished after it."""
    with _compiles_lock:
        if since is None:
            return len(_compile_times)
        return len(_compile_times) - bisect.bisect_right(_compile_times,
                                                         since)


# --------------------------------------------------------------------------
# pure query pipeline (jit-able; params must be fully resolved)
# --------------------------------------------------------------------------

def first_stage(index: LemurIndex, q_tokens, q_mask, params: SearchParams):
    """Pool queries and run the selected backend (or the exact latent scan).

    One-launch routing happens HERE, not in the backend protocol: the fused
    first stage consumes the raw query tokens plus ψ (the projection runs
    inside the kernel), while ``be.search`` only ever sees the pooled
    latent.  The candidate ids are bit-identical either way (fp32).

    Every path ends in :func:`pages.mask_dead`: first-stage backends are
    never rebuilt on ``delete()``, so their candidate lists can contain
    tombstoned slots — the mask turns those into ``-1`` pads, the single
    choke point that guarantees a deleted doc never surfaces."""
    store = index.store
    if (params.use_ann and index.backend == "ivf"
            and getattr(params.backend, "use_one_launch", False)):
        bp = params.backend
        nprobe = min(int(bp.nprobe or min(32, index.ann.nlist)),
                     index.ann.nlist)
        _, cand = _ivf.search_ivf_one_launch(
            index.ann, index.psi, q_tokens, q_mask, nprobe, params.k_prime)
        return pages.mask_dead(store, cand)
    psi_q = pool_queries(index.psi, q_tokens, q_mask)  # (B, d')
    if not params.use_ann:
        # exact latent scan over the store's full slot CAPACITY — dead and
        # unallocated slots are masked by the (traced) alive bits, so the
        # scan shape is jit-static across mutations
        kk = min(params.k_prime, store.W.shape[0])
        if params.use_one_launch:
            # fused dense scan + in-kernel top-k' — never materializes the
            # (B, C) score matrix; ids match the blocked mips_topk bit for bit
            top, cand = ops.mips_topk_fused(psi_q, store.W, None, kk,
                                            valid=store.alive)
        else:
            top, cand = mips_topk(psi_q, store.W, kk, valid=store.alive)
        cand = pad_topk(top, cand, params.k_prime)[1]
        return pages.mask_dead(store, cand)
    be = registry.get_backend(index.backend)
    _, cand = be.search(index.ann, QueryBatch(psi_q, q_tokens, q_mask),
                        params.k_prime, params.backend)
    return pages.mask_dead(store, cand)


def search_pipeline(index: LemurIndex, q_tokens, q_mask, params: SearchParams):
    """pool -> first-stage candidates -> exact MaxSim rerank -> top-k.

    ``-1``-padded first-stage rows (including tombstoned docs masked by
    ``first_stage``) score NEG inside the rerank — pads can never surface
    as results.  ``params.use_fused_gather`` (the resolved default) sends
    the rerank through the page-fed kernel path
    (``kernels.ops.fused_rerank_paged``: each candidate's token pages are
    DMA'd straight into VMEM on TPU, page ids from SMEM, instead of
    materializing the ``(B, k', Tm, d)`` gather in HBM); ``False`` keeps
    the legacy materialize-from-pages + ``maxsim.rerank_gathered`` path
    benchmarkable — both return bit-identical ids on fp32."""
    # the two stages are named scopes: each device op's op_name (and the
    # profiler's view of it) says which stage it belongs to.  A scope must
    # hold the whole kernel call: the while a lax.map lowers to takes the
    # scope of the map's caller, not of its body
    with jax.named_scope("first_stage"):
        cand = first_stage(index, q_tokens, q_mask, params)
    store = index.store
    with jax.named_scope("rerank"):
        if store.residual and params.use_residual and params.use_fused_gather:
            # compressed tier, fused path: candidate pages are DMA'd as
            # centroid ids + packed residual codes and dequantized INSIDE
            # the rerank kernel — fp32 token pages never exist
            return ops.fused_rerank_paged_res(
                q_tokens, q_mask, cand, store.cent_pages, store.code_pages,
                store.page_table, store.n_tokens, store.codec.centroids,
                store.codec.values, params.k)
        if params.use_fused_gather and not store.residual:
            return ops.fused_rerank_paged(q_tokens, q_mask, cand,
                                          store.tok_pages, store.page_table,
                                          store.n_tokens, params.k)
        # legacy HBM gather; on the compressed tier gather_docs
        # residual-decodes on the fly, so this is also the
        # use_residual=False decoded-view path
        toks, tmask = pages.gather_docs(store, cand)
        return maxsim.rerank_gathered(q_tokens, q_mask, cand, toks, tmask,
                                      params.k)


def launch_plan(resolved: SearchParams) -> dict[str, int]:
    """Static per-search kernel-launch breakdown for a RESOLVED params.

    The legacy first stage is 3 corpus-scale launches before the rerank
    (ψ projection → scan → top-k'); the one-launch path collapses them into
    a single fused kernel.  This is the accounting BENCH rows and
    ``examples/serve_batched.py`` print, and what :meth:`LemurRetriever.
    launches` asserts: the one-launch plan has exactly 1 pre-rerank launch.
    """
    one = bool(getattr(resolved.backend, "use_one_launch", False)
               if resolved.use_ann else resolved.use_one_launch)
    if one:
        plan = {"one_launch": 1, "rerank": 1}
    else:
        plan = {"projection": 1, "scan": 1, "topk": 1, "rerank": 1}
    pre = sum(v for name, v in plan.items() if name != "rerank")
    assert not one or pre == 1, plan   # the one-launch contract
    return plan


# --------------------------------------------------------------------------
# the facade
# --------------------------------------------------------------------------

class LemurRetriever:
    """Stable facade over a :class:`LemurIndex` (see module docstring).

    Construct via :meth:`build` / :meth:`load`, or wrap an existing
    ``LemurIndex`` directly (``LemurRetriever(index)``)."""

    def __init__(self, index: LemurIndex, *, solver_state: dict | None = None,
                 x_ols: jax.Array | None = None):
        self._index = index
        self._solver = solver_state
        self._x_ols = x_ols if x_ols is not None else (
            solver_state["x_ols"] if solver_state else None)
        self._compiled: dict[tuple, Any] = {}
        self._trace_counts: dict[tuple, int] = {}
        self._trace_shapes: dict[tuple, int] = {}
        self._resolve_memo: dict[SearchParams | None, SearchParams] = {}
        self._version = 0
        # page allocator: lazily derived from the store (deterministic —
        # snapshots/checkpoints never persist it), then threaded through
        # mutations.  Byte counters feed the add-amortization bench.
        self._free_pages: list[int] | None = None
        self._last_added_ids = np.empty((0,), np.int32)
        self._last_mutation_bytes = 0
        self._bytes_moved = 0

    # -- introspection ------------------------------------------------------

    @property
    def index(self) -> LemurIndex:
        return self._index

    @property
    def cfg(self) -> LemurConfig:
        return self._index.cfg

    @property
    def backend(self) -> str:
        return self._index.backend

    @property
    def m(self) -> int:
        return self._index.m

    @property
    def n_alive(self) -> int:
        """Live (non-tombstoned) docs; ``m`` stays the slot high-water mark
        because external ids are stable slot indices."""
        return self._index.n_alive

    @property
    def version(self) -> int:
        """Snapshot version: bumped by every :meth:`add` / :meth:`delete` /
        :meth:`update` (update bumps ONCE).  Serving layers
        (``repro.serving``) use it to tell which corpus snapshot answered a
        request."""
        return self._version

    @property
    def last_added_ids(self) -> np.ndarray:
        """Slot ids allocated by the most recent :meth:`add`/:meth:`update`."""
        return self._last_added_ids

    @property
    def last_mutation_bytes(self) -> int:
        """Logical bytes the most recent mutation wrote (pages + touched
        table/W rows + any bucket-growth copy) — O(doc) when the pool has
        capacity; the add-amortization bench gates on this."""
        return self._last_mutation_bytes

    @property
    def bytes_moved(self) -> int:
        """Cumulative logical mutation bytes since construction."""
        return self._bytes_moved

    def snapshot(self) -> LemurIndex:
        """The current immutable index snapshot.  ``add()`` swaps the whole
        ``LemurIndex`` atomically (it is a NamedTuple — existing references
        keep serving the old corpus), which is what makes add-while-serving
        safe for readers holding a snapshot."""
        return self._index

    def __repr__(self) -> str:
        return (f"LemurRetriever(m={self.m}, d_prime={self.cfg.d_prime}, "
                f"backend={self.backend!r})")

    # -- lifecycle ----------------------------------------------------------

    @classmethod
    def build(cls, corpus, cfg: LemurConfig | None = None, *, key=None,
              mesh=None, x_train: np.ndarray | None = None,
              verbose: bool = False):
        """Full offline build: training-token selection (§4.2) -> ψ
        pre-training against m' sampled docs (§4.3) -> OLS output layer over
        the full corpus (eq. 7) -> first-stage index via the backend
        registry.  ``corpus`` is any object with doc_tokens/doc_mask arrays
        (e.g. ``data.synthetic.MultiVectorCorpus``).

        ``mesh`` builds a corpus larger than one chip: the docs are split
        into one contiguous shard per device of the mesh, ψ and the OLS
        solver are trained once as above, and each shard's W rows, IVF
        lists and paged store are built on its own chip.  Returns a
        :class:`~repro.retriever.sharded.MeshLemurRetriever`, which serves
        each query from every shard with the one-chip pipeline and merges
        their top-k.  Without a mesh, ``cfg.build_per_shard`` stops after
        the OLS solver and returns a :class:`~repro.retriever.sharded.
        TrainedLemur`, whose ``shard(mesh)`` builds the shards the same
        way."""
        cfg = cfg or LemurConfig()
        per_shard = mesh is not None or cfg.build_per_shard
        if per_shard:
            from repro.retriever import sharded

            sharded.check_mesh_config(cfg)
        if key is None:
            key = jax.random.PRNGKey(0)
        t0 = time.time()
        keys = jax.random.split(key, 4)
        # the dense padded corpus stays on the HOST: every stage below moves
        # only the block it works on to the device, so a corpus the size of
        # the device memory can be built into a paged store that fits it
        doc_tokens = np.asarray(corpus.doc_tokens)
        doc_mask = np.asarray(corpus.doc_mask)
        m = doc_tokens.shape[0]

        # 1. training tokens (§4.2)
        if x_train is None:
            x_train = indexer.make_training_tokens(corpus, cfg, seed=0)
        x_train = jnp.asarray(x_train)

        # 2. ψ pre-training against m' sampled documents (§4.3)
        m_pre = min(cfg.m_pretrain, m)
        pre_idx = np.asarray(jax.random.choice(keys[0], m, (m_pre,),
                                               replace=False))
        g_pre = maxsim.token_maxsim(x_train, doc_tokens[pre_idx], doc_mask[pre_idx])
        phi, stats, losses = train_phi(keys[1], x_train, g_pre, cfg)
        # the (n, m') pre-training targets are done with: 3.3 GB at the
        # paper's n=100k, m'=8192, which the paged store needs next
        del g_pre
        if verbose:
            print(f"[build] psi pretrain done ({time.time()-t0:.1f}s, "
                  f"loss {losses[-1]:.4f})")

        # 3. OLS output layer over the full corpus (eq. 7); the solver state
        # (Gram factor + tokens) is retained so add() reuses it verbatim
        n_ols = min(cfg.n_ols, x_train.shape[0])
        x_ols = x_train[jax.random.choice(keys[2], x_train.shape[0], (n_ols,),
                                          replace=False)]
        solver = indexer.ols_solver_state(phi["psi"], x_ols, cfg)
        if per_shard:
            trained = sharded.TrainedLemur(cfg, keys, phi["psi"], stats,
                                           solver, doc_tokens, doc_mask,
                                           verbose=verbose)
            return trained if mesh is None else trained.shard(mesh)
        W = indexer.fit_output_layer_ols(phi["psi"], x_ols, doc_tokens,
                                         doc_mask, cfg, stats,
                                         solver_state=solver)
        if verbose:
            print(f"[build] OLS W ({m} docs) done ({time.time()-t0:.1f}s)")

        # 4. first-stage index via the backend registry
        backend = registry.canonical(cfg.anns)
        be = registry.get_backend(backend)
        ann = be.build(keys[3], CorpusView(W, doc_tokens, doc_mask),
                       cfg.backend_config(backend))
        if verbose:
            print(f"[build] {backend} index complete ({time.time()-t0:.1f}s)")

        # 5. corpus store — optionally pooled to a constant per-doc token
        # budget and/or residual-encoded (cfg.residual).  ψ/OLS/backend above
        # always train on the RAW tokens; pooling/compression only change
        # what the store keeps for the exact-MaxSim rerank.
        st_tokens, st_mask, codec = doc_tokens, doc_mask, None
        rcfg = cfg.residual
        if int(rcfg.token_budget) > 0:
            st_tokens, st_mask = pages.pool_tokens(doc_tokens, doc_mask,
                                                   int(rcfg.token_budget))
            st_tokens = jnp.asarray(st_tokens)
            st_mask = jnp.asarray(st_mask)
        if rcfg.enabled:
            from repro.anns import quantization as _q

            flat = np.asarray(st_tokens)[np.asarray(st_mask)]
            # fold_in (not a wider split) keeps keys[0..3] — and thus ψ/W —
            # bit-identical to a build without the compressed tier
            codec = _q.train_residual_codec(
                jax.random.fold_in(keys[3], 1), jnp.asarray(flat),
                bits=int(rcfg.bits), ncent=int(rcfg.ncent),
                iters=int(rcfg.kmeans_iters), sample=int(rcfg.train_sample))
            if verbose:
                print(f"[build] residual codec trained "
                      f"({time.time()-t0:.1f}s)")
        # the store re-lays W out on the host; drop the device copy first
        W = np.asarray(W)
        index = LemurIndex.from_dense(cfg, phi["psi"], stats, W, st_tokens,
                                      st_mask, backend, ann, codec=codec)
        return cls(index, solver_state=solver)

    def with_backend(self, backend: str, *, key=None,
                     cfg: LemurConfig | None = None) -> "LemurRetriever":
        """A new retriever over the SAME trained reduction (ψ/W/doc tokens
        shared, never re-trained) with a different first-stage backend —
        what benchmarks use to sweep backends over one build."""
        idx = self._index
        cfg = cfg or idx.cfg
        backend = registry.canonical(backend)
        be = registry.get_backend(backend)
        if key is None:
            key = jax.random.PRNGKey(0)
        view = CorpusView(idx.W, idx.doc_tokens, idx.doc_mask)
        ann = be.build(key, view, cfg.backend_config(backend))
        index = idx._replace(cfg=cfg.replace(anns=backend), backend=backend,
                             ann=ann)
        return LemurRetriever(index, solver_state=self._solver,
                              x_ols=self._x_ols)

    def add(self, doc_tokens, doc_mask, *, seed: int = 0) -> "LemurRetriever":
        """Incremental growth: fit new W rows with the frozen-ψ OLS solver,
        push them into the first-stage backend via its ``add`` hook — ψ and
        existing rows are never touched (§4.3) — and allocate token PAGES
        for the new docs (slots ``[m, m+n)``: stable ids).  Reuses the
        build-time solver state when available (also after ``load()``); the
        corpus-sampling fallback is seeded by the explicit ``seed``.

        Compiled query fns are NOT invalidated: they take the store/backend
        state as jit arguments, so an add that fits the pre-grown pool
        issues zero new traces (only a power-of-two capacity-bucket growth
        retraces).  Mutates this retriever and returns it; the new slot ids
        are in :attr:`last_added_ids`."""
        self._mutate_add(doc_tokens, doc_mask, seed)
        self._version += 1
        return self

    def delete(self, doc_ids) -> "LemurRetriever":
        """Tombstone docs and return their pages to the free list.  Ids of
        surviving docs are unchanged (slots are never reused); the
        first-stage backends are NOT rebuilt — their stale candidates are
        masked out after every first stage (``pages.mask_dead``), so a
        deleted doc can never surface.  Raises ``ValueError`` on unknown or
        already-deleted ids.  Mutates this retriever and returns it."""
        self._mutate_delete(doc_ids)
        self._version += 1
        return self

    def update(self, doc_ids, doc_tokens, doc_mask, *,
               seed: int = 0) -> np.ndarray:
        """Replace docs: delete ``doc_ids`` + add the new contents under ONE
        snapshot version bump.  The replacement docs get NEW slot ids
        (returned; also in :attr:`last_added_ids`) — an updated doc is a
        new document as far as stable external ids are concerned."""
        self._mutate_delete(doc_ids)
        ids = self._mutate_add(doc_tokens, doc_mask, seed)
        self._version += 1
        return ids

    def _free(self) -> list[int]:
        if self._free_pages is None:
            self._free_pages = pages.free_list(self._index.store)
        return self._free_pages

    def _mutate_add(self, doc_tokens, doc_mask, seed: int) -> np.ndarray:
        idx = self._index
        doc_tokens = jnp.asarray(doc_tokens)
        doc_mask = jnp.asarray(doc_mask)
        solver = self._ensure_solver(seed)
        w_new = indexer.fit_docs(solver, doc_tokens, doc_mask, idx.stats)
        be = registry.get_backend(idx.backend)
        ann = be.add(idx.ann, CorpusView(w_new, doc_tokens, doc_mask))
        # mirror build(): W/backend see raw tokens, the store keeps the
        # pooled view (add_docs residual-encodes via store.codec itself)
        budget = int(idx.cfg.residual.token_budget)
        if budget > 0:
            doc_tokens, doc_mask = pages.pool_tokens(doc_tokens, doc_mask,
                                                     budget)
        store, free, ids, moved = pages.add_docs(
            idx.store, self._free(), w_new, doc_tokens, doc_mask)
        self._free_pages = free
        self._index = idx._replace(store=store, ann=ann)
        self._last_added_ids = ids
        self._last_mutation_bytes = moved
        self._bytes_moved += moved
        return ids

    def _mutate_delete(self, doc_ids) -> None:
        idx = self._index
        store, free, moved = pages.delete_docs(idx.store, self._free(),
                                               doc_ids)
        self._free_pages = free
        self._index = idx._replace(store=store)
        self._last_mutation_bytes = moved
        self._bytes_moved += moved

    def clone(self) -> "LemurRetriever":
        """An independent replica over the SAME built state — zero re-train,
        zero re-build.  The immutable ``LemurIndex`` and the OLS solver state
        are shared (both are read-only under search; ``add()`` swaps the
        index atomically per-replica), compile caches are private, and
        ``version`` is carried over so a fleet can stamp every replica to a
        common snapshot numbering.  Because ``fit_docs`` is deterministic
        given the shared solver, fanning the same ``add()`` out to every
        clone produces bit-identical W rows — the invariant the fleet write
        barrier checks."""
        r = LemurRetriever(self._index, solver_state=self._solver,
                           x_ols=self._x_ols)
        r._version = self._version
        return r

    def install_refresh(self, refresh) -> "LemurRetriever":
        """Warm-swap a background rebuild (``lifecycle.build_refresh``) in.

        Three stages, atomic from any reader's point of view:

        1. **validate** — backend match, W shape/finiteness, solver keys,
           and a probe search through the rebuilt first stage (latent
           backends) checking candidate ids stay in ``[0, m0)``.  Any
           failure raises :class:`CorruptIndexError` BEFORE anything is
           touched: the last-good snapshot keeps serving.
        2. **catch up** — docs added since the rebuild snapshotted
           (slots ``[m0, m_now)``) get W rows fit with the NEW solver and
           are appended to the rebuilt backend in slot order (dead slots as
           zero rows, preserving the slot-numbering invariant); rows the
           rebuild covered but that were deleted meanwhile are re-zeroed.
        3. **swap** — one atomic ``LemurIndex`` replace + ONE version bump.
           Readers holding the old snapshot keep it; compiled query fns
           survive (state is a jit argument — only a shape change retraces).

        Deterministic given the same ``RefreshResult`` and mutation history,
        so fanning one result out to every fleet replica lands the same
        post-swap snapshot version with bit-identical search results — the
        invariant the fleet write barrier checks.  Mutates this retriever
        and returns it; meant to run inside a server mutation barrier
        (``RetrieverServer.apply`` / ``Router.apply``)."""
        idx = self._index

        # -- 1. validate (raise BEFORE touching anything) ------------------
        def bad(msg: str) -> CorruptIndexError:
            return CorruptIndexError(f"install_refresh rejected: {msg}")

        if getattr(refresh, "backend", None) != idx.backend:
            raise bad(f"backend {getattr(refresh, 'backend', None)!r} != "
                      f"{idx.backend!r}")
        m_now = self.m
        m0 = int(refresh.m0)
        if not 0 < m0 <= m_now:
            raise bad(f"m0={m0} outside (0, {m_now}]")
        W_new = jnp.asarray(refresh.W)
        if W_new.shape != (m0, idx.cfg.d_prime):
            raise bad(f"W shape {W_new.shape} != {(m0, idx.cfg.d_prime)}")
        if not bool(jnp.isfinite(W_new).all()):
            raise bad("non-finite values in refit W")
        solver = refresh.solver
        if not (isinstance(solver, dict)
                and {"chol", "feats", "x_ols"} <= set(solver)):
            raise bad("solver state missing chol/feats/x_ols")
        # chol is a cho_factor (factor, lower) pair — validate the factor
        if not bool(jnp.isfinite(jnp.asarray(solver["chol"][0])).all()):
            raise bad("non-finite OLS Gram factor")
        be = registry.get_backend(idx.backend)
        if be.representation == "latent":
            try:
                _, cand = be.search(
                    refresh.ann, QueryBatch(W_new[:1], None, None),
                    min(8, m0),
                    be.default_params(idx.cfg.backend_config(idx.backend)))
                cand = np.asarray(cand)
            except Exception as e:
                raise bad(f"probe search through rebuilt backend failed: "
                          f"{e}") from e
            if cand.size == 0 or (cand >= m0).any() or (cand < -1).any():
                raise bad("rebuilt backend emits out-of-range candidate ids")

        # -- 2. catch up slots [m0, m_now) with the NEW solver -------------
        alive_now = np.asarray(idx.store.alive)
        W2 = idx.store.W.at[:m0].set(
            jnp.where(jnp.asarray(alive_now[:m0])[:, None], W_new, 0.0))
        ann = refresh.ann
        caught = 0
        if m_now > m0:
            catch = jnp.arange(m0, m_now, dtype=jnp.int32)
            toks_c, mask_c = pages.gather_docs(idx.store, catch)
            alive_c = np.flatnonzero(alive_now[m0:m_now])
            w_c = jnp.zeros((m_now - m0, idx.cfg.d_prime),
                            idx.store.W.dtype)
            if alive_c.size:
                sub = jnp.asarray(alive_c.astype(np.int32))
                w_fit = indexer.fit_docs(solver, toks_c[sub], mask_c[sub],
                                         idx.stats)
                w_c = w_c.at[sub].set(w_fit)
                caught = int(alive_c.size)
            # append ALL slots in order (dead as zero rows): backend
            # numbering must equal slot numbering, mask_dead does the rest
            ann = be.add(ann, CorpusView(w_c, toks_c, mask_c))
            W2 = W2.at[m0:m_now].set(w_c)

        # -- 3. atomic swap + ONE version bump -----------------------------
        self._index = idx._replace(store=idx.store._replace(W=W2), ann=ann)
        self._solver = solver
        self._x_ols = solver["x_ols"]
        self._version += 1
        self._last_refresh_caught_up = caught
        return self

    def shard(self, mesh, *, sq8: bool | None = None,
              k_prime_local: int | None = None):
        """Multi-device serving: a :class:`~repro.retriever.sharded.
        ShardedLemurRetriever` over this built retriever, with the corpus
        block-sharded across every axis of ``mesh`` (Fig. 1 at pod scale —
        each shard runs latent scan → local top-k' → local exact rerank,
        only (k, score) pairs cross the wire).

        ``sq8`` selects the SQ8 code path for the resident corpus (default:
        the build config's ``cfg.ivf.sq8``); ``k_prime_local`` overrides the
        per-shard candidate budget (default: a 4x oversample of k'/n_shards,
        see ``repro.dist.serve.default_k_prime_local``)."""
        from repro.retriever.sharded import ShardedLemurRetriever

        return ShardedLemurRetriever(self, mesh, sq8=sq8,
                                     k_prime_local=k_prime_local)

    def _ensure_solver(self, seed: int) -> dict:
        if self._solver is not None:
            return self._solver
        idx = self._index
        if self._x_ols is not None:
            # persisted/handed-down OLS tokens: rebuild the Gram factor
            # deterministically (bit-exact W scales across save/load)
            self._solver = indexer.ols_solver_state(idx.psi, self._x_ols, idx.cfg)
            return self._solver
        # legacy fallback: resample OLS tokens from the stored corpus
        # ("corpus" strategy) — seeded explicitly, not a hidden rng(0)
        flat = np.asarray(idx.doc_tokens)[np.asarray(idx.doc_mask)]
        pick = np.random.default_rng(seed).integers(
            0, flat.shape[0], size=min(idx.cfg.n_ols, flat.shape[0]))
        self._solver = indexer.ols_solver_state(
            idx.psi, jnp.asarray(flat[pick]), idx.cfg)
        return self._solver

    # -- query --------------------------------------------------------------

    def resolve(self, params: SearchParams | None = None) -> SearchParams:
        """Fill a (possibly partial) SearchParams from the build config.
        Memoized — cfg and backend are fixed for this retriever's lifetime,
        so repeated serving calls skip the per-call resolution work."""
        resolved = self._resolve_memo.get(params)
        if resolved is None:
            resolved = (params or SearchParams()).resolve(self.cfg, self.backend)
            self._resolve_memo[params] = resolved
        return resolved

    def search(self, q_tokens, q_mask=None, params: SearchParams | None = None):
        """q_tokens: (B, Tq, d) -> (scores (B, k), doc_ids (B, k)).

        Runs the compiled pool -> candidates -> exact-rerank pipeline for
        the resolved params (one XLA graph; compiled once per params and
        batch shape).  Returns once the program is dispatched, before the
        device finishes it; the ``lemur.search`` host span covers the
        query's host-to-device copy, ``resolve`` and the dispatch."""
        with jax.profiler.TraceAnnotation("lemur.search"):
            q_tokens = jnp.asarray(q_tokens)
            if q_mask is None:
                q_mask = jnp.ones(q_tokens.shape[:2], bool)
            return self._compiled_fn(self.resolve(params))(q_tokens, q_mask)

    def candidates(self, q_tokens, q_mask=None,
                   params: SearchParams | None = None):
        """First-stage candidate ids only (recall@k' ablations, Fig. 2)."""
        q_tokens = jnp.asarray(q_tokens)
        if q_mask is None:
            q_mask = jnp.ones(q_tokens.shape[:2], bool)
        return first_stage(self._index, q_tokens, q_mask, self.resolve(params))

    def _compiled_fn(self, resolved: SearchParams):
        key = (self.backend, resolved)
        run = self._compiled.get(key)
        if run is None:
            counts = self._trace_counts
            shapes = self._trace_shapes
            cfg, backend = self.cfg, self.backend

            def pipeline(psi, stats, store, ann, q, qm):
                # trace-time only: bucket-aware compile accounting — each
                # (backend, params, q-shape) cache entry is observable, so
                # serving layers can assert their shape-ladder compile bound
                counts[key] = counts.get(key, 0) + 1
                skey = key + (tuple(q.shape),)
                shapes[skey] = shapes.get(skey, 0) + 1
                idx = LemurIndex(cfg, psi, stats, store, backend, ann)
                return search_pipeline(idx, q, qm, resolved)

            jitted = jax.jit(pipeline)
            use_ann = bool(resolved.use_ann)

            # the WHOLE mutable state rides in as jit arguments — mutations
            # that keep shapes (pool has capacity) hit the compiled program
            # with zero retraces; only a pow2 bucket growth traces again.
            # Exact-scan params drop the (unused) backend state from the
            # arguments so a backend whose state grows per add (e.g.
            # bruteforce's concatenated W view) cannot retrace them.
            def run(q, qm):
                i = self._index
                return jitted(i.psi, i.stats, i.store,
                              i.ann if use_ann else None, q, qm)

            self._compiled[key] = run
        return run

    def trace_count(self, params: SearchParams | None = None) -> int:
        """jit traces so far: for one resolved SearchParams, or in total.
        The API contract is one trace per (backend, params, batch-shape).
        What XLA compiled, or loaded from the persistent cache, for them is
        :func:`xla_compile_count`."""
        if params is None:
            return sum(self._trace_counts.values())
        return self._trace_counts.get((self.backend, self.resolve(params)), 0)

    def launches(self, params: SearchParams | None = None) -> dict[str, int]:
        """Per-search launch breakdown for ``params`` (resolved first) —
        see :func:`launch_plan`.  Pairs with :meth:`trace_count`: traces say
        how many XLA programs exist, this says how many corpus-scale kernel
        launches each search issues."""
        return launch_plan(self.resolve(params))

    def trace_shapes(self) -> dict[tuple, int]:
        """Per-shape compile accounting: ``{(batch, Tq[, d]): n_traces}``
        aggregated over params.  The online server's shape-bucket ladder
        bounds ``len(trace_shapes())`` per resolved params no matter how
        request shapes churn — asserted in tests/test_serving_runtime.py."""
        out: dict[tuple, int] = {}
        for (*_, shape), n in self._trace_shapes.items():
            out[shape] = out.get(shape, 0) + n
        return out

    # -- persistence --------------------------------------------------------

    def save(self, directory) -> pathlib.Path:
        """Persist everything needed to serve (and grow) this retriever:
        cfg, ψ, target stats, the PAGED store (token pages, page table,
        token counts, W, alive tombstones, doc count), the backend name +
        its opaque packed state, and the OLS training tokens when available.
        The ``alive`` mask is load-bearing: tombstoned slots keep zeroed W
        rows, and without it they would resurface as zero-score docs after
        a reload.  The page free list is NOT persisted — it is derived
        deterministically from the page table on first mutation.
        Uses the checkpoint manager's atomic manifest+shards layout."""
        idx = self._index
        st = idx.store
        be = registry.get_backend(idx.backend)
        ann_arrays, ann_meta = be.pack_state(idx.ann)
        tree = {
            "psi": idx.psi,
            "stats": {"mean": idx.stats.mean, "std": idx.stats.std},
            "pages": {
                "tok_pages": st.tok_pages,
                "page_table": st.page_table,
                "n_tokens": st.n_tokens,
                "W": st.W,
                "alive": st.alive,
                "n_docs": st.n_docs,
            },
            "ann": dict(ann_arrays),
        }
        if st.codec is not None:
            # compressed tier: id/code pools + the trained codec tables
            # (cuts included so add() keeps encoding after a reload)
            tree["pages"]["cent_pages"] = st.cent_pages
            tree["pages"]["code_pages"] = st.code_pages
            tree["codec"] = {"centroids": st.codec.centroids,
                             "cuts": st.codec.cuts,
                             "values": st.codec.values}
        if self._x_ols is not None:
            tree["solver"] = {"x_ols": self._x_ols}
        extra = {"format": FORMAT, "cfg": idx.cfg.to_dict(),
                 "backend": idx.backend, "ann_meta": ann_meta}
        return ckpt.save(directory, 0, tree, extra=extra)

    @classmethod
    def load(cls, directory, *, step: int | None = None) -> "LemurRetriever":
        """Inverse of :meth:`save`; search ids reproduce bit-identically."""
        directory = pathlib.Path(directory)
        if step is None:
            step = ckpt.latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no committed retriever checkpoint under {directory}")
        manifest = json.loads(
            (directory / f"step_{step:08d}" / "manifest.json").read_text())
        extra = manifest.get("extra", {})
        if extra.get("format") != FORMAT:
            raise ValueError(
                f"{directory} is not a {FORMAT} checkpoint "
                f"(format={extra.get('format')!r})")
        target = _tree_from_manifest(manifest["leaves"])
        tree, _ = ckpt.restore(directory, target, step=step)
        cfg = LemurConfig.from_dict(extra["cfg"])
        backend = extra["backend"]
        be = registry.get_backend(backend)
        ann = be.unpack_state(tree["ann"], extra.get("ann_meta", {}))
        stats = TargetStats(tree["stats"]["mean"], tree["stats"]["std"])
        if "pages" in tree:
            p = tree["pages"]
            codec = None
            if "codec" in tree:
                from repro.anns.quantization import ResidualCodec

                c = tree["codec"]
                codec = ResidualCodec(centroids=c["centroids"],
                                      cuts=c["cuts"], values=c["values"])
            store = pages.PagedStore(
                p["tok_pages"], p["page_table"], p["n_tokens"], p["W"],
                jnp.asarray(p["alive"], bool),
                jnp.asarray(p["n_docs"], jnp.int32),
                cent_pages=p.get("cent_pages"),
                code_pages=p.get("code_pages"), codec=codec)
            index = LemurIndex(cfg, tree["psi"], stats, store, backend, ann)
        else:
            # legacy dense checkpoint (pre-paged format): migrate on load
            index = LemurIndex.from_dense(cfg, tree["psi"], stats, tree["W"],
                                          tree["doc_tokens"],
                                          tree["doc_mask"], backend, ann)
        x_ols = tree.get("solver", {}).get("x_ols")
        return cls(index, x_ols=x_ols)


def _tree_from_manifest(leaves: dict[str, dict]) -> dict:
    """Rebuild the (pure nested-dict) save tree's structure from manifest
    leaf names, with ShapeDtypeStruct leaves (no allocation) for restore."""
    root: dict = {}
    for name, spec in leaves.items():
        parts = name.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jax.ShapeDtypeStruct(
            tuple(spec["shape"]), _np_dtype(spec["dtype"]))
    return root


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:  # ml_dtypes names (bfloat16 et al.)
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))
