"""Multi-device serving: a corpus sharded by document over a mesh.

Two deployments, both answering as the one-chip facade does (``search``,
``resolve``, ``trace_count``, ``trace_shapes``, ``version``), so
``RetrieverServer`` serves either unchanged.

**A corpus larger than one chip** is built with ``LemurRetriever.build(
corpus, cfg, mesh=mesh)``, which returns a :class:`MeshLemurRetriever`::

    sr = LemurRetriever.build(corpus, cfg, key=key, mesh=mesh)
    scores, ids = sr.search(q, qm, SearchParams(k=10))
    cand = sr.candidates(q, qm)           # (B, shards x k'_local) global ids

ψ and the OLS solver are trained once over the whole corpus, as a
one-chip build does; then each shard -- a contiguous block of docs -- gets
its W rows, IVF lists and paged token store built on its own chip, so no
chip ever holds another shard's lists or pages.  Every query goes to every
shard; each runs the one-chip pipeline (``facade.search_pipeline``: IVF
first stage at the per-shard budget k'_local, fp32 paged rerank) on its own
index, and the shards' top-k meet in ``dist.merge_topk``.  It is
read-only: ``add``/``delete``/``update`` raise.  With
``cfg.build_per_shard``, ``LemurRetriever.build(corpus, cfg)`` stops after
the OLS solver and returns a :class:`TrainedLemur`, whose ``shard(mesh)``
builds the same shards.

**The slot pool** (``LemurRetriever.shard(mesh)``) re-lays a built
one-chip retriever out over the mesh: :class:`ShardedLemurRetriever`::

    r = LemurRetriever.build(corpus, cfg)
    sr = r.shard(mesh)                       # corpus block-sharded over mesh
    scores, ids = sr.search(q, qm, SearchParams(k=10))
    sr.add(new_tokens, new_mask)             # shard-balanced growth
    sr.delete(sr.base.last_added_ids)        # in-place slot eviction
    sr.update([3, 7], new_tokens[:2], new_mask[:2])
    sr.save("idx/"); sr = ShardedLemurRetriever.load("idx/", mesh)

It mirrors the single-device facade's surface (``search`` / ``add`` /
``save`` / ``load`` / ``trace_count``) on top of the slot-pool serve step
in :mod:`repro.dist.serve`: the latent corpus W and the doc token store are
block-sharded over the *flattened* mesh, each shard runs latent scan →
local top-k' → local exact rerank, and only (k, score) pairs cross the
wire in the hierarchical merge.  The whole index passes through one chip
on its way, so it serves only corpora that one chip can build.

Design points of the slot pool:

* **State build.**  ``ShardedRetrievalState`` is materialized from any
  built retriever as a SLOT POOL: every shard owns a power-of-two bucket of
  ``rows_per_shard`` physical rows, ``row_ids``/``row_valid`` map rows to
  the base facade's stable external slot ids (free rows are ``-1`` and
  masked out of the latent scan), and rows are either kept fp
  (bit-identical to the local facade's exact-scan search when k' covers
  the corpus) or scalar-quantized to SQ8 codes + per-row/per-token scales
  (``sq8=True``; 2-4x less resident HBM per shard, scores exact w.r.t. the
  quantized representation — per-row quantization means in-place row
  writes requantize ONLY the touched rows, exactly).  The default follows
  the build config's ``cfg.ivf.sq8`` knob.

* **Compilation contract.**  Like the single-device facade: exactly one
  compiled serve step per (mesh, resolved ``SearchParams``, batch shape),
  observable via :meth:`trace_count`.  The sharded state rides into the
  compiled step as a jit ARGUMENT, so in-capacity mutations (add into free
  rows, delete, update) keep every leaf shape and issue ZERO new traces —
  only a bucket-growing rebuild re-specializes.  The first-stage backend
  and ``use_ann`` are ignored here — the sharded first stage IS the
  per-shard exact latent scan (the paper's k' budget becomes the per-shard
  ``k_prime_local`` oversample, see ``dist.serve.default_k_prime_local``).

* **Shard-balanced mutation.**  ``add()`` fits new W rows with the base
  retriever's frozen-ψ OLS solver, then writes them into free rows of the
  LEAST-occupied shards (in-place ``.at[rows].set`` — no resharding, no
  O(corpus) copy while the pool has capacity).  ``delete()`` evicts rows
  in place (scan-masked + token-masked, so a deleted doc can never
  surface) and returns them to the per-shard free lists; ``update()`` is
  delete+add under the base facade's single version bump.  External ids
  keep the base facade's stable numbering throughout.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.anns import ivf, registry
from repro.anns.base import CorpusView
from repro.anns.quantization import sq8_quant
from repro.core import indexer, maxsim, pages
from repro.core.config import LemurConfig
from repro.retriever.facade import LemurRetriever
from repro.retriever.params import SearchParams


STATE_CHUNK_ROWS = 8192   # slot-pool rows materialized per device step


class ShardedLemurRetriever:
    """Multi-device serving facade over a built :class:`LemurRetriever`
    (see module docstring).  Construct via ``LemurRetriever.shard(mesh)``."""

    def __init__(self, base: LemurRetriever, mesh, *, sq8: bool | None = None,
                 k_prime_local: int | None = None):
        self._base = base
        self._mesh = dist.auto_axes(mesh)
        self._sq8 = bool(base.cfg.ivf.sq8) if sq8 is None else bool(sq8)
        self._k_prime_local = k_prime_local
        self._compiled: dict[tuple, Any] = {}
        self._trace_counts: dict[tuple, int] = {}
        self._trace_shapes: dict[tuple, int] = {}
        self._state: dist.ShardedRetrievalState | None = None
        # slot-pool allocator mirrors (host side): external id -> physical
        # row, and per-shard LIFO free-row lists for balanced placement
        self._row_of: dict[int, int] = {}
        self._free_rows: list[list[int]] = []
        self._rows_per_shard = 0
        self._rebuild_state()

    # -- introspection ------------------------------------------------------

    @property
    def base(self) -> LemurRetriever:
        return self._base

    @property
    def mesh(self):
        return self._mesh

    @property
    def cfg(self) -> LemurConfig:
        return self._base.cfg

    @property
    def m(self) -> int:
        """Slot high-water mark of the base facade (stable external ids)."""
        return self._base.m

    @property
    def n_alive(self) -> int:
        return self._base.n_alive

    @property
    def rows_per_shard(self) -> int:
        """Physical slot-pool rows each shard owns (pow2 bucket)."""
        return self._rows_per_shard

    @property
    def last_added_ids(self) -> np.ndarray:
        """External ids allocated by the most recent add/update (base's)."""
        return self._base.last_added_ids

    @property
    def sq8(self) -> bool:
        return self._sq8

    @property
    def version(self) -> int:
        """Snapshot version of the underlying facade (bumped per
        add/delete/update; update bumps ONCE)."""
        return self._base.version

    @property
    def state(self) -> dist.ShardedRetrievalState:
        return self._state

    def __repr__(self) -> str:
        shape = "x".join(str(self._mesh.shape[a]) for a in self._mesh.axis_names)
        return (f"ShardedLemurRetriever(m={self.m}, mesh={shape}, "
                f"sq8={self._sq8})")

    # -- state build --------------------------------------------------------

    def _rebuild_state(self) -> None:
        """Materialize the sharded slot pool from the base index: every shard
        owns a pow2 bucket of ``rows_per_shard`` rows (block-balanced
        placement; slot i lands on row i, so a fresh pool reproduces the
        legacy block layout), dead/unused rows are free (``row_ids=-1``,
        scan-masked), then quantize (SQ8) or keep fp and place per
        ``dist.state_shardings``.  Only runs at construction and when a
        mutation outgrows the pool (rows or token width)."""
        idx = self._base.index
        st = idx.store
        n = dist.n_corpus_shards(self._mesh)
        m = idx.m
        rps = max(1, pages.next_pow2(-(-m // n) if m else 1))
        total = n * rps
        alive = np.asarray(st.alive[:m])
        row_ids = np.full(total, -1, np.int32)
        row_ids[:m][alive] = np.arange(m, dtype=np.int32)[alive]
        row_valid = row_ids >= 0
        self._rows_per_shard = rps
        self._row_of = {int(i): int(i) for i in np.flatnonzero(alive)}
        free = np.flatnonzero(~row_valid)
        self._free_rows = [
            sorted(free[(free >= s * rps) & (free < (s + 1) * rps)].tolist(),
                   reverse=True)
            for s in range(n)]
        # rows are materialized (and quantized) a chunk at a time and
        # assembled on the host, then placed straight onto their shards:
        # no device ever holds the whole dense (total, Td, d) slot pool.
        # Rows past m (pool padding) gather as id -1: zero tokens, no mask
        host: dict[str, list] = {}
        for lo in range(0, total, STATE_CHUNK_ROWS):
            ids = jnp.arange(lo, min(lo + STATE_CHUNK_ROWS, total),
                             dtype=jnp.int32)
            ids = jnp.where(ids < m, ids, -1)
            docs, mask = pages.gather_docs(st, ids)
            W = jnp.where((ids >= 0)[:, None],
                          jnp.take(st.W, jnp.maximum(ids, 0), axis=0),
                          0.0).astype(jnp.float32)
            chunk = {"doc_mask": mask}
            if self._sq8:
                chunk["W"], chunk["W_scales"] = sq8_quant(W)
                chunk["doc_tokens"], chunk["doc_scales"] = sq8_quant(docs)
            else:
                chunk["W"], chunk["doc_tokens"] = W, docs
            for name, a in jax.device_get(chunk).items():
                host.setdefault(name, []).append(a)
        leaves = {name: np.concatenate(parts) for name, parts in host.items()}
        state = dist.ShardedRetrievalState(
            psi=idx.psi, row_ids=row_ids, row_valid=row_valid, **leaves)
        self._state = jax.device_put(
            state, dist.state_shardings(self._mesh, state))

    # -- query --------------------------------------------------------------

    def resolve(self, params: SearchParams | None = None) -> SearchParams:
        """Resolution is delegated to the base facade (same cfg defaults)."""
        return self._base.resolve(params)

    def search(self, q_tokens, q_mask=None, params: SearchParams | None = None):
        """q_tokens: (B, Tq, d) -> (scores (B, k), doc_ids (B, k)).

        One compiled serve step per (mesh, resolved params, batch shape);
        padded corpus rows are filtered to ``(NEG, -1)`` — the same pad
        convention as the single-device pipeline."""
        q_tokens = jnp.asarray(q_tokens)
        if q_mask is None:
            q_mask = jnp.ones(q_tokens.shape[:2], bool)
        resolved = self.resolve(params)
        return self._compiled_fn(resolved)(self._state, q_tokens, q_mask)

    def _compiled_fn(self, resolved: SearchParams):
        key = (resolved.k, resolved.k_prime, resolved.use_fused_gather,
               resolved.use_one_launch, resolved.use_residual)
        fn = self._compiled.get(key)
        if fn is None:
            serve = dist.make_serve_step(
                self._mesh,
                self.cfg.replace(k=resolved.k, k_prime=resolved.k_prime),
                k_prime_local=self._k_prime_local,
                use_fused_gather=resolved.use_fused_gather,
                use_one_launch=resolved.use_one_launch,
                use_residual=resolved.use_residual)
            counts = self._trace_counts
            shapes = self._trace_shapes

            def run(state, q, qm):
                counts[key] = counts.get(key, 0) + 1  # trace-time only
                skey = key + (tuple(q.shape),)
                shapes[skey] = shapes.get(skey, 0) + 1
                scores, ids = serve(state, q, qm)
                # free/tombstoned rows arrive id -1 (the row_ids map), score
                # NEG-ish — pin their scores so they sort last deterministically
                valid = ids >= 0
                scores = jnp.where(valid, scores, maxsim.NEG)
                if scores.shape[1] < resolved.k:
                    # k exceeds the (padded) corpus: keep the facade's (B, k)
                    # pad-to-k contract instead of the merge's narrower width
                    extra = resolved.k - scores.shape[1]
                    scores = jnp.pad(scores, ((0, 0), (0, extra)),
                                     constant_values=maxsim.NEG)
                    ids = jnp.pad(ids, ((0, 0), (0, extra)),
                                  constant_values=-1)
                return scores, ids

            fn = self._compiled[key] = jax.jit(run)
        return fn

    def trace_count(self, params: SearchParams | None = None) -> int:
        """jit traces so far: for one resolved SearchParams, or in total.
        The contract is one trace per (mesh, params, batch shape)."""
        if params is None:
            return sum(self._trace_counts.values())
        resolved = self.resolve(params)
        return self._trace_counts.get(
            (resolved.k, resolved.k_prime, resolved.use_fused_gather,
             resolved.use_one_launch, resolved.use_residual), 0)

    def trace_shapes(self) -> dict[tuple, int]:
        """Per-shape compile accounting (same contract as the single-device
        facade): ``{q.shape: n_traces}`` aggregated over params."""
        out: dict[tuple, int] = {}
        for (*_, shape), n in self._trace_shapes.items():
            out[shape] = out.get(shape, 0) + n
        return out

    def clone(self) -> "ShardedLemurRetriever":
        """An independent replica over a clone of the base facade (shared
        immutable index + OLS solver, private compile caches and sharded
        state) on the SAME mesh — the fleet router's replica factory for
        multi-device serving."""
        return ShardedLemurRetriever(self._base.clone(), self._mesh,
                                     sq8=self._sq8,
                                     k_prime_local=self._k_prime_local)

    # -- mutation -----------------------------------------------------------

    def add(self, doc_tokens, doc_mask, *, seed: int = 0) -> "ShardedLemurRetriever":
        """Incremental growth (§4.3) with shard-balanced placement: new W
        rows come from the base facade's frozen-ψ OLS solver, then the new
        docs are written IN PLACE into free rows of the least-occupied
        shards.  While the pool has rows (and the token width fits), no
        leaf changes shape — compiled serve steps survive with zero new
        traces; an outgrown pool triggers one bucket-doubling rebuild."""
        self._base.add(doc_tokens, doc_mask, seed=seed)
        self._place(self._base.last_added_ids)
        return self

    def delete(self, doc_ids) -> "ShardedLemurRetriever":
        """Tombstone docs: evict their rows in place (scan mask off, tokens
        masked — a deleted doc can never surface) and return the rows to
        the per-shard free lists.  Surviving ids are unchanged."""
        self._base.delete(doc_ids)
        self._evict(doc_ids)
        return self

    def update(self, doc_ids, doc_tokens, doc_mask, *,
               seed: int = 0) -> np.ndarray:
        """Replace docs under ONE version bump (the base facade's
        delete+add); returns the NEW external ids."""
        ids = self._base.update(doc_ids, doc_tokens, doc_mask, seed=seed)
        self._evict(doc_ids)
        self._place(ids)
        return ids

    def install_refresh(self, refresh) -> "ShardedLemurRetriever":
        """Warm-swap a background rebuild: delegate validation + catch-up +
        atomic swap to the base facade (raises ``CorruptIndexError`` with
        this sharded state untouched), then rebuild the sharded slot pool
        from the new index — the refit W rows must reach the devices, so the
        one-bucket re-place is unavoidable and billed to the swap, never to
        serving."""
        self._base.install_refresh(refresh)
        self._rebuild_state()
        return self

    def _evict(self, doc_ids) -> None:
        rows = np.asarray([self._row_of.pop(int(i))
                           for i in np.asarray(doc_ids).reshape(-1)],
                          np.int64)
        st = self._state
        state = st._replace(
            W=st.W.at[rows].set(jnp.zeros((), st.W.dtype)),
            doc_mask=st.doc_mask.at[rows].set(False),
            row_ids=st.row_ids.at[rows].set(-1),
            row_valid=st.row_valid.at[rows].set(False),
        )
        self._state = jax.device_put(
            state, dist.state_shardings(self._mesh, state))
        for r in rows.tolist():
            self._free_rows[r // self._rows_per_shard].append(r)

    def _place(self, new_ids) -> None:
        ids = np.asarray(new_ids, np.int32).reshape(-1)
        if not ids.size:
            return
        st = self._state
        store = self._base.index.store
        if (store.td_max > st.doc_tokens.shape[1]
                or ids.size > sum(len(f) for f in self._free_rows)):
            self._rebuild_state()
            return
        rows = []
        for _ in ids:
            s = max(range(len(self._free_rows)),
                    key=lambda i: len(self._free_rows[i]))
            rows.append(self._free_rows[s].pop())
        rows_np = np.asarray(rows, np.int64)
        jids = jnp.asarray(ids)
        toks, tmask = pages.gather_docs(store, jids)
        w = jnp.take(store.W, jids, axis=0).astype(jnp.float32)
        wide = st.doc_tokens.shape[1] - toks.shape[1]
        if wide:
            toks = jnp.pad(toks, ((0, 0), (0, wide), (0, 0)))
            tmask = jnp.pad(tmask, ((0, 0), (0, wide)))
        upd = {"doc_mask": st.doc_mask.at[rows_np].set(tmask),
               "row_ids": st.row_ids.at[rows_np].set(jids),
               "row_valid": st.row_valid.at[rows_np].set(True)}
        if self._sq8:
            # per-row/per-token quantization: requantizing ONLY the new rows
            # is exactly what quantizing the whole array would produce
            w, ws = sq8_quant(w)
            toks, ts = sq8_quant(toks)
            upd.update(W_scales=st.W_scales.at[rows_np].set(ws),
                       doc_scales=st.doc_scales.at[rows_np].set(ts))
        state = st._replace(
            W=st.W.at[rows_np].set(w.astype(st.W.dtype)),
            doc_tokens=st.doc_tokens.at[rows_np].set(
                toks.astype(st.doc_tokens.dtype)),
            **upd)
        self._state = jax.device_put(
            state, dist.state_shardings(self._mesh, state))
        for i, r in zip(ids.tolist(), rows):
            self._row_of[int(i)] = r

    # -- persistence --------------------------------------------------------

    def save(self, directory):
        """Persist the UNDERLYING retriever (mesh/device placement is a
        runtime concern, not an index property): any saved index reloads
        onto any mesh via :meth:`load`."""
        return self._base.save(directory)

    @classmethod
    def load(cls, directory, mesh, *, step: int | None = None,
             sq8: bool | None = None,
             k_prime_local: int | None = None) -> "ShardedLemurRetriever":
        """``LemurRetriever.load(...)`` then shard onto ``mesh``."""
        base = LemurRetriever.load(directory, step=step)
        return cls(base, mesh, sq8=sq8, k_prime_local=k_prime_local)


# --------------------------------------------------------------------------
# a corpus larger than one chip: one one-chip index per shard
# --------------------------------------------------------------------------

def check_mesh_config(cfg: LemurConfig) -> None:
    """Raise unless ``build(mesh=)`` can build ``cfg``: IVF shards over
    fp32 token pages (the one-chip pipeline each shard runs)."""
    if registry.canonical(cfg.anns) != "ivf":
        raise ValueError(f"build(mesh=) builds IVF shards; cfg.anns is "
                         f"{cfg.anns!r}")
    if cfg.residual.enabled or int(cfg.residual.token_budget) > 0:
        raise ValueError("build(mesh=) stores fp32 token pages; the "
                         "residual tier and token pooling are one-chip only")


class TrainedLemur:
    """What ``LemurRetriever.build`` trains over a whole corpus -- ψ, the
    target stats, the OLS solver -- with the corpus on the host and no
    index yet.  ``build`` returns it, without a mesh, for a configuration
    with ``build_per_shard``: the index of a corpus larger than one chip
    is built only where its shards go, by :meth:`shard`."""

    def __init__(self, cfg: LemurConfig, keys, psi, stats, solver,
                 doc_tokens: np.ndarray, doc_mask: np.ndarray, *,
                 verbose: bool = False):
        self._cfg = cfg
        self._parts = (keys, psi, stats, solver, doc_tokens, doc_mask)
        self._verbose = verbose

    @property
    def cfg(self) -> LemurConfig:
        return self._cfg

    @property
    def m(self) -> int:
        return self._parts[4].shape[0]

    def __repr__(self) -> str:
        return f"TrainedLemur(m={self.m}, d_prime={self.cfg.d_prime})"

    def shard(self, mesh, *, k_prime_local: int | None = None
              ) -> "MeshLemurRetriever":
        """Each shard's index on its own device of ``mesh``
        (:func:`build_shards`).  ``k_prime_local`` pins the per-shard
        candidate budget (default: ``dist.default_k_prime_local`` of each
        search's k and k')."""
        return build_shards(mesh, self.cfg, *self._parts,
                            k_prime_local=k_prime_local,
                            verbose=self._verbose)


def build_shards(mesh, cfg: LemurConfig, keys, psi, stats, solver,
                 doc_tokens: np.ndarray, doc_mask: np.ndarray, *,
                 k_prime_local: int | None = None,
                 verbose: bool = False) -> "MeshLemurRetriever":
    """The per-shard half of ``LemurRetriever.build(mesh=)``, given ψ, the
    target stats and the OLS solver trained over the whole corpus.

    Shard s holds the docs ``[s·m/n, (s+1)·m/n)``.  On its own chip it gets
    its W rows (the build's solver), its IVF (key ``fold_in(keys[3], s)``,
    nlist by the paper's rule on the largest shard's m, shared by all) and
    its fp32 paged store, floored to the shapes every shard needs; the
    shards build concurrently, one host thread each.  The dense corpus
    stays on the host."""
    t0 = time.time()
    mesh = dist.auto_axes(mesh)
    devices = dist.shard_devices(mesh)
    n, m = len(devices), doc_tokens.shape[0]
    bounds = [s * m // n for s in range(n + 1)]
    sizes = [bounds[s + 1] - bounds[s] for s in range(n)]
    bcfg = cfg.ivf.replace(nlist=int(cfg.ivf.nlist)
                           or ivf.default_nlist(max(sizes)))
    be = registry.get_backend("ivf")
    need = [pages.pages_needed(doc_mask[bounds[s]:bounds[s + 1]])
            for s in range(n)]
    floors = dict(min_capacity=max(pages.MIN_CAPACITY,
                                   pages.next_pow2(max(sizes))),
                  min_pages=pages.next_pow2(max(max(p for p, _ in need), 1)),
                  min_pmax=max(w for _, w in need))

    def on_device(tree, dev):
        return jax.tree.map(
            lambda a: jax.device_put(a, dev) if isinstance(a, jax.Array)
            else a, tree)

    def build_one(s: int):
        dev, lo, hi = devices[s], bounds[s], bounds[s + 1]
        toks, mask = doc_tokens[lo:hi], doc_mask[lo:hi]
        with jax.default_device(dev):
            sv = on_device(solver, dev)
            W = indexer.fit_output_layer_ols(
                on_device(psi, dev), sv["x_ols"], toks, mask, cfg,
                on_device(stats, dev), solver_state=sv)
            ann = be.build(jax.random.fold_in(keys[3], s),
                           CorpusView(W, toks, mask), bcfg)
            # the store lays W out on the host; drop the device copy first
            W = np.asarray(W)
            store, _ = pages.from_dense(W, toks, mask, **floors)
        return on_device((store, ann), dev)

    with concurrent.futures.ThreadPoolExecutor(n) as pool:
        blocks = list(pool.map(build_one, range(n)))
    cap = max(ann.capacity for _, ann in blocks)
    blocks = [(store, on_device(ivf.pad_lists(ann, cap), dev))
              for (store, ann), dev in zip(blocks, devices)]
    store, ann = dist.join_shards(mesh, blocks)
    base = dist.join_shards(mesh, [
        jax.device_put(np.asarray([lo], np.int32), dev)
        for lo, dev in zip(bounds, devices)])
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    state = dist.ShardedIndexState(
        psi=jax.device_put(psi, replicated),
        stats=jax.device_put(stats, replicated),
        store=store, ann=ann, base=base)
    if verbose:
        print(f"[build] {n} shards of {min(sizes)}-{max(sizes)} docs "
              f"(nlist {bcfg.nlist}, cap {cap}) done ({time.time()-t0:.1f}s)")
    return MeshLemurRetriever(cfg, mesh, state, m,
                              k_prime_local=k_prime_local)


class MeshLemurRetriever:
    """A doc-sharded deployment whose shards are one-chip indexes (see the
    module docstring).  Construct via ``LemurRetriever.build(mesh=)``.
    ``k_prime_local`` pins each shard's candidate budget for every search
    (default: :meth:`k_prime_local`'s rule)."""

    backend = "ivf"

    def __init__(self, cfg: LemurConfig, mesh, state: dist.ShardedIndexState,
                 m: int, *, k_prime_local: int | None = None):
        self._cfg = cfg
        self._mesh = mesh
        self._state = state
        self._m = m
        self._k_prime_local = k_prime_local
        self._compiled: dict[tuple, Any] = {}
        self._candidates: dict[SearchParams, Any] = {}
        self._trace_counts: dict[tuple, int] = {}
        self._trace_shapes: dict[tuple, int] = {}
        self._resolve_memo: dict[SearchParams | None, SearchParams] = {}

    # -- introspection ------------------------------------------------------

    @property
    def cfg(self) -> LemurConfig:
        return self._cfg

    @property
    def mesh(self):
        return self._mesh

    @property
    def m(self) -> int:
        return self._m

    @property
    def n_shards(self) -> int:
        return dist.n_corpus_shards(self._mesh)

    @property
    def rows_per_shard(self) -> int:
        """Docs of the largest shard."""
        return -(-self._m // self.n_shards)

    @property
    def version(self) -> int:
        """Snapshot version: 0, as nothing mutates this deployment."""
        return 0

    @property
    def state(self) -> dist.ShardedIndexState:
        return self._state

    def __repr__(self) -> str:
        shape = "x".join(str(self._mesh.shape[a]) for a in self._mesh.axis_names)
        return (f"MeshLemurRetriever(m={self.m}, mesh={shape}, "
                f"k_prime_local={self.k_prime_local()})")

    # -- query --------------------------------------------------------------

    def resolve(self, params: SearchParams | None = None) -> SearchParams:
        """Fill a (possibly partial) SearchParams from the build config
        (memoized, as the facade's)."""
        resolved = self._resolve_memo.get(params)
        if resolved is None:
            resolved = (params or SearchParams()).resolve(self.cfg,
                                                          self.backend)
            self._resolve_memo[params] = resolved
        return resolved

    def k_prime_local(self, params: SearchParams | None = None) -> int:
        """Each shard's candidate budget for ``params``: the pinned one, or
        the global k' spread over the shards with a 4x oversample
        (``dist.default_k_prime_local``)."""
        if self._k_prime_local is not None:
            return self._k_prime_local
        r = self.resolve(params)
        return dist.default_k_prime_local(r.k, r.k_prime, self.n_shards)

    def _local(self, resolved: SearchParams) -> SearchParams:
        return dataclasses.replace(resolved,
                                   k_prime=self.k_prime_local(resolved))

    def search(self, q_tokens, q_mask=None, params: SearchParams | None = None):
        """q_tokens: (B, Tq, d) -> (scores (B, k), global doc ids (B, k)).

        One compiled step per (resolved params, batch shape): every shard
        runs the one-chip pipeline at k'_local, then the merge.  Returns
        once dispatched; ``lemur.search`` is its host span, as the
        facade's."""
        with jax.profiler.TraceAnnotation("lemur.search"):
            q_tokens = jnp.asarray(q_tokens)
            if q_mask is None:
                q_mask = jnp.ones(q_tokens.shape[:2], bool)
            return self._compiled_fn(self.resolve(params))(
                self._state, q_tokens, q_mask)

    def candidates(self, q_tokens, q_mask=None,
                   params: SearchParams | None = None):
        """Every shard's first-stage candidates, the serve step's own (same
        params, same k'_local): (B, n_shards × k'_local) global ids, shard
        s's in the s-th block of columns, pads ``-1``."""
        q_tokens = jnp.asarray(q_tokens)
        if q_mask is None:
            q_mask = jnp.ones(q_tokens.shape[:2], bool)
        resolved = self.resolve(params)
        fn = self._candidates.get(resolved)
        if fn is None:
            fn = self._candidates[resolved] = jax.jit(
                dist.make_shard_candidates_step(
                    self._mesh, self.cfg, self.backend,
                    self._local(resolved)))
        return fn(self._state, q_tokens, q_mask)

    def _compiled_fn(self, resolved: SearchParams):
        key = (self.backend, resolved)
        fn = self._compiled.get(key)
        if fn is None:
            step = dist.make_shard_search_step(
                self._mesh, self.cfg, self.backend, self._local(resolved))
            counts, shapes = self._trace_counts, self._trace_shapes

            def run(state, q, qm):
                counts[key] = counts.get(key, 0) + 1     # trace-time only
                skey = key + (tuple(q.shape),)
                shapes[skey] = shapes.get(skey, 0) + 1
                return step(state, q, qm)

            fn = self._compiled[key] = jax.jit(run)
        return fn

    def trace_count(self, params: SearchParams | None = None) -> int:
        """jit traces of ``search`` so far: for one resolved SearchParams,
        or in total; one per (params, batch shape)."""
        if params is None:
            return sum(self._trace_counts.values())
        return self._trace_counts.get((self.backend, self.resolve(params)),
                                      0)

    def trace_shapes(self) -> dict[tuple, int]:
        """``{q.shape: n_traces}`` aggregated over params, as the facade's."""
        out: dict[tuple, int] = {}
        for (*_, shape), n in self._trace_shapes.items():
            out[shape] = out.get(shape, 0) + n
        return out

    # -- mutation -----------------------------------------------------------

    def _read_only(self, *_, **__):
        raise NotImplementedError(
            "a retriever built with build(mesh=) is read-only: its shards "
            "take no add/delete/update yet (LemurRetriever.shard(mesh) "
            "serves a mutable corpus that one chip can build)")

    add = delete = update = _read_only
