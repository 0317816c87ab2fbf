"""Paged corpus memory: fixed-size token pages + per-doc indirection.

The dense ``(N, Td, d)`` corpus layout made streaming ``add()`` an O(N)
``jnp.concatenate`` and made ``delete``/``update`` impossible — every growth
changed the corpus array shapes, so every compiled query fn died with them.
This module rebuilds corpus storage on the paged-KV serving idiom
(vLLM/flashinfer ``NUM_TOKENS_IN_BLOCK``: a pool of fixed-size token pages,
per-sequence page tables fed to kernels):

* ``tok_pages (P, page, d)`` — the page pool.  Each page holds
  ``TOKENS_PER_PAGE`` compacted (mask-stripped) token embeddings; a doc's
  tokens span ``ceil(n_tokens / page)`` pages, the last one zero-padded.
* ``page_table (C, pmax)`` + ``n_tokens (C,)`` — per-doc-slot indirection:
  which pages, how many real tokens.  ``-1`` pads unused table entries.
* ``W (C, d')`` — the OLS latent rows, slot-aligned (dead slots zeroed).
* ``alive (C,)`` — tombstone mask.  ``delete()`` returns pages to the free
  list and flips this bit; the first-stage backends are never rebuilt, so
  stale candidates are filtered by :func:`mask_dead` after every first stage.
* ``n_docs (1,)`` — the slot high-water mark, kept as an int32 ARRAY leaf
  (not static aux) so growing the corpus does not retrace compiled fns.

Doc ids are **stable**: the external id IS the slot index, slots are
allocated monotonically and never reused, and only PAGES return to the free
list.  (Backends number docs by arrival order, so slot numbering and
backend numbering coincide by construction — the invariant that lets
tombstone masking work without ever rebuilding a backend.)

All shapes — pool size ``P``, slot capacity ``C``, pages-per-doc ``pmax``,
and the page size itself — are jit-static and grow in power-of-two buckets
with amortized doubling, so an ``add()`` that fits the pre-grown pool
changes NO shapes and compiled query fns survive it (the compile key gains
only the capacity bucket).  Compacting tokens into pages is *exact* for
MaxSim: per-token dot products are unchanged and the per-query-token max
over a doc's tokens is order-independent, so paged scores are bit-identical
to the dense layout's.

Mutation entry points (:func:`from_dense`, :func:`add_docs`,
:func:`delete_docs`) are host-side (concrete arrays) and return the bytes
they logically moved — the accounting ``benchmarks/serving_online.py`` gates
on (paged bytes-per-add must be O(doc), not O(corpus)).  The traced helpers
(:func:`gather_docs`, :func:`mask_dead`) are jit-safe and feed the query
pipeline.

**Compressed tier** (``codec=...``): the same page/slot machinery can store
tokens as a ColBERTv2-style residual code instead of fp32 — a per-token
centroid id (``cent_pages``) plus a 2/4-bit packed residual
(``code_pages``), with the trained :class:`~repro.anns.quantization.
ResidualCodec` riding along as pytree leaves.  Slot ids, tombstones,
page accounting, and the in-capacity zero-retrace mutation contract are
IDENTICAL to the fp32 tier; only the page payload changes.  Index-time
constant-space pooling (:func:`pool_tokens`) caps every doc at a fixed
token budget before pagination, so corpus memory is bounded per doc.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns.quantization import (
    ResidualCodec,
    residual_decode,
    residual_encode,
)

TOKENS_PER_PAGE = 16   # power of two — the paged-KV NUM_TOKENS_IN_BLOCK
MIN_CAPACITY = 8       # smallest doc-slot bucket
_ITEM = 4              # fp32 / int32 bytes, the accounting unit


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 if n <= 1 else 1 << int(n - 1).bit_length()


class PagedStore(NamedTuple):
    """The paged corpus: a pure jax pytree, safe to pass as a jit ARGUMENT
    (which is how compiled query fns survive mutation — see facade)."""

    tok_pages: jax.Array   # (P, page, d)  fp32 compacted token embeddings
    page_table: jax.Array  # (C, pmax)     int32 page ids, -1 padded
    n_tokens: jax.Array    # (C,)          int32 real tokens per slot
    W: jax.Array           # (C, d')       latent rows (dead slots zeroed)
    alive: jax.Array       # (C,)          bool tombstone mask
    n_docs: jax.Array      # (1,)          int32 slot high-water mark
    # compressed tier (None on the fp32 tier; tok_pages is then (P, page, 0))
    cent_pages: jax.Array | None = None   # (P, page)      int32 centroid ids
    code_pages: jax.Array | None = None   # (P, page, db)  uint8 packed residuals
    codec: ResidualCodec | None = None    # trained codec tables (pytree leaves)

    # shape-derived introspection (trace-safe: static under jit)
    @property
    def n_pages(self) -> int:
        return self.tok_pages.shape[0]

    @property
    def page(self) -> int:
        return self.tok_pages.shape[1]

    @property
    def d(self) -> int:
        if self.codec is not None:
            return self.codec.d
        return self.tok_pages.shape[2]

    @property
    def residual(self) -> bool:
        """True when tokens live in the compressed (codec) tier."""
        return self.codec is not None

    @property
    def capacity(self) -> int:
        return self.page_table.shape[0]

    @property
    def pages_per_doc(self) -> int:
        return self.page_table.shape[1]

    @property
    def td_max(self) -> int:
        return self.page_table.shape[1] * self.tok_pages.shape[1]

    @property
    def d_prime(self) -> int:
        return self.W.shape[1]


# --------------------------------------------------------------------------
# host-side mutation (concrete arrays; returns logical bytes moved)
# --------------------------------------------------------------------------

def _paginate(doc_mask, page: int, pmax: int, flats: list):
    """Compact n docs' per-token payloads into page-sized chunks (host).

    ``flats``: arrays ``(k, ...)`` over the k VALID tokens in doc-major
    order (one per payload stream — fp32 tokens, or centroid ids + packed
    residual codes).  Returns ``(chunks [one (need, page, ...) array per
    payload], local_table (n, pmax) int32 of LOCAL chunk indices or -1,
    counts (n,) int32)`` — callers map local chunk indices through their
    page allocation."""
    dm = np.asarray(doc_mask, bool)
    counts = dm.sum(axis=1).astype(np.int64)
    ppd = -(-counts // page)                       # pages per doc (0 if empty)
    if int(ppd.max(initial=0)) > pmax:
        raise ValueError(
            f"doc needs {int(ppd.max())} pages > pmax={pmax} (caller grows)")
    starts = np.concatenate([[0], np.cumsum(ppd)[:-1]]).astype(np.int64)
    need = int(ppd.sum())
    j = np.arange(pmax, dtype=np.int64)[None, :]
    local = np.where(j < ppd[:, None], starts[:, None] + j, -1).astype(np.int32)
    if need:
        tok_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
        t = np.arange(int(counts.sum())) - np.repeat(tok_start, counts)
        rows = np.repeat(starts, counts) + t // page
        cols = t % page
    chunks = []
    for f in flats:
        f = np.asarray(f)
        out = np.zeros((need, page) + f.shape[1:], f.dtype)
        if need:
            out[rows, cols] = f
        chunks.append(out)
    return chunks, local, counts.astype(np.int32)


def _encode_flat(codec: ResidualCodec, flat: np.ndarray):
    """fp32 valid tokens (k, d) -> [cent (k,) int32, packed (k, db) uint8]."""
    cid, packed = residual_encode(codec, jnp.asarray(flat, jnp.float32))
    return [np.asarray(cid, np.int32), np.asarray(packed, np.uint8)]


def pages_needed(doc_mask, page: int = TOKENS_PER_PAGE) -> tuple[int, int]:
    """``(pages, pages_per_doc)`` that :func:`from_dense` lays ``doc_mask``'s
    docs out in: the pages their tokens fill, and the widest doc's page
    count (at least 1)."""
    ppd = -(-np.asarray(doc_mask, bool).sum(axis=1) // page)
    return int(ppd.sum()), max(1, int(ppd.max(initial=1)))


def from_dense(W, doc_tokens, doc_mask, *, page: int = TOKENS_PER_PAGE,
               min_capacity: int = MIN_CAPACITY, min_pages: int = 1,
               min_pmax: int = 1, codec: ResidualCodec | None = None):
    """Build a :class:`PagedStore` from the dense padded layout.

    With ``codec`` the tokens are residual-encoded into the compressed tier
    (``cent_pages``/``code_pages``; ``tok_pages`` keeps a zero-width fp32
    pool so every shape property still derives from it).  ``min_capacity``,
    ``min_pages`` and ``min_pmax`` floor the slot capacity, the page pool
    and the page-table width, so stores of several corpora can share one
    shape.  Returns ``(store, bytes_moved)`` — the one-time O(corpus) build
    cost.  The free list is derivable (:func:`free_list`), so it is not
    threaded through immutable index snapshots."""
    W = np.asarray(W)
    m = W.shape[0]
    dt = np.asarray(doc_tokens, np.float32)
    dm = np.asarray(doc_mask, bool)
    d = dt.shape[2]
    pmax = max(min_pmax, pages_needed(dm, page)[1])
    flat = dt[dm]
    flats = [flat] if codec is None else _encode_flat(codec, flat)
    chunks, local, counts = _paginate(dm, page, pmax, flats)
    need = chunks[0].shape[0]
    C = max(min_capacity, next_pow2(m))
    P = max(min_pages, next_pow2(max(1, need)))
    table = np.full((C, pmax), -1, np.int32)
    table[:m] = local                               # local idx == page id here
    ntok = np.zeros((C,), np.int32)
    ntok[:m] = counts
    Wc = np.zeros((C, W.shape[1]), W.dtype)
    Wc[:m] = W
    alive = np.zeros((C,), bool)
    alive[:m] = True
    extra = {}
    if codec is None:
        pool = np.zeros((P, page, d), np.float32)
        pool[:need] = chunks[0]
    else:
        pool = np.zeros((P, page, 0), np.float32)
        cent_pool = np.zeros((P, page), np.int32)
        cent_pool[:need] = chunks[0]
        code_pool = np.zeros((P, page, chunks[1].shape[-1]), np.uint8)
        code_pool[:need] = chunks[1]
        extra = dict(cent_pages=jnp.asarray(cent_pool),
                     code_pages=jnp.asarray(code_pool), codec=codec)
    store = PagedStore(jnp.asarray(pool), jnp.asarray(table),
                       jnp.asarray(ntok), jnp.asarray(Wc),
                       jnp.asarray(alive),
                       jnp.asarray([m], dtype=jnp.int32), **extra)
    moved = (sum(c.nbytes for c in chunks) + table.nbytes + ntok.nbytes
             + Wc.nbytes + alive.nbytes)
    return store, moved


def pool_tokens(doc_tokens, doc_mask, budget: int):
    """Index-time constant-space token pooling (PAPERS.md: Efficient
    Constant-Space Multi-Vector Retrieval): hierarchically cluster-pool each
    doc's token embeddings down to a fixed per-doc ``budget``.

    Deterministic (greedy closest-pair agglomeration, count-weighted means,
    first-index tie-break) and host-side — pooling happens once at
    index/add time, never on the query path.  Returns
    ``(pooled (n, min(T, budget), d) fp32, mask)``; ``budget <= 0`` is a
    no-op passthrough."""
    dt = np.asarray(doc_tokens, np.float32)
    dm = np.asarray(doc_mask, bool)
    if budget <= 0 or dt.shape[1] <= budget:
        return dt, dm
    n, T, d = dt.shape
    tp = min(T, budget)
    out = np.zeros((n, tp, d), np.float32)
    om = np.zeros((n, tp), bool)
    for i in range(n):
        toks = dt[i][dm[i]]
        if toks.shape[0] > budget:
            toks = _pool_one(toks, budget)
        t = toks.shape[0]
        out[i, :t] = toks
        om[i, :t] = True
    return out, om


def _pool_one(toks: np.ndarray, budget: int) -> np.ndarray:
    """Agglomerate one doc's (t, d) tokens to ``budget`` count-weighted
    means by repeatedly merging the closest pair (squared Euclidean)."""
    reps = toks.astype(np.float64)
    w = np.ones(len(reps))
    alive = np.ones(len(reps), bool)
    while int(alive.sum()) > budget:
        idx = np.flatnonzero(alive)
        sub = reps[idx]
        sq = np.sum(np.square(sub), axis=1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (sub @ sub.T)
        iu = np.triu_indices(len(idx), k=1)
        flatpos = np.argmin(d2[iu])
        a, b = iu[0][flatpos], iu[1][flatpos]
        i, j = int(idx[a]), int(idx[b])
        reps[i] = (w[i] * reps[i] + w[j] * reps[j]) / (w[i] + w[j])
        w[i] += w[j]
        alive[j] = False
    return reps[alive].astype(np.float32)


def token_bytes(store: PagedStore) -> int:
    """Resident bytes of the token payload: the fp32 page pool, or the
    compressed tier's id/code pools plus the (corpus-amortized) codec
    tables.  The recall bench's bytes-per-doc column divides this by the
    live doc count."""
    if store.codec is not None:
        tables = sum(int(np.asarray(x).nbytes) for x in store.codec)
        return int(store.cent_pages.nbytes + store.code_pages.nbytes) + tables
    return int(store.tok_pages.nbytes)


# --------------------------------------------------------------------------
# mutation taps (observability seam for the index lifecycle)
# --------------------------------------------------------------------------

_MUTATION_TAPS: list = []


def register_mutation_tap(fn) -> None:
    """Subscribe ``fn(kind, ids, **payload)`` to every store mutation.

    ``kind`` is ``"add"`` (payload: ``doc_tokens``, ``doc_mask``, ``w`` —
    host numpy views of the NEW docs only) or ``"delete"`` (ids only).
    Taps run synchronously on the mutating thread AFTER the store is
    updated; they must be cheap and must never raise (exceptions are
    swallowed so a broken observer cannot corrupt a mutation barrier).
    This is the reservoir feed for ``lifecycle.DriftMonitor``."""
    if fn not in _MUTATION_TAPS:
        _MUTATION_TAPS.append(fn)


def unregister_mutation_tap(fn) -> None:
    try:
        _MUTATION_TAPS.remove(fn)
    except ValueError:
        pass


def _notify_taps(kind: str, ids, **payload) -> None:
    for fn in list(_MUTATION_TAPS):
        try:
            fn(kind, ids, **payload)
        except Exception:
            pass


def free_list(store: PagedStore) -> list[int]:
    """Ascending free page ids: the complement of the referenced pages.
    Deterministic, so snapshots/checkpoints never persist the allocator."""
    used = np.asarray(store.page_table).ravel()
    mask = np.ones(store.n_pages, bool)
    mask[used[used >= 0]] = False
    return np.flatnonzero(mask).tolist()


def add_docs(store: PagedStore, free_pages: list[int], w_new, doc_tokens,
             doc_mask):
    """Allocate pages for n new docs into slots ``[m, m+n)``.

    Returns ``(store, free_pages, new_ids (n,) int32, bytes_moved)``.
    When the new docs fit the pre-grown pool/capacity, no array changes
    shape — compiled query fns taking the store as an argument survive.
    Growth (capacity, pool, or pages-per-doc) pads in power-of-two buckets
    with amortized doubling and bills the copy it forces."""
    dt = np.asarray(doc_tokens, np.float32)
    dm = np.asarray(doc_mask, bool)
    n = dt.shape[0]
    if n == 0:
        return store, list(free_pages), np.empty((0,), np.int32), 0
    m = int(store.n_docs[0])
    page = store.page
    moved = 0

    # 1. pages-per-doc bucket (only a doc LONGER than any before grows it)
    pmax = store.pages_per_doc
    need_pmax = int((-(-dm.sum(axis=1) // page)).max(initial=1))
    if need_pmax > pmax:
        new_pmax = next_pow2(need_pmax)
        moved += store.page_table.size * _ITEM
        store = store._replace(page_table=jnp.pad(
            store.page_table, ((0, 0), (0, new_pmax - pmax)),
            constant_values=-1))
        pmax = new_pmax

    # 2. doc-slot capacity bucket
    C = store.capacity
    if m + n > C:
        newC = max(next_pow2(m + n), 2 * C)
        moved += (store.page_table.nbytes + store.n_tokens.nbytes
                  + store.W.nbytes + store.alive.nbytes)
        store = store._replace(
            page_table=jnp.pad(store.page_table, ((0, newC - C), (0, 0)),
                               constant_values=-1),
            n_tokens=jnp.pad(store.n_tokens, (0, newC - C)),
            W=jnp.pad(store.W, ((0, newC - C), (0, 0))),
            alive=jnp.pad(store.alive, (0, newC - C)),
        )

    # 3. page-pool bucket (amortized doubling)
    flat = dt[dm]
    flats = [flat] if store.codec is None else _encode_flat(store.codec, flat)
    chunks, local, counts = _paginate(dm, page, pmax, flats)
    need = chunks[0].shape[0]
    free_pages = list(free_pages)
    if need > len(free_pages):
        P = store.n_pages
        newP = max(next_pow2(P - len(free_pages) + need), 2 * P)
        moved += store.tok_pages.nbytes
        grown = dict(tok_pages=jnp.pad(
            store.tok_pages, ((0, newP - P), (0, 0), (0, 0))))
        if store.codec is not None:
            moved += store.cent_pages.nbytes + store.code_pages.nbytes
            grown.update(
                cent_pages=jnp.pad(store.cent_pages, ((0, newP - P), (0, 0))),
                code_pages=jnp.pad(store.code_pages,
                                   ((0, newP - P), (0, 0), (0, 0))))
        store = store._replace(**grown)
        free_pages.extend(range(P, newP))

    # 4. allocate (lowest page ids first — deterministic) and scatter
    alloc = np.asarray(free_pages[:need], np.int32)
    free_pages = free_pages[need:]
    table_rows = np.where(local >= 0, alloc[np.maximum(local, 0)],
                          -1).astype(np.int32)
    ids = np.arange(m, m + n, dtype=np.int32)
    pools = {}
    if need:
        ja = jnp.asarray(alloc)
        if store.codec is None:
            pools["tok_pages"] = store.tok_pages.at[ja].set(
                jnp.asarray(chunks[0]))
        else:
            pools["cent_pages"] = store.cent_pages.at[ja].set(
                jnp.asarray(chunks[0]))
            pools["code_pages"] = store.code_pages.at[ja].set(
                jnp.asarray(chunks[1]))
    store = store._replace(
        page_table=store.page_table.at[m:m + n].set(jnp.asarray(table_rows)),
        n_tokens=store.n_tokens.at[m:m + n].set(jnp.asarray(counts)),
        W=store.W.at[m:m + n].set(jnp.asarray(w_new, store.W.dtype)),
        alive=store.alive.at[m:m + n].set(True),
        n_docs=jnp.asarray([m + n], dtype=jnp.int32),
        **pools,
    )
    # logical write set: the new pages + the touched table/W/count rows.
    # O(doc), never O(corpus) — the property the serving bench gates on.
    moved += (sum(c.nbytes for c in chunks) + table_rows.nbytes + counts.nbytes
              + n * store.d_prime * _ITEM + n + _ITEM)
    if _MUTATION_TAPS:
        _notify_taps("add", ids, doc_tokens=dt, doc_mask=dm,
                     w=np.asarray(w_new, np.float32))
    return store, free_pages, ids, moved


def delete_docs(store: PagedStore, free_pages: list[int], doc_ids):
    """Tombstone slots and return their pages to the free list.

    Slots are never reused (ids stay stable); ``W`` rows are zeroed so a
    dead slot can never win a latent scan even unmasked.  Raises
    ``ValueError`` on unknown, already-deleted, or duplicate ids.
    Returns ``(store, free_pages, bytes_moved)``."""
    ids = np.asarray(doc_ids, np.int64).ravel()
    if ids.size == 0:
        return store, list(free_pages), 0
    m = int(store.n_docs[0])
    alive = np.asarray(store.alive)
    if np.unique(ids).size != ids.size:
        raise ValueError(f"duplicate doc ids in delete: {ids.tolist()}")
    bad = ids[(ids < 0) | (ids >= m)]
    if bad.size:
        raise ValueError(f"unknown doc ids {bad.tolist()} (n_docs={m})")
    dead = ids[~alive[ids]]
    if dead.size:
        raise ValueError(f"doc ids already deleted: {dead.tolist()}")
    rows = np.asarray(store.page_table)[ids]
    freed = rows[rows >= 0].tolist()
    free_pages = sorted(list(free_pages) + freed)
    jids = jnp.asarray(ids, jnp.int32)
    store = store._replace(
        page_table=store.page_table.at[jids].set(-1),
        n_tokens=store.n_tokens.at[jids].set(0),
        W=store.W.at[jids].set(0),
        alive=store.alive.at[jids].set(False),
    )
    moved = int(ids.size) * (store.pages_per_doc * _ITEM + _ITEM
                             + store.d_prime * _ITEM + 1)
    if _MUTATION_TAPS:
        _notify_taps("delete", ids.astype(np.int32))
    return store, free_pages, moved


def dense_add_bytes(m_total: int, td: int, d: int, d_prime: int) -> int:
    """What ONE flat-layout add used to write: the full concatenated corpus
    (`jnp.concatenate` materializes all three outputs) — the O(corpus)
    baseline the amortization bench compares against."""
    return m_total * td * d * _ITEM + m_total * td + m_total * d_prime * _ITEM


# --------------------------------------------------------------------------
# traced helpers (jit-safe; feed the query pipeline)
# --------------------------------------------------------------------------

def mask_dead(store: PagedStore, cand_ids):
    """Tombstone filter: candidate ids of deleted slots -> ``-1``.

    Applied after EVERY first stage — backends are never rebuilt on delete,
    so they keep emitting stale ids; this is the single choke point that
    guarantees a deleted doc never surfaces (fused and legacy paths both
    treat ``-1`` as NEG-scored pad)."""
    safe = jnp.maximum(cand_ids, 0)
    ok = (cand_ids >= 0) & jnp.take(store.alive, safe, axis=0)
    return jnp.where(ok, cand_ids, -1)


def gather_docs(store: PagedStore, doc_ids):
    """Materialize docs from pages: ``(...,) int32`` slot ids ->
    ``(tokens (..., pmax*page, d), mask (..., pmax*page) bool)``.

    ``-1`` (or dead) ids yield an all-False mask and zeroed tokens.  This
    is the legacy-gather twin of the paged rerank kernel — identical token
    values in identical positions, so scores agree bit for bit.

    On the compressed tier the tokens are residual-DECODED on the fly
    (pure jnp, jit-safe): callers always see fp32 ``(…, td_max, d)``
    tokens, whichever tier backs them."""
    doc_ids = jnp.asarray(doc_ids)
    safe = jnp.maximum(doc_ids, 0)
    table = jnp.take(store.page_table, safe, axis=0)       # (..., pmax)
    nt = jnp.take(store.n_tokens, safe, axis=0)            # (...,)
    nt = jnp.where(doc_ids >= 0, nt, 0)
    safe_pg = jnp.maximum(table, 0)
    if store.codec is not None:
        cent = jnp.take(store.cent_pages, safe_pg, axis=0)   # (..., pmax, page)
        codes = jnp.take(store.code_pages, safe_pg, axis=0)  # (..., pmax, pg, db)
        cent = cent.reshape(doc_ids.shape + (store.td_max,))
        codes = codes.reshape(doc_ids.shape + (store.td_max,
                                               codes.shape[-1]))
        toks = residual_decode(store.codec, cent, codes)
    else:
        toks = jnp.take(store.tok_pages, safe_pg, axis=0)
        toks = toks.reshape(doc_ids.shape + (store.td_max, store.d))
    pos = jnp.arange(store.td_max, dtype=jnp.int32)
    mask = pos < nt[..., None]
    return toks * mask[..., None], mask
