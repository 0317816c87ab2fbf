"""Distributed LEMUR: sharded indexing + sharded serving on the mesh.

Serving (Fig. 1 at pod scale): the corpus is sharded by document over the
*flattened* mesh (every chip owns m/n_devices docs).  A query batch is
replicated across the corpus axis; each shard retrieves its local top-k
entirely locally, and only the (k, score) pairs cross the wire in a final
all-gather merge (:func:`merge_topk`) — per-query traffic is
k·n_devices·8 bytes, independent of m.  Two shard layouts:

* :class:`ShardedIndexState` (``LemurRetriever.build(mesh=)``): each shard
  is a one-chip index — paged store and IVF lists — and runs the one-chip
  pipeline (``facade.search_pipeline``) on it
  (:func:`make_shard_search_step`, :func:`make_shard_candidates_step`);
* :class:`ShardedRetrievalState` (``LemurRetriever.shard``): a dense slot
  pool, scanned exactly and reranked by the gather kernel
  (:func:`make_serve_step`).

Indexing (§4.3): the Gram factor is tiny ((d')² fp32) and replicated; each
shard fits OLS rows for its own documents with zero communication.

``repro.core.distributed`` re-exports this module for v0 call sites.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import maxsim
from repro.core.config import LemurConfig
from repro.core.model import pool_queries
from repro.kernels import ops


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The serve step lays out its
    operands with sharding constraints and lets the partitioner place the
    rest, which only ``Auto`` axes allow; ``jax.make_mesh`` gives
    ``Explicit`` axes by default."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def corpus_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)  # shard the corpus over every axis


def n_corpus_shards(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in corpus_axes(mesh)]))


class ShardedRetrievalState(NamedTuple):
    """Device arrays for the serving step (pytree).

    With scales present, W / doc_tokens are int8 SQ codes (Glass-style SQ8 —
    the layout repro.kernels.mips_sq8 scans on TPU): 2-4x less resident HBM
    and per-step traffic than bf16/fp32 (EXPERIMENTS.md §Perf iteration 3).

    ``row_ids`` / ``row_valid`` (optional — the paged sharded facade sets
    them) decouple physical rows from external doc ids: rows become SLOTS
    that mutations rewrite in place (add/delete/update without resharding),
    ``row_valid=False`` rows are masked out of the latent scan, and the
    merge maps surviving local rows to external ids through ``row_ids``
    (``-1`` for free rows).  When absent, row position IS the doc id (the
    legacy contract; ``m_real`` masks the tail padding)."""
    psi: dict
    W: jax.Array                    # (m, d') latent corpus (fp or int8 codes)
    doc_tokens: jax.Array           # (m, Td, d) token store (fp or int8 codes)
    doc_mask: jax.Array             # (m, Td)
    W_scales: jax.Array | None = None      # (m,) per-row scales (int8 mode)
    doc_scales: jax.Array | None = None    # (m, Td) per-token scales
    row_ids: jax.Array | None = None       # (m,) int32 external ids, -1 free
    row_valid: jax.Array | None = None     # (m,) bool occupied-and-alive


def state_shardings(mesh: Mesh, state: ShardedRetrievalState | None = None):
    """NamedShardings for a ShardedRetrievalState: ψ replicated, every
    corpus-sized leaf block-sharded over the flattened mesh.  With ``state``
    given, its ψ tree structure (and scale/row-map presence) is mirrored
    exactly."""
    corpus = NamedSharding(mesh, P(corpus_axes(mesh)))
    repl = NamedSharding(mesh, P())
    psi_tree = state.psi if state is not None else {
        "dense": {"kernel": 0, "bias": 0}, "ln": {"scale": 0, "bias": 0}}
    has_scales = state is not None and state.W_scales is not None
    has_rows = state is not None and state.row_ids is not None
    return ShardedRetrievalState(
        psi=jax.tree_util.tree_map(lambda _: repl, psi_tree),
        W=corpus,
        doc_tokens=corpus,
        doc_mask=corpus,
        W_scales=corpus if has_scales else None,
        doc_scales=corpus if has_scales else None,
        row_ids=corpus if has_rows else None,
        row_valid=corpus if has_rows else None,
    )


def _local_retrieve(psi_q, W, W_scales, doc_tokens, doc_scales, doc_mask,
                    row_ids, row_valid, q_tokens, q_mask, *, k: int,
                    k_prime: int, axes: tuple[str, ...],
                    axis_sizes: tuple[int, ...],
                    m_real: int | None = None, use_fused_gather: bool = True,
                    use_one_launch: bool = False):
    """Per-shard body (inside shard_map): local MIPS + local rerank + merge.

    * latent scan: int8 codes x fp query with per-row scales (the
      kernels.mips_sq8 contraction) when scales are present;
    * rerank: ``use_fused_gather=True`` routes the per-shard candidate
      rerank through ``kernels.ops.fused_rerank`` — the SAME gather-at-
      source kernel the single-device facade serves with (candidate token
      slabs DMA'd straight into VMEM on TPU; per-token SQ8 scales folded
      into the score rows in-kernel).  ``False`` keeps the legacy
      gather-then-contract path benchmarkable.  Either way only the k'
      CANDIDATE docs are touched and scores stay exact w.r.t. the stored
      (quantized) representation, matching Glass+SQ in the paper;
    * merge: hierarchical per-axis top-k (tree reduction) — gather volume
      k*|axis| per stage instead of k*n_devices at once.

    ``m_real``: true corpus size when the leading dim carries padding rows
    (the facade pads m up to the device count) — padded columns are masked
    out of the latent scan so they can never displace a real candidate.
    ``row_ids``/``row_valid`` (the paged slot contract, see
    :class:`ShardedRetrievalState`): the scan mask comes from the TRACED
    ``row_valid`` bits and the merge maps local rows to external ids
    through ``row_ids`` — free/tombstoned rows score NEG and resolve to
    ``-1``, so in-place slot mutation never changes shapes."""
    # psi_q: (B, d') pooled queries, already encoded batch-sharded OUTSIDE the
    # corpus shard_map (encoding inside would replicate the psi MLP's (B,Tq,d')
    # intermediates on every corpus shard — §Perf iteration 3)
    m_loc = W.shape[0]
    kp = min(k_prime, m_loc)
    # globalize ids: offset by this shard's first row (sizes are static —
    # old jax has no lax.axis_size)
    idx = 0
    for ax, size in zip(axes, axis_sizes):
        idx = idx * size + jax.lax.axis_index(ax)
    valid = row_valid
    if valid is None and m_real is not None:
        valid = (idx * m_loc + jnp.arange(m_loc)) < m_real
    if use_one_launch:
        # fused latent scan + in-kernel top-k': the (B, m_loc) score matrix
        # never exists in HBM.  The pad mask depends on TRACED state (shard
        # index / row_valid bits), so it rides into the kernel as an array
        # input (masked rows keep their position ids at NEG — identical to
        # the legacy branch).
        _, cand = ops.mips_topk_fused(psi_q, W, W_scales, kp, valid)
    else:
        s = psi_q @ W.T.astype(psi_q.dtype)                     # (B, m_loc)
        if W_scales is not None:
            s = s * W_scales[None, :].astype(s.dtype)
        if valid is not None:
            s = jnp.where(valid[None, :], s, maxsim.NEG)
        _, cand = jax.lax.top_k(s, kp)                          # local candidates
    if use_fused_gather:
        scores, local_ids = ops.fused_rerank(
            q_tokens, q_mask, cand, doc_tokens, doc_mask, min(k, kp),
            doc_scales=doc_scales)
    elif doc_scales is not None:
        cd = jnp.take(doc_tokens, cand, axis=0).astype(q_tokens.dtype)
        cs = jnp.take(doc_scales, cand, axis=0)
        cm = jnp.take(doc_mask, cand, axis=0)
        # fold the per-token scale into the SCORE tensor: score(q, s*c) =
        # s*(q.c) — avoids materializing a dequantized (B,k',Td,d) fp copy
        # (the fused kernel path does the same dequant in-VMEM on TPU)
        sc = jnp.einsum("bqd,bmtd->bmqt", q_tokens, cd,
                        precision=maxsim.HIGHEST,
                        preferred_element_type=jnp.float32)
        sc = sc * cs.astype(jnp.float32)[:, :, None, :]
        sc = jnp.where(cm[:, :, None, :], sc, -1e30)
        best = jnp.where(q_mask[:, None, :], jnp.max(sc, axis=-1), 0.0)
        scores = jnp.sum(best, axis=-1)
        scores, pos = jax.lax.top_k(scores, min(k, kp))
        local_ids = jnp.take_along_axis(cand, pos, axis=1)
    else:
        scores, local_ids = maxsim.rerank(q_tokens, q_mask, cand, doc_tokens,
                                          doc_mask, min(k, kp))
    if row_ids is not None:
        # slot contract: map surviving local rows to external ids; -1 rerank
        # pads and free rows (row_ids -1) stay -1
        safe = jnp.maximum(local_ids, 0)
        gids = jnp.where(local_ids >= 0, jnp.take(row_ids, safe), -1)
    else:
        gids = local_ids + idx * m_loc
    return merge_topk(scores, gids, k=k, axes=axes)


def merge_topk(scores, ids, *, k: int, axes: tuple[str, ...]):
    """The shards' (B, k_loc) top-k -> the global (B, min(k, ·)) top-k,
    replicated (inside ``shard_map``).  Hierarchical: an all-gather along
    one mesh axis at a time, reduced back to top-k after each, so a stage
    gathers k·|axis| columns instead of k·n_devices at once.  Every op sits
    under the ``merge/`` scope."""
    with jax.named_scope("merge"):
        for ax in axes:
            scores = jax.lax.all_gather(scores, ax, axis=1, tiled=True)
            ids = jax.lax.all_gather(ids, ax, axis=1, tiled=True)
            scores, pos = jax.lax.top_k(scores, min(k, scores.shape[1]))
            ids = jnp.take_along_axis(ids, pos, axis=1)
    return scores, ids


def default_k_prime_local(cfg_k: int, cfg_k_prime: int, n_shards: int) -> int:
    """Per-shard candidate budget: the paper's k' is a global budget; with N
    corpus shards the expected per-shard share is k'/N, so a 4x oversample
    keeps merge recall while bounding the per-shard rerank at
    O(B · k'_loc · Tq · Td)."""
    return max(cfg_k, (4 * cfg_k_prime + n_shards - 1) // n_shards)


def make_serve_step(mesh: Mesh, cfg: LemurConfig, *,
                    k_prime_local: int | None = None,
                    m_real: int | None = None,
                    use_fused_gather: bool | None = None,
                    use_one_launch: bool | None = None,
                    use_residual: bool | None = None):
    """Returns a jit-able serve_step(state, q_tokens, q_mask) -> (scores, ids).

    Queries are replicated over the corpus shards (the corpus uses every mesh
    axis, so there is no spare axis for query-batch parallelism; batchwise
    throughput comes from the batch dimension itself).

    ``k_prime_local``: per-shard candidate budget; defaults to
    :func:`default_k_prime_local`'s 4x oversample of the global k'.
    ``m_real``: true corpus size when state rows carry padding (see
    :func:`_local_retrieve`).
    ``use_fused_gather``: per-shard rerank through the gather-at-source
    kernel path (default: ``cfg.use_fused_gather``).
    ``use_one_launch``: per-shard latent scan + top-k' as ONE fused kernel
    launch (default: ``cfg.use_one_launch``); ids match the legacy
    scan-then-top-k branch bit for bit on fp32.
    ``use_residual``: the compressed-token-tier compile key (default:
    ``cfg.residual.enabled``, i.e. OFF unless the index was built with the
    residual codec).  The sharded slot pool stores DECODED rows — a
    residual base store is dequantized once at state build (then optionally
    SQ8-requantized per row), never on the serve path — so the knob only
    pins the compiled-step identity to match the single-device facade's
    (backend, resolved-params) cache contract."""
    mesh = auto_axes(mesh)
    axes = corpus_axes(mesh)
    axis_sizes = tuple(mesh.shape[a] for a in axes)
    n_shards = int(np.prod(axis_sizes))
    if k_prime_local is None:
        k_prime_local = default_k_prime_local(cfg.k, cfg.k_prime, n_shards)
    if use_fused_gather is None:
        use_fused_gather = bool(cfg.use_fused_gather)
    if use_one_launch is None:
        use_one_launch = bool(getattr(cfg, "use_one_launch", False))
    if use_residual is None:
        use_residual = bool(getattr(cfg, "residual", None) is not None
                            and cfg.residual.enabled)
    corpus_spec = P(axes)
    body = functools.partial(
        _local_retrieve, k=cfg.k, k_prime=k_prime_local, axes=axes,
        axis_sizes=axis_sizes, m_real=m_real,
        use_fused_gather=bool(use_fused_gather),
        use_one_launch=bool(use_one_launch),
    )
    del use_residual  # resolved + part of the caller's compile key; the
    #                   per-shard body always scans the decoded slot pool

    def serve_step(state: ShardedRetrievalState, q_tokens, q_mask):
        sq8 = state.W_scales is not None
        rows = state.row_ids is not None
        # encode + pool queries batch-sharded (GSPMD), replicate only the
        # pooled (B, d') vectors into the corpus shard_map
        ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        nb = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
        if q_tokens.shape[0] % max(nb, 1) == 0 and ba:
            qt = jax.lax.with_sharding_constraint(
                q_tokens, NamedSharding(mesh, P(ba, None, None)))
        else:
            qt = q_tokens
        psi_q = pool_queries(state.psi, qt.astype(jnp.float32), q_mask)
        psi_q = jax.lax.with_sharding_constraint(
            psi_q, NamedSharding(mesh, P())).astype(q_tokens.dtype)
        in_specs = (P(), corpus_spec, corpus_spec if sq8 else P(),
                    corpus_spec, corpus_spec if sq8 else P(), corpus_spec,
                    corpus_spec if rows else P(),
                    corpus_spec if rows else P(), P(), P())
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P()),
            check_vma=False,
        )(psi_q, state.W, state.W_scales, state.doc_tokens,
          state.doc_scales, state.doc_mask, state.row_ids, state.row_valid,
          q_tokens, q_mask)

    return serve_step


class ShardedIndexState(NamedTuple):
    """Device arrays of a doc-sharded deployment whose shards are one-chip
    indexes (``LemurRetriever.build(mesh=)``), as one pytree.

    ψ and the target stats are replicated.  Every leaf of ``store`` (a
    ``PagedStore``) and ``ann`` (an ``IVFIndex``) is the shards' own arrays,
    of one common shape, joined along axis 0 and sharded over the flattened
    mesh: inside ``shard_map`` shard s sees exactly the store and IVF lists
    a one-chip build of its docs holds, numbered from 0.  ``base`` holds
    each shard's first global doc id."""
    psi: dict
    stats: Any
    store: Any
    ann: Any
    base: jax.Array                 # (n_shards,) int32


def shard_devices(mesh: Mesh) -> list:
    """The devices of ``mesh`` in shard order: block s (along axis 0) of a
    leaf sharded over the flattened mesh lives on the s-th."""
    return list(mesh.devices.flat)


def join_shards(mesh: Mesh, blocks: list):
    """One corpus-sharded pytree from per-shard pytrees of one structure and
    shape, shard s's leaves committed to ``shard_devices(mesh)[s]``: each
    global leaf is their blocks joined along axis 0, without a copy."""
    sharding = NamedSharding(mesh, P(corpus_axes(mesh)))

    def join(*xs):
        shape = (len(xs) * xs[0].shape[0],) + tuple(xs[0].shape[1:])
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        list(xs))

    return jax.tree.map(join, *blocks)


def _over_shards(mesh: Mesh, body, out_specs):
    """``step(state, q_tokens, q_mask)``: ``body(local state, q, qm)`` on
    every shard of a :class:`ShardedIndexState`, the queries replicated."""
    rows = P(corpus_axes(mesh))
    specs = ShardedIndexState(psi=P(), stats=P(), store=rows, ann=rows,
                              base=rows)

    def step(state, q_tokens, q_mask):
        return jax.shard_map(body, mesh=mesh, in_specs=(specs, P(), P()),
                             out_specs=out_specs, check_vma=False)(
            state, q_tokens, q_mask)

    return step


def _shard_index(cfg: LemurConfig, backend: str, state: ShardedIndexState):
    from repro.core.index import LemurIndex

    return LemurIndex(cfg, state.psi, state.stats, state.store, backend,
                      state.ann)


def _global_ids(ids, base):
    return jnp.where(ids >= 0, ids + base[0], -1)


def make_shard_search_step(mesh: Mesh, cfg: LemurConfig, backend: str,
                           params):
    """``serve_step(state, q_tokens, q_mask) -> (scores, ids)``, (B,
    params.k) and replicated, over a :class:`ShardedIndexState`.

    Each shard runs the one-chip ``facade.search_pipeline`` on its own
    index with ``params`` (resolved; its ``k_prime`` is the per-shard
    budget), for at most k' answers, as many as it has candidates; its
    local ids become global ids, then :func:`merge_topk` merges the
    shards' top-k.  Pads stay ``(NEG, -1)``, also where k exceeds every
    shard's k' together."""
    from repro.retriever.facade import search_pipeline

    axes = corpus_axes(mesh)
    local = dataclasses.replace(params, k=min(params.k, params.k_prime))

    def body(state, q, qm):
        s, ids = search_pipeline(_shard_index(cfg, backend, state), q, qm,
                                 local)
        s, ids = merge_topk(s, _global_ids(ids, state.base), k=params.k,
                            axes=axes)
        short = params.k - s.shape[1]
        return (jnp.pad(s, ((0, 0), (0, short)), constant_values=maxsim.NEG),
                jnp.pad(ids, ((0, 0), (0, short)), constant_values=-1))

    return _over_shards(mesh, body, (P(), P()))


def make_shard_candidates_step(mesh: Mesh, cfg: LemurConfig, backend: str,
                               params):
    """``step(state, q_tokens, q_mask) -> ids``, (B, n_shards ×
    params.k_prime): every shard's first-stage candidates
    (``facade.first_stage``) as global ids, shard s's in the s-th block of
    columns, pads ``-1``."""
    from repro.retriever.facade import first_stage

    def body(state, q, qm):
        cand = first_stage(_shard_index(cfg, backend, state), q, qm, params)
        return _global_ids(cand, state.base)

    return _over_shards(mesh, body, P(None, corpus_axes(mesh)))


def make_index_step(mesh: Mesh, cfg: LemurConfig, *, doc_block: int = 128):
    """Distributed OLS indexing step: every shard fits W rows for its local
    doc block against the replicated Gram factor.  jit-able; zero comms."""
    axes = corpus_axes(mesh)
    corpus_spec = P(axes)

    def body(chol_c, feats, x_ols, doc_tokens, doc_mask, mean, std):
        g = maxsim.token_maxsim(x_ols, doc_tokens, doc_mask, block=doc_block)
        g = (g - mean) / std
        rhs = feats.T @ g
        return jax.scipy.linalg.cho_solve((chol_c, False), rhs).T

    def index_step(chol_c, feats, x_ols, doc_tokens, doc_mask, mean, std):
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), corpus_spec, corpus_spec, P(), P()),
            out_specs=corpus_spec,
            check_vma=False,
        )(chol_c, feats, x_ols, doc_tokens, doc_mask, mean, std)

    return index_step
