"""MaxSim similarity (eq. 1) — reference ops used across the framework.

All functions are pure jnp and memory-bounded: the corpus axis is processed
in blocks with ``lax.map`` so the (B, m, Tq, Td) score tensor never
materializes beyond one block.  ``repro.kernels.maxsim`` provides the Pallas
TPU kernel for the same contraction; these ops are its oracle and the
portable fallback inside jitted system graphs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG = -1e30
# MaxSim is an exact score: fp32 operands contract at full fp32 precision
# (XLA's TPU default would round them through bf16).  The OLS target
# generator token_maxsim keeps the default — it feeds a least-squares fit.
HIGHEST = jax.lax.Precision.HIGHEST


def maxsim_pair(q, q_mask, c, c_mask):
    """MaxSim(X, C) for one pair.  q: (Tq, d); c: (Td, d)."""
    s = jnp.matmul(q, c.T, precision=HIGHEST)  # (Tq, Td)
    s = jnp.where(c_mask[None, :], s, NEG)
    best = jnp.max(s, axis=-1)
    best = jnp.where(q_mask, best, 0.0)
    return jnp.sum(best)


def _score_block(q, q_mask, docs, docs_mask):
    """q: (B, Tq, d); docs: (Mb, Td, d) -> (B, Mb)."""
    s = jnp.einsum("bqd,mtd->bmqt", q, docs, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(docs_mask[None, :, None, :], s, NEG)
    best = jnp.max(s, axis=-1)  # (B, Mb, Tq)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    return jnp.sum(best, axis=-1)


def maxsim_scores(q, q_mask, docs, docs_mask, *, block: int = 1024):
    """MaxSim of each query against every doc.  q: (B, Tq, d);
    docs: (m, Td, d) -> (B, m) fp32."""
    m = docs.shape[0]
    if m <= block:
        return _score_block(q, q_mask, docs, docs_mask)
    nb = -(-m // block)
    pad = nb * block - m
    docs_p = jnp.pad(docs, ((0, pad), (0, 0), (0, 0)))
    mask_p = jnp.pad(docs_mask, ((0, pad), (0, 0)))
    db = docs_p.reshape(nb, block, *docs.shape[1:])
    mb = mask_p.reshape(nb, block, docs.shape[1])
    out = jax.lax.map(lambda xs: _score_block(q, q_mask, xs[0], xs[1]), (db, mb))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], nb * block)[:, :m]


BLOCK_BYTES = 1 << 30   # the (n, block, T) fp32 score block of token_maxsim


def token_maxsim(x, docs, docs_mask, *, block: int | None = None):
    """g(x)_l = max_{c in C_l} <c, x>  (§3.1).  x: (n, d) -> (n, m) fp32.

    This is both the OLS/MLP training target generator and the per-token
    inner loop of reranking.  The corpus axis is processed ``block`` docs
    at a time; by default as many (up to 1024) as keep the ``(n, block,
    T)`` score block within :data:`BLOCK_BYTES`."""
    m = docs.shape[0]
    if block is None:
        per_doc = x.shape[0] * docs.shape[1] * 4
        block = max(1, min(1024, BLOCK_BYTES // max(per_doc, 1)))

    def blk(d, dm):
        s = jnp.einsum("nd,mtd->nmt", x, d, preferred_element_type=jnp.float32)
        s = jnp.where(dm[None, :, :], s, NEG)
        return jnp.max(s, axis=-1)

    if m <= block:
        return blk(docs, docs_mask)
    nb = -(-m // block)
    pad = nb * block - m
    docs_p = jnp.pad(docs, ((0, pad), (0, 0), (0, 0)))
    mask_p = jnp.pad(docs_mask, ((0, pad), (0, 0)))
    db = docs_p.reshape(nb, block, *docs.shape[1:])
    mb = mask_p.reshape(nb, block, docs.shape[1])
    out = jax.lax.map(lambda xs: blk(xs[0], xs[1]), (db, mb))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], nb * block)[:, :m]


def rerank(q, q_mask, cand_ids, docs, docs_mask, k: int):
    """Exact MaxSim rerank of candidates (the second stage of Fig. 1).

    q: (B, Tq, d); cand_ids: (B, k') -> (topk_scores (B, k), topk_ids (B, k)).

    ``-1``-padded candidate rows (first-stage backends pad short results)
    score ``NEG`` so a pad can only surface — still carrying id ``-1`` — when
    a row has fewer than ``k`` real candidates.  Clamping pads to doc 0
    instead would duplicate doc 0 and inflate recall.
    """
    valid = cand_ids >= 0                       # (B, k')
    safe = jnp.maximum(cand_ids, 0)
    cd = jnp.take(docs, safe, axis=0)           # (B, k', Td, d)
    cm = jnp.take(docs_mask, safe, axis=0)      # (B, k', Td)
    s = jnp.einsum("bqd,bmtd->bmqt", q, cd, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(cm[:, :, None, :], s, NEG)
    best = jnp.max(s, axis=-1)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    scores = jnp.sum(best, axis=-1)             # (B, k')
    scores = jnp.where(valid, scores, NEG)
    top, idx = jax.lax.top_k(scores, k)
    return top, jnp.take_along_axis(cand_ids, idx, axis=1)


def rerank_gathered(q, q_mask, cand_ids, cand_docs, cand_mask, k: int):
    """:func:`rerank` over PRE-GATHERED candidate docs — the legacy-path
    twin for the paged store, where candidates are materialized from token
    pages (``pages.gather_docs``) instead of ``jnp.take`` on a dense corpus.

    q: (B, Tq, d); cand_docs: (B, k', Tm, d); cand_mask: (B, k', Tm) ->
    (topk_scores (B, k), topk_ids (B, k)).  Same NEG/pad semantics as
    :func:`rerank`; per-token dots and the order-independent max make the
    scores bit-identical to the dense layout's."""
    valid = cand_ids >= 0
    s = jnp.einsum("bqd,bmtd->bmqt", q, cand_docs, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(cand_mask[:, :, None, :], s, NEG)
    best = jnp.max(s, axis=-1)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    scores = jnp.sum(best, axis=-1)
    scores = jnp.where(valid, scores, NEG)
    top, idx = jax.lax.top_k(scores, k)
    return top, jnp.take_along_axis(cand_ids, idx, axis=1)


def true_topk(q, q_mask, docs, docs_mask, k: int, *, block: int = 1024):
    """Exact MaxSim k-nn (ground truth for recall eval)."""
    scores = maxsim_scores(q, q_mask, docs, docs_mask, block=block)
    return jax.lax.top_k(scores, k)


def recall_at(retrieved, truth) -> jnp.ndarray:
    """Recall (eq. 3): |retrieved ∩ truth| / |truth| per row."""
    hits = (retrieved[:, :, None] == truth[:, None, :]).any(axis=1)
    return hits.mean(axis=-1)
