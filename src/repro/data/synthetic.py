"""Synthetic dataset generators (no BEIR/ViDoRe/Criteo/OGB offline).

The multi-vector corpus generator is statistically matched to the paper's
setting (Table 1): unit-norm token embeddings, variable tokens/doc, topical
cluster structure so that MaxSim has learnable signal, and three query
distributions mirroring §4.2 / App. D:

* ``queries_from_corpus_query``  — documents re-encoded "as queries"
  (token subset + query-encoder noise + fixed query length): the paper's
  default *corpus-query* strategy.
* ``queries_from_corpus``        — raw document token samples (*corpus*).
* ``queries_held_out``           — fresh queries from the topic model
  (*query* strategy; mimics actual training queries).

All generators return numpy (host) arrays; the loader shards them onto the
mesh.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class MultiVectorCorpus:
    doc_tokens: np.ndarray  # (m, T_max, d) fp32, unit-norm rows (zeros padded)
    doc_mask: np.ndarray    # (m, T_max) bool
    topics: np.ndarray      # (m, n_topics_per_doc) int32 (generator metadata)
    centers: np.ndarray     # (K, d)

    @property
    def m(self) -> int:
        return self.doc_tokens.shape[0]

    @property
    def d(self) -> int:
        return self.doc_tokens.shape[-1]


def _unit(x: np.ndarray, axis: int = -1) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=axis, keepdims=True), 1e-9)


def make_corpus(
    m: int = 20000,
    d: int = 64,
    avg_tokens: int = 24,
    max_tokens: int = 32,
    n_centers: int = 256,
    topics_per_doc: int = 2,
    topic_strength: float = 1.2,
    seed: int = 0,
) -> MultiVectorCorpus:
    rng = np.random.default_rng(seed)
    centers = _unit(rng.standard_normal((n_centers, d), dtype=np.float32))
    topics = rng.integers(0, n_centers, size=(m, topics_per_doc), dtype=np.int32)
    counts = np.clip(rng.poisson(avg_tokens, size=m), 4, max_tokens).astype(np.int32)

    tok = rng.standard_normal((m, max_tokens, d), dtype=np.float32)
    which = rng.integers(0, topics_per_doc, size=(m, max_tokens))
    mask = np.arange(max_tokens)[None, :] < counts[:, None]
    # finished in place, a block of docs at a time: the same values as the
    # whole-array expressions, without (m, T, d) temporaries
    for lo in range(0, m, 4096):
        t = tok[lo:lo + 4096]
        c = centers[np.take_along_axis(topics[lo:lo + 4096],
                                       which[lo:lo + 4096], axis=1)]
        c *= topic_strength
        t += c
        t /= np.maximum(np.linalg.norm(t, axis=-1, keepdims=True), 1e-9)
        t *= mask[lo:lo + 4096, :, None]
    return MultiVectorCorpus(tok, mask, topics, centers)


def queries_from_corpus_query(
    corpus: MultiVectorCorpus,
    n_queries: int,
    q_tokens: int = 8,
    encoder_noise: float = 0.25,
    seed: int = 1,
) -> np.ndarray:
    """Paper-default *corpus-query* strategy: re-encode sampled docs as
    queries (subset of doc tokens + query-encoder perturbation, fixed
    length).  Returns (n_queries, q_tokens, d) unit-norm."""
    rng = np.random.default_rng(seed)
    docs = rng.integers(0, corpus.m, size=n_queries)
    counts = corpus.doc_mask.sum(1)[docs]
    pick = (rng.random((n_queries, q_tokens)) * counts[:, None]).astype(np.int64)
    toks = corpus.doc_tokens[docs[:, None], pick]  # (n, q, d)
    toks = toks + encoder_noise * rng.standard_normal(toks.shape).astype(np.float32)
    return _unit(toks)


def queries_from_corpus(
    corpus: MultiVectorCorpus, n_queries: int, q_tokens: int = 8, seed: int = 1
) -> np.ndarray:
    """*corpus* strategy (App. D.1): raw document-encoder token samples."""
    rng = np.random.default_rng(seed)
    docs = rng.integers(0, corpus.m, size=n_queries)
    counts = corpus.doc_mask.sum(1)[docs]
    pick = (rng.random((n_queries, q_tokens)) * counts[:, None]).astype(np.int64)
    return corpus.doc_tokens[docs[:, None], pick].astype(np.float32)


def queries_held_out(
    corpus: MultiVectorCorpus, n_queries: int, q_tokens: int = 8,
    topic_strength: float = 1.2, seed: int = 2
) -> np.ndarray:
    """*query* strategy (App. D.2): fresh queries from the same topic model."""
    rng = np.random.default_rng(seed)
    d = corpus.d
    t = rng.integers(0, corpus.centers.shape[0], size=n_queries)
    tok = rng.standard_normal((n_queries, q_tokens, d), dtype=np.float32)
    return _unit(tok + topic_strength * corpus.centers[t][:, None, :])


# ---------------------------------------------------------------------------
# LM token streams
# ---------------------------------------------------------------------------

def lm_token_batches(vocab: int, batch: int, seq: int, n_batches: int, seed: int = 0):
    """Zipf-ish synthetic token stream; yields (tokens, labels) int32 pairs."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / ranks
    p /= p.sum()
    for _ in range(n_batches):
        toks = rng.choice(vocab, size=(batch, seq + 1), p=p).astype(np.int32)
        yield toks[:, :-1], toks[:, 1:]


# ---------------------------------------------------------------------------
# graphs (MeshGraphNet-style simulation meshes + big CSR graphs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Graph:
    senders: np.ndarray     # (E,) int32
    receivers: np.ndarray   # (E,) int32
    node_feat: np.ndarray   # (N, d) fp32
    edge_feat: np.ndarray   # (E, de) fp32
    labels: np.ndarray      # (N, dy) fp32 regression targets
    row_ptr: np.ndarray     # (N+1,) CSR over incoming edges (for sampling)
    col_idx: np.ndarray     # (E,)


def make_mesh_graph(n_nodes: int, avg_degree: int = 6, d_feat: int = 16,
                    d_edge: int = 4, d_out: int = 2, seed: int = 0) -> Graph:
    """Random geometric graph ~= a 2-D simulation mesh (MeshGraphNet regime)."""
    rng = np.random.default_rng(seed)
    pos = rng.random((n_nodes, 2), dtype=np.float32)
    # k-nearest by grid hashing (cheap O(N k) approximation, fine for synthesis)
    k = max(2, avg_degree // 2)
    idx = np.argsort(pos[:, 0], kind="stable")
    senders, receivers = [], []
    for j in range(1, k + 1):
        senders.append(idx[:-j])
        receivers.append(idx[j:])
    s = np.concatenate(senders + receivers)
    r = np.concatenate(receivers + senders)
    rel = pos[s] - pos[r]
    dist = np.linalg.norm(rel, axis=1, keepdims=True)
    edge_feat = np.concatenate(
        [rel, dist, np.ones_like(dist)], axis=1
    )[:, :d_edge].astype(np.float32)
    node_feat = np.concatenate(
        [pos, rng.standard_normal((n_nodes, max(0, d_feat - 2)), dtype=np.float32)], axis=1
    )[:, :d_feat].astype(np.float32)
    labels = np.stack(
        [np.sin(4 * np.pi * pos[:, 0]), np.cos(4 * np.pi * pos[:, 1])], axis=1
    )[:, :d_out].astype(np.float32)

    order = np.argsort(r, kind="stable")
    s, r = s[order].astype(np.int32), r[order].astype(np.int32)
    edge_feat = edge_feat[order]
    row_ptr = np.zeros(n_nodes + 1, np.int64)
    np.add.at(row_ptr, r + 1, 1)
    row_ptr = np.cumsum(row_ptr).astype(np.int64)
    return Graph(s, r, node_feat, edge_feat, labels, row_ptr, s.copy())


# ---------------------------------------------------------------------------
# recsys click logs
# ---------------------------------------------------------------------------

def make_clicks(batch: int, n_fields: int, vocab_sizes: np.ndarray, seed: int = 0,
                hist_len: int = 0, n_items: int = 0):
    """Power-law categorical ids + planted-logistic labels.  Returns dict."""
    rng = np.random.default_rng(seed)
    ids = np.stack(
        [
            np.minimum(
                rng.zipf(1.2, size=batch) - 1, vocab_sizes[f] - 1
            ).astype(np.int32)
            for f in range(n_fields)
        ],
        axis=1,
    )  # (batch, n_fields)
    w = rng.standard_normal(n_fields).astype(np.float32) * 0.3
    logit = (np.sin(ids[:, : n_fields]) * w[None, :]).sum(1)
    labels = (rng.random(batch) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    out = {"ids": ids, "labels": labels}
    if hist_len:
        out["history"] = np.minimum(
            rng.zipf(1.2, size=(batch, hist_len)) - 1, n_items - 1
        ).astype(np.int32)
        out["target_item"] = np.minimum(
            rng.zipf(1.2, size=batch) - 1, n_items - 1
        ).astype(np.int32)
    return out
