"""The benchmark's own corpus and queries, made from the seed.

ColBERTv2 token embeddings (d = 128, unit norm) sit near a large set of
centroids: its residual codec keeps a token as a centroid id plus a few
bits per dimension, which works because the residual is small next to the
centroid.  The generator builds that structure directly:

* ``centroids`` unit vectors (ColBERTv2's indexer rule for the corpus's
  token count gives their number, see the configuration file);
* each doc draws ``topics_per_doc`` topics; a topic is a fixed set of
  ``topic_words`` centroids (the words of one subject).  A token is a
  common word with probability ``common_share`` (Zipf over all centroids,
  exponent ``zipf_s``, Zipf's law of word frequency), else a word of one of
  the doc's topics;
* token = unit(centroid + residual), the residual Gaussian with norm about
  ``residual_ratio`` of the centroid's;
* tokens per doc: Poisson(``avg_tokens``) clipped to
  [``min_tokens``, ``max_tokens``].

Queries follow the corpus-query strategy at a fixed ``query_tokens``: a
source doc (distinct per query), that many of its tokens drawn uniformly
with replacement, each perturbed by query-encoder noise of norm about
``query_noise_ratio`` and renormalized.

The corpus is made on the device a block of docs at a time, one jitted
call per block keyed by the block's index, and copied to a host array (the
program's ``build`` and the reference read the host copy).  Seeds of any
size map to keys without collisions (:func:`base_key`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_DOCS = 8192
STREAM_CORPUS, STREAM_TABLES, STREAM_BUILD = 0, 1, 2
STREAM_QUERIES, STREAM_ARRIVALS, STREAM_SAMPLE = 3, 4, 5


def base_key(seed: int):
    """A PRNG key for any whole-number seed: the low 32 bits key it, the
    bits above are folded in (``PRNGKey`` alone drops them)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    return jax.random.fold_in(key, hi) if hi else key


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 64), stream]))


@dataclasses.dataclass(frozen=True)
class Corpus:
    doc_tokens: np.ndarray   # (m, max_tokens, d) fp32, zero rows padded
    doc_mask: np.ndarray     # (m, max_tokens) bool

    @property
    def m(self) -> int:
        return self.doc_tokens.shape[0]

    @property
    def d(self) -> int:
        return self.doc_tokens.shape[-1]

    @property
    def n_tokens(self) -> np.ndarray:
        return self.doc_mask.sum(axis=1).astype(np.int64)


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


@functools.partial(jax.jit, static_argnames=("p",))
def _tables(key, p):
    """Centroids (C, d), topic word lists (topics, words) and the common-
    word CDF (C,), all from the seed."""
    kc, kt = jax.random.split(key)
    cent = _unit(jax.random.normal(kc, (p.centroids, p.d), jnp.float32))
    words = jax.random.randint(kt, (p.topics, p.topic_words), 0, p.centroids,
                               jnp.int32)
    w = 1.0 / jnp.arange(1, p.centroids + 1, dtype=jnp.float32) ** p.zipf_s
    return cent, words, jnp.cumsum(w) / jnp.sum(w)


@functools.partial(jax.jit, static_argnames=("p",))
def _block(key, cent, words, cdf, p):
    """One block of BLOCK_DOCS docs -> (tokens (n, T, d), mask (n, T))."""
    n, T = BLOCK_DOCS, p.max_tokens
    kn, kt, kw, ks, kc, kr = jax.random.split(key, 6)
    count = jnp.clip(jax.random.poisson(kn, p.avg_tokens, (n,)),
                     p.min_tokens, T)
    topics = jax.random.randint(kt, (n, p.topics_per_doc), 0, p.topics)
    which = jax.random.randint(ks, (n, T), 0, p.topics_per_doc)
    word = jax.random.randint(kw, (n, T), 0, p.topic_words)
    topical = words[jnp.take_along_axis(topics, which, axis=1), word]
    u = jax.random.uniform(kc, (n, T, 2))
    common = jnp.minimum(jnp.searchsorted(cdf, u[..., 0]), p.centroids - 1)
    ids = jnp.where(u[..., 1] < p.common_share, common, topical)
    sigma = p.residual_ratio / np.sqrt(p.d)
    tok = _unit(cent[ids] + sigma * jax.random.normal(kr, (n, T, p.d)))
    mask = jnp.arange(T)[None, :] < count[:, None]
    return jnp.where(mask[..., None], tok, 0.0), mask


@dataclasses.dataclass(frozen=True)
class CorpusParams:
    """The ``corpus`` group of a configuration file (hashable: jit-static)."""
    docs: int
    d: int
    avg_tokens: float
    min_tokens: int
    max_tokens: int
    centroids: int
    residual_ratio: float
    topics: int
    topic_words: int
    topics_per_doc: int
    common_share: float
    zipf_s: float
    query_tokens: int
    query_noise_ratio: float

    @classmethod
    def from_config(cls, config: dict) -> "CorpusParams":
        g = config["corpus"]
        return cls(**{f.name: g[f.name] for f in dataclasses.fields(cls)})


def make_corpus(seed: int, p: CorpusParams) -> Corpus:
    """The corpus of ``seed``: made on the device block by block, copied
    into one host array."""
    key = base_key(seed)
    cent, words, cdf = _tables(jax.random.fold_in(key, STREAM_TABLES), p)
    kcorp = jax.random.fold_in(key, STREAM_CORPUS)
    tokens = np.empty((p.docs, p.max_tokens, p.d), np.float32)
    mask = np.empty((p.docs, p.max_tokens), bool)
    for b, lo in enumerate(range(0, p.docs, BLOCK_DOCS)):
        t, mk = _block(jax.random.fold_in(kcorp, b), cent, words, cdf, p)
        hi = min(lo + BLOCK_DOCS, p.docs)
        tokens[lo:hi] = np.asarray(t[:hi - lo])
        mask[lo:hi] = np.asarray(mk[:hi - lo])
    return Corpus(tokens, mask)


def make_queries(corpus: Corpus, n: int, seed: int, p: CorpusParams):
    """``n`` corpus-query queries -> (queries (n, Tq, d) fp32, source doc
    ids (n,)).  Sources are distinct while ``n`` <= m."""
    rng = host_rng(seed, STREAM_QUERIES)
    m, tq = corpus.m, p.query_tokens
    src = np.concatenate([rng.permutation(m)
                          for _ in range(-(-n // m))])[:n]
    pick = (rng.random((n, tq)) * corpus.n_tokens[src][:, None]).astype(
        np.int64)
    q = corpus.doc_tokens[src[:, None], pick]
    sigma = p.query_noise_ratio / np.sqrt(p.d)
    q += sigma * rng.standard_normal(q.shape, dtype=np.float32)
    q /= np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    return q.astype(np.float32), src
