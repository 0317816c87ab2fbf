"""The plain reference: exact MaxSim over the benchmark's own corpus.

score(q, doc) = sum over the query's tokens of the largest dot product with
any of the doc's real tokens (ColBERT's late interaction), in fp32 at
``Precision.HIGHEST`` over the whole corpus (which docs rank first), and
in float64 on the host for the docs a query was served (the scores those
docs were served with are held to it: fp32 MaxSim rounds by about as much
as the program does, float64 by nothing that shows).  It imports nothing
of the program and takes nothing the program made: only the corpus and
queries the benchmark generated.  It runs after the window, once the
program's device state is freed, a slab of docs at a time so it fits
beside nothing else, the slabs spread over the cell's chips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SLAB_DOCS = 8192      # docs uploaded per call
CHUNK_DOCS = 64       # docs scored per step inside a call
ROWS = 64             # queries scored per call of scores_of


@functools.partial(jax.jit, static_argnames=("precision", "chunk"))
def _slab_scores(q, docs, mask, *, precision, chunk):
    """q (N, Tq, d), docs (S, T, d), mask (S, T) -> (N, S) MaxSim."""
    n, tq, d = q.shape
    s, t, _ = docs.shape
    qf = q.reshape(n * tq, d)

    def one(args):
        dc, mc = args                                    # (c, T, d), (c, T)
        dots = jnp.einsum("qd,ctd->qct", qf, dc, precision=precision,
                          preferred_element_type=jnp.float32)
        best = jnp.max(jnp.where(mc[None], dots, -jnp.inf), axis=-1)
        return jnp.sum(best.reshape(n, tq, -1), axis=1)   # (N, c)

    out = jax.lax.map(one, (docs.reshape(s // chunk, chunk, t, d),
                            mask.reshape(s // chunk, chunk, t)))
    return jnp.moveaxis(out, 0, 1).reshape(n, s)


def exact_scores(queries: np.ndarray, doc_tokens: np.ndarray,
                 doc_mask: np.ndarray, *, precision=HIGHEST,
                 devices=(None,)) -> np.ndarray:
    """(N, Tq, d) queries against every doc -> (N, m) fp32 scores.  Docs
    with no real token score 0 (never the case in this corpus).  Slab i of
    ``SLAB_DOCS`` docs is scored on ``devices[i % len(devices)]`` (the
    cell's chips; None is the default device): one slab per chip is in
    flight at a time, and a slab's scores do not depend on its chip."""
    m = doc_tokens.shape[0]
    slab = min(SLAB_DOCS, m)
    chunk = min(CHUNK_DOCS, slab)
    q = [jax.device_put(jnp.asarray(queries, jnp.float32), dev)
         for dev in devices]
    out = np.empty((queries.shape[0], m), np.float32)
    for first in range(0, m, slab * len(devices)):
        running = []
        for i, lo in enumerate(range(first, min(first + slab * len(devices),
                                                m), slab)):
            hi = min(lo + slab, m)
            docs = np.zeros((slab,) + doc_tokens.shape[1:], np.float32)
            mask = np.zeros((slab, doc_mask.shape[1]), bool)
            docs[:hi - lo], mask[:hi - lo] = doc_tokens[lo:hi], doc_mask[lo:hi]
            mask[hi - lo:, 0] = True      # pad docs: any finite score, dropped
            s = _slab_scores(q[i], jax.device_put(docs, devices[i]),
                             jax.device_put(mask, devices[i]),
                             precision=precision, chunk=chunk)
            running.append((lo, hi, s))
        for lo, hi, s in running:
            out[:, lo:hi] = np.asarray(s)[:, :hi - lo]
    return out


def served_scores64(queries: np.ndarray, ids: np.ndarray,
                    doc_tokens: np.ndarray,
                    doc_mask: np.ndarray) -> np.ndarray:
    """MaxSim of each query against its own listed docs, ids (N, k), in
    float64 on the host, one query at a time; -1 entries score NaN."""
    n, k = ids.shape
    out = np.full((n, k), np.nan)
    for i in range(n):
        real = ids[i] >= 0
        if not real.any():
            continue
        docs = doc_tokens[ids[i][real]].astype(np.float64)   # (k, T, d)
        kr, t, d = docs.shape
        dots = (queries[i].astype(np.float64) @ docs.reshape(kr * t, d).T
                ).reshape(-1, kr, t)
        dots = np.where(doc_mask[ids[i][real]][None], dots, -np.inf)
        out[i, real] = dots.max(-1).sum(0)
    return out


def _split(x):
    """fp32 -> (hi, lo) bf16 parts as ``Precision.HIGH`` forms them: hi is
    x rounded to bf16 (to nearest, ties to even), lo is x - hi rounded to
    bf16.  hi is rounded on the bits: a round trip through bf16 may be
    folded away by the compiler, which would leave lo 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("mode",))
def _listed_scores(q, docs, mask, *, mode):
    """q (N, Tq, d) fp32, docs (N, k, T, d) fp32, mask (N, k, T) -> (N, k)
    MaxSim, the dots formed as ``mode`` says."""
    def dot(a, b):
        return jnp.einsum("nqd,nktd->nqkt", a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    if mode == "fp32":
        dots = dot(q, docs)
    elif mode == "bf16":
        dots = dot(q.astype(jnp.bfloat16), docs.astype(jnp.bfloat16))
    elif mode == "bf16_3x":
        (qh, ql), (dh, dl) = _split(q), _split(docs)
        dots = dot(qh, dh) + dot(qh, dl) + dot(ql, dh)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    best = jnp.max(jnp.where(mask[:, None], dots, -jnp.inf), axis=-1)
    return jnp.sum(best, axis=1)


def scores_of(queries: np.ndarray, ids: np.ndarray, doc_tokens: np.ndarray,
              doc_mask: np.ndarray, *, mode: str = "fp32") -> np.ndarray:
    """MaxSim of each query against its own listed docs, ids (N, k); -1
    entries score NaN; ``ROWS`` queries a call.  ``mode`` forms the dots:

    * ``fp32`` -- the reference, fp32 at ``HIGHEST``;
    * ``bf16_3x`` -- the control for fp32 at ``HIGHEST``: what
      ``Precision.HIGH`` does on the MXU, each fp32 input split into two
      bf16 parts and the three larger part products summed in fp32 (the
      lo x lo product dropped), spelt out so that it reads the same on
      every platform;
    * ``bf16`` -- inputs rounded to bf16, one MXU pass."""
    out = np.empty(ids.shape, np.float32)
    for lo in range(0, ids.shape[0], ROWS):
        safe = np.maximum(ids[lo:lo + ROWS], 0)
        out[lo:lo + ROWS] = np.asarray(_listed_scores(
            jnp.asarray(queries[lo:lo + ROWS], jnp.float32),
            jnp.asarray(doc_tokens[safe], jnp.float32),
            jnp.asarray(doc_mask[safe]), mode=mode))
    return np.where(ids >= 0, out, np.nan)


def top_ids(scores: np.ndarray, k: int) -> np.ndarray:
    """Exact top-k doc ids per row, best first (ties: the lower id first;
    which of tied docs at the k-th place enter is arbitrary)."""
    part = np.argpartition(-scores, k, axis=1)[:, :k]
    vals = np.take_along_axis(scores, part, axis=1)
    order = np.lexsort((part, -vals), axis=1)
    return np.take_along_axis(part, order, axis=1)
