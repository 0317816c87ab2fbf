"""IVF index — the TPU-native replacement for Glass/HNSW (DESIGN.md §3).

Build: k-means coarse quantizer over the latent corpus; vectors are packed
into fixed-capacity padded cluster lists (capacity = max cluster size) with
optional SQ8 storage.  Search: one (B, nlist) centroid matmul, top-`nprobe`
clusters, then either the gather-at-source probe scan (default —
``kernels.gather_scan`` DMAs each probed cluster tile straight into VMEM on
TPU) or the legacy gathered block scan, and a masked top-k'.  Everything is
dense matmul + gather — no pointer chasing — so it maps onto MXU tiles and
shards (each device holds a slice of the cluster lists).

The recall/latency knob is ``nprobe`` (HNSW's ef_search analogue, §6.2).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns.base import pad_topk
from repro.anns.kmeans import kmeans
from repro.anns.quantization import (
    ResidualCodec,
    residual_decode,
    residual_encode,
    sq8_dequant,
    sq8_quant,
)
from repro.kernels import ops, ref


class IVFIndex(NamedTuple):
    centroids: jax.Array   # (nlist, d)
    ids: jax.Array         # (nlist, cap) int32, -1 padded
    vecs: jax.Array        # (nlist, cap, d) fp32  OR int8 codes when sq8
                           # OR (nlist, cap, d*bits//8) uint8 packed residual
                           # codes when rq (coded against the OWN cluster
                           # centroid — the id is implicit in the list row)
    scales: jax.Array | None  # (nlist, cap) fp32 when sq8 else None
    counts: jax.Array      # (nlist,) int32
    mean: jax.Array | None = None  # (d,) corpus mean (centered MIPS: ranking
                                   # by q.(w-mean) == ranking by q.w)
    # residual-codec storage tier (None unless built with residual_bits)
    rq_cuts: jax.Array | None = None    # (d, L-1) per-dim bucket boundaries
    rq_values: jax.Array | None = None  # (d, L)   per-dim reconstruction vals

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.ids.shape[1]

    @property
    def residual(self) -> bool:
        return self.rq_values is not None


PACK_BLOCK_BYTES = 1 << 30   # fp32 list gather quantized per SQ8 pack step


def default_nlist(m: int) -> int:
    """Paper's clustering rule (§6.3): 16·sqrt(n) rounded down to pow2 is for
    token-level indexes; for the (much smaller) latent corpus we use
    4·sqrt(m) rounded to pow2, floor 16."""
    raw = 4 * int(np.sqrt(max(m, 1)))
    return max(16, 1 << (raw.bit_length() - 1))


def build_ivf(key, vectors: jax.Array, nlist: int = 0, *, sq8: bool = False,
              residual_bits: int = 0, kmeans_iters: int = 10,
              train_sample: int = 131072, center: bool = True) -> IVFIndex:
    """``center=True`` subtracts the corpus mean before clustering/scan:
    learned LEMUR W rows carry a large shared component (globally
    standardized OLS targets) that otherwise dominates the coarse quantizer;
    MIPS ranking is invariant to it (q·mean is constant per query).

    ``residual_bits`` (2 or 4) switches the list storage to the residual
    codec: each vector is kept as a packed 2/4-bit per-dim residual against
    its OWN cluster centroid (the centroid id is the list row — free), with
    per-dim bucket boundaries/values trained from the corpus residual
    quantiles.  Supersedes ``sq8`` (d/2 or d/4 bytes/vector vs d+4)."""
    m, d = vectors.shape
    mean = None
    if center:
        mean = jnp.mean(vectors, axis=0)
        vectors = vectors - mean[None, :]
    nlist = nlist or default_nlist(m)
    ktrain, kassign = jax.random.split(jax.random.PRNGKey(0) if key is None else key)
    sample = vectors
    if m > train_sample:
        idx = jax.random.choice(ktrain, m, (train_sample,), replace=False)
        sample = vectors[idx]
    centroids, _ = kmeans(ktrain, sample, nlist, iters=kmeans_iters)
    assign = assign_clusters(vectors, centroids)  # full corpus
    ids, vecs, scales, counts = _pack_lists(vectors, np.asarray(assign), nlist,
                                            sq8=sq8 and not residual_bits)
    if residual_bits:
        cuts, values = _train_rq(vecs, ids, centroids, int(residual_bits))
        vecs = _residual_pack(centroids, cuts, values, ids, vecs)
        return IVFIndex(centroids, ids, vecs, None, counts, mean,
                        rq_cuts=cuts, rq_values=values)
    return IVFIndex(centroids, ids, vecs, scales, counts, mean)


def assign_clusters(vectors: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest-centroid assignment (MIPS form with the -||c||²/2 correction)."""
    half = 0.5 * jnp.sum(jnp.square(centroids), axis=1)
    return jnp.argmax(vectors @ centroids.T - half[None, :], axis=1)


def _pack_lists(vectors, assign: np.ndarray, nlist: int, *, sq8: bool,
                cap_floor: int = 1):
    """Pack vectors into fixed-capacity padded cluster lists (host-side).

    ``cap`` is bucketed to a power of two (and never below ``cap_floor`` —
    :func:`extend_ivf` passes the old capacity so adds can only keep or
    double it): the list shapes are jit-static, so shape-stable adds leave
    compiled query fns alive instead of retracing per add."""
    from repro.core.pages import next_pow2

    counts = np.bincount(assign, minlength=nlist)
    cap = max(next_pow2(int(max(1, counts.max()))), int(cap_floor))
    ids = np.full((nlist, cap), -1, np.int32)
    order = np.argsort(assign, kind="stable")
    pos = np.zeros(nlist, np.int64)
    for i in order:
        c = assign[i]
        ids[c, pos[c]] = i
        pos[c] += 1
    ids = jnp.asarray(ids)
    vectors = jnp.asarray(vectors)

    def pack(rows):        # (n, cap) ids -> (n, cap, d) lists (+ SQ8 codes)
        v = jnp.take(vectors, jnp.maximum(rows, 0), axis=0)
        v = v * (rows >= 0)[..., None]
        return sq8_quant(v) if sq8 else (v, None)

    # SQ8 lists are quantized a block of lists at a time (per-row scales, so
    # the result is the same) and the blocks are joined on the host: the
    # device never holds the fp32 (nlist, cap, d) gather, nor two copies of
    # the int8 lists
    step = nlist if not sq8 else max(1, (PACK_BLOCK_BYTES // 4)
                                     // (cap * vectors.shape[1]))
    if step >= nlist:
        vecs, scales = pack(ids)
    else:
        parts = [jax.device_get(pack(ids[lo:lo + step]))
                 for lo in range(0, nlist, step)]
        vecs = jnp.asarray(np.concatenate([v for v, _ in parts]))
        scales = jnp.asarray(np.concatenate([sc for _, sc in parts]))
    return ids, vecs, scales, jnp.asarray(counts, jnp.int32)


def _train_rq(vecs_fp, ids, centroids, bits: int):
    """Per-dim residual quantile tables over the packed lists' VALID rows:
    cuts at (l+1)/L, reconstruction values at bucket midpoints (l+0.5)/L
    (same rule as ``quantization.train_residual_codec``, but the residuals
    are against each vector's own cluster centroid)."""
    L = 1 << int(bits)
    r = np.asarray(vecs_fp - centroids[:, None, :])[np.asarray(ids) >= 0]
    rv = jnp.asarray(r, jnp.float32)                    # (n_valid, d)
    qs_cut = jnp.arange(1, L, dtype=jnp.float32) / L
    qs_val = (jnp.arange(L, dtype=jnp.float32) + 0.5) / L
    cuts = jnp.quantile(rv, qs_cut, axis=0).T           # (d, L-1)
    values = jnp.quantile(rv, qs_val, axis=0).T         # (d, L)
    return cuts, values


def _residual_pack(centroids, cuts, values, ids, vecs_fp):
    """fp32 padded lists (nlist, cap, d) -> packed residual codes
    (nlist, cap, d*bits//8) uint8 coded against the own-cluster centroid."""
    codec = ResidualCodec(centroids=centroids, cuts=cuts, values=values)
    nlist, cap = ids.shape
    cent = jnp.broadcast_to(
        jnp.arange(nlist, dtype=jnp.int32)[:, None], (nlist, cap))
    _, packed = residual_encode(codec, vecs_fp, cent)
    return jnp.where((ids >= 0)[..., None], packed, jnp.uint8(0))


def _residual_unpack(index: IVFIndex) -> jax.Array:
    """Decode the packed lists back to (nlist, cap, d) fp32 (centered)."""
    codec = ResidualCodec(centroids=index.centroids, cuts=index.rq_cuts,
                          values=index.rq_values)
    nlist, cap = index.ids.shape
    cent = jnp.broadcast_to(
        jnp.arange(nlist, dtype=jnp.int32)[:, None], (nlist, cap))
    full = residual_decode(codec, cent, index.vecs)
    return full * (index.ids >= 0)[..., None]


def extend_ivf(index: IVFIndex, new_vectors: jax.Array) -> IVFIndex:
    """Incremental add: assign new vectors to the FROZEN coarse quantizer and
    re-pack the padded lists (host-side, like build).  New docs get ids
    continuing the existing numbering; centroids/mean are not re-fit, so
    recall degrades only as far as the data drifts from the original
    clustering."""
    nlist, d = index.centroids.shape
    newv = jnp.asarray(new_vectors)
    if index.mean is not None:
        newv = newv - index.mean[None, :]
    assign_new = np.asarray(assign_clusters(newv, index.centroids))

    ids = np.asarray(index.ids)
    valid = ids >= 0
    m_old = int(valid.sum())
    m_new = newv.shape[0]
    sq8 = index.scales is not None
    rq = index.residual
    # reconstruct the (centered) stored vectors; SQ8 requant is exact because
    # each row's max code is 127, so the recomputed scale equals the old one;
    # residual re-encode is code-stable because decode reconstructs bucket
    # MIDPOINTS, which fall strictly inside their own bucket and so re-bucket
    # to the same code — repeated adds never drift the retained rows
    if rq:
        full = _residual_unpack(index)
    elif sq8:
        full = sq8_dequant(index.vecs, index.scales)
    else:
        full = index.vecs
    full = np.asarray(full)
    all_vecs = np.zeros((m_old + m_new, d), np.float32)
    all_assign = np.zeros(m_old + m_new, np.int64)
    cluster_of = np.broadcast_to(np.arange(nlist)[:, None], ids.shape)
    all_vecs[ids[valid]] = full[valid]
    all_assign[ids[valid]] = cluster_of[valid]
    all_vecs[m_old:] = np.asarray(newv)
    all_assign[m_old:] = assign_new
    ids2, vecs2, scales2, counts2 = _pack_lists(all_vecs, all_assign, nlist,
                                                sq8=sq8,
                                                cap_floor=index.capacity)
    if rq:
        # the trained tables are FROZEN like the coarse quantizer — new
        # vectors are coded with the existing cuts/values
        vecs2 = _residual_pack(index.centroids, index.rq_cuts,
                               index.rq_values, ids2, vecs2)
    return IVFIndex(index.centroids, ids2, vecs2, scales2, counts2,
                    index.mean, rq_cuts=index.rq_cuts,
                    rq_values=index.rq_values)


def pad_lists(index: IVFIndex, cap: int) -> IVFIndex:
    """``index`` with its lists padded to ``cap`` slots (``-1`` ids, zero
    vectors and scales), so indexes of several corpora can share one
    shape.  Pads score ``-inf`` in every scan, so search answers as
    before."""
    extra = cap - index.capacity
    if extra < 0:
        raise ValueError(f"cap {cap} < the lists' {index.capacity}")
    if not extra:
        return index

    def pad(a, value=0):
        widths = [(0, 0)] * a.ndim
        widths[1] = (0, extra)
        return jnp.pad(a, widths, constant_values=value)

    return index._replace(
        ids=pad(index.ids, -1), vecs=pad(index.vecs),
        scales=None if index.scales is None else pad(index.scales))


def probe_lists(index: IVFIndex, q: jax.Array, nprobe: int) -> jax.Array:
    """(B, d) queries -> (B, nprobe) ids of the best-scoring centroids.
    Scored at full fp32 precision: on the TPU, XLA's default rounds a
    batched product through bf16 but computes a one-row one in fp32, so a
    query's probes would depend on the size of its batch."""
    cs = jnp.matmul(q, index.centroids.T, precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(cs, nprobe)[1]


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "use_fused_gather"))
def search_ivf(index: IVFIndex, q: jax.Array, nprobe: int, k: int,
               use_fused_gather: bool = False):
    """q: (B, d) -> (scores (B, k), ids (B, k)).

    ``use_fused_gather=True`` scores the probed cluster lists through the
    gather-at-source kernel path (``ops.fused_ivf_scan``: the scalar-prefetch
    Pallas scan on TPU, its gather-then-score oracle elsewhere) — only the
    ``(B, nprobe, cap)`` id strip is ever gathered in HBM.  ``False`` keeps
    the legacy materialize-then-score path benchmarkable.
    """
    B, d = q.shape
    probe = probe_lists(index, q, nprobe)          # (B, nprobe)
    ids = jnp.take(index.ids, probe, axis=0)       # (B, nprobe, cap)
    if index.residual:
        # decode-at-source scan (in-kernel on TPU); the "legacy" path for
        # this tier IS the decode-then-score oracle, so use_fused_gather
        # only decides whether the Pallas kernel may be used
        s = ops.fused_ivf_scan_res(q, probe, index.ids, index.vecs,
                                   index.centroids, index.rq_values,
                                   use_kernel=None if use_fused_gather
                                   else False)
    elif use_fused_gather:
        # masked -inf inside the scan (same pad convention as below)
        s = ops.fused_ivf_scan(q, probe, index.ids, index.vecs, index.scales)
    else:
        vecs = ref.take_lists(index.vecs, probe)   # (B, nprobe, cap, d)
        cap = vecs.shape[2]
        if index.scales is not None:
            # batched SQ8 scan: all B queries' gathered lists in ONE call
            # (the old path vmapped B one-row mips_sq8 launches — 1/128 MXU
            # tile utilization at block_q=128)
            sc = jnp.take(index.scales, probe, axis=0)         # (B, P, cap)
            s = ops.mips_sq8_batched(q, vecs.reshape(B, -1, d),
                                     sc.reshape(B, -1))        # (B, P*cap)
            s = s.reshape(B, nprobe, cap)
        else:
            s = jnp.einsum("bd,bpcd->bpc", q, vecs.astype(q.dtype),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        s = jnp.where(ids >= 0, s, -jnp.inf)
    flat_s = s.reshape(B, -1)
    flat_i = ids.reshape(B, -1)
    kk = min(k, flat_s.shape[1])
    top, pos = jax.lax.top_k(flat_s, kk)
    out_ids = jnp.take_along_axis(flat_i, pos, axis=1)
    return pad_topk(top, out_ids, k)


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def search_ivf_one_launch(index: IVFIndex, psi_params, q_tokens, q_mask,
                          nprobe: int, k: int):
    """One-launch first stage: raw query TOKENS in, top-k' candidates out.

    Unlike :func:`search_ivf` this takes the query tokens, not the pooled
    latent — the ψ projection, pooling, probe scan and top-k' all happen in
    ONE Pallas launch on TPU (``ops.fused_query``; its legacy-composition
    oracle elsewhere), so the ``(B, Tq, d')`` features and the
    ``(B, nprobe, cap)`` score strip never round-trip HBM.  Same math as
    ``pool_queries`` + :func:`search_ivf` — fp32 ids are bit-identical.
    q_tokens: (B, Tq, d) -> (scores (B, k), ids (B, k))."""
    kp = min(k, nprobe * index.capacity)
    if index.residual:
        top, out_ids = ops.fused_query_res(
            q_tokens, q_mask, psi_params, index.centroids, index.ids,
            index.vecs, index.rq_values, nprobe=nprobe, kp=kp)
    else:
        top, out_ids = ops.fused_query(
            q_tokens, q_mask, psi_params, index.centroids, index.ids,
            index.vecs, index.scales, nprobe=nprobe, kp=kp)
    return pad_topk(top, out_ids, k)
