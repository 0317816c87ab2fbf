"""jit'd public wrappers over the Pallas kernels with platform dispatch.

On TPU the Pallas path compiles natively; on this CPU container the kernels
run in ``interpret=True`` mode (Python-interpreted kernel body — exact
semantics, slow), so system-level code defaults to the pure-jnp reference
unless ``use_kernel=True`` is forced (tests do force it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused_psi as _fp
from repro.kernels import gather_scan as _gs
from repro.kernels import maxsim as _mx
from repro.kernels import mips_sq8 as _mq
from repro.kernels import query_fused as _qf
from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _one_launch_kernel(use_kernel: bool | None) -> bool:
    """Kernel dispatch for the one-launch first stages, whose carried top-k'
    merge calls ``jax.lax.top_k`` inside the kernel.  Mosaic has no lowering
    for ``top_k`` (jax 0.9), so on the TPU these paths raise rather than
    quietly answering from the XLA reference."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel and _on_tpu():
        raise NotImplementedError(
            "one-launch first stage (use_one_launch=True) is not available "
            "on TPU: Mosaic cannot lower the in-kernel jax.lax.top_k merge; "
            "serve with use_one_launch=False")
    return use_kernel


def token_maxsim(x, doc_tokens, doc_mask, *, use_kernel: bool | None = None,
                 block_n: int = 256, block_m: int = 64):
    """(n, d) x (m, T, d) -> (n, m) fp32 per-token MaxSim contributions."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return ref.token_maxsim_ref(x, doc_tokens, doc_mask)
    return _mx.token_maxsim(
        x, doc_tokens, doc_mask, block_n=block_n, block_m=block_m,
        interpret=not _on_tpu(),
    )


def maxsim_scores(q, q_mask, doc_tokens, doc_mask, *, use_kernel: bool | None = None):
    """(B, Tq, d) -> (B, m): full MaxSim via the token kernel + masked sum."""
    B, Tq, d = q.shape
    g = token_maxsim(q.reshape(B * Tq, d), doc_tokens, doc_mask, use_kernel=use_kernel)
    g = g.reshape(B, Tq, -1)
    return jnp.sum(jnp.where(q_mask[:, :, None], g, 0.0), axis=1)


def fused_psi(x, psi_params, *, use_kernel: bool | None = None, block_n: int = 256):
    """Fused ψ(x) (see repro.core.model.psi_apply for the unfused version)."""
    kernel = psi_params["dense"]["kernel"]
    bias = psi_params["dense"]["bias"]
    g = psi_params["ln"]["scale"]
    b = psi_params["ln"]["bias"]
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return ref.fused_psi_ref(x, kernel, bias, g, b)
    return _fp.fused_psi(x, kernel, bias, g, b, block_n=block_n,
                         interpret=not _on_tpu())


def mips_sq8(q, codes, scales, *, use_kernel: bool | None = None,
             block_q: int = 128, block_m: int = 1024):
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return ref.mips_sq8_ref(q, codes, scales)
    return _mq.mips_sq8(q, codes, scales, block_q=block_q, block_m=block_m,
                        interpret=not _on_tpu())


def mips_sq8_batched(q, codes, scales, *, use_kernel: bool | None = None,
                     block_q: int = 128, block_m: int = 1024):
    """Per-query SQ8 scan: q (B, d) x codes (B, n, d) / scales (B, n) ->
    (B, n), every query scoring its OWN gathered list.

    The fallback is ONE batched contraction (``ref.mips_sq8_batched_ref``)
    instead of B one-row ``mips_sq8`` calls (1/128 MXU tile utilization at
    ``block_q=128``).  The kernel path flattens the per-query lists into a
    single ``mips_sq8`` launch — the B query rows fill a whole MXU tile,
    whose off-diagonal strips were dead weight in the one-row calls anyway
    — and slices each query's own strip back out.  Prefer
    :func:`fused_ivf_scan` on TPU: it skips the HBM gather entirely.
    """
    B, n, d = codes.shape
    # the flattened launch materializes a (B, B*n) score matrix before the
    # strip slice; past ~256 MB that HBM spike costs more than the tile-
    # utilization win, so large shapes take the single-contraction fallback
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel or B * B * n * 4 > 256 * 2**20:
        return ref.mips_sq8_batched_ref(q, codes, scales)
    full = _mq.mips_sq8(q, codes.reshape(B * n, d), scales.reshape(B * n),
                        block_q=block_q, block_m=block_m,
                        interpret=not _on_tpu())            # (B, B*n)
    strip = jnp.arange(B)[:, None] * n + jnp.arange(n)[None, :]
    return jnp.take_along_axis(full, strip, axis=1)         # (B, n)


def fused_ivf_scan(q, probe, ids, vecs, scales=None, *,
                   use_kernel: bool | None = None):
    """Gather-at-source IVF probe scan: score the probed cluster lists
    without materializing the ``(B, nprobe, cap, d)`` gather in HBM.

    q: (B, d); probe: (B, nprobe) int32; ids/vecs/scales are the IVF
    index's padded cluster lists -> (B, nprobe, cap) fp32 scores, pad slots
    ``-inf``.  TPU: the scalar-prefetch Pallas kernel
    (:func:`repro.kernels.gather_scan.ivf_probe_scan`); otherwise the
    gather-then-score oracle (identical math to the legacy path).
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return ref.ivf_scan_ref(q, probe, ids, vecs, scales)
    return _gs.ivf_probe_scan(q, probe, ids, vecs, scales,
                              interpret=not _on_tpu())


def fused_ivf_scan_res(q, probe, ids, codes, centroids, values, *,
                       use_kernel: bool | None = None):
    """Residual-tier IVF probe scan: the packed 2/4-bit cluster lists are
    decoded at the source (in-kernel on TPU) — the fp32 lists never exist.

    q: (B, d); probe: (B, nprobe) int32; ids (nlist, cap) / codes (nlist,
    cap, db) uint8 coded against each cluster's own centroid; centroids
    (nlist, d); values (d, L) -> (B, nprobe, cap) fp32, pad slots ``-inf``.
    Decode is bit-identical between the kernel (one-hot/select-sum) and the
    host oracle (``quantization.residual_decode``), so both paths agree.
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return ref.ivf_scan_res_ref(q, probe, ids, codes, centroids, values)
    return _gs.ivf_probe_res_scan(q, probe, ids, codes, centroids, values,
                                  interpret=not _on_tpu())


def fused_rerank(q, q_mask, cand_ids, doc_tokens, doc_mask, k: int, *,
                 doc_scales=None, use_kernel: bool | None = None):
    """Fused candidate-gather exact MaxSim rerank -> (scores, ids), (B, k).

    Drop-in for ``core.maxsim.rerank`` (same ``-1``-pad contract: pads
    score ``NEG`` and can only surface, id ``-1``, when a row has fewer
    than ``k`` real candidates; rows are padded out to ``k`` when
    ``k > k'``).  ``doc_scales`` selects the SQ8 token store (per-token
    scales folded into the score rows).  TPU: the scalar-prefetch Pallas
    kernel; otherwise the gather-then-contract oracle.
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        s = ref.rerank_scores_ref(q, q_mask, cand_ids, doc_tokens, doc_mask,
                                  doc_scales)
    else:
        s = _gs.rerank_gather_scores(q, q_mask, cand_ids, doc_tokens,
                                     doc_mask, doc_scales,
                                     interpret=not _on_tpu())
    s = jnp.where(cand_ids >= 0, s, ref.NEG)
    kk = min(k, s.shape[1])
    top, idx = jax.lax.top_k(s, kk)
    out_ids = jnp.take_along_axis(cand_ids, idx, axis=1)
    if kk < k:
        top = jnp.pad(top, ((0, 0), (0, k - kk)), constant_values=ref.NEG)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)), constant_values=-1)
    return top, out_ids


def fused_rerank_paged(q, q_mask, cand_ids, tok_pages, page_table, n_tokens,
                       k: int, *, use_kernel: bool | None = None):
    """Paged-corpus exact MaxSim rerank -> (scores, ids), (B, k).

    The corpus arrives as its paged-store pieces (``core.pages.PagedStore``:
    token pages + per-doc page table + token counts) instead of dense
    ``(m, Td, d)`` slabs; candidates' page ids are fed to the kernel through
    SMEM scalar prefetch.  Same ``-1``-pad contract as :func:`fused_rerank`,
    and the same fp32 per-token dots as the dense paths on the same docs
    (the token max is order-independent).  TPU: the scalar-prefetch Pallas kernel
    (:func:`repro.kernels.gather_scan.rerank_paged_scores`), each grid step
    scoring a block of candidates whose pages it DMAs into a VMEM double
    buffer while the block before is scored; otherwise the
    gather-from-pages oracle.  fp32 only (the SQ8 token tier stays on the
    dense sharded path).
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        s = ref.rerank_scores_paged_ref(q, q_mask, cand_ids, tok_pages,
                                        page_table, n_tokens)
    else:
        s = _gs.rerank_paged_scores(q, q_mask, cand_ids, tok_pages,
                                    page_table, n_tokens,
                                    interpret=not _on_tpu())
    s = jnp.where(cand_ids >= 0, s, ref.NEG)
    kk = min(k, s.shape[1])
    top, idx = jax.lax.top_k(s, kk)
    out_ids = jnp.take_along_axis(cand_ids, idx, axis=1)
    if kk < k:
        top = jnp.pad(top, ((0, 0), (0, k - kk)), constant_values=ref.NEG)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)), constant_values=-1)
    return top, out_ids


def fused_rerank_paged_res(q, q_mask, cand_ids, cent_pages, code_pages,
                           page_table, n_tokens, centroids, values, k: int,
                           *, use_kernel: bool | None = None):
    """Residual-tier paged MaxSim rerank -> (scores, ids), (B, k).

    The compressed twin of :func:`fused_rerank_paged`: candidates' token
    pages arrive as centroid-id pages (P, page) int32 + packed residual
    pages (P, page, db) uint8 plus the codec tables, decoded in VMEM on the
    TPU path (host-side by the oracle — bit-identical).  Same ``-1``-pad
    contract as :func:`fused_rerank`.
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        s = ref.rerank_scores_paged_res_ref(q, q_mask, cand_ids, cent_pages,
                                            code_pages, page_table, n_tokens,
                                            centroids, values)
    else:
        s = _gs.rerank_paged_res_scores(q, q_mask, cand_ids, cent_pages,
                                        code_pages, page_table, n_tokens,
                                        centroids, values,
                                        interpret=not _on_tpu())
    s = jnp.where(cand_ids >= 0, s, ref.NEG)
    kk = min(k, s.shape[1])
    top, idx = jax.lax.top_k(s, kk)
    out_ids = jnp.take_along_axis(cand_ids, idx, axis=1)
    if kk < k:
        top = jnp.pad(top, ((0, 0), (0, k - kk)), constant_values=ref.NEG)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)), constant_values=-1)
    return top, out_ids


def fused_query(q_tokens, q_mask, psi_params, centroids, ids, vecs,
                scales=None, *, nprobe: int, kp: int,
                use_kernel: bool | None = None):
    """One-launch first stage: ψ-pool + IVF probe scan + in-kernel top-k'.

    The probe SELECTION (pooled query vs the tiny (nlist, d') centroid
    table + ``top_k(nprobe)``) runs as a query-scale XLA prelude in both
    paths — it feeds the kernel's SMEM scalar prefetch, so it cannot live
    inside the grid it steers.  Everything corpus-scale — the per-cluster
    gather, MXU scoring, and the top-k' reduction — is one Pallas launch on
    TPU (ψ is recomputed in-kernel at grid step 0: cheaper than an HBM
    round-trip of the (B, d') latent).  Returns (scores, ids), (B, kp),
    short rows padded with ``(-inf, -1)`` exactly like the legacy flat
    top-k over the gathered strip.
    """
    kernel = psi_params["dense"]["kernel"]
    bias = psi_params["dense"]["bias"]
    g = psi_params["ln"]["scale"]
    b = psi_params["ln"]["bias"]
    psi_q = ref.psi_pool_ref(q_tokens, q_mask, kernel, bias, g, b)
    cs = psi_q @ centroids.T
    _, probe = jax.lax.top_k(cs, nprobe)
    if not _one_launch_kernel(use_kernel):
        return ref.query_fused_ref(q_tokens, q_mask, kernel, bias, g, b,
                                   probe, ids, vecs, scales, kp=kp)
    return _qf.query_fused(q_tokens, q_mask, kernel, bias, g, b, probe, ids,
                           vecs, scales, kp=kp, interpret=not _on_tpu())


def fused_query_res(q_tokens, q_mask, psi_params, centroids, ids, codes,
                    rq_values, *, nprobe: int, kp: int,
                    use_kernel: bool | None = None):
    """One-launch first stage over a RESIDUAL-compressed IVF index.

    Same contract as :func:`fused_query`; the cluster lists are packed
    2/4-bit residual codes (nlist, cap, db) coded against each cluster's
    own centroid row (the same (nlist, d') table the probe-select prelude
    scores), with rq_values (d', L) the per-dim reconstruction tables.
    """
    kernel = psi_params["dense"]["kernel"]
    bias = psi_params["dense"]["bias"]
    g = psi_params["ln"]["scale"]
    b = psi_params["ln"]["bias"]
    psi_q = ref.psi_pool_ref(q_tokens, q_mask, kernel, bias, g, b)
    cs = psi_q @ centroids.T
    _, probe = jax.lax.top_k(cs, nprobe)
    if not _one_launch_kernel(use_kernel):
        return ref.query_fused_res_ref(q_tokens, q_mask, kernel, bias, g, b,
                                       probe, ids, codes, centroids,
                                       rq_values, kp=kp)
    return _qf.query_fused_res(q_tokens, q_mask, kernel, bias, g, b, probe,
                               ids, codes, centroids, rq_values, kp=kp,
                               interpret=not _on_tpu())


def mips_topk_fused(q, W, W_scales, kp: int, valid=None, *,
                    use_kernel: bool | None = None, block_m: int = 512):
    """Fused dense latent scan + in-kernel top-k' (the sharded serve step's
    one-launch first stage): never materializes the (B, m) score matrix.

    Contract matches the legacy ``psi_q @ W.T`` → mask → ``top_k``: ids are
    corpus POSITIONS (``valid=False`` rows keep their position but score
    ``NEG``, so with ``kp`` ≤ #valid rows they never surface).  ``valid``
    may be a traced array — the sharded path's pad mask depends on
    ``jax.lax.axis_index``.  Returns (scores, ids), (B, kp).
    """
    if not _one_launch_kernel(use_kernel):
        return ref.mips_topk_ref(q, W, W_scales, valid, kp=kp)
    return _qf.mips_topk(q, W, W_scales, valid, kp=kp, block_m=block_m,
                         interpret=not _on_tpu())
