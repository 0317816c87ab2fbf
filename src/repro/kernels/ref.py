"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` takes the same logical arguments as the corresponding
``ops.*`` wrapper and is used by tests/benchmarks as ground truth."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30
# every oracle contracts fp32 operands at full fp32 precision: on the TPU,
# XLA's default would round them through bf16 and the oracle would no longer
# be the exact score the kernels are held to
HIGHEST = jax.lax.Precision.HIGHEST


def token_maxsim_ref(x, doc_tokens, doc_mask):
    """g(x)_l = max_{c in C_l} <c, x>.   x: (n, d); docs: (m, T, d) -> (n, m)."""
    s = jnp.einsum("nd,mtd->nmt", x, doc_tokens, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(doc_mask[None], s, NEG)
    return jnp.max(s, axis=-1)


def maxsim_scores_ref(q, q_mask, doc_tokens, doc_mask):
    """MaxSim(X, C_j).  q: (B, Tq, d) -> (B, m)."""
    s = jnp.einsum("bqd,mtd->bmqt", q, doc_tokens, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(doc_mask[None, :, None, :], s, NEG)
    best = jnp.max(s, axis=-1)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    return jnp.sum(best, axis=-1)


def fused_psi_ref(x, kernel, bias, ln_scale, ln_bias, eps: float = 1e-5):
    """LN(GELU(x @ kernel + bias)).  x: (n, d) -> (n, d')."""
    h = x @ kernel + bias
    h = jax.nn.gelu(h, approximate=True)
    hf = h.astype(jnp.float32)
    mu = jnp.mean(hf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(hf - mu), axis=-1, keepdims=True)
    y = (hf - mu) * jax.lax.rsqrt(var + eps) * ln_scale + ln_bias
    return y.astype(x.dtype)


def mips_sq8_ref(q, codes, scales):
    """fp32 queries x int8 corpus with per-row scales.
    q: (B, d); codes: (m, d) int8; scales: (m,) -> (B, m) fp32."""
    return jnp.matmul(q, codes.astype(jnp.float32).T,
                      precision=HIGHEST) * scales[None, :]


def mips_sq8_batched_ref(q, codes, scales):
    """Per-query SQ8 MIPS: every query scores its OWN code list, all B rows
    in ONE contraction (the batched non-Pallas fallback for the IVF scan —
    no per-row vmap, no B one-row kernel launches).
    q: (B, d); codes: (B, n, d) int8; scales: (B, n) -> (B, n) fp32."""
    s = jnp.einsum("bd,bnd->bn", q, codes.astype(jnp.float32),
                   precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    return s * scales.astype(jnp.float32)


def take_lists(vecs, probe):
    """``vecs[probe]`` for (nlist, cap, d) cluster lists and (B, P) probe
    ids -> (B, P, cap, d), copied one list at a time.  XLA's TPU gather of
    int8 lists first relayouts the whole list array (3.75 GiB of temporaries
    at nlist=1024, cap=d=2048); a loop of dynamic slices needs none."""
    one = lambda i: jax.lax.dynamic_index_in_dim(vecs, i, 0, keepdims=False)
    out = jax.lax.map(one, probe.reshape(-1))
    return out.reshape(probe.shape + vecs.shape[1:])


def ivf_scan_ref(q, probe, ids, vecs, scales=None):
    """Oracle for :func:`repro.kernels.gather_scan.ivf_probe_scan` — the
    gather-then-score path (what the legacy ``search_ivf`` computes).
    q: (B, d); probe: (B, nprobe); ids: (nlist, cap); vecs: (nlist, cap, d)
    fp32 or int8 (with scales (nlist, cap)) -> (B, nprobe, cap) fp32,
    pad slots at ``-inf``."""
    gids = jnp.take(ids, probe, axis=0)                 # (B, P, cap)
    gv = take_lists(vecs, probe)                        # (B, P, cap, d)
    if scales is not None:
        # same flattened contraction as mips_sq8_batched_ref (the legacy
        # SQ8 fallback), so fused-ref == legacy bit for bit on CPU
        B, P, cap, d = gv.shape
        s = jnp.einsum("bd,bnd->bn", q,
                       gv.reshape(B, P * cap, d).astype(jnp.float32),
                       precision=HIGHEST,
                       preferred_element_type=jnp.float32).reshape(B, P, cap)
        s = s * jnp.take(scales, probe, axis=0).astype(jnp.float32)
    else:
        s = jnp.einsum("bd,bpcd->bpc", q, gv.astype(q.dtype),
                       precision=HIGHEST,
                       preferred_element_type=jnp.float32)
    return jnp.where(gids >= 0, s, -jnp.inf)


def _residual_codec(centroids, values):
    # cuts are only used at ENCODE time; decode needs centroids + values
    from repro.anns.quantization import ResidualCodec
    return ResidualCodec(centroids=centroids, cuts=None, values=values)


def ivf_scan_res_ref(q, probe, ids, codes, centroids, values):
    """Oracle for :func:`repro.kernels.gather_scan.ivf_probe_res_scan` —
    gather the probed packed lists, decode host-side
    (``quantization.residual_decode`` with each vector's centroid id = its
    own cluster row), then the fp32 contraction.
    q: (B, d); probe: (B, nprobe); ids: (nlist, cap); codes: (nlist, cap,
    db) uint8; centroids: (nlist, d); values: (d, L) -> (B, nprobe, cap)
    fp32, pad slots ``-inf``."""
    from repro.anns.quantization import residual_decode
    codec = _residual_codec(centroids, values)
    gids = jnp.take(ids, probe, axis=0)                 # (B, P, cap)
    gc = jnp.take(codes, probe, axis=0)                 # (B, P, cap, db)
    cent = jnp.broadcast_to(probe[..., None], gids.shape)
    v = residual_decode(codec, cent, gc)                # (B, P, cap, d)
    s = jnp.einsum("bd,bpcd->bpc", q.astype(jnp.float32), v,
                   precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    return jnp.where(gids >= 0, s, -jnp.inf)


def rerank_scores_ref(q, q_mask, cand_ids, doc_tokens, doc_mask,
                      doc_scales=None):
    """Oracle for :func:`repro.kernels.gather_scan.rerank_gather_scores` —
    gathers the ``(B, k', Td, d)`` candidate slab and contracts it (what
    ``core.maxsim.rerank`` computes before its top-k).  ``-1`` candidates
    score doc 0 here; the caller masks them.
    q: (B, Tq, d); cand_ids: (B, k') -> (B, k') fp32 raw pair scores."""
    safe = jnp.maximum(cand_ids, 0)
    cd = jnp.take(doc_tokens, safe, axis=0)             # (B, k', Td, d)
    cm = jnp.take(doc_mask, safe, axis=0)               # (B, k', Td)
    s = jnp.einsum("bqd,bmtd->bmqt", q, cd.astype(q.dtype),
                   precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    if doc_scales is not None:
        cs = jnp.take(doc_scales, safe, axis=0)
        s = s * cs.astype(jnp.float32)[:, :, None, :]
    s = jnp.where(cm[:, :, None, :], s, NEG)
    best = jnp.max(s, axis=-1)                          # (B, k', Tq)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    return jnp.sum(best, axis=-1)                       # (B, k')


def rerank_scores_paged_ref(q, q_mask, cand_ids, tok_pages, page_table,
                            n_tokens):
    """Oracle for :func:`repro.kernels.gather_scan.rerank_paged_scores` —
    materializes each candidate's tokens FROM PAGES (same gather as
    ``core.pages.gather_docs``) and contracts the slab.  ``-1``/dead
    candidates score all-NEG positions here; the caller masks them.
    q: (B, Tq, d); cand_ids: (B, k'); tok_pages: (P, page, d); page_table:
    (C, pmax); n_tokens: (C,) -> (B, k') fp32 raw pair scores."""
    safe = jnp.maximum(cand_ids, 0)
    table = jnp.take(page_table, safe, axis=0)          # (B, k', pmax)
    nt = jnp.where(cand_ids >= 0, jnp.take(n_tokens, safe, axis=0), 0)
    toks = jnp.take(tok_pages, jnp.maximum(table, 0), axis=0)
    B, kp, pmax, page, d = toks.shape
    toks = toks.reshape(B, kp, pmax * page, d)
    cm = jnp.arange(pmax * page, dtype=jnp.int32) < nt[..., None]
    s = jnp.einsum("bqd,bmtd->bmqt", q, toks.astype(q.dtype),
                   precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = jnp.where(cm[:, :, None, :], s, NEG)
    best = jnp.max(s, axis=-1)                          # (B, k', Tq)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    return jnp.sum(best, axis=-1)                       # (B, k')


def rerank_scores_paged_res_ref(q, q_mask, cand_ids, cent_pages, code_pages,
                                page_table, n_tokens, centroids, values):
    """Oracle for :func:`repro.kernels.gather_scan.rerank_paged_res_scores`
    — decode the WHOLE compressed page pool host-side, then run the fp32
    paged oracle on the reconstructed pages (same math, and the decode is
    bit-identical to the in-kernel one-hot path).
    cent_pages: (P, page) int32; code_pages: (P, page, db) uint8."""
    from repro.anns.quantization import residual_decode
    codec = _residual_codec(centroids, values)
    tok_pages = residual_decode(codec, cent_pages, code_pages)  # (P, page, d)
    return rerank_scores_paged_ref(q, q_mask, cand_ids, tok_pages,
                                   page_table, n_tokens)


def query_fused_res_ref(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias,
                        probe, ids, codes, centroids, values, *, kp: int):
    """Oracle for :func:`repro.kernels.query_fused.query_fused_res` — the
    legacy composition over a residual-compressed index: ψ-pool, decode-
    then-score probe scan, flat top-k' (same stable tie contract as
    :func:`query_fused_ref`)."""
    psi_q = psi_pool_ref(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias)
    s = ivf_scan_res_ref(psi_q, probe, ids, codes, centroids, values)
    gids = jnp.take(ids, probe, axis=0)                 # (B, P, cap)
    B = s.shape[0]
    flat_s = s.reshape(B, -1)
    flat_i = gids.reshape(B, -1)
    kk = min(kp, flat_s.shape[1])
    top, pos = jax.lax.top_k(flat_s, kk)
    out_i = jnp.take_along_axis(flat_i, pos, axis=1)
    if kk < kp:
        top = jnp.pad(top, ((0, 0), (0, kp - kk)), constant_values=-jnp.inf)
        out_i = jnp.pad(out_i, ((0, 0), (0, kp - kk)), constant_values=-1)
    return top, out_i


def psi_pool_ref(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias,
                 eps: float = 1e-5):
    """Pooled query latent: sum_t mask_t * psi(x_t)  (eq. 5).

    Op-for-op the same graph as ``core.model.pool_queries`` (dense → GELU →
    LayerNorm → mask → sum), spelled on the raw weight arrays so the
    one-launch oracle does not import the model layer.  For fp32 inputs the
    two jit to identical XLA programs — bit-identical pooled latents.
    q_tokens: (B, Tq, d) -> (B, d')."""
    h = q_tokens @ kernel.astype(q_tokens.dtype) + bias.astype(q_tokens.dtype)
    h = jax.nn.gelu(h, approximate=True)
    hf = h.astype(jnp.float32)
    mu = jnp.mean(hf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(hf - mu), axis=-1, keepdims=True)
    y = (hf - mu) * jax.lax.rsqrt(var + eps)
    y = y * ln_scale.astype(jnp.float32) + ln_bias.astype(jnp.float32)
    y = y.astype(q_tokens.dtype)
    if q_mask is not None:
        y = y * q_mask[..., None]
    return jnp.sum(y, axis=-2)


def query_fused_ref(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias,
                    probe, ids, vecs, scales=None, *, kp: int):
    """Oracle for :func:`repro.kernels.query_fused.query_fused` — the
    legacy 3-launch composition: ψ-pool, gather-then-score probe scan, flat
    top-k' over the (B, nprobe*cap) strip (stable: earlier flat positions
    win ties, the contract the kernel's carried merge reproduces).
    Returns (scores (B, kp), ids (B, kp)) padded with (-inf, -1)."""
    psi_q = psi_pool_ref(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias)
    s = ivf_scan_ref(psi_q, probe, ids, vecs, scales)   # (B, P, cap)
    gids = jnp.take(ids, probe, axis=0)                 # (B, P, cap)
    B = s.shape[0]
    flat_s = s.reshape(B, -1)
    flat_i = gids.reshape(B, -1)
    kk = min(kp, flat_s.shape[1])
    top, pos = jax.lax.top_k(flat_s, kk)
    out_i = jnp.take_along_axis(flat_i, pos, axis=1)
    if kk < kp:
        top = jnp.pad(top, ((0, 0), (0, kp - kk)), constant_values=-jnp.inf)
        out_i = jnp.pad(out_i, ((0, 0), (0, kp - kk)), constant_values=-1)
    return top, out_i


def mips_topk_ref(q, W, W_scales=None, valid=None, *, kp: int):
    """Oracle for :func:`repro.kernels.query_fused.mips_topk` — exactly the
    sharded serve step's legacy math: full (B, m) latent score matrix,
    optional per-row scales, invalid rows pinned to ``NEG`` (position ids
    kept), then ``jax.lax.top_k``.
    q: (B, d'); W: (m, d') fp32 or int8 -> (scores, position ids) (B, kp)."""
    s = q @ W.T.astype(q.dtype)
    if W_scales is not None:
        s = s * W_scales[None, :].astype(s.dtype)
    if valid is not None:
        s = jnp.where(valid[None, :], s, NEG)
    return jax.lax.top_k(s, kp)
