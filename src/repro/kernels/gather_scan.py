"""Pallas TPU gather-at-source serving kernels (scalar-prefetch DMA).

LEMUR inference is two memory-bound gathers: the IVF probe scan pulls
``nprobe`` cluster lists per query, the exact rerank pulls ``k'`` candidate
documents per query.  The pure-XLA path materializes both gathers in HBM
(``jnp.take`` copies a ``(B, nprobe, cap, d)`` / ``(B, k', Td, d)`` tensor)
before any math runs — every gathered byte makes three HBM trips (read at
the source, write to the copy, read by the scoring op) and the copies are
duplicated per query row.

These kernels move the gather INTO the grid instead: the probe / candidate
ids are scalar-prefetched to SMEM (``pltpu.PrefetchScalarGridSpec``), and
each grid step's BlockSpec ``index_map`` reads the prefetched id to DMA
exactly one cluster (or candidate) tile HBM→VMEM, where the MXU contraction
runs immediately.  Per query the HBM read volume is O(nprobe·cap·d) /
O(k'·Td·d) source bytes streamed exactly once; nothing is materialized.
Consecutive grid steps double-buffer their DMAs automatically (the Pallas
grid pipeline), so the scan runs at HBM bandwidth.

``ivf_probe_scan`` — grid ``(B, nprobe, cap/bc)``; step ``(b, p, t)`` DMAs
cap-tile ``t`` (``bc`` rows, :func:`cap_tile`) of cluster ``probe[b, p]``'s
list (fp32, or int8 codes dequantized
in-kernel via the same exact bf16 split as ``mips_sq8``), scores it against
query row ``b`` in one MXU matmul, masks ``-1`` pad slots to ``-inf`` and
writes a compact ``(B, nprobe, cap)`` score strip (the top-k' runs on the
strip outside, like the legacy path — bit-identical ids on fp32).

VMEM per step: a list tile of at most 1 MiB, the query row and a ``(1,
bc)`` score strip — ×2 for the pipeline's double buffer, inside v5e VMEM at
any cap and d.  Row vectors travel with a unit axis (``(B, 1, d)``,
``(nlist, 1, cap)``) so every block's last two dims are the array's own or
(8, 128)-aligned, as the TPU lowering requires.

``rerank_gather_scores`` — grid ``(B, k')``; step ``(b, c)`` DMAs candidate
``cand[b, c]``'s ``(Td, d)`` token slab (fp or int8 + per-token scales),
computes the masked ``(Tq × Td)`` MXU contraction, token-max and
query-masked sum entirely in VMEM, and writes the MaxSim score into lane
``c`` of query ``b``'s ``(1, k')`` output strip, which stays in VMEM
across the candidate steps.  A k' whose prefetched ids would overflow
SMEM is split into chunks scored as rows of their own.
``-1`` candidates are clamped to doc 0 for the DMA and masked by the
caller (``ops.fused_rerank``), matching ``core.maxsim.rerank``.

VMEM per step (Tq=32, Td=32, d=128): query slab 16 KiB, doc slab 16 KiB
(int8: 4 KiB + 128 B scales), score tile 4 KiB — the whole working set of
one candidate fits in registers-adjacent VMEM; the ``(B, k', Td, d)`` HBM
tensor of the legacy path never exists.

``rerank_paged_scores`` — the paged-corpus twin of the rerank: the corpus
lives as fixed-size token PAGES behind a per-doc page table
(``core.pages.PagedStore``), so a candidate's tokens are not one contiguous
``(Td, d)`` slab.  The per-candidate page ids and token counts are
scalar-prefetched to SMEM (the paged-KV page-table-in-SMEM idiom).  Grid
``(B, ⌈k'/G⌉)``: step ``(b, i)`` scores a block of ``G`` candidates
(:func:`rerank_paged_plan`: 16 at the served widths), since a grid step
has a fixed cost (≈ 0.3 µs on a v5e) that one 8 KiB page does not cover.
The page pool stays in HBM (``memory_space=pl.ANY``); each step starts the
page DMAs of the NEXT block into the other half of a VMEM double buffer,
waits for its own block's pages, and scores them in one
``(G·pmax·page, d) × (d, Tq)`` matmul.  Per candidate, a row slice of
that product masks token positions ``>= n_tokens`` to ``NEG`` (every
candidate fetches all ``pmax`` pages, the page table's pads as page 0),
takes the per-query-token max and the query-masked sum; the ``G`` scores
land in the ``(1, k')`` output strip with one store.  The page-id strips
are flat per query and split, with their queries, into groups that fit
SMEM.

VMEM per step (G 16, pmax 5, page 16, d 128, Tq 32): the page double
buffer 2 × 640 KiB, the query slab 16 KiB ×2, the (1,280 × 32) score tile
and the output strip; per-token dots are exact fp32 (``HIGHEST``) as in
the other reranks.

Every ``pallas_call`` is named after the function that issues it
(``name="ivf_probe_scan"``, ``"rerank_paged_scores"``, …), so a compiled
program and a device trace know each kernel by that name, also where it
runs inside a ``lax.map`` over row groups.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mips_sq8 import split_dot

NEG = -1e30
# fp32 operands contract at full fp32 precision in every kernel, as in the
# XLA reference paths: the exact rerank must not round through bf16
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# scalar-prefetch IVF probe scan
# --------------------------------------------------------------------------

def _ivf_scan_fp_kernel(probe_ref, q_ref, ids_ref, vecs_ref, out_ref):
    # q: (1, d); ids: (1, bc); vecs: (bc, d) — one cap-tile of ONE cluster,
    # DMA'd by the index_map from the prefetched probe id; out: (1, bc)
    s = jax.lax.dot_general(
        q_ref[...], vecs_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (1, bc)
    out_ref[...] = jnp.where(ids_ref[...] >= 0, s, -jnp.inf)


def _ivf_scan_sq8_kernel(probe_ref, q_ref, ids_ref, codes_ref, scales_ref,
                         out_ref):
    # int8 cluster codes dequantized IN-KERNEL: the exact bf16 split of the
    # fp32 query x bf16-widened codes (kernels.mips_sq8.split_dot), per-slot
    # scales folded into the score strip
    s = split_dot(q_ref[...], codes_ref[...]) * scales_ref[...]   # (1, bc)
    out_ref[...] = jnp.where(ids_ref[...] >= 0, s, -jnp.inf)


def cap_tile(cap: int, row_bytes: int, budget: int = 1 << 20) -> int:
    """Rows of one cluster list streamed per grid step: the whole list when
    it fits ``budget`` bytes of VMEM, else the largest halving of ``cap``
    that fits and stays a multiple of 128 (the lane width — the tile is
    also the last dim of the id/score blocks)."""
    bc = cap
    while bc * row_bytes > budget and bc % 256 == 0:
        bc //= 2
    return bc


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_probe_scan(q, probe, ids, vecs, scales=None, *, interpret: bool = False):
    """Scan the probed IVF cluster lists without gathering them to HBM.

    q: (B, d) fp32; probe: (B, nprobe) int32 cluster ids; ids: (nlist, cap)
    int32 (-1 padded); vecs: (nlist, cap, d) fp32 — or int8 codes with
    scales: (nlist, cap) — returns (B, nprobe, cap) fp32 scores with pad
    slots at ``-inf``.  Each grid step DMAs one cap-tile of cluster
    ``probe[b, p]``.  Row vectors travel with a unit axis (``(B, 1, d)``,
    ``(nlist, 1, cap)``) so every block's last two dims are either the
    array's own or (8, 128)-aligned, as the TPU lowering requires.
    """
    B, d = q.shape
    nprobe = probe.shape[1]
    nlist, cap = ids.shape
    bc = cap_tile(cap, d * vecs.dtype.itemsize)
    row = lambda b, p, t, pr: (pr[b, p], 0, t)
    in_specs = [
        pl.BlockSpec((None, 1, d), lambda b, p, t, pr: (b, 0, 0)),
        pl.BlockSpec((None, 1, bc), row),
        pl.BlockSpec((None, bc, d), lambda b, p, t, pr: (pr[b, p], t, 0)),
    ]
    args = [q.reshape(B, 1, d), ids.reshape(nlist, 1, cap), vecs]
    kernel = _ivf_scan_fp_kernel
    if scales is not None:
        in_specs.append(pl.BlockSpec((None, 1, bc), row))
        args.append(scales.reshape(nlist, 1, cap))
        kernel = _ivf_scan_sq8_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nprobe, cap // bc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, 1, bc),
                               lambda b, p, t, pr: (b, p, 0, t)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nprobe, 1, cap), jnp.float32),
        interpret=interpret,
        name="ivf_probe_scan",
    )(probe.astype(jnp.int32), *args)
    return out.reshape(B, nprobe, cap)


# --------------------------------------------------------------------------
# fused candidate-gather MaxSim rerank
# --------------------------------------------------------------------------

def _put_lane(out_ref, c, val):
    """Write the (1, 1) ``val`` into lane ``c`` of the (1, k') output strip
    (a select — the TPU has no scalar store into a vector block).  The
    strip stays resident in VMEM across the candidate steps of one query
    and is written back once."""
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == c, val, out_ref[...])


def _maxsim_flush(best, qm_ref):
    """(Tq, 1) per-query-token maxima -> (1, 1) query-masked sum."""
    best = jnp.where(qm_ref[...] > 0, best, 0.0)
    return jnp.sum(best, axis=0, keepdims=True)


def _rerank_fp_kernel(cand_ref, q_ref, qm_ref, docs_ref, dm_ref, out_ref):
    # q: (Tq, d); qm: (Tq, 1); docs: (Td, d) — ONE candidate's token slab,
    # DMA'd by the index_map from the prefetched (clamped) candidate id; the
    # mask dm: (1, Td) arrives pre-gathered per (b, c) (Td entries against
    # the slab's Td·d — see rerank_gather_scores); out: (1, k') strip of b
    c = pl.program_id(1)
    s = jax.lax.dot_general(
        q_ref[...], docs_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (Tq, Td)
    s = jnp.where(dm_ref[...] > 0, s, NEG)
    best = jnp.max(s, axis=-1, keepdims=True)        # (Tq, 1)
    _put_lane(out_ref, c, _maxsim_flush(best, qm_ref))


def _rerank_sq8_kernel(cand_ref, q_ref, qm_ref, codes_ref, dm_ref, ds_ref,
                       out_ref):
    # per-token scales fold into the SCORE rows — score(q, s·c) = s·(q·c) —
    # so the dequantized fp slab never materializes (same identity the
    # sharded serve step used in jnp, now in VMEM)
    c_id = pl.program_id(1)
    s = split_dot(q_ref[...], codes_ref[...]) * ds_ref[...]
    s = jnp.where(dm_ref[...] > 0, s, NEG)
    best = jnp.max(s, axis=-1, keepdims=True)
    _put_lane(out_ref, c_id, _maxsim_flush(best, qm_ref))


def _query_rows(q_mask):
    """(B, Tq) query mask -> (B, Tq, 1) int32: one column per query, so its
    block is the array's own last two dims."""
    return q_mask.astype(jnp.int32)[..., None]


SMEM_PREFETCH_BYTES = 256 << 10   # of the v5e core's 1 MiB SMEM


def _cand_chunk(kp: int, words: int) -> int:
    """Candidates per kernel row: k' itself when one query's prefetched
    strips (``words`` int32 per candidate) fit :data:`SMEM_PREFETCH_BYTES`,
    else the largest divisor of k' that does."""
    fit = max(1, SMEM_PREFETCH_BYTES // (4 * words))
    return max(c for c in range(1, min(kp, fit) + 1) if kp % c == 0)


def _fold_rows(nc: int, *xs):
    """Repeat every query row ``nc`` times, one copy per candidate chunk:
    (B, ...) -> (B·nc, ...), matching ``cand.reshape(B·nc, k'/nc)``."""
    return [jnp.repeat(x, nc, axis=0) for x in xs]


def _over_row_chunks(call, prefetch, per_row):
    """Run ``call(*prefetch_flat, *per_row)`` over groups of rows small
    enough that the scalar-prefetched strips (``prefetch``: (R, n) int32
    each, flattened per group) fit :data:`SMEM_PREFETCH_BYTES`.  Groups run
    in a ``lax.map``, so one kernel is compiled however many there are;
    rows are independent and the results stack on the row axis."""
    R = prefetch[0].shape[0]
    per = sum(a.shape[1] for a in prefetch) * 4
    bb = max([c for c in range(1, R + 1)
              if R % c == 0 and c * per <= SMEM_PREFETCH_BYTES] or [1])
    flat = lambda a: a.reshape(-1)
    if bb == R:
        return call(*map(flat, prefetch), *per_row)
    group = lambda a: a.reshape((R // bb, bb) + a.shape[1:])
    n_pf = len(prefetch)
    out = jax.lax.map(
        lambda xs: call(*map(flat, xs[:n_pf]), *xs[n_pf:]),
        tuple(map(group, (*prefetch, *per_row))))
    return out.reshape((R,) + out.shape[2:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def rerank_gather_scores(q, q_mask, cand_ids, doc_tokens, doc_mask,
                         doc_scales=None, *, interpret: bool = False):
    """Exact MaxSim of each query against ITS OWN candidate docs, gathering
    each candidate's token slab at the source.

    q: (B, Tq, d); cand_ids: (B, k') int32 (-1 padded — pads are clamped to
    doc 0 here and must be masked by the caller); doc_tokens: (m, Td, d) fp
    — or int8 codes with doc_scales: (m, Td) — returns (B, k') fp32 raw
    pair scores.  The candidate ids are scalar-prefetched to SMEM; a k'
    too long for it is split into chunks, each scored as a row of its own.
    """
    B, Tq, d = q.shape
    kp = cand_ids.shape[1]
    m, Td, _ = doc_tokens.shape
    kc = _cand_chunk(kp, 1)
    nc = kp // kc
    safe = jnp.maximum(cand_ids, 0).astype(jnp.int32).reshape(B * nc, kc)
    # masks (and SQ8 scales) are gathered per candidate in XLA — B·k'·Td
    # slots, tiny next to the (Td, d) token slabs the kernel streams, and it
    # avoids converting/copying the corpus-sized (m, Td) mask every call
    per_cand = lambda a: jnp.take(a, safe, axis=0).reshape(B * nc, kc, 1, Td)
    q, q_mask = _fold_rows(nc, q, q_mask)
    per_row = [q, _query_rows(q_mask), per_cand(doc_mask.astype(jnp.int32))]
    kernel = _rerank_fp_kernel
    if doc_scales is not None:
        per_row.append(per_cand(doc_scales))
        kernel = _rerank_sq8_kernel

    def call(cr, *rows):
        bb = rows[0].shape[0]
        strip = pl.BlockSpec((None, None, 1, Td),
                             lambda b, c, cr: (b, c, 0, 0))
        in_specs = [
            pl.BlockSpec((None, Tq, d), lambda b, c, cr: (b, 0, 0)),
            pl.BlockSpec((None, Tq, 1), lambda b, c, cr: (b, 0, 0)),
            pl.BlockSpec((None, Td, d),
                         lambda b, c, cr: (cr[b * kc + c], 0, 0)),
            *[strip] * (len(rows) - 2),
        ]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bb, kc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, 1, kc), lambda b, c, cr: (b, 0, 0)),
        )
        q, qm, *cand = rows
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bb, 1, kc), jnp.float32),
            interpret=interpret,
            name="rerank_gather_scores",
        )(cr, q, qm, doc_tokens, *cand)

    return _over_row_chunks(call, (safe,), per_row).reshape(B, kp)


# --------------------------------------------------------------------------
# paged-corpus MaxSim rerank (page table fed through SMEM)
# --------------------------------------------------------------------------

RERANK_VMEM_BYTES = 2 << 20   # the fp32 paged rerank's page double buffer
MAX_CANDS_PER_STEP = 16


class RerankPagedPlan(NamedTuple):
    """The blocking :func:`rerank_paged_scores` runs with."""
    cands_per_step: int   # candidates whose pages one grid step scores
    grid_steps: int       # summed over every launch of one call
    page_dmas: int        # pmax per candidate slot of every step
    vmem_bytes: int       # the double-buffered page scratch


def rerank_paged_plan(B: int, kp: int, pmax: int, page: int,
                      d: int) -> RerankPagedPlan:
    """Blocking of the fp32 paged rerank for a (B, k') candidate batch of
    docs of at most ``pmax`` pages of ``(page, d)`` fp32 tokens: the most
    candidates per grid step, up to :data:`MAX_CANDS_PER_STEP`, whose
    pages fit :data:`RERANK_VMEM_BYTES` twice (the DMA of the next block
    lands while this one is scored).  k' is first cut into SMEM-sized
    chunks (:func:`_cand_chunk`), each a row of its own; a chunk that is
    not a multiple of the block leaves a short last block."""
    kc = _cand_chunk(kp, pmax + 1)
    cand_bytes = 2 * pmax * page * d * 4
    g = max(1, min(MAX_CANDS_PER_STEP, RERANK_VMEM_BYTES // cand_bytes))
    steps = B * (kp // kc) * pl.cdiv(kc, g)
    return RerankPagedPlan(cands_per_step=g, grid_steps=steps,
                           page_dmas=steps * g * pmax,
                           vmem_bytes=g * cand_bytes)


def _rerank_paged_fp_kernel(pt_ref, nt_ref, q_ref, qm_ref, pages_hbm, out_ref,
                            buf, sem, *, kc, g, pmax):
    # step (b, i) scores candidates i·g .. i·g+g-1 of row b.  Their pages
    # arrive by manual DMA from the HBM pool (pages_hbm: (P, page, d)) into
    # slot (step % 2) of buf: (2, g·pmax·page, d), candidate-major, while
    # the step before is scored: a step starts the DMAs of the next block
    # (the grid runs in order), then waits for its own.
    # q: (Tq, d); qm: (1, Tq); out: the (1, kc) strip of row b
    b, i = pl.program_id(0), pl.program_id(1)
    nblk = pl.num_programs(1)
    step = b * nblk + i
    slot = step % 2
    page = pages_hbm.shape[1]
    span = pmax * page

    def fetch(row, blk, slot):
        """Start the DMAs of every page of block ``blk`` of ``row``: all
        pmax of each candidate, the page table's pads as page 0 (masked
        below), a short last block's extra slots as its last candidate.
        No DMA depends on a token count: on a v5e a branch per page cost
        more than the bytes it saved."""
        for k in range(g):
            base = (row * kc + jnp.minimum(blk * g + k, kc - 1)) * pmax
            for j in range(pmax):
                pltpu.make_async_copy(
                    pages_hbm.at[pt_ref[base + j]],
                    buf.at[slot, pl.ds((k * pmax + j) * page, page)],
                    sem.at[slot]).start()

    def fetch_step(t, carry):
        fetch(t // nblk, t % nblk, t % 2)
        return carry

    # the first step fetches its own block too; the last fetches nothing
    jax.lax.fori_loop(jnp.where(step == 0, 0, step + 1),
                      jnp.minimum(step + 2, pl.num_programs(0) * nblk),
                      fetch_step, 0)

    # a DMA semaphore counts bytes: one wait for the size of the whole slot
    # waits for every page DMA'd into it
    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    # every token of the block against every query token: one matmul with
    # tokens on the sublanes, so each candidate is a row slice of span
    s = jax.lax.dot_general(
        buf[slot], q_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (g·span, Tq)
    Tq = q_ref.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (span, Tq), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    strip = out_ref[...]
    for k in range(g):
        c = i * g + k
        # 0 tokens past the strip: a short last block's extra slots are dead
        n = jnp.where(c < kc, nt_ref[b * kc + jnp.minimum(c, kc - 1)], 0)
        sc = jnp.where(pos < n, s[k * span:(k + 1) * span], NEG)
        best = jnp.max(sc, axis=0, keepdims=True)             # (1, Tq)
        score = jnp.sum(jnp.where(qm_ref[...] > 0, best, 0.0), axis=1,
                        keepdims=True)
        strip = jnp.where(lane == c, score, strip)
    out_ref[...] = strip


def _paged_prefetch(cand_ids, page_table, n_tokens, pmax):
    """Fold k' into SMEM-sized candidate chunks (see :func:`_cand_chunk`)
    and gather, per chunk row, the candidates' page-id strip (R, kc·pmax),
    flat so SMEM does not pad a short pmax axis out to a full word row, and
    token counts (R, kc) for scalar prefetch: pads/dead slots clamp to page
    0 with 0 tokens.  Returns (kc, nc, pt, nt)."""
    B, kp = cand_ids.shape
    kc = _cand_chunk(kp, pmax + 1)
    nc = kp // kc
    cand_ids = cand_ids.reshape(B * nc, kc)
    safe = jnp.maximum(cand_ids, 0).astype(jnp.int32)
    pt = jnp.maximum(jnp.take(page_table, safe, axis=0), 0).astype(jnp.int32)
    nt = jnp.take(n_tokens, safe, axis=0).astype(jnp.int32)
    return kc, nc, pt.reshape(B * nc, -1), jnp.where(cand_ids >= 0, nt, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rerank_paged_scores(q, q_mask, cand_ids, tok_pages, page_table, n_tokens,
                        *, interpret: bool = False):
    """Exact MaxSim of each query against ITS OWN candidates, streaming each
    candidate's token PAGES at the source.

    q: (B, Tq, d); cand_ids: (B, k') int32 (-1 padded — pads/dead slots
    are clamped for the DMA, score all-NEG here, and must be masked by the
    caller); tok_pages: (P, page, d) fp32; page_table: (C, pmax) int32 (-1
    padded); n_tokens: (C,) int32 — returns (B, k') fp32 raw pair scores.
    The per-candidate page-id strip (B·k'·pmax int32, tiny next to the
    token pages) is gathered in XLA and scalar-prefetched to SMEM; each
    grid step scores a block of candidates (:func:`rerank_paged_plan`).
    """
    B, Tq, d = q.shape
    kp = cand_ids.shape[1]
    _, page, _ = tok_pages.shape
    pmax = page_table.shape[1]
    g = rerank_paged_plan(B, kp, pmax, page, d).cands_per_step
    kc, nc, pt, nt = _paged_prefetch(cand_ids, page_table, n_tokens, pmax)
    row = lambda b, i, pt, nt: (b, 0, 0)

    def call(pt, nt, q, qm):
        bb = q.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bb, pl.cdiv(kc, g)),
            in_specs=[
                pl.BlockSpec((None, Tq, d), row),
                pl.BlockSpec((None, 1, Tq), row),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, 1, kc), row),
            scratch_shapes=[pltpu.VMEM((2, g * pmax * page, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        )
        return pl.pallas_call(
            functools.partial(_rerank_paged_fp_kernel, kc=kc, g=g, pmax=pmax),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bb, 1, kc), jnp.float32),
            # a step prefetches the next step's pages: the grid runs in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="rerank_paged_scores",
        )(pt, nt, q, qm, tok_pages)

    q, q_mask = _fold_rows(nc, q, q_mask)
    out = _over_row_chunks(call, (pt, nt),
                           (q, q_mask.astype(jnp.int32)[:, None, :]))
    return out.reshape(B, kp)


# --------------------------------------------------------------------------
# residual-codec tier: in-kernel centroid lookup + residual unpack
# --------------------------------------------------------------------------
#
# The compressed corpus stores each token as a centroid id (int32) plus a
# packed 2/4-bit per-dim residual code (``repro.anns.quantization``).  The
# kernels below decode INSIDE the grid — the fp32 token slab never exists in
# HBM — generalizing the SQ8 bf16-split trick from "scale a cheap int8 dot"
# to "reconstruct, then dot".  Mosaic has no dynamic-gather primitive, so
# the decode avoids gathers entirely:
#
# * packed codes unpack with int32 shifts/ANDs (vector ALU);
# * per-dim reconstruction values resolve by a select-sum over the L
#   levels (``sum_l values[:, l] * (idx == l)``), looped so one (n, d)
#   accumulator lives in VMEM; the table travels transposed, (L, d), so a
#   level is one row;
# * centroid rows resolve by a one-hot MXU matmul
#   (``onehot(cent, ncent) @ centroids``).
#
# Every output element is the sum of exactly one fp32 term plus zeros, so
# the in-kernel decode is BIT-IDENTICAL to the host-side
# ``quantization.residual_decode`` (``jnp.take``/``take_along_axis``) — the
# property ``tests/test_residual_codec.py`` pins down.


def _unpack_codes_i32(codes, *, bits):
    """Packed (n, db) uint8 -> (n, db * 8//bits) int32 bucket indices.

    Same little-endian-within-byte layout as ``quantization.pack_codes``:
    dim ``i*per + j`` sits at bit ``bits*j`` of byte ``i``.  Each byte is
    first spread over its ``per`` dims by a 0/1 expansion matmul (byte
    values are integers below 256, exact in every MXU precision) — the TPU
    has no lane interleave — then each dim shifts out its own bit field."""
    per = 8 // bits
    n, db = codes.shape
    w = 128 if db % 128 == 0 else db
    x = codes.astype(jnp.int32).astype(jnp.float32)
    spread = (jax.lax.broadcasted_iota(jnp.int32, (w, w * per), 1) // per
              == jax.lax.broadcasted_iota(jnp.int32, (w, w * per), 0)
              ).astype(jnp.float32)
    parts = [jax.lax.dot_general(x[:, u:u + w], spread,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             for u in range(0, db, w)]
    full = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    shift = (jax.lax.broadcasted_iota(jnp.int32, full.shape, 1) % per) * bits
    return (full.astype(jnp.int32) >> shift) & ((1 << bits) - 1)


def _residual_values(idx, vt_ref):
    """Bucket indices (n, d) + the transposed per-dim table (L, d), as a
    kernel ref -> (n, d) fp32 via a select-sum over the L levels (exactly
    one nonzero term per element, so the loop order cannot change a bit)."""
    def level(l, res):
        return res + jnp.where(idx == l, vt_ref[pl.ds(l, 1), :], 0.0)

    return jax.lax.fori_loop(0, vt_ref.shape[0], level,
                             jnp.zeros(idx.shape, jnp.float32))


class _Rows:
    """Array stand-in for a (L, d) ref: ``[pl.ds(l, 1), :]`` slicing."""

    def __init__(self, a):
        self.a, self.shape = a, a.shape

    def __getitem__(self, key):
        return jax.lax.dynamic_slice_in_dim(self.a, key[0].start, 1, 0)


def _decode_rows(cent_row, codes, centroids, vt, *, bits):
    """Kernel-side residual decode: cent_row (1, n) int32 centroid ids (a
    row: the one-hot is built transposed, (ncent, n), and contracted on its
    leading axis, so no id column is ever needed); codes (n, db) uint8; vt
    (L, d) the transposed level table -> (n, d) fp32."""
    n = codes.shape[0]
    ncent = centroids.shape[0]
    idx = _unpack_codes_i32(codes, bits=bits)          # (n, d)
    res = _residual_values(idx, vt)                    # (n, d)
    onehot_t = (cent_row == jax.lax.broadcasted_iota(jnp.int32, (ncent, n), 0)
                ).astype(jnp.float32)
    cvec = jax.lax.dot_general(
        onehot_t, centroids, (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )                                                  # (n, d)
    return cvec + res


def residual_decode_onehot(cent, codes, centroids, values, *, bits):
    """Gather-free residual decode (kernel-safe, also called by tests).

    cent: (n,) int32 centroid ids; codes: (n, db) uint8 packed residuals;
    centroids: (ncent, d) fp32; values: (d, L) fp32 -> (n, d) fp32,
    bit-identical to ``quantization.residual_decode`` on the same inputs."""
    return _decode_rows(cent[None, :], codes, centroids, _Rows(values.T),
                        bits=bits)


def _ivf_scan_res_kernel(probe_ref, q_ref, ids_ref, codes_ref, cent_ref,
                         val_ref, out_ref, *, bits):
    # codes: (bc, db) packed residuals of one cap-tile of ONE cluster; cent:
    # (1, d) the SAME cluster's centroid row (IVF storage codes each vector
    # against its own cluster, so the id is implicit in the list and both
    # tiles are DMA'd by the one prefetched probe id) — no one-hot lookup
    idx = _unpack_codes_i32(codes_ref[...], bits=bits)
    v = _residual_values(idx, val_ref) + cent_ref[...]        # (bc, d)
    s = jax.lax.dot_general(
        q_ref[...], v, (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (1, bc)
    out_ref[...] = jnp.where(ids_ref[...] >= 0, s, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_probe_res_scan(q, probe, ids, codes, centroids, values, *,
                       interpret: bool = False):
    """Residual-tier IVF probe scan: decode-at-source, never materializing
    the fp32 cluster lists.

    q: (B, d) fp32; probe: (B, nprobe) int32; ids: (nlist, cap) int32 (-1
    padded); codes: (nlist, cap, db) uint8 packed residuals coded against
    each vector's OWN cluster centroid; centroids: (nlist, d) fp32; values:
    (d, L) fp32 -> (B, nprobe, cap) fp32 scores, pad slots ``-inf``.
    """
    B, d = q.shape
    nprobe = probe.shape[1]
    nlist, cap = ids.shape
    db = codes.shape[2]
    L = values.shape[1]
    bits = int(L).bit_length() - 1
    # the decoded (bc, d) fp32 tile and its int32 bucket indices live in
    # VMEM next to the packed codes
    bc = cap_tile(cap, 8 * d)
    row = lambda b, p, t, pr: (pr[b, p], 0, t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nprobe, cap // bc),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda b, p, t, pr: (b, 0, 0)),
            pl.BlockSpec((None, 1, bc), row),
            pl.BlockSpec((None, bc, db), lambda b, p, t, pr: (pr[b, p], t, 0)),
            pl.BlockSpec((None, 1, d), lambda b, p, t, pr: (pr[b, p], 0, 0)),
            pl.BlockSpec((L, d), lambda b, p, t, pr: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, bc),
                               lambda b, p, t, pr: (b, p, 0, t)),
    )
    out = pl.pallas_call(
        functools.partial(_ivf_scan_res_kernel, bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nprobe, 1, cap), jnp.float32),
        interpret=interpret,
        name="ivf_probe_res_scan",
    )(probe.astype(jnp.int32), q.reshape(B, 1, d), ids.reshape(nlist, 1, cap),
      codes, centroids.reshape(nlist, 1, d), values.T)
    return out.reshape(B, nprobe, cap)


def _rerank_paged_res_kernel(pt_ref, nt_ref, q_ref, qm_ref, cent_ref,
                             code_ref, cb_ref, val_ref, out_ref, acc_ref, *,
                             kc, pmax, bits):
    # one token page per grid step (grid (B, k', pmax)), its cent ids +
    # packed codes (page, db) uint8 decoded in VMEM and folded into a
    # running per-query-token max carried in acc.  The id block holds the
    # aligned group of cent-page rows around the wanted page (a single
    # (1, page) row is not a legal TPU block); the row is picked here.  The
    # codec tables (cb: (ncent, d), val: (L, d)) ride along as full blocks
    b, c, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    group = cent_ref.shape[0]
    row = pt_ref[(b * kc + c) * pmax + j] % group

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, NEG, jnp.float32)

    Tq = q_ref.shape[0]
    page = code_ref.shape[0]
    toks = _decode_rows(cent_ref[pl.ds(row, 1), :], code_ref[...],
                        cb_ref[...], val_ref, bits=bits)   # (page, d)
    s = jax.lax.dot_general(
        q_ref[...], toks, (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (Tq, page)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (Tq, page), 1)
    s = jnp.where(pos < nt_ref[b * pl.num_programs(1) + c], s, NEG)
    acc_ref[...] = jnp.maximum(acc_ref[...],
                               jnp.max(s, axis=-1, keepdims=True))

    @pl.when(j == pmax - 1)
    def _flush():
        _put_lane(out_ref, c, _maxsim_flush(acc_ref[...], qm_ref))


@functools.partial(jax.jit, static_argnames=("interpret",))
def rerank_paged_res_scores(q, q_mask, cand_ids, cent_pages, code_pages,
                            page_table, n_tokens, centroids, values, *,
                            interpret: bool = False):
    """Residual-tier paged MaxSim rerank: stream each candidate's COMPRESSED
    token pages and decode in VMEM — the fp32 slab never exists in HBM.

    q: (B, Tq, d); cand_ids: (B, k') int32 (-1 padded, caller masks);
    cent_pages: (P, page) int32; code_pages: (P, page, db) uint8;
    page_table: (C, pmax) int32 (-1 padded); n_tokens: (C,) int32;
    centroids: (ncent, d) / values: (d, L) the codec tables -> (B, k') fp32
    raw pair scores, bit-identical to decoding the pages host-side and
    running the fp32 paged oracle (``ref.rerank_scores_paged_ref``).
    """
    B, Tq, d = q.shape
    kp = cand_ids.shape[1]
    P, page = cent_pages.shape
    db = code_pages.shape[2]
    ncent = centroids.shape[0]
    L = values.shape[1]
    bits = int(L).bit_length() - 1
    pmax = page_table.shape[1]
    kc, nc, pt, nt = _paged_prefetch(cand_ids, page_table, n_tokens, pmax)
    pg = lambda b, c, j, pt, nt: (pt[(b * kc + c) * pmax + j], 0, 0)
    group = min(8, P)
    grp = lambda b, c, j, pt, nt: (pt[(b * kc + c) * pmax + j] // group, 0)
    fixed = lambda b, c, j, pt, nt: (0, 0)

    def call(pt, nt, q, qm):
        bb = q.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bb, kc, pmax),
            in_specs=[
                pl.BlockSpec((None, Tq, d), lambda b, c, j, pt, nt: (b, 0, 0)),
                pl.BlockSpec((None, Tq, 1), lambda b, c, j, pt, nt: (b, 0, 0)),
                pl.BlockSpec((group, page), grp),
                pl.BlockSpec((None, page, db), pg),
                pl.BlockSpec((ncent, d), fixed),
                pl.BlockSpec((L, d), fixed),
            ],
            out_specs=pl.BlockSpec((None, 1, kc),
                                   lambda b, c, j, pt, nt: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((Tq, 1), jnp.float32)],
        )
        return pl.pallas_call(
            functools.partial(_rerank_paged_res_kernel, kc=kc, pmax=pmax,
                              bits=bits),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bb, 1, kc), jnp.float32),
            interpret=interpret,
            name="rerank_paged_res_scores",
        )(pt, nt, q, qm, cent_pages, code_pages, centroids, values.T)

    q, q_mask = _fold_rows(nc, q, q_mask)
    out = _over_row_chunks(call, (pt, nt), (q, _query_rows(q_mask)))
    return out.reshape(B, kp)
