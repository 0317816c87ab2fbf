"""Parity suite for the gather-at-source serving kernels (PR: fused serving).

Three contracts, each across a shape grid that includes ``-1``-padded
candidate rows, ``k >`` #valid-candidates, non-128-multiple ``d``, tiny
cluster capacity, and ``B=1``:

* fused IVF probe scan (``search_ivf(use_fused_gather=True)``) returns
  bit-identical ids to the legacy gather-then-score path on fp32, and
  ≤2^-16-relative scores on SQ8 (the in-kernel hi/lo-bf16 dequant);
* fused candidate-gather rerank (``ops.fused_rerank``) is bit-identical to
  the ``maxsim.rerank`` oracle on fp32 (ids AND scores);
* the interpret-mode Pallas kernels themselves (``use_kernel=True``) match
  the pure-jnp refs.

Plus the compilation contract: the fused path still compiles exactly once
per (backend, resolved params, batch shape), and the fused/legacy toggle is
part of the compiled-fn key (flipping it may not silently reuse a trace).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.anns import ivf
from repro.anns.quantization import sq8_quant
from repro.kernels import gather_scan, ops, ref

SQ8_RTOL = 2 ** -16 * 4  # hi/lo bf16 split: ~2^-16 relative, small slack


def _mk_ivf(rng, m, d, nlist, *, sq8):
    vecs = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    return ivf.build_ivf(jax.random.PRNGKey(0), vecs, nlist, sq8=sq8,
                         kmeans_iters=2)


# --------------------------------------------------------------------------
# fused IVF scan vs the legacy search_ivf path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,m,d,nlist,nprobe,k", [
    (8, 200, 16, 16, 4, 10),
    (1, 120, 24, 16, 3, 5),       # B=1, non-128-multiple d
    (5, 60, 20, 16, 16, 100),     # k > #valid candidates in the probed lists
    (4, 40, 8, 32, 8, 6),         # tiny clusters (cap < any realistic block)
])
@pytest.mark.parametrize("sq8", [False, True])
def test_fused_ivf_scan_matches_legacy(B, m, d, nlist, nprobe, k, sq8):
    rng = np.random.default_rng(B * m + d)
    index = _mk_ivf(rng, m, d, nlist, sq8=sq8)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    ws, wi = ivf.search_ivf(index, q, nprobe, k, use_fused_gather=False)
    gs, gi = ivf.search_ivf(index, q, nprobe, k, use_fused_gather=True)
    if not sq8:
        # fp32: bit-exact — identical contraction, identical top-k
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    else:
        fin = np.isfinite(np.asarray(ws))
        np.testing.assert_array_equal(np.isfinite(np.asarray(gs)), fin)
        np.testing.assert_allclose(np.asarray(gs)[fin], np.asarray(ws)[fin],
                                   rtol=SQ8_RTOL, atol=1e-5)
    # the (B, k) pad contract survives either path: same -1 columns
    np.testing.assert_array_equal(np.asarray(gi) < 0, np.asarray(wi) < 0)


def test_fused_ivf_scan_strip_masks_pads():
    """The kernel-facing scan masks every padded cluster slot to -inf."""
    rng = np.random.default_rng(0)
    index = _mk_ivf(rng, 50, 12, 16, sq8=False)   # ragged lists => many pads
    q = jnp.asarray(rng.standard_normal((3, 12)), jnp.float32)
    probe = jnp.asarray(rng.integers(0, index.nlist, (3, 5)), jnp.int32)
    s = ops.fused_ivf_scan(q, probe, index.ids, index.counts, index.vecs,
                           index.scales)
    pads = np.asarray(jnp.take(index.ids, probe, axis=0)) < 0
    assert np.all(np.isneginf(np.asarray(s)[pads]))
    assert np.all(np.isfinite(np.asarray(s)[~pads]))


# --------------------------------------------------------------------------
# fused rerank vs the maxsim.rerank oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,m,Tq,Td,d,kp,k", [
    (6, 40, 5, 7, 16, 8, 4),
    (1, 30, 3, 4, 20, 6, 3),      # B=1, non-128-multiple d
    (4, 25, 4, 6, 16, 10, 10),    # k == k', rows with < k valid candidates
])
def test_fused_rerank_matches_oracle(B, m, Tq, Td, d, kp, k):
    from repro.core import maxsim

    rng = np.random.default_rng(B + m + kp)
    q = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Tq)) > 0.3).at[:, 0].set(True)
    docs = jnp.asarray(rng.standard_normal((m, Td, d)), jnp.float32)
    dm = jnp.asarray(rng.random((m, Td)) > 0.3).at[:, 0].set(True)
    cand = jnp.asarray(rng.integers(-1, m, (B, kp)), jnp.int32)  # -1 pads mixed in
    ws, wi = maxsim.rerank(q, qm, cand, docs, dm, k)
    gs, gi = ops.fused_rerank(q, qm, cand, docs, dm, k)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


def test_fused_rerank_pads_beyond_kprime():
    """k > k': the fused path pads out to (B, k) with (NEG, -1) instead of
    crashing — strictly wider than the oracle's contract."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 3, 8)), jnp.float32)
    qm = jnp.ones((2, 3), bool)
    docs = jnp.asarray(rng.standard_normal((10, 4, 8)), jnp.float32)
    dm = jnp.ones((10, 4), bool)
    cand = jnp.asarray([[1, 2, -1], [3, -1, -1]], jnp.int32)
    s, i = ops.fused_rerank(q, qm, cand, docs, dm, 5)
    assert s.shape == (2, 5) and i.shape == (2, 5)
    assert np.all(np.asarray(i)[:, 3:] == -1)
    assert np.all(np.asarray(i)[0, :2] >= 0) and np.asarray(i)[1, 0] >= 0


def test_fused_rerank_sq8_matches_sharded_math():
    """SQ8 rerank (per-token scales folded into score rows) == the exact
    gather-then-contract reference, and ≤2^-16-relative via the kernel."""
    rng = np.random.default_rng(2)
    B, m, Tq, Td, d, kp = 3, 20, 4, 5, 16, 6
    q = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.ones((B, Tq), bool)
    docs = jnp.asarray(rng.standard_normal((m, Td, d)), jnp.float32)
    dm = jnp.asarray(rng.random((m, Td)) > 0.2).at[:, 0].set(True)
    codes, scales = sq8_quant(docs)
    cand = jnp.asarray(rng.integers(0, m, (B, kp)), jnp.int32)
    want = ref.rerank_scores_ref(q, qm, cand, codes, dm, scales)
    got = gather_scan.rerank_gather_scores(q, qm, cand, codes, dm, scales,
                                           interpret=True)
    denom = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got - want))) / denom < SQ8_RTOL


# --------------------------------------------------------------------------
# the Pallas kernels themselves (interpret mode) vs the jnp refs
# --------------------------------------------------------------------------

# count-bounded lists: list c holds its real entries in slots [0, count),
# tiles of 128 rows (ivf_scan_plan at cap 512), probed so that a row
# mixes every fill, repeats a list, and starts or follows an empty one
PREFIX_COUNTS = (0, 37, 128, 256, 300, 512)
#                empty, a partial tile, one tile, an exact multiple, several
#                tiles and a partial one, count = cap


@pytest.mark.parametrize("B,nlist,cap,d,nprobe,counts,probe", [
    (4, 8, 5, 12, 3, None, None),     # tiny cap, non-128 d
    (1, 16, 9, 32, 8, None, None),    # B=1
    (2, 6, 512, 8, 2, PREFIX_COUNTS, [[0, 0], [1, 0]]),     # empty lists
    (1, 6, 512, 8, 3, PREFIX_COUNTS, [[1, 2, 1]]),          # partial tile
    (1, 6, 512, 8, 2, PREFIX_COUNTS, [[3, 3]]),             # exact multiple
    (2, 6, 512, 8, 1, PREFIX_COUNTS, [[5], [5]]),           # count = cap
    (1, 6, 512, 8, 2, PREFIX_COUNTS, [[4, 0]]),             # several tiles
    (1, 6, 512, 8, 4, PREFIX_COUNTS, [[4, 1, 4, 4]]),       # a list twice
    (3, 6, 512, 8, 4, PREFIX_COUNTS,                        # B > 1
     [[0, 1, 2, 3], [4, 5, 4, 1], [1, 0, 0, 5]]),
])
def test_ivf_scan_kernel_interpret_vs_ref(B, nlist, cap, d, nprobe, counts,
                                          probe):
    rng = np.random.default_rng(B * nlist + cap)
    if counts is None:
        # holes anywhere, every list's count its cap: the whole list streams
        ids = jnp.asarray(rng.integers(-1, 99, (nlist, cap)), jnp.int32)
        counts = jnp.full((nlist,), cap, jnp.int32)
        probe = rng.integers(0, nlist, (B, nprobe))
    else:
        real = np.arange(cap)[None, :] < np.asarray(counts)[:, None]
        ids = jnp.asarray(np.where(real, rng.integers(0, 1 << 20,
                                                      (nlist, cap)), -1),
                          jnp.int32)
        counts = jnp.asarray(counts, jnp.int32)
    probe = jnp.asarray(probe, jnp.int32)
    vecs = jnp.asarray(rng.standard_normal((nlist, cap, d)),
                       jnp.float32) * (ids >= 0)[..., None]
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    past = (np.arange(cap)[None, None, :]
            >= np.asarray(counts)[np.asarray(probe)][..., None])
    out = gather_scan.ivf_probe_scan(q, probe, ids, counts, vecs,
                                     interpret=True)
    want = ref.ivf_scan_ref(q, probe, ids, vecs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert np.all(np.isneginf(np.asarray(out)[past]))
    # SQ8 variant: in-kernel dequant within the hi/lo-bf16 tolerance
    codes, scales = sq8_quant(vecs)
    out = gather_scan.ivf_probe_scan(q, probe, ids, counts, codes, scales,
                                     interpret=True)
    want = ref.ivf_scan_ref(q, probe, ids, codes, scales)
    fin = np.isfinite(np.asarray(want))
    np.testing.assert_array_equal(np.isfinite(np.asarray(out)), fin)
    assert np.all(np.isneginf(np.asarray(out)[past]))
    denom = max(float(np.max(np.abs(np.asarray(want)[fin]), initial=0.0)),
                1.0)
    assert np.max(np.abs(np.asarray(out)[fin] - np.asarray(want)[fin]),
                  initial=0.0) / denom < SQ8_RTOL


def test_count_bounded_scan_keeps_search_ivf_top_k(monkeypatch):
    """``search_ivf`` through the count-bounded kernel (interpret mode)
    returns the top-k' ids and scores of the padded gather-then-score
    scan, on lists long enough for several tiles and a partial one."""
    rng = np.random.default_rng(13)
    index = _mk_ivf(rng, 1200, 8, 4, sq8=False)
    counts = np.asarray(index.counts)
    assert (counts > 128).any() and (counts % 128 != 0).any()
    q = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    want_s, want_i = ivf.search_ivf(index, q, 3, 200, use_fused_gather=False)
    monkeypatch.setattr(ops, "fused_ivf_scan", functools.partial(
        ops.fused_ivf_scan, use_kernel=True))
    jax.clear_caches()
    got_s, got_i = ivf.search_ivf(index, q, 3, 200, use_fused_gather=True)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


@pytest.mark.parametrize("B", [1, 2, 4, 8, 16])
def test_ivf_scan_plan_at_the_cells_widths(B):
    """At d' 2048 int8 and cap 2048, over the server's batch ladder: tiles
    of a multiple of 32 rows, their double buffer inside the VMEM budget,
    a full list streaming cap/bc tiles and an empty one none."""
    cap, dp, nprobe = 2048, 2048, 32
    plan = gather_scan.ivf_scan_plan(cap, dp)
    bc = plan.rows_per_tile
    assert bc % 32 == 0 and cap % bc == 0
    assert plan.vmem_bytes == 2 * bc * dp <= gather_scan.SCAN_VMEM_BYTES
    full = gather_scan.ivf_scan_plan(cap, dp, np.full((B, nprobe), cap))
    assert full.tiles == full.padded_tiles == B * nprobe * cap // bc
    mixed = gather_scan.ivf_scan_plan(
        cap, dp, np.tile([0, 1, bc, bc + 1], (B, nprobe // 4)))
    assert mixed.tiles == B * nprobe // 4 * (0 + 1 + 1 + 2)
    assert mixed.padded_tiles == full.padded_tiles
    # fp32 rows of 4 d' fit the budget too
    assert gather_scan.ivf_scan_plan(cap, 4 * dp).vmem_bytes \
        <= gather_scan.SCAN_VMEM_BYTES


def test_rerank_kernel_interpret_vs_ref():
    rng = np.random.default_rng(5)
    B, m, Tq, Td, d, kp = 3, 15, 4, 6, 20, 5
    q = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Tq)) > 0.4).at[:, 0].set(True)
    docs = jnp.asarray(rng.standard_normal((m, Td, d)), jnp.float32)
    dm = jnp.asarray(rng.random((m, Td)) > 0.4).at[:, 0].set(True)
    cand = jnp.asarray(rng.integers(-1, m, (B, kp)), jnp.int32)
    out = gather_scan.rerank_gather_scores(q, qm, cand, docs, dm,
                                           interpret=True)
    want = ref.rerank_scores_ref(q, qm, cand, docs, dm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("B,nlist,cap,d,nprobe,bits", [
    (4, 8, 5, 16, 3, 4),      # tiny cap
    (1, 16, 9, 8, 8, 2),      # B=1, 2-bit codes
])
def test_ivf_res_scan_kernel_interpret_vs_ref(B, nlist, cap, d, nprobe, bits):
    """Residual-tier probe scan (in-kernel decode-at-source) is BIT-identical
    to the host decode-then-score oracle — the one-hot decode sums exactly
    one fp32 term per element, so no tolerance is needed."""
    rng = np.random.default_rng(B * nlist + bits)
    ids = jnp.asarray(rng.integers(-1, 99, (nlist, cap)), jnp.int32)
    codes = jnp.asarray(rng.integers(0, 256, (nlist, cap, d * bits // 8)),
                        jnp.uint8)
    centroids = jnp.asarray(rng.standard_normal((nlist, d)), jnp.float32)
    values = jnp.asarray(np.sort(rng.standard_normal((d, 1 << bits)), axis=1),
                         jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    probe = jnp.asarray(rng.integers(0, nlist, (B, nprobe)), jnp.int32)
    out = gather_scan.ivf_probe_res_scan(q, probe, ids, codes, centroids,
                                         values, interpret=True)
    want = ref.ivf_scan_res_ref(q, probe, ids, codes, centroids, values)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("B,C,Tq,d,kp,bits", [
    (3, 12, 4, 16, 5, 4),
    (1, 8, 3, 8, 6, 2),       # B=1, 2-bit, k' > #docs
])
def test_rerank_paged_res_kernel_interpret_vs_ref(B, C, Tq, d, kp, bits):
    """Residual-tier paged rerank (compressed pages decoded in VMEM) is
    bit-identical to decoding the whole pool host-side and running the fp32
    paged oracle, -1 pads and short docs included."""
    rng = np.random.default_rng(B * C + bits)
    page, pmax = 4, 2
    P = C * pmax
    cent_pages = jnp.asarray(rng.integers(0, 10, (P, page)), jnp.int32)
    code_pages = jnp.asarray(rng.integers(0, 256, (P, page, d * bits // 8)),
                             jnp.uint8)
    centroids = jnp.asarray(rng.standard_normal((10, d)), jnp.float32)
    values = jnp.asarray(np.sort(rng.standard_normal((d, 1 << bits)), axis=1),
                         jnp.float32)
    table = jnp.asarray(
        rng.permutation(P).reshape(C, pmax), jnp.int32)
    n_tokens = jnp.asarray(rng.integers(1, pmax * page + 1, (C,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Tq)) > 0.3).at[:, 0].set(True)
    cand = jnp.asarray(rng.integers(-1, C, (B, kp)), jnp.int32)
    out = gather_scan.rerank_paged_res_scores(
        q, qm, cand, cent_pages, code_pages, table, n_tokens, centroids,
        values, interpret=True)
    want = ref.rerank_scores_paged_res_ref(
        q, qm, cand, cent_pages, code_pages, table, n_tokens, centroids,
        values)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def _paged_case(rng, B, C, Tq, d, kp, page, pmax):
    """A paged corpus behind a permuted (non-contiguous) page table, with
    dead docs (0 tokens), docs of exactly pmax pages and ``-1`` candidates
    in the first row."""
    P = C * pmax
    pages = jnp.asarray(rng.standard_normal((P, page, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(P).reshape(C, pmax), jnp.int32)
    n_tokens = rng.integers(1, pmax * page + 1, (C,))
    n_tokens[:2], n_tokens[2:4] = 0, pmax * page
    q = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Tq)) > 0.3).at[:, 0].set(True)
    cand = rng.integers(-1, C, (B, kp))
    cand[0, :4] = [-1, 0, 2, 3]        # a pad, a dead doc, two full docs
    return (q, qm, jnp.asarray(cand, jnp.int32), pages, table,
            jnp.asarray(n_tokens, jnp.int32))


@pytest.mark.parametrize("B,kp", [
    (2, 20),      # k' not a multiple of the block: a short last block
    (3, 5),       # k' below the block
    (1, 16),      # batch 1, k' one whole block
    (16, 24),     # batch 16 of the server's ladder
], ids=["kc-not-multiple-of-block", "kprime-below-block", "batch-1",
        "batch-16"])
def test_rerank_paged_kernel_interpret_vs_ref(B, kp):
    """The blocked fp32 paged rerank (a block of candidates per grid step,
    pages DMA'd by hand into a double buffer) matches the gather-from-pages
    oracle on every slot, pads and dead docs included."""
    rng = np.random.default_rng(B * 100 + kp)
    C, Tq, d, page, pmax = 20, 5, 16, 8, 3
    assert gather_scan.rerank_paged_plan(
        B, kp, pmax, page, d).cands_per_step == gather_scan.MAX_CANDS_PER_STEP
    args = _paged_case(rng, B, C, Tq, d, kp, page, pmax)
    out = gather_scan.rerank_paged_scores(*args, interpret=True)
    want = ref.rerank_scores_paged_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_rerank_paged_block_size_leaves_scores_unchanged(monkeypatch):
    """A VMEM budget that fits 3 candidates' pages twice in place of 16
    changes the blocking (:func:`rerank_paged_plan`, which the kernel
    reads) and not one score."""
    rng = np.random.default_rng(21)
    B, C, Tq, d, kp, page, pmax = 2, 20, 5, 16, 20, 8, 3
    args = _paged_case(rng, B, C, Tq, d, kp, page, pmax)
    jax.clear_caches()
    wide = np.asarray(gather_scan.rerank_paged_scores(*args, interpret=True))
    monkeypatch.setattr(gather_scan, "RERANK_VMEM_BYTES",
                        3 * 2 * pmax * page * d * 4)
    plan = gather_scan.rerank_paged_plan(B, kp, pmax, page, d)
    assert plan.cands_per_step == 3 and plan.grid_steps == B * 7
    jax.clear_caches()
    narrow = np.asarray(gather_scan.rerank_paged_scores(*args, interpret=True))
    jax.clear_caches()
    np.testing.assert_array_equal(narrow, wide)


@pytest.mark.parametrize("B", [1, 2, 4, 8, 16])
def test_rerank_paged_plan_at_the_cell_widths(B):
    """At the served widths (k' 1024, docs of 5 pages of 16 tokens, d 128)
    every batch of the server's ladder takes 16 candidates per grid step,
    64 steps per query, with a page double buffer inside the VMEM
    budget."""
    plan = gather_scan.rerank_paged_plan(B, 1024, 5, 16, 128)
    assert plan.cands_per_step == 16
    assert plan.grid_steps == B * 64 <= 2048
    assert plan.page_dmas == B * 1024 * 5
    assert plan.vmem_bytes == 2 * 16 * 5 * 16 * 128 * 4 \
        <= gather_scan.RERANK_VMEM_BYTES
    # a k' as long as the corpus is cut into SMEM-sized chunks first
    long = gather_scan.rerank_paged_plan(B, 1 << 17, 5, 16, 128)
    assert long.cands_per_step == 16 and long.grid_steps == B * (1 << 17) // 16


@pytest.mark.parametrize("kernel", ["gather", "paged", "paged_res"])
def test_rerank_kernels_split_long_kprime_for_smem(monkeypatch, kernel):
    """A k' whose scalar-prefetched strips overflow SMEM is folded into
    candidate chunks and row groups (here forced by a 48-byte budget):
    the scores are bit-identical to the unsplit launch and match the
    oracle."""
    rng = np.random.default_rng(11)
    B, C, Tq, d, kp, page, pmax, bits = 2, 20, 5, 16, 24, 4, 2, 4
    q = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Tq)) > 0.3).at[:, 0].set(True)
    cand = jnp.asarray(rng.integers(-1, C, (B, kp)), jnp.int32)
    table = jnp.asarray(rng.permutation(C * pmax).reshape(C, pmax), jnp.int32)
    n_tokens = jnp.asarray(rng.integers(1, pmax * page + 1, (C,)), jnp.int32)
    if kernel == "gather":
        docs = jnp.asarray(rng.standard_normal((C, 6, d)), jnp.float32)
        dm = jnp.asarray(rng.random((C, 6)) > 0.4).at[:, 0].set(True)
        args = (q, qm, cand, docs, dm)
        run, oracle = gather_scan.rerank_gather_scores, ref.rerank_scores_ref
    elif kernel == "paged":
        pages = jnp.asarray(rng.standard_normal((C * pmax, page, d)),
                            jnp.float32)
        args = (q, qm, cand, pages, table, n_tokens)
        run = gather_scan.rerank_paged_scores
        oracle = ref.rerank_scores_paged_ref
    else:
        cent = jnp.asarray(rng.integers(0, 10, (C * pmax, page)), jnp.int32)
        codes = jnp.asarray(rng.integers(0, 256, (C * pmax, page,
                                                  d * bits // 8)), jnp.uint8)
        centroids = jnp.asarray(rng.standard_normal((10, d)), jnp.float32)
        values = jnp.asarray(np.sort(rng.standard_normal((d, 1 << bits)),
                                     axis=1), jnp.float32)
        args = (q, qm, cand, cent, codes, table, n_tokens, centroids, values)
        run = gather_scan.rerank_paged_res_scores
        oracle = ref.rerank_scores_paged_res_ref
    jax.clear_caches()
    whole = np.asarray(run(*args, interpret=True))
    monkeypatch.setattr(gather_scan, "SMEM_PREFETCH_BYTES", 48)
    jax.clear_caches()
    split = np.asarray(run(*args, interpret=True))
    jax.clear_caches()
    np.testing.assert_array_equal(split, whole)
    live = np.asarray(cand) >= 0
    np.testing.assert_allclose(split[live], np.asarray(oracle(*args))[live],
                               rtol=1e-6, atol=1e-6)


def test_ops_fused_dispatch_kernel_vs_ref():
    """ops wrappers: forced-kernel (interpret) results == forced-ref results
    (fp32 exact), i.e. platform dispatch cannot change answers."""
    rng = np.random.default_rng(9)
    index = _mk_ivf(rng, 80, 16, 16, sq8=False)
    q = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    probe = jnp.asarray(rng.integers(0, index.nlist, (2, 4)), jnp.int32)
    a = ops.fused_ivf_scan(q, probe, index.ids, index.counts, index.vecs,
                           use_kernel=True)
    b = ops.fused_ivf_scan(q, probe, index.ids, index.counts, index.vecs,
                           use_kernel=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mips_sq8_batched_single_call_equivalence():
    """The batched SQ8 fallback (ONE contraction / ONE flattened kernel
    launch) == B independent per-row scans."""
    rng = np.random.default_rng(11)
    B, n, d = 5, 12, 16
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    codes = jnp.asarray(rng.integers(-127, 128, (B, n, d)), jnp.int8)
    scales = jnp.asarray(rng.random((B, n)) + 0.1, jnp.float32)
    want = jnp.stack([ref.mips_sq8_ref(q[b:b + 1], codes[b], scales[b])[0]
                      for b in range(B)])
    got_ref = ops.mips_sq8_batched(q, codes, scales, use_kernel=False)
    # fp32 associativity: batched einsum vs per-row matmul reduction order
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    got_kern = ops.mips_sq8_batched(q, codes, scales, use_kernel=True,
                                    block_q=8, block_m=32)
    denom = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got_kern - want))) / denom < SQ8_RTOL


# --------------------------------------------------------------------------
# compilation contract
# --------------------------------------------------------------------------

def test_fused_path_trace_count(tiny_corpus):
    """One jit trace per (backend, resolved params, batch shape) with the
    fused path on (the default), and the fused/legacy toggle is a distinct
    cache entry — equivalent spellings of the default still share one."""
    from repro.core import LemurConfig
    from repro.retriever import IVFSearchParams, LemurRetriever, SearchParams

    cfg = LemurConfig(d=16, d_prime=24, m_pretrain=64, n_train=512, n_ols=256,
                      epochs=2, k=5, k_prime=32, anns="ivf")
    r = LemurRetriever.build(tiny_corpus, cfg, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((4, 6, 16)), jnp.float32)
    qm = jnp.ones((4, 6), bool)

    fused = SearchParams()
    r.search(q, qm, fused)
    r.search(q, qm, fused)
    # explicit spelling of the resolved default => same compiled fn
    r.search(q, qm, SearchParams(
        use_fused_gather=True, backend=IVFSearchParams(use_fused_gather=True)))
    assert r.trace_count(fused) == 1
    assert r.trace_count() == 1

    legacy = SearchParams(use_fused_gather=False,
                          backend=IVFSearchParams(use_fused_gather=False))
    r.search(q, qm, legacy)
    assert r.trace_count(legacy) == 1
    assert r.trace_count() == 2

    # new batch shape => exactly one more trace for the fused entry
    r.search(q[:2], qm[:2], fused)
    assert r.trace_count(fused) == 2
