"""Pallas TPU one-launch query kernels: ψ-projection → scan → in-kernel top-k'.

LEMUR's speed claim is that MaxSim retrieval collapses into a single latent
MIPS pass — yet the serving path still ran it as 3+ XLA launches with full
HBM round-trips between them (ψ latent projection → IVF probe scan → top-k'):
the ``(B, Tq, d')`` ψ features and the ``(B, nprobe, cap)`` score strip each
made an HBM write+read purely to cross a launch boundary.  These kernels
keep the whole pre-rerank pipeline inside ONE grid:

``query_fused`` — grid ``(B, nprobe, cap/bc)``, probe ids scalar-prefetched
to SMEM (``pltpu.PrefetchScalarGridSpec``, same scheme as ``gather_scan``):

* step ``(b, 0, 0)`` computes ψ for query ``b``'s tokens in-kernel (the
  ``fused_psi`` matmul+GELU+LayerNorm body), masks and pools them
  (eq. 5) into a ``(1, d')`` VMEM scratch — the pooled query never touches
  HBM, and is carried across the minor grid steps (the TPU grid iterates
  the last dimension innermost, so scratch persists per ``b``);
* every step ``(b, p, t)`` DMAs cap-tile ``t`` of cluster ``probe[b, p]``
  HBM→VMEM (BlockSpec index_map reads the prefetched id; consecutive steps
  double-buffer automatically), scores it against the pooled query (fp32,
  or int8 codes dequantized in-kernel via the exact bf16 split), masks
  ``-1`` pad slots to ``-inf``;
* the per-step ``(1, bc)`` score strip is merged into a carried ``(1, k')``
  best-scores/best-ids strip (local ``jax.lax.top_k`` over
  ``concat([carried, strip])`` — carried first, so earlier flat positions
  win score ties exactly like the legacy flat top-k), and only the final
  ``(B, k')`` ids+scores are written to HBM.

Per query the HBM traffic is the probed source bytes streamed once plus
``k'`` result slots — the ``(B, Tq, d')`` feature tensor and the
``(B, nprobe, cap)`` strip never exist.

VMEM per step (Tq=32, d=128, d'=2048, k'=1024, fp32): W' tile 1 MiB +
token slab 16 KiB + pooled query 8 KiB + a cap-tile of at most 1 MiB
(``gather_scan.cap_tile``; ×2 for the pipeline's double buffer) + the
carried strips 8 KiB — inside v5e VMEM at any cap.

``mips_topk`` — the dense-scan twin for the sharded serving step: grid
``(B, m/bm)`` over corpus tiles of the local latent shard, per-step MXU
contraction + validity mask (corpus pad rows → ``NEG``) + the same carried
top-k' merge.  Replaces ``psi_q @ W.T`` → mask → ``top_k`` (a full
``(B, m_loc)`` HBM score matrix) with one launch returning ``(B, k')``.

The in-kernel ``jax.lax.top_k`` merge is validated in interpret mode (the
tests' parity grid).  Mosaic has no lowering for ``top_k``, so these
kernels do not compile for the TPU; ``kernels.ops`` raises there instead
of answering from the reference (``use_one_launch=False`` serves).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_scan import HIGHEST, cap_tile
from repro.kernels.mips_sq8 import split_dot

NEG = -1e30


def _merge_topk(best_s, best_i, s, ids):
    """Fold one (1, n) score/id strip into the carried (1, k') strip.

    The carried strip goes FIRST in the concat: its entries came from
    earlier flat positions, so a stable ``jax.lax.top_k`` (lowest index
    first on ties) reproduces the legacy flat top-k's tie-breaking, step by
    step, by induction."""
    kp = best_s.shape[1]
    cs = jnp.concatenate([best_s[...], s], axis=1)
    ci = jnp.concatenate([best_i[...], ids.astype(jnp.int32)], axis=1)
    top, pos = jax.lax.top_k(cs, kp)
    best_s[...] = top
    best_i[...] = jnp.take_along_axis(ci, pos, axis=1)


def _pool_psi(qt_ref, qm_ref, w_ref, b_ref, g_ref, beta_ref, eps):
    """The ``fused_psi`` kernel body + mask + pool: (Tq, d) -> (1, d')."""
    h = jax.lax.dot_general(
        qt_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    h = h + b_ref[...]
    h = jax.nn.gelu(h, approximate=True)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    y = (h - mu) * jax.lax.rsqrt(var + eps)
    y = y * g_ref[...] + beta_ref[...]
    y = y * (qm_ref[...] > 0)
    return jnp.sum(y, axis=0, keepdims=True)


def _scan_fp(q, vecs_ref, scales_ref):
    return jax.lax.dot_general(
        q, vecs_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (1, bc)


def _scan_sq8(q, codes_ref, scales_ref):
    # int8 cluster codes dequantized IN-KERNEL (the exact bf16 split of
    # kernels.mips_sq8.split_dot), per-slot scales folded into the strip —
    # same identity as gather_scan._ivf_scan_sq8_kernel
    return split_dot(q, codes_ref[...]) * scales_ref[...]


def _query_fused_kernel(probe_ref, qt_ref, qm_ref, w_ref, b_ref, g_ref,
                        beta_ref, ids_ref, *refs, eps, scan):
    # refs: the list tile(s) of this step, then the two (1, k') outputs and
    # the three scratch strips.  Step (b, p, t): cap-tile t of cluster
    # probe[b, p]; the pooled ψ query is computed at the first step of b
    *tiles, out_s_ref, out_i_ref, q_acc, best_s, best_i = refs
    p, t = pl.program_id(1), pl.program_id(2)
    first = (p == 0) & (t == 0)
    last = (p == pl.num_programs(1) - 1) & (t == pl.num_programs(2) - 1)

    @pl.when(first)
    def _init():
        q_acc[...] = _pool_psi(qt_ref, qm_ref, w_ref, b_ref, g_ref, beta_ref,
                               eps)
        best_s[...] = jnp.full(best_s.shape, -jnp.inf, jnp.float32)
        best_i[...] = jnp.full(best_i.shape, -1, jnp.int32)

    s = scan(q_acc[...], *tiles)
    s = jnp.where(ids_ref[...] >= 0, s, -jnp.inf)
    _merge_topk(best_s, best_i, s, ids_ref[...])

    @pl.when(last)
    def _flush():
        out_s_ref[...] = best_s[...]
        out_i_ref[...] = best_i[...]


def _scan_res(bits):
    # residual-tier cluster lists decoded IN-KERNEL (gather_scan.
    # _ivf_scan_res_kernel): packed codes unpack via an expansion matmul +
    # shifts, per-dim values via a select-sum over the L levels, and the
    # cluster's OWN centroid row arrives as a (1, d') tile DMA'd by the same
    # prefetched probe id — the fp32 cluster list never exists in HBM
    from repro.kernels.gather_scan import _residual_values, _unpack_codes_i32

    def scan(q, codes_ref, cent_ref, val_ref):
        idx = _unpack_codes_i32(codes_ref[...], bits=bits)
        v = _residual_values(idx, val_ref) + cent_ref[...]   # (bc, d')
        return jax.lax.dot_general(
            q, v, (((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )

    return scan


def _query_fused_call(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias,
                      probe, ids, tiles, tile_specs, scan, bc, kp, interpret,
                      eps):
    """Shared launch of the one-launch query kernel: grid (B, nprobe,
    cap/bc); ``tiles``/``tile_specs`` are the per-step list operands."""
    B, Tq, d = q_tokens.shape
    nprobe = probe.shape[1]
    nlist, cap = ids.shape
    dp = kernel.shape[1]
    qb = lambda b, p, t, pr: (b, 0, 0)
    fixed = lambda b, p, t, pr: (0, 0)
    out_spec = pl.BlockSpec((None, 1, kp), qb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nprobe, cap // bc),
        in_specs=[
            pl.BlockSpec((None, Tq, d), qb),
            pl.BlockSpec((None, Tq, 1), qb),
            pl.BlockSpec((d, dp), fixed),
            pl.BlockSpec((1, dp), fixed),
            pl.BlockSpec((1, dp), fixed),
            pl.BlockSpec((1, dp), fixed),
            pl.BlockSpec((None, 1, bc), lambda b, p, t, pr: (pr[b, p], 0, t)),
            *tile_specs,
        ],
        out_specs=[out_spec, out_spec],
        scratch_shapes=[pltpu.VMEM((1, dp), jnp.float32),
                        pltpu.VMEM((1, kp), jnp.float32),
                        pltpu.VMEM((1, kp), jnp.int32)],
    )
    s, i = pl.pallas_call(
        functools.partial(_query_fused_kernel, eps=eps, scan=scan),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, kp), jnp.int32)],
        interpret=interpret,
    )(probe.astype(jnp.int32), q_tokens, q_mask.astype(jnp.int32)[..., None],
      kernel, bias.reshape(1, dp), ln_scale.reshape(1, dp),
      ln_bias.reshape(1, dp), ids.reshape(nlist, 1, cap), *tiles)
    return s.reshape(B, kp), i.reshape(B, kp)


@functools.partial(jax.jit, static_argnames=("kp", "interpret"))
def query_fused(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias, probe,
                ids, vecs, scales=None, *, kp: int, interpret: bool = False,
                eps: float = 1e-5):
    """One-launch fused query: pooled ψ(X) + probed IVF scan + top-k'.

    q_tokens: (B, Tq, d); kernel/bias/ln_*: the ψ weights (d, d') / (d',);
    probe: (B, nprobe) int32 cluster ids (the query-scale probe-select
    prelude runs in XLA — see ``ops.fused_query``); ids: (nlist, cap) int32
    (-1 padded); vecs: (nlist, cap, d') fp32 — or int8 codes with scales:
    (nlist, cap) — returns (scores (B, kp) fp32, ids (B, kp) int32), rows
    padded with ``(-inf, -1)`` when fewer than ``kp`` real candidates were
    probed.  Only these two (B, kp) strips ever reach HBM.
    """
    nlist, cap, dp = vecs.shape
    bc = cap_tile(cap, dp * vecs.dtype.itemsize)
    tile = pl.BlockSpec((None, bc, dp), lambda b, p, t, pr: (pr[b, p], t, 0))
    tiles, specs, scan = [vecs], [tile], _scan_fp
    if scales is not None:
        tiles.append(scales.reshape(nlist, 1, cap))
        specs.append(pl.BlockSpec((None, 1, bc),
                                  lambda b, p, t, pr: (pr[b, p], 0, t)))
        scan = _scan_sq8
    else:
        scan = lambda q, vecs_ref: _scan_fp(q, vecs_ref, None)
    return _query_fused_call(q_tokens, q_mask, kernel, bias, ln_scale,
                             ln_bias, probe, ids, tiles, specs, scan, bc, kp,
                             interpret, eps)


@functools.partial(jax.jit, static_argnames=("kp", "interpret"))
def query_fused_res(q_tokens, q_mask, kernel, bias, ln_scale, ln_bias, probe,
                    ids, codes, centroids, rq_values, *, kp: int,
                    interpret: bool = False, eps: float = 1e-5):
    """One-launch fused query over a RESIDUAL-compressed IVF index.

    Same contract as :func:`query_fused`, with the cluster lists stored as
    packed residual codes: codes (nlist, cap, db) uint8 coded against each
    cluster's own centroid row; centroids (nlist, d') fp32 (the SAME table
    the probe-select prelude scores); rq_values (d', L) fp32.  Returns
    (scores (B, kp) fp32, ids (B, kp) int32) padded with ``(-inf, -1)``.
    """
    nlist, cap, db = codes.shape
    dp = centroids.shape[1]
    L = rq_values.shape[1]
    bits = int(L).bit_length() - 1
    bc = cap_tile(cap, 8 * dp)
    specs = [
        pl.BlockSpec((None, bc, db), lambda b, p, t, pr: (pr[b, p], t, 0)),
        pl.BlockSpec((None, 1, dp), lambda b, p, t, pr: (pr[b, p], 0, 0)),
        pl.BlockSpec((L, dp), lambda b, p, t, pr: (0, 0)),
    ]
    tiles = [codes, centroids.reshape(nlist, 1, dp), rq_values.T]
    return _query_fused_call(q_tokens, q_mask, kernel, bias, ln_scale,
                             ln_bias, probe, ids, tiles, specs,
                             _scan_res(bits), bc, kp, interpret, eps)


# --------------------------------------------------------------------------
# dense-scan twin: fused latent MIPS + in-kernel top-k' (the sharded path)
# --------------------------------------------------------------------------

def _mips_topk_kernel(q_ref, w_ref, *refs, nt, bm, sq8):
    if sq8:
        ws_ref, valid_ref, out_s_ref, out_i_ref, best_s, best_i = refs
    else:
        ws_ref = None
        valid_ref, out_s_ref, out_i_ref, best_s, best_i = refs
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        best_s[...] = jnp.full(best_s.shape, -jnp.inf, jnp.float32)
        best_i[...] = jnp.full(best_i.shape, -1, jnp.int32)

    s = (_scan_sq8 if sq8 else _scan_fp)(q_ref[...], w_ref, ws_ref)
    ids = t * bm + jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
    s = jnp.where(valid_ref[...] > 0, s, NEG)
    _merge_topk(best_s, best_i, s, ids)

    @pl.when(t == nt - 1)
    def _flush():
        out_s_ref[...] = best_s[...]
        out_i_ref[...] = best_i[...]


@functools.partial(jax.jit, static_argnames=("kp", "block_m", "interpret"))
def mips_topk(q, W, W_scales=None, valid=None, *, kp: int,
              block_m: int = 512, interpret: bool = False):
    """Fused latent scan + top-k': q (B, d') x W (m, d') -> top-k' of each
    row without materializing the (B, m) score matrix in HBM.

    ``W`` is fp32 — or int8 codes with per-row ``W_scales`` (m,).  ``valid``
    (m,) bool masks rows to ``NEG`` (score only — their POSITION ids are
    kept, matching the sharded serve step's pad-row convention); the rows
    this wrapper pads up to the tile multiple are masked the same way and,
    sitting at the highest positions, can never displace a real row.
    Returns (scores (B, kp) fp32, ids (B, kp) int32 positions).
    """
    B, dp = q.shape
    m = W.shape[0]
    bm = min(block_m, m)
    mp = -(-m // bm) * bm
    if valid is None:
        valid = jnp.ones((m,), bool)
    valid = jnp.pad(valid, (0, mp - m)).reshape(1, mp).astype(jnp.int32)
    Wp = jnp.pad(W, ((0, mp - m), (0, 0)))
    nt = mp // bm
    strip = pl.BlockSpec((1, bm), lambda b, t: (0, t))
    in_specs = [pl.BlockSpec((None, 1, dp), lambda b, t: (b, 0, 0)),
                pl.BlockSpec((bm, dp), lambda b, t: (t, 0))]
    args = [q.reshape(B, 1, dp), Wp]
    if W_scales is not None:
        in_specs.append(strip)
        args.append(jnp.pad(W_scales, (0, mp - m)).reshape(1, mp))
    out_spec = pl.BlockSpec((None, 1, kp), lambda b, t: (b, 0, 0))
    s, i = pl.pallas_call(
        functools.partial(_mips_topk_kernel, nt=nt, bm=bm,
                          sq8=W_scales is not None),
        grid=(B, nt),
        in_specs=in_specs + [strip],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, kp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, kp), jnp.float32),
                        pltpu.VMEM((1, kp), jnp.int32)],
        interpret=interpret,
    )(*args, valid)
    return s.reshape(B, kp), i.reshape(B, kp)
