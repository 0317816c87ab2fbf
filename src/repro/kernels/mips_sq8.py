"""Pallas TPU kernel: int8 scalar-quantized MIPS scan (Glass-style SQ on MXU).

Scores a block of fp32 queries against an int8-quantized latent corpus with
per-row scales, dequantizing INSIDE the kernel — HBM traffic for the corpus
is 4x lower than fp32, which matters because the latent scan is memory-bound
(arithmetic intensity 2·B flops/byte at int8).

    s = q (Bq, d') @ codes^T (d', Bm) * scales (Bm)

int8 codes are widened to bf16 for the MXU dot (int8×int8→int32 MXU paths
are not exposed via Pallas dot_general on all generations; bf16 exactly
represents ints up to 256).  The fp32 query is split into three bf16 parts
that sum to it exactly (:func:`split_dot`, three MXU passes): every product
with an int8 code is then exact in fp32, and the result matches the fp32
oracle up to accumulation order.  A two-part split leaves ~2^-16 of each
query element behind, which over d'=2048 terms already exceeds 2^-16 of
the score; the scan is memory-bound, so the third pass costs nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def split_dot(q, codes):
    """q (n, d) fp32 x int8 ``codes`` (m, d) -> (n, m) fp32 on the MXU.

    The codes widen to bf16 exactly; q splits into hi + mid + lo bf16 parts
    whose sum is q, so each bf16 pass multiplies exactly and only the fp32
    accumulation rounds."""
    c = codes.astype(jnp.float32).astype(jnp.bfloat16)
    dot = lambda a: jax.lax.dot_general(
        a, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    hi = q.astype(jnp.bfloat16)
    r = q - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(hi) + dot(mid) + dot(lo)


def _mips_sq8_kernel(q_ref, codes_ref, scales_ref, out_ref):
    s = split_dot(q_ref[...], codes_ref[...])   # (Bq, Bm) fp32
    out_ref[...] = s * scales_ref[...][None, :]


@functools.partial(jax.jit, static_argnames=("block_q", "block_m", "interpret"))
def mips_sq8(q, codes, scales, *, block_q: int = 128, block_m: int = 1024,
             interpret: bool = False):
    """q: (B, d) fp32; codes: (m, d) int8; scales: (m,) -> (B, m) fp32."""
    B, d = q.shape
    m = codes.shape[0]
    dp = -(-d // 128) * 128
    bp = -(-B // block_q) * block_q
    mp = -(-m // block_m) * block_m
    q_p = jnp.pad(q, ((0, bp - B), (0, dp - d)))
    c_p = jnp.pad(codes, ((0, mp - m), (0, dp - d)))
    s_p = jnp.pad(scales, (0, mp - m))

    out = pl.pallas_call(
        _mips_sq8_kernel,
        grid=(bp // block_q, mp // block_m),
        in_specs=[
            pl.BlockSpec((block_q, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((block_q, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        interpret=interpret,
    )(q_p, c_p, s_p)
    return out[:B, :m]
