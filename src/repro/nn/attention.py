"""Attention layers: RoPE, GQA/MQA/MHA, MLA (DeepSeek), KV caches.

Two execution paths:

* ``flash_attention`` — blockwise online-softmax attention in pure JAX
  (double ``lax.scan`` over query/KV blocks).  Never materializes the full
  (T, S) score matrix, so 32k prefill fits per-device HBM; GSPMD shards it
  like any einsum.  This is the path used inside the jitted system graphs
  (a Pallas flash kernel would not lower on the CPU-only container; the
  Pallas MaxSim/MIPS kernels in ``repro.kernels`` cover the paper's own
  hot spots and are validated in interpret mode).
* ``decode_attention`` — single-token query against a padded KV cache
  (scores are (B, H, 1, S): linear in S, safe to materialize).

Layouts: activations (B, T, D); q/k/v projections (D, H, head_dim);
caches (B, S_max, n_kv, head_dim) — batch on the data axis, heads or
sequence on the model axis (see repro.dist.sharding).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.nn import layers

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, base: float = 10000.0):
    return 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, base: float = 10000.0):
    """x: (B, T, H, D); positions: (B, T) int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, base)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * inv  # (B, T, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# masking rule (never materialize (T, S) globally — evaluated per block)
# ---------------------------------------------------------------------------

def _allowed(q_pos, kv_pos, *, causal: bool, chunk: int | None = None, kv_len=None):
    """q_pos: (..., Tq), kv_pos: (Sb,) -> bool (..., Tq, Sb)."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= kp <= qp
    if chunk is not None:
        ok &= (kp // chunk) == (qp // chunk)
    if kv_len is not None:
        ok &= kp < kv_len
    return ok


# ---------------------------------------------------------------------------
# blockwise flash attention (pure JAX)
# ---------------------------------------------------------------------------

class _Carry(NamedTuple):
    o: jax.Array  # (B, Tq, K, G, D) fp32 — unnormalized output accumulator
    m: jax.Array  # (B, Tq, K, G) running max
    l: jax.Array  # (B, Tq, K, G) running sum


def _flash_q_block(q, k, v, q_pos, kv_pos, *, scale, causal, chunk, kv_block):
    """q: (B, Tq, K, G, D); k/v: (B, S, K, D). Returns (B, Tq, K, G, D)."""
    B, Tq, K, G, D = q.shape
    S = k.shape[1]
    nkv = S // kv_block

    kb = k.reshape(B, nkv, kv_block, K, -1)
    vb = v.reshape(B, nkv, kv_block, K, v.shape[-1])
    pb = kv_pos.reshape(nkv, kv_block)

    init = _Carry(
        o=jnp.zeros((B, Tq, K, G, v.shape[-1]), jnp.float32),
        m=jnp.full((B, Tq, K, G), NEG_INF, jnp.float32),
        l=jnp.zeros((B, Tq, K, G), jnp.float32),
    )

    def step(carry: _Carry, xs):
        kc, vc, pc = xs  # (B, Sb, K, Dk), (B, Sb, K, Dv), (Sb,)
        # scores: (B, Tq, K, G, Sb)
        s = jnp.einsum("btkgd,bskd->btkgs", q, kc, preferred_element_type=jnp.float32)
        s = s * scale
        ok = _allowed(q_pos, pc, causal=causal, chunk=chunk)  # (B?, Tq, Sb)
        ok = ok[:, :, None, None, :] if ok.ndim == 3 else ok[None, :, None, None, :]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(carry.m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new == NEG_INF)
        m_safe = jnp.maximum(m_new, -0.5 * NEG_INF * 0 + NEG_INF * 0.99)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(carry.m - m_new)
        alpha = jnp.where(carry.m <= NEG_INF * 0.5, 0.0, alpha)
        l_new = carry.l * alpha + jnp.sum(p, axis=-1)
        o_new = carry.o * alpha[..., None] + jnp.einsum(
            "btkgs,bskd->btkgd", p, vc.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        return _Carry(o_new, m_new, l_new), None

    carry, _ = jax.lax.scan(
        step,
        init,
        (
            jnp.moveaxis(kb, 1, 0),
            jnp.moveaxis(vb, 1, 0),
            pb,
        ),
    )
    denom = jnp.maximum(carry.l, 1e-30)[..., None]
    return carry.o / denom


def flash_attention(
    q,
    k,
    v,
    q_positions,
    kv_positions,
    *,
    causal: bool = True,
    chunk: int | None = None,
    q_block: int = 1024,
    kv_block: int = 1024,
    scale: float | None = None,
):
    """q: (B, T, Hq, D), k/v: (B, S, Kv, D[v]).  Hq % Kv == 0 (GQA groups).

    Returns (B, T, Hq, Dv) in q.dtype.  Positions are absolute token indices
    (ints); masking (causal / chunked-local / cache-validity) is computed
    per block from positions, so no global mask tensor exists.
    """
    B, T, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    scale = scale if scale is not None else D**-0.5

    Tp = -(-T // q_block) * q_block
    Sp = -(-k.shape[1] // kv_block) * kv_block
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - k.shape[1]), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - v.shape[1]), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, ((0, 0), (0, Tp - T)), constant_values=-1)
    kvpos = jnp.pad(kv_positions, (0, Sp - kv_positions.shape[0]), constant_values=2**30)

    qg = qp.reshape(B, Tp // q_block, q_block, Kv, G, D)

    def per_qblock(qb, qposb):
        # qb: (B, q_block, Kv, G, D), qposb: (B, q_block)
        return _flash_q_block(
            qb, kp, vp, qposb, kvpos, scale=scale, causal=causal, chunk=chunk, kv_block=kv_block
        )

    # scan over query blocks (keeps peak memory at one (q_block, kv_block) tile)
    qg_t = jnp.moveaxis(qg, 1, 0)  # (nq, B, q_block, Kv, G, D)
    qpos_t = jnp.moveaxis(qpos.reshape(B, Tp // q_block, q_block), 1, 0)
    out_blocks = jax.lax.map(lambda xs: per_qblock(*xs), (qg_t, qpos_t))
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, Tp, H, v.shape[-1])
    return out[:, :T].astype(q.dtype)


def flash_attention_cp(q, k, v, q_positions, mesh, *, causal=True, chunk=None,
                       q_block: int = 1024, kv_block: int = 1024, scale=None):
    """Context-parallel flash attention (shard_map over the "model" axis).

    q/k/v enter seq-sharded (the residual stream's sequence-parallel layout);
    each shard all-gathers K/V ONCE and runs the blockwise flash core on its
    local T/|model| query rows.  Per layer this costs exactly one (B, S, Kv, D)
    gather — versus GSPMD re-gathering K/V inside every (q-block × kv-block)
    loop iteration when the nested-scan version is left to the partitioner
    (measured 440x collective inflation on the 32k prefill cells; see
    EXPERIMENTS.md §Perf iteration 1).  Causal load imbalance across shards
    is accepted (ring/striped attention is the documented next step).
    """
    from jax.sharding import PartitionSpec as P

    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    S = k.shape[1]
    kv_pos = jnp.arange(S)

    def body(q_l, k_l, v_l, pos_l, kv_pos_f):
        k_f = jax.lax.all_gather(k_l, "model", axis=1, tiled=True)
        v_f = jax.lax.all_gather(v_l, "model", axis=1, tiled=True)
        return flash_attention(q_l, k_f, v_f, pos_l, kv_pos_f, causal=causal,
                               chunk=chunk, q_block=min(q_block, q_l.shape[1]),
                               kv_block=kv_block, scale=scale)

    seq4 = P(ba, "model", None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(seq4, seq4, seq4, P(ba, "model"), P()),
        out_specs=seq4,
        check_vma=False,
    )(q, k, v, q_positions, kv_pos)


def _use_cp(mesh, T: int) -> bool:
    return (
        mesh is not None
        and "model" in getattr(mesh, "axis_names", ())
        and T % mesh.shape["model"] == 0
        and T // mesh.shape["model"] >= 128
    )


def decode_attention(q, k_cache, v_cache, kv_len, *, chunk: int | None = None, scale=None):
    """One-step decode.  q: (B, 1, Hq, D); caches: (B, S, Kv, D); kv_len: ()/(B,)."""
    B, _, H, D = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    G = H // Kv
    scale = scale if scale is not None else D**-0.5
    qg = q.reshape(B, 1, Kv, G, D)
    s = jnp.einsum("btkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    kv_pos = jnp.arange(S)
    q_pos = (jnp.broadcast_to(jnp.asarray(kv_len), (B,)) - 1)[:, None]
    ok = _allowed(q_pos, kv_pos, causal=True, chunk=chunk, kv_len=jnp.asarray(kv_len))
    # ok: (B, 1, S) -> (B, 1, 1, S) broadcast over (Kv, G)
    s = jnp.where(ok[:, None, :, :] if ok.ndim == 3 else ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (init / train / prefill / decode)
# ---------------------------------------------------------------------------

def init_gqa(key, d_model, n_heads, n_kv, head_dim, qkv_bias=False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.variance_scaling(ks[0], (d_model, n_heads, head_dim), dtype=dtype),
        "wk": layers.variance_scaling(ks[1], (d_model, n_kv, head_dim), dtype=dtype),
        "wv": layers.variance_scaling(ks[2], (d_model, n_kv, head_dim), dtype=dtype),
        "wo": layers.variance_scaling(ks[3], (n_heads, head_dim, d_model), mode="fan_out", dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    return p


def _qkv(params, x):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    return q, k, v


def gqa_train(params, x, positions, *, rope_base=10000.0, chunk=None, q_block=1024,
              kv_block=1024, mesh=None):
    """Full causal self-attention over x: (B, T, D)."""
    q, k, v = _qkv(params, x)
    q = apply_rope(q, positions, rope_base)
    k = apply_rope(k, positions, rope_base)
    if _use_cp(mesh, x.shape[1]):
        o = flash_attention_cp(q, k, v, positions, mesh, causal=True, chunk=chunk,
                               q_block=q_block, kv_block=kv_block)
    else:
        o = flash_attention(
            q, k, v, positions, positions[0], causal=True, chunk=chunk,
            q_block=q_block, kv_block=kv_block
        )
    return jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))


def gqa_prefill(params, x, positions, cache_len, *, rope_base=10000.0, chunk=None,
                q_block=1024, kv_block=1024, mesh=None):
    """Prefill: returns (out, (k_cache, v_cache)) with caches padded to cache_len."""
    q, k, v = _qkv(params, x)
    q = apply_rope(q, positions, rope_base)
    k = apply_rope(k, positions, rope_base)
    if _use_cp(mesh, x.shape[1]):
        o = flash_attention_cp(q, k, v, positions, mesh, causal=True, chunk=chunk,
                               q_block=q_block, kv_block=kv_block)
    else:
        o = flash_attention(
            q, k, v, positions, positions[0], causal=True, chunk=chunk,
            q_block=q_block, kv_block=kv_block
        )
    out = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    pad = cache_len - k.shape[1]
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, (kc, vc)


def _masked_cache_write(cache, new, idx):
    """Write ``new`` (B, 1, ...) at seq position ``idx`` via a predicated
    select instead of dynamic-update-slice: elementwise select partitions
    under ANY cache sharding (seq-sharded included), whereas a dynamic-start
    DUS on the sharded axis makes GSPMD all-gather the cache."""
    S = cache.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, S) + (1,) * (cache.ndim - 2), 1)
    return jnp.where(iota == idx, new.astype(cache.dtype), cache)


def gqa_decode(params, x, cache, kv_len, *, rope_base=10000.0, chunk=None):
    """Decode one token.  x: (B, 1, D); cache: (k, v) each (B, S, Kv, hd).

    Returns (out, new_cache).  The new token is written at position kv_len-1...
    convention: ``kv_len`` INCLUDES the new token; its position is kv_len-1.
    """
    kc, vc = cache
    B = x.shape[0]
    pos = (jnp.broadcast_to(jnp.asarray(kv_len), (B,)) - 1)[:, None]  # (B, 1)
    q, k, v = _qkv(params, x)
    q = apply_rope(q, pos, rope_base)
    k = apply_rope(k, pos, rope_base)
    idx = jnp.asarray(kv_len) - 1
    kc = _masked_cache_write(kc, k, idx)
    vc = _masked_cache_write(vc, v, idx)
    o = decode_attention(q, kc, vc, kv_len, chunk=chunk)
    out = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    return out, (kc, vc)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2/V3 multi-head latent attention), absorbed formulation
# ---------------------------------------------------------------------------

def init_mla(key, d_model, n_heads, q_lora, kv_lora, qk_nope, qk_rope, v_head, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    return {
        "wq_a": layers.variance_scaling(ks[0], (d_model, q_lora), dtype=dtype),
        "q_norm": layers.init_rmsnorm(q_lora, dtype),
        "wq_b": layers.variance_scaling(ks[1], (q_lora, n_heads, qk_nope + qk_rope), dtype=dtype),
        "wkv_a": layers.variance_scaling(ks[2], (d_model, kv_lora + qk_rope), dtype=dtype),
        "kv_norm": layers.init_rmsnorm(kv_lora, dtype),
        "wk_b": layers.variance_scaling(ks[3], (kv_lora, n_heads, qk_nope), dtype=dtype),
        "wv_b": layers.variance_scaling(ks[4], (kv_lora, n_heads, v_head), dtype=dtype),
        "wo": layers.variance_scaling(ks[5], (n_heads, v_head, d_model), mode="fan_out", dtype=dtype),
    }


def _mla_query(params, x, positions, qk_nope, rope_base):
    ql = layers.rmsnorm(params["q_norm"], x @ params["wq_a"].astype(x.dtype))
    q = jnp.einsum("btl,lhk->bthk", ql, params["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, positions, rope_base)
    # absorb k_up: q_nope (B,T,H,nope) x (kv_lora,H,nope) -> (B,T,H,kv_lora)
    q_lat = jnp.einsum("bthk,lhk->bthl", q_nope, params["wk_b"].astype(x.dtype))
    return q_lat, q_rope


def _mla_kv(params, x, positions, kv_lora, rope_base):
    kv = x @ params["wkv_a"].astype(x.dtype)  # (B, T, kv_lora + qk_rope)
    c_kv = layers.rmsnorm(params["kv_norm"], kv[..., :kv_lora])
    k_rope = kv[..., kv_lora:][:, :, None, :]  # (B, T, 1, rope)
    k_rope = apply_rope(k_rope, positions, rope_base)[:, :, 0, :]
    return c_kv, k_rope


def _mla_attend(params, q_lat, q_rope, c_kv, k_rope, q_pos, kv_pos, *, scale, kv_len=None):
    """Absorbed MLA attention.  q_lat: (B,T,H,L); c_kv: (B,S,L); k_rope: (B,S,R)."""
    s = jnp.einsum("bthl,bsl->bhts", q_lat, c_kv, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope, preferred_element_type=jnp.float32)
    s = s * scale
    ok = _allowed(q_pos, kv_pos, causal=True, kv_len=kv_len)  # (B, T, S) or (T, S)
    ok = ok[:, None] if ok.ndim == 3 else ok[None, None]
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhts,bsl->bthl", p, c_kv.astype(jnp.float32))  # (B,T,H,L)
    o = jnp.einsum("bthl,lhv->bthv", o_lat.astype(q_lat.dtype), params["wv_b"].astype(q_lat.dtype))
    return o


def mla_train(params, x, positions, *, qk_nope, qk_rope, kv_lora, rope_base=10000.0,
              kv_block: int = 2048, q_block: int = 1024, mesh=None):
    """MLA causal self-attention via the flash core.

    The absorbed formulation IS MQA over the latent cache: the query is
    concat(q_lat, q_rope) with per-head dim kv_lora+qk_rope, the (single,
    shared) key is concat(c_kv, k_rope), and the value is c_kv — so the
    generic blockwise/context-parallel flash attention applies unchanged
    (Kv=1), with the true 1/sqrt(qk_nope+qk_rope) scale passed explicitly."""
    scale = (qk_nope + qk_rope) ** -0.5
    q_lat, q_rope = _mla_query(params, x, positions, qk_nope, rope_base)
    c_kv, k_rope = _mla_kv(params, x, positions, kv_lora, rope_base)
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)          # (B, T, H, L+R)
    k_cat = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]  # (B, S, 1, L+R)
    v = c_kv[:, :, None, :]                                    # (B, S, 1, L)
    if _use_cp(mesh, x.shape[1]):
        o = flash_attention_cp(q_cat, k_cat, v, positions, mesh, causal=True,
                               q_block=q_block, kv_block=kv_block, scale=scale)
    else:
        o = flash_attention(q_cat, k_cat, v, positions, positions[0], causal=True,
                            q_block=q_block, kv_block=kv_block, scale=scale)
    o = jnp.einsum("bthl,lhv->bthv", o, params["wv_b"].astype(x.dtype))
    return jnp.einsum("bthv,hvd->btd", o, params["wo"].astype(x.dtype))


def mla_prefill(params, x, positions, cache_len, *, qk_nope, qk_rope, kv_lora,
                rope_base=10000.0, kv_block: int = 2048, q_block: int = 1024,
                mesh=None):
    out = mla_train(params, x, positions, qk_nope=qk_nope, qk_rope=qk_rope,
                    kv_lora=kv_lora, rope_base=rope_base, kv_block=kv_block,
                    q_block=q_block, mesh=mesh)
    c_kv, k_rope = _mla_kv(params, x, positions, kv_lora, rope_base)
    pad = cache_len - c_kv.shape[1]
    c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
    k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return out, (c_kv, k_rope)


def mla_decode(params, x, cache, kv_len, *, qk_nope, qk_rope, kv_lora, rope_base=10000.0):
    """Decode one token with the compressed latent cache (B, S, kv_lora)+(B, S, rope)."""
    c_cache, r_cache = cache
    scale = (qk_nope + qk_rope) ** -0.5
    B = x.shape[0]
    pos = (jnp.broadcast_to(jnp.asarray(kv_len), (B,)) - 1)[:, None]
    q_lat, q_rope = _mla_query(params, x, pos, qk_nope, rope_base)
    c_new, r_new = _mla_kv(params, x, pos, kv_lora, rope_base)
    idx = jnp.asarray(kv_len) - 1
    c_cache = _masked_cache_write(c_cache, c_new, idx)
    r_cache = _masked_cache_write(r_cache, r_new, idx)
    kv_pos = jnp.arange(c_cache.shape[1])
    o = _mla_attend(params, q_lat, q_rope, c_cache, r_cache, pos, kv_pos,
                    scale=scale, kv_len=jnp.asarray(kv_len))
    out = jnp.einsum("bthv,hvd->btd", o, params["wo"].astype(x.dtype))
    return out, (c_cache, r_cache)
