"""RecSys family: DeepFM, xDeepFM (CIN), BST, two-tower retrieval.

The substrate JAX lacks natively is built here:

* ``sharded_embedding_lookup`` — the distributed EmbeddingBag: tables are
  row-sharded over the ``model`` axis; each shard resolves the ids that land
  in its row range (gather + mask) and the partial rows are psum-combined.
  One combined table holds all fields (ids are field-offset, FBGEMM-style).
* ``embedding_bag`` — multi-hot gather + segment-sum/mean (BST histories).

``retrieval_cand`` (two-tower) reuses the corpus-sharded MIPS pattern from
the LEMUR serving path: candidates sharded over the whole mesh, local top-k,
all-gather merge.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import ConfigBase
from repro.common.prng import PRNGSeq
from repro.nn import layers


@dataclasses.dataclass(frozen=True)
class RecsysConfig(ConfigBase):
    name: str = "deepfm"
    model: str = "deepfm"            # deepfm | xdeepfm | bst | two_tower
    vocab_sizes: tuple[int, ...] = (1000,) * 39
    embed_dim: int = 10
    mlp_dims: tuple[int, ...] = (400, 400, 400)
    # xDeepFM
    cin_dims: tuple[int, ...] = (200, 200, 200)
    # BST
    seq_len: int = 20
    n_heads: int = 8
    n_blocks: int = 1
    n_items: int = 2_000_000
    # two-tower
    tower_dims: tuple[int, ...] = (1024, 512, 256)
    out_dim: int = 256
    temperature: float = 0.05

    @property
    def n_fields(self) -> int:
        return len(self.vocab_sizes)

    @property
    def total_vocab(self) -> int:
        return int(sum(self.vocab_sizes))

    @property
    def field_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.vocab_sizes)[:-1]]).astype(np.int64)


# ---------------------------------------------------------------------------
# distributed embedding substrate
# ---------------------------------------------------------------------------

def _lookup_body(table, ids, *, n_rows_global: int):
    """shard_map body: table (rows_loc, d) on 'model'; ids (B_loc, ...)."""
    j = jax.lax.axis_index("model")
    rows_loc = table.shape[0]
    local = ids - j * rows_loc
    ok = (local >= 0) & (local < rows_loc)
    rows = jnp.take(table, jnp.clip(local, 0, rows_loc - 1), axis=0)
    rows = rows * ok[..., None].astype(table.dtype)
    return jax.lax.psum(rows, "model")


def sharded_embedding_lookup(table, ids, mesh, *, batch_axes=("pod", "data")):
    """table: (V, d) P('model', None); ids: (B, ...) batch-sharded -> (B, ..., d).

    Batches that don't divide the batch axes (e.g. the single-query retrieval
    cell) fall back to replicated ids."""
    import numpy as np

    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    n_batch = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if ids.shape[0] % max(n_batch, 1) != 0:
        axes = ()
    body = functools.partial(_lookup_body, n_rows_global=table.shape[0])
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("model", None), P(axes)),
        out_specs=P(axes),
        check_vma=False,
    )(table, ids)


def embedding_lookup(table, ids, mesh=None):
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return jnp.take(table, ids, axis=0)
    return sharded_embedding_lookup(table, ids, mesh)


def embedding_bag(table, ids, mesh=None, *, combiner: str = "mean", pad_id: int = 0):
    """Multi-hot bag: ids (B, L) -> (B, d) with mean/sum over valid (id != pad)."""
    e = embedding_lookup(table, ids, mesh)                  # (B, L, d)
    mask = (ids != pad_id)[..., None].astype(e.dtype)
    s = jnp.sum(e * mask, axis=-2)
    if combiner == "sum":
        return s
    return s / jnp.maximum(jnp.sum(mask, axis=-2), 1.0)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_recsys(key, cfg: RecsysConfig):
    ks = PRNGSeq(key)
    d = cfg.embed_dim
    params: dict[str, Any] = {}
    if cfg.model in ("deepfm", "xdeepfm"):
        params["table"] = layers.init_embedding(next(ks), cfg.total_vocab, d)
        params["first_order"] = layers.init_embedding(next(ks), cfg.total_vocab, 1)
        params["bias"] = jnp.zeros(())
        deep_in = cfg.n_fields * d
        params["deep"] = layers.init_mlp(next(ks), (deep_in, *cfg.mlp_dims, 1))
        if cfg.model == "xdeepfm":
            dims = (cfg.n_fields, *cfg.cin_dims)
            params["cin"] = {
                f"layer_{i}": layers.variance_scaling(
                    next(ks), (dims[i + 1], dims[i], cfg.n_fields)
                )
                for i in range(len(cfg.cin_dims))
            }
            params["cin_out"] = layers.init_dense(next(ks), sum(cfg.cin_dims), 1, True)
    elif cfg.model == "bst":
        params["item_table"] = layers.init_embedding(next(ks), cfg.n_items, d)
        params["pos_table"] = layers.init_embedding(next(ks), cfg.seq_len + 1, d)
        from repro.nn import attention

        params["blocks"] = {}
        for b in range(cfg.n_blocks):
            params["blocks"][f"block_{b}"] = {
                "attn": attention.init_gqa(next(ks), d, cfg.n_heads, cfg.n_heads,
                                           max(1, d // cfg.n_heads)),
                "ln1": layers.init_layernorm(d),
                "ln2": layers.init_layernorm(d),
                "ffn": layers.init_ffn(next(ks), d, 4 * d, gated=False, use_bias=True),
            }
        mlp_in = (cfg.seq_len + 1) * d
        params["mlp"] = layers.init_mlp(next(ks), (mlp_in, *cfg.mlp_dims, 1))
    elif cfg.model == "two_tower":
        params["user_table"] = layers.init_embedding(next(ks), cfg.total_vocab, d)
        params["item_table"] = layers.init_embedding(next(ks), cfg.n_items, d)
        user_in = cfg.n_fields * d
        params["user_tower"] = layers.init_mlp(next(ks), (user_in, *cfg.tower_dims, cfg.out_dim))
        params["item_tower"] = layers.init_mlp(next(ks), (d, *cfg.tower_dims, cfg.out_dim))
    else:
        raise ValueError(cfg.model)
    return params


# ---------------------------------------------------------------------------
# forwards
# ---------------------------------------------------------------------------

def _offset_ids(cfg: RecsysConfig, ids):
    return ids + jnp.asarray(cfg.field_offsets, ids.dtype)[None, :]


def deepfm_forward(params, ids, cfg: RecsysConfig, mesh=None):
    """ids: (B, F) per-field ids (unoffset) -> logits (B,)."""
    gids = _offset_ids(cfg, ids)
    emb = embedding_lookup(params["table"]["embedding"], gids, mesh)   # (B, F, d)
    first = embedding_lookup(params["first_order"]["embedding"], gids, mesh)[..., 0]
    sum_v = jnp.sum(emb, axis=1)
    fm = 0.5 * jnp.sum(jnp.square(sum_v) - jnp.sum(jnp.square(emb), axis=1), axis=-1)
    deep = layers.mlp(params["deep"], emb.reshape(emb.shape[0], -1))[:, 0]
    return params["bias"] + jnp.sum(first, axis=1) + fm + deep


def xdeepfm_forward(params, ids, cfg: RecsysConfig, mesh=None):
    gids = _offset_ids(cfg, ids)
    emb = embedding_lookup(params["table"]["embedding"], gids, mesh)   # (B, F, d)
    first = embedding_lookup(params["first_order"]["embedding"], gids, mesh)[..., 0]
    # CIN (arXiv:1803.05170 eq. 6): x^{k+1}_h = sum_ij W^k_{h,i,j} (x^k_i ∘ x^0_j)
    x0, xk = emb, emb
    pools = []
    for i in range(len(cfg.cin_dims)):
        w = params["cin"][f"layer_{i}"]                                # (H, Hk, F)
        xk = jnp.einsum("bid,bjd,hij->bhd", xk, x0, w)
        pools.append(jnp.sum(xk, axis=-1))                             # (B, H)
    cin = layers.dense(params["cin_out"], jnp.concatenate(pools, axis=-1))[:, 0]
    deep = layers.mlp(params["deep"], emb.reshape(emb.shape[0], -1))[:, 0]
    return params["bias"] + jnp.sum(first, axis=1) + cin + deep


def bst_forward(params, history, target_item, cfg: RecsysConfig, mesh=None):
    """history: (B, L); target_item: (B,) -> logits (B,)."""
    B, L = history.shape
    seq = jnp.concatenate([history, target_item[:, None]], axis=1)     # (B, L+1)
    e = embedding_lookup(params["item_table"]["embedding"], seq, mesh)
    e = e + params["pos_table"]["embedding"][None, : L + 1]
    for b in range(cfg.n_blocks):
        blk = params["blocks"][f"block_{b}"]
        h = layers.layernorm(blk["ln1"], e)
        q = jnp.einsum("btd,dhk->bthk", h, blk["attn"]["wq"])
        k = jnp.einsum("btd,dhk->bthk", h, blk["attn"]["wk"])
        v = jnp.einsum("btd,dhk->bthk", h, blk["attn"]["wv"])
        s = jnp.einsum("bthk,bshk->bhts", q, k) / jnp.sqrt(q.shape[-1] * 1.0)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhts,bshk->bthk", a, v)
        e = e + jnp.einsum("bthk,hkd->btd", o, blk["attn"]["wo"])
        h = layers.layernorm(blk["ln2"], e)
        e = e + layers.ffn(blk["ffn"], h, "gelu")
    return layers.mlp(params["mlp"], e.reshape(B, -1), activation="relu")[:, 0]


def two_tower_user(params, ids, cfg: RecsysConfig, mesh=None):
    gids = _offset_ids(cfg, ids)
    emb = embedding_lookup(params["user_table"]["embedding"], gids, mesh)
    u = layers.mlp(params["user_tower"], emb.reshape(emb.shape[0], -1))
    return u / jnp.maximum(jnp.linalg.norm(u, axis=-1, keepdims=True), 1e-6)


def two_tower_item(params, item_ids, cfg: RecsysConfig, mesh=None):
    e = embedding_lookup(params["item_table"]["embedding"], item_ids, mesh)
    v = layers.mlp(params["item_tower"], e)
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)


FORWARDS = {
    "deepfm": deepfm_forward,
    "xdeepfm": xdeepfm_forward,
}


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------

def bce_loss(logits, labels):
    logits = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def ctr_loss(params, batch, cfg: RecsysConfig, mesh=None):
    if cfg.model == "bst":
        logits = bst_forward(params, batch["history"], batch["target_item"], cfg, mesh)
    else:
        logits = FORWARDS[cfg.model](params, batch["ids"], cfg, mesh)
    return bce_loss(logits, batch["labels"])


def two_tower_loss(params, batch, cfg: RecsysConfig, mesh=None):
    """In-batch sampled softmax with logQ correction (Yi et al. RecSys'19)."""
    u = two_tower_user(params, batch["ids"], cfg, mesh)         # (B, D)
    v = two_tower_item(params, batch["item"], cfg, mesh)        # (B, D)
    logits = (u @ v.T) / cfg.temperature                        # (B, B)
    logq = batch.get("logq")
    if logq is not None:
        logits = logits - logq[None, :]
    labels = jnp.arange(u.shape[0])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def make_train_step(cfg: RecsysConfig, mesh=None, lr: float = 1e-3):
    from repro.optim import adam_update

    lf = two_tower_loss if cfg.model == "two_tower" else ctr_loss

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: lf(p, batch, cfg, mesh))(params)
        params, opt_state, om = adam_update(grads, opt_state, params, lr=lr, grad_clip=1.0)
        return params, opt_state, {"loss": loss, **om}

    return step


def make_serve_step(cfg: RecsysConfig, mesh=None, *, chunk: int = 0):
    """Pointwise scoring step.  ``chunk`` > 0 streams the batch through
    fixed-size tiles with lax.map (bounds the CIN/MLP activation footprint for
    the 262k/1M bulk-scoring cells — offline scoring is throughput-bound, not
    latency-bound, so tiling is free)."""

    def score(params, batch):
        if cfg.model == "bst":
            return bst_forward(params, batch["history"], batch["target_item"], cfg, mesh)
        if cfg.model == "two_tower":
            u = two_tower_user(params, batch["ids"], cfg, mesh)
            v = two_tower_item(params, batch["item"], cfg, mesh)
            return jnp.sum(u * v, axis=-1)
        return FORWARDS[cfg.model](params, batch["ids"], cfg, mesh)

    def step(params, batch):
        n = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if not chunk or n <= chunk or n % chunk != 0:
            return score(params, batch)
        nc = n // chunk
        tiled = jax.tree_util.tree_map(
            lambda x: x.reshape(nc, chunk, *x.shape[1:]), batch
        )
        out = jax.lax.map(lambda b: score(params, b), tiled)
        return out.reshape(n)

    return step


def _retrieval_body(u, cand, *, k: int, axes: tuple[str, ...]):
    """shard_map body: u (B, D) replicated; cand (m_loc, D) corpus-sharded."""
    s = u @ cand.T                               # (B, m_loc)
    m_loc = cand.shape[0]
    kk = min(k, m_loc)
    top, ids = jax.lax.top_k(s, kk)
    idx = 0
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    gids = ids + idx * m_loc
    for ax in axes:
        top = jax.lax.all_gather(top, ax, axis=1, tiled=True)
        gids = jax.lax.all_gather(gids, ax, axis=1, tiled=True)
    out_s, pos = jax.lax.top_k(top, k)
    return out_s, jnp.take_along_axis(gids, pos, axis=1)


def make_retrieval_step(cfg: RecsysConfig, mesh, k: int = 100):
    """Score one query batch against the full candidate matrix (sharded over
    the whole mesh) and return global top-k — the `retrieval_cand` cell."""

    axes = tuple(mesh.axis_names)

    def step(params, batch, candidates):
        u = two_tower_user(params, batch["ids"], cfg, mesh)
        return jax.shard_map(
            functools.partial(_retrieval_body, k=k, axes=axes),
            mesh=mesh,
            in_specs=(P(), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )(u, candidates)

    return step
