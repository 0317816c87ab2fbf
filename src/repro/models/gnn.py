"""MeshGraphNet (Pfaff et al., arXiv:2010.03409) — encode-process-decode GNN.

Message passing is built on ``jax.ops.segment_sum`` over an edge index (the
JAX sparse-op substrate — no SpMM primitive needed).  Distribution: edges are
sharded over the whole mesh inside a single shard_map (nodes replicated;
per-layer partial node aggregates are psum-reduced), so the 61M/114M-edge
cells scan locally and communicate one (N, d_hidden) reduction per layer.

Shape regimes:
  full-graph      — forward over all edges (full_graph_sm / ogb_products)
  sampled         — in-graph uniform neighbor sampler (fanout 15-10) +
                    two-hop aggregation (minibatch_lg)
  batched-small   — many small graphs flattened with graph-id segment ids
                    (molecule), graph-level readout.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.common.config import ConfigBase
from repro.common.prng import PRNGSeq
from repro.nn import layers


@dataclasses.dataclass(frozen=True)
class GNNConfig(ConfigBase):
    name: str = "meshgraphnet"
    n_layers: int = 15
    d_hidden: int = 128
    mlp_layers: int = 2          # hidden layers per MLP (paper: 2)
    aggregator: str = "sum"
    d_node_in: int = 16
    d_edge_in: int = 4
    d_out: int = 2
    task: str = "regression"     # regression | classification
    graph_readout: bool = False  # molecule: graph-level output
    fanout: tuple[int, ...] = (15, 10)
    layernorm: bool = True


def _mlp_dims(cfg: GNNConfig, d_in: int, d_out: int) -> tuple[int, ...]:
    return (d_in, *([cfg.d_hidden] * cfg.mlp_layers), d_out)


def _init_block(key, cfg: GNNConfig, d_in: int, d_out: int):
    k1, _ = jax.random.split(key)
    p = {"mlp": layers.init_mlp(k1, _mlp_dims(cfg, d_in, d_out))}
    if cfg.layernorm:
        p["ln"] = layers.init_layernorm(d_out)
    return p


def _block(p, x, activation="relu"):
    h = layers.mlp(p["mlp"], x, activation)
    if "ln" in p:
        h = layers.layernorm(p["ln"], h)
    return h


def init_gnn(key, cfg: GNNConfig):
    ks = PRNGSeq(key)
    dh = cfg.d_hidden
    params: dict[str, Any] = {
        "node_enc": _init_block(next(ks), cfg, cfg.d_node_in, dh),
        "edge_enc": _init_block(next(ks), cfg, cfg.d_edge_in, dh),
    }
    proc_keys = jnp.stack(ks.take(cfg.n_layers))

    def init_layer(k):
        k1, k2 = jax.random.split(k)
        return {
            "edge": _init_block(k1, cfg, 3 * dh, dh),
            "node": _init_block(k2, cfg, 2 * dh, dh),
        }

    params["proc"] = jax.vmap(init_layer)(proc_keys)
    dec_in = dh
    params["decoder"] = {"mlp": layers.init_mlp(next(ks), _mlp_dims(cfg, dec_in, cfg.d_out))}
    return params


# ---------------------------------------------------------------------------
# full-graph forward (edge-sharded message passing)
# ---------------------------------------------------------------------------

def _aggregate(cfg: GNNConfig, msgs, receivers, n_nodes):
    if cfg.aggregator == "sum":
        return jax.ops.segment_sum(msgs, receivers, num_segments=n_nodes)
    if cfg.aggregator == "max":
        return jax.ops.segment_max(msgs, receivers, num_segments=n_nodes)
    if cfg.aggregator == "mean":
        s = jax.ops.segment_sum(msgs, receivers, num_segments=n_nodes)
        c = jax.ops.segment_sum(jnp.ones_like(receivers, jnp.float32), receivers,
                                num_segments=n_nodes)
        return s / jnp.maximum(c[:, None], 1.0)
    raise ValueError(cfg.aggregator)


def _forward_body(params, node_feat, edge_feat, senders, receivers, cfg: GNNConfig,
                  edge_axes: tuple[str, ...] = (), node_axes: tuple[str, ...] = ()):
    """shard_map body (or unsharded when axes are empty).

    Layout: node tensors sharded over ``node_axes`` (pod, data); edge tensors
    sharded over ALL mesh axes.  Each layer all-gathers the node states
    (transient), computes local edge messages, segment-sums into a full-N
    partial aggregate, psums it over the edge axes, and keeps only the local
    node slice — so the *persistent* per-layer state is O(N/|node_axes| +
    E/|mesh|) while the O(N) buffers are transient.  Layers are remat'd."""
    h_loc = _block(params["node_enc"], node_feat)
    e = _block(params["edge_enc"], edge_feat)
    n_loc = h_loc.shape[0]
    n_total = n_loc
    node_idx = 0
    for ax in node_axes:
        n_total *= jax.lax.axis_size(ax)
        node_idx = node_idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)

    def gather_full(h_l):
        h = h_l
        for ax in reversed(node_axes):
            h = jax.lax.all_gather(h, ax, axis=0, tiled=True)
        return h

    def layer(carry, lp):
        h_l, e = carry
        h = gather_full(h_l)
        hs = jnp.take(h, senders, axis=0)
        hr = jnp.take(h, receivers, axis=0)
        e_new = e + _block(lp["edge"], jnp.concatenate([e, hs, hr], axis=-1))
        agg = _aggregate(cfg, e_new, receivers, h.shape[0])
        for ax in edge_axes:
            agg = jax.lax.psum(agg, ax)
        agg_l = jax.lax.dynamic_slice_in_dim(agg, node_idx * n_loc, n_loc, axis=0)
        h_new = h_l + _block(lp["node"], jnp.concatenate([h_l, agg_l], axis=-1))
        return (h_new, e_new), None

    (h_loc, e), _ = jax.lax.scan(jax.checkpoint(layer), (h_loc, e), params["proc"])
    return layers.mlp(params["decoder"]["mlp"], h_loc)


def _loss_from_out(out, batch, cfg: GNNConfig, node_axes: tuple[str, ...] = ()):
    def allsum(x):
        for ax in node_axes:
            x = jax.lax.psum(x, ax)
        return x

    if cfg.graph_readout:
        g = jax.ops.segment_sum(out, batch["graph_ids"],
                                num_segments=batch["graph_labels"].shape[0])
        g = allsum(g)  # graphs may straddle node shards
        return jnp.mean(jnp.square(g - batch["graph_labels"]))
    if cfg.task == "classification":
        logits = out.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
        mask = batch.get("label_mask", jnp.ones_like(lse))
        return allsum(jnp.sum((lse - gold) * mask)) / jnp.maximum(
            allsum(jnp.sum(mask)), 1.0
        )
    mask = batch.get("label_mask", jnp.ones(out.shape[0], out.dtype))
    se = jnp.sum(jnp.square(out - batch["labels"]) * mask[:, None])
    n = jnp.maximum(allsum(jnp.sum(mask)) * out.shape[-1], 1.0)
    return allsum(se) / n


def forward(params, node_feat, edge_feat, senders, receivers, cfg: GNNConfig,
            mesh=None):
    """Full-graph forward -> (N_local, d_out) per node shard (global (N, d_out)
    array sharded over the batch axes when a mesh is given)."""
    if mesh is None:
        return _forward_body(params, node_feat, edge_feat, senders, receivers, cfg)

    axes = tuple(mesh.axis_names)
    node_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    espec, nspec = P(axes), P(node_axes)
    body = functools.partial(_forward_body, cfg=cfg, edge_axes=axes, node_axes=node_axes)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), nspec, espec, espec, espec),
        out_specs=nspec,
        check_vma=False,
    )(params, node_feat, edge_feat, senders, receivers)


def loss_fn(params, batch, cfg: GNNConfig, mesh=None):
    if mesh is None:
        out = _forward_body(params, batch["node_feat"], batch["edge_feat"],
                            batch["senders"], batch["receivers"], cfg)
        return _loss_from_out(out, batch, cfg)

    axes = tuple(mesh.axis_names)
    node_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    espec, nspec = P(axes), P(node_axes)

    node_keys = [k for k in ("labels", "label_mask", "graph_ids") if k in batch]
    repl_keys = [k for k in ("graph_labels",) if k in batch]

    def body(params, node_feat, edge_feat, senders, receivers, *rest):
        out = _forward_body(params, node_feat, edge_feat, senders, receivers, cfg,
                            edge_axes=axes, node_axes=node_axes)
        b = dict(zip(node_keys + repl_keys, rest))
        return _loss_from_out(out, b, cfg, node_axes)

    in_specs = (
        (P(), nspec, espec, espec, espec)
        + tuple(nspec for _ in node_keys)
        + tuple(P() for _ in repl_keys)
    )
    loss = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)(
        params, batch["node_feat"], batch["edge_feat"], batch["senders"],
        batch["receivers"], *[batch[k] for k in node_keys + repl_keys]
    )
    return loss


def make_train_step(cfg: GNNConfig, mesh=None, lr: float = 1e-3):
    from repro.optim import adam_update

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg, mesh))(params)
        params, opt_state, om = adam_update(grads, opt_state, params, lr=lr, grad_clip=1.0)
        return params, opt_state, {"loss": loss, **om}

    return step


# ---------------------------------------------------------------------------
# neighbor sampling (minibatch_lg): uniform fanout over CSR, in-graph
# ---------------------------------------------------------------------------

def sample_neighbors(key, row_ptr, col_idx, nodes, fanout: int):
    """Uniform-with-replacement fanout sample.  nodes: (...,) -> (..., fanout).

    Zero-degree nodes self-loop."""
    deg = row_ptr[nodes + 1] - row_ptr[nodes]
    u = jax.random.uniform(key, (*nodes.shape, fanout))
    off = jnp.floor(u * jnp.maximum(deg, 1)[..., None]).astype(row_ptr.dtype)
    idx = row_ptr[nodes][..., None] + off
    nbr = col_idx[jnp.minimum(idx, col_idx.shape[0] - 1)]
    return jnp.where((deg > 0)[..., None], nbr, nodes[..., None])


def sampled_forward(params, key, batch, cfg: GNNConfig):
    """GraphSAGE-regime two-hop forward for seed nodes.

    batch: {row_ptr, col_idx, node_feat (N, d), seeds (B,)} -> (B, d_out).
    Uses the encoder + first two processor-layer node MLPs as the two
    aggregation levels (weight-shared with the full-graph model)."""
    k1, k2 = jax.random.split(key)
    seeds = batch["seeds"]
    f1, f2 = cfg.fanout[0], cfg.fanout[1]
    n1 = sample_neighbors(k1, batch["row_ptr"], batch["col_idx"], seeds, f1)       # (B, f1)
    n2 = sample_neighbors(k2, batch["row_ptr"], batch["col_idx"], n1, f2)          # (B, f1, f2)

    enc = lambda x: _block(params["node_enc"], x)
    h_seed = enc(batch["node_feat"][seeds])
    h1 = enc(batch["node_feat"][n1])
    h2 = enc(batch["node_feat"][n2])

    lp0 = jax.tree_util.tree_map(lambda x: x[0], params["proc"])
    lp1 = jax.tree_util.tree_map(lambda x: x[1], params["proc"])
    agg2 = jnp.sum(h2, axis=2)  # (B, f1, d)
    h1 = h1 + _block(lp0["node"], jnp.concatenate([h1, agg2], axis=-1))
    agg1 = jnp.sum(h1, axis=1)  # (B, d)
    h_seed = h_seed + _block(lp1["node"], jnp.concatenate([h_seed, agg1], axis=-1))
    return layers.mlp(params["decoder"]["mlp"], h_seed)


def make_sampled_train_step(cfg: GNNConfig, lr: float = 1e-3):
    from repro.optim import adam_update

    def step(params, opt_state, key, batch):
        def lf(p):
            out = sampled_forward(p, key, batch, cfg).astype(jnp.float32)
            if cfg.task == "classification":
                lse = jax.nn.logsumexp(out, axis=-1)
                gold = jnp.take_along_axis(out, batch["labels"][:, None], axis=-1)[:, 0]
                return jnp.mean(lse - gold)
            return jnp.mean(jnp.square(out - batch["labels"]))

        loss, grads = jax.value_and_grad(lf)(params)
        params, opt_state, om = adam_update(grads, opt_state, params, lr=lr, grad_clip=1.0)
        return params, opt_state, {"loss": loss, **om}

    return step
