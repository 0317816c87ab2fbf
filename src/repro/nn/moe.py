"""Mixture-of-Experts with explicit expert parallelism (shard_map).

Two compute layouts, both ZeRO-sharded for storage and combined with a
single psum over the ``model`` axis:

* ``ep``        — experts sharded over ("model","data"); inside the shard,
                  weights are all-gathered over "data" so each model-shard
                  owns a contiguous block of E/|model| experts.  Tokens are
                  masked to local experts, packed into an (E_loc, C, d)
                  capacity buffer, computed, and psum-combined over "model".
                  Used when E % (|model|·|data|) == 0 (deepseek-v3: 256).
* ``ffslice``   — experts sharded over "data" (storage) with d_ff sharded
                  over "model".  After the "data" all-gather every device
                  holds ALL experts with a 1/|model| slice of d_ff, so
                  dispatch is local and the ff-partial outputs are
                  psum-reduced over "model".  Used when E doesn't divide the
                  full mesh (llama4-maverick: 128 experts, top-1).

Dispatch uses capacity-based packing (GShard-style dropping) built from a
cumsum position-in-expert — the (N, E, C) one-hot dispatch tensor of the
original GShard einsum is never materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.nn import layers


def init_moe(key, n_experts, d_model, d_ff, *, gated=True, n_shared=0, shared_d_ff=None,
             dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    fan = d_model
    def w(k, shape, mode="fan_in"):
        return layers.variance_scaling(k, shape, mode=mode, dtype=dtype)

    p = {
        "router": w(ks[0], (d_model, n_experts)),
        "wo": w(ks[3], (n_experts, d_ff, d_model), mode="fan_out"),
    }
    if gated:
        p["wi_0"] = w(ks[1], (n_experts, d_model, d_ff))
        p["wi_1"] = w(ks[2], (n_experts, d_model, d_ff))
    else:
        p["wi"] = w(ks[1], (n_experts, d_model, d_ff))
    if n_shared:
        p["shared"] = layers.init_ffn(ks[4], d_model, (shared_d_ff or d_ff) * n_shared,
                                      gated=gated, dtype=dtype)
    return p


def moe_param_specs(layout: str, *, stacked: bool = False):
    """PartitionSpecs for the expert weights (prepend None if scan-stacked)."""
    if layout == "ep":
        e3 = P(("model", "data"), None, None)
        router = P(None, None)
    else:  # ffslice
        e3 = P("data", None, "model")
        router = P(None, None)
    wo = P(("model", "data"), None, None) if layout == "ep" else P("data", "model", None)
    specs = {"router": router, "wi_0": e3, "wi_1": e3, "wi": e3, "wo": wo}
    if stacked:
        specs = {k: P(None, *v) for k, v in specs.items()}
    return specs


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float, floor: int = 8):
    ideal = (n_tokens * top_k + n_experts - 1) // n_experts
    return int(min(max(floor, int(ideal * factor)), max(1, n_tokens * top_k)))


def _pack_dispatch(x, eid, gate, n_local: int, capacity: int):
    """Pack selected (token, expert) pairs into an (E_loc, C, d) buffer.

    x: (N, d); eid: (N, k) LOCAL expert ids (may be out of [0, n_local) =>
    dropped); gate: (N, k).  Returns (buffer, eid_flat, pos_flat, keep).
    """
    N, k = eid.shape
    e_flat = eid.reshape(-1)
    valid = (e_flat >= 0) & (e_flat < n_local)
    e_safe = jnp.where(valid, e_flat, n_local)  # park invalid in a trash row
    onehot = jax.nn.one_hot(e_safe, n_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos_flat = jnp.take_along_axis(pos, e_safe[:, None], axis=1)[:, 0]
    keep = valid & (pos_flat < capacity)
    tok = jnp.repeat(jnp.arange(N), k)
    buf = jnp.zeros((n_local, capacity, x.shape[-1]), x.dtype)
    buf = buf.at[
        jnp.where(keep, e_flat, n_local - 1),
        jnp.where(keep, pos_flat, capacity - 1),
    ].add(jnp.where(keep[:, None], x[tok], 0))
    return buf, e_flat, pos_flat, keep, tok


def _expert_ffn(buf, wi_0, wi_1, wi, wo, activation):
    act = layers.ACTIVATIONS[activation]
    if wi_0 is not None:
        h = act(jnp.einsum("ecd,edf->ecf", buf, wi_0)) * jnp.einsum("ecd,edf->ecf", buf, wi_1)
    else:
        h = act(jnp.einsum("ecd,edf->ecf", buf, wi))
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _moe_shard_body(x, router_w, wi_0, wi_1, wi, wo, *, layout, n_experts, top_k,
                    capacity_factor, activation, model_size, router_noise_eps=0.0):
    """Runs per-shard inside shard_map.  x: (Nloc, d) local tokens."""
    axis = "model"
    j = jax.lax.axis_index(axis)
    # ZeRO weight gather over the fsdp ("data") axis
    gather = lambda a: None if a is None else jax.lax.all_gather(a, "data", axis=0, tiled=True)
    wi_0, wi_1, wi, wo = gather(wi_0), gather(wi_1), gather(wi), gather(wo)

    N, d = x.shape
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, top_k)  # (N, k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    # load-balance aux loss (computed identically on all shards)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eid, n_experts, dtype=jnp.float32), axis=1), axis=0
    )
    aux = jnp.sum(me * ce) * n_experts

    if layout == "ep":
        n_local = n_experts // model_size
        lo = j * n_local
        local_eid = jnp.where((eid >= lo) & (eid < lo + n_local), eid - lo, -1)
    else:  # all experts local (ff sliced)
        n_local = n_experts
        local_eid = eid

    # capacity per expert derives from the GLOBAL expert count (expected
    # tokens/expert = N*k/E); sizing by the local count inflates the buffer
    # |model|x (found via the MODEL/HLO roofline ratio, EXPERIMENTS Perf-4)
    C = _capacity(N, top_k, n_experts, capacity_factor)
    buf, e_flat, pos_flat, keep, tok = _pack_dispatch(x, local_eid, gate, n_local, C)
    out_buf = _expert_ffn(buf, wi_0, wi_1, wi, wo, activation)  # (E_loc, C, d)

    # un-pack: gather each kept (token, slot) row back and weight by its gate
    rows = out_buf[
        jnp.where(keep, e_flat, 0), jnp.where(keep, pos_flat, 0)
    ]  # (N*k, d)
    g = (gate.reshape(-1) * keep).astype(rows.dtype)
    y = jnp.zeros_like(x).at[tok].add(rows * g[:, None])
    y = jax.lax.psum(y, axis)
    aux = jax.lax.pmean(aux, axis)
    return y, aux


def _moe_tokengather_body(x, router_w, wi_0, wi_1, wi, wo, *, layout, n_experts,
                          top_k, capacity_factor, activation, model_size,
                          data_size, batch_axes, n_local_tokens):
    """Decode-path MoE: gather TOKENS (KBs), never weights (GBs).

    Inverse of the ZeRO-gather body: each device keeps only its stored
    expert shard, all-gathers the (tiny) token set over the batch axes,
    computes its local experts, and one psum over ("model","data") combines
    the full expert sum — collective volume per layer is O(tokens·d) instead
    of O(E_local·d·ff) for the weight gather (4–5 orders of magnitude at
    decode shapes; EXPERIMENTS.md §Perf iteration 2)."""
    for ax in reversed(batch_axes):  # innermost first -> major-axis-ordered
        x = jax.lax.all_gather(x, ax, axis=0, tiled=True)
    N, d = x.shape
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(eid, n_experts, dtype=jnp.float32), axis=1), axis=0)
    aux = jnp.sum(me * ce) * n_experts

    j = jax.lax.axis_index("model")
    i = jax.lax.axis_index("data")
    if layout == "ep":  # storage P(("model","data")) on E: shard s = j*data + i
        n_local = max(1, n_experts // (model_size * data_size))
        lo = (j * data_size + i) * n_local
    else:  # ffslice: storage P("data", None, "model"): data shard i owns E/data
        n_local = max(1, n_experts // data_size)
        lo = i * n_local
    local_eid = jnp.where((eid >= lo) & (eid < lo + n_local), eid - lo, -1)
    C = _capacity(N, top_k, n_experts, capacity_factor)
    buf, e_flat, pos_flat, keep, tok = _pack_dispatch(x, local_eid, gate, n_local, C)
    out_buf = _expert_ffn(buf, wi_0, wi_1, wi, wo, activation)
    rows = out_buf[jnp.where(keep, e_flat, 0), jnp.where(keep, pos_flat, 0)]
    g = (gate.reshape(-1) * keep).astype(rows.dtype)
    y = jnp.zeros_like(x).at[tok].add(rows * g[:, None])
    y = jax.lax.psum(y, ("model", "data"))
    idx = 0
    for ax in batch_axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    y = jax.lax.dynamic_slice_in_dim(y, idx * n_local_tokens, n_local_tokens, axis=0)
    return y, jax.lax.pmean(aux, "model")


def moe_apply(params, x, *, layout: str, n_experts: int, top_k: int, mesh,
              capacity_factor: float = 1.25, activation: str = "silu",
              token_spec=None, token_gather_threshold: int = 4096):
    """x: (B, T, d) -> (y, aux_loss).  Must run under `mesh`.

    ``token_spec`` shards the flattened token axis; expert weights follow
    ``moe_param_specs(layout)``.  When the global token count is at most
    ``token_gather_threshold`` (decode shapes), the token-gather body is used
    instead of the ZeRO weight-gather body.
    """
    import numpy as np

    B, T, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_tok_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
    if (B * T) % max(n_tok_shards, 1) != 0:
        batch_axes = ()  # tiny decode batches: replicate tokens
        n_tok_shards = 1
    if token_spec is None:
        token_spec = P(batch_axes, None)
    xf = x.reshape(B * T, d)
    specs = moe_param_specs(layout)
    model_size = mesh.shape["model"]
    data_size = mesh.shape.get("data", 1)

    wi_0 = params.get("wi_0")
    wi_1 = params.get("wi_1")
    wi = params.get("wi")
    wo = params["wo"]

    in_specs = (
        token_spec,
        specs["router"],
        specs["wi_0"] if wi_0 is not None else P(),
        specs["wi_1"] if wi_1 is not None else P(),
        specs["wi"] if wi is not None else P(),
        specs["wo"],
    )
    if B * T <= token_gather_threshold:
        body = functools.partial(
            _moe_tokengather_body,
            layout=layout, n_experts=n_experts, top_k=top_k,
            capacity_factor=capacity_factor, activation=activation,
            model_size=model_size, data_size=data_size, batch_axes=batch_axes,
            n_local_tokens=(B * T) // n_tok_shards,
        )
    else:
        body = functools.partial(
            _moe_shard_body,
            layout=layout,
            n_experts=n_experts,
            top_k=top_k,
            capacity_factor=capacity_factor,
            activation=activation,
            model_size=model_size,
        )
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(token_spec, P()),
        check_vma=False,
    )(xf, params["router"], wi_0, wi_1, wi, wo)

    y = y.reshape(B, T, d)
    if "shared" in params:
        y = y + layers.ffn(params["shared"], x, activation)
    return y, aux


def moe_apply_dense(params, x, *, n_experts: int, top_k: int,
                    activation: str = "silu"):
    """Reference single-device MoE (no dropping): computes ALL experts for all
    tokens and mixes with the gate.  Used for smoke tests / oracles only."""
    B, T, d = x.shape
    xf = x.reshape(-1, d)
    logits = (xf @ params["router"].astype(xf.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    act = layers.ACTIVATIONS[activation]
    if "wi_0" in params:
        h = act(jnp.einsum("nd,edf->nef", xf, params["wi_0"].astype(xf.dtype)))
        h = h * jnp.einsum("nd,edf->nef", xf, params["wi_1"].astype(xf.dtype))
    else:
        h = act(jnp.einsum("nd,edf->nef", xf, params["wi"].astype(xf.dtype)))
    y_all = jnp.einsum("nef,efd->ned", h, params["wo"].astype(xf.dtype))
    mix = jnp.sum(
        jax.nn.one_hot(eid, n_experts, dtype=xf.dtype) * gate[..., None].astype(xf.dtype),
        axis=1,
    )  # (N, E)
    y = jnp.einsum("ne,ned->nd", mix, y_all).reshape(B, T, d)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(eid, n_experts, dtype=jnp.float32), axis=1), axis=0)
    aux = jnp.sum(me * ce) * n_experts
    if "shared" in params:
        y = y + layers.ffn(params["shared"], x, activation)
    return y, aux
