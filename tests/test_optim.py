"""Optimizers: Adam reference semantics, 8-bit Adam, clip, compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adam_init, adam_update, linear_warmup_cosine, quantize_int8, dequantize_int8
from repro.optim.adam import clip_by_global_norm
from repro.optim.adam8bit import Q8, adam8_init, adam8_update, _quantize, _dequantize


def test_adam_first_step_matches_closed_form():
    params = {"w": jnp.asarray([1.0, 2.0])}
    grads = {"w": jnp.asarray([0.1, -0.2])}
    st = adam_init(params)
    new, st2, m = adam_update(grads, st, params, lr=0.01, grad_clip=None)
    # step 1: mhat = g, vhat = g^2 -> delta = g/(|g|+eps) = sign(g)
    np.testing.assert_allclose(np.asarray(new["w"]), [1.0 - 0.01, 2.0 + 0.01], rtol=1e-4)


def test_clip_by_global_norm():
    grads = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    assert abs(float(norm) - 5.0) < 1e-6
    total = jnp.sqrt(clipped["a"][0] ** 2 + clipped["b"][0] ** 2)
    assert abs(float(total) - 1.0) < 1e-5


def test_adam_converges_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    st = adam_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, st, _ = adam_update(grads, st, params, lr=0.1, grad_clip=None)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.05


def test_adam8_tracks_adam():
    p1 = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)}
    p2 = jax.tree_util.tree_map(lambda x: x, p1)
    s1, s2 = adam_init(p1), adam8_init(p2)
    rng = np.random.default_rng(1)
    for _ in range(20):
        g = {"w": jnp.asarray(rng.standard_normal((8, 64)) * 0.1, jnp.float32)}
        p1, s1, _ = adam_update(g, s1, p1, lr=0.01, grad_clip=None)
        p2, s2, _ = adam8_update(g, s2, p2, lr=0.01, grad_clip=None)
    diff = float(jnp.max(jnp.abs(p1["w"] - p2["w"])))
    assert diff < 0.15, diff  # int8 moments: bounded drift, not bit-exact


def test_q8_shapes_and_sharding_friendliness():
    """Per-row scales: no flat reshape (the GSPMD-safety property)."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 6, 32)), jnp.float32)
    q = _quantize(x)
    assert q.q.shape == x.shape
    assert q.scale.shape == (4, 6)
    err = jnp.abs(_dequantize(q) - x)
    assert float(jnp.max(err - q.scale[..., None] / 2)) <= 1e-6


def test_schedule_warmup_then_decay():
    lr = linear_warmup_cosine(1.0, warmup_steps=10, total_steps=100)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert abs(float(lr(jnp.asarray(10))) - 1.0) < 0.11
    assert float(lr(jnp.asarray(100))) < 0.2


def test_int8_compression_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(100) * 3, jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(jnp.max(err)) <= float(s) / 2 + 1e-6


def test_ef_int8_allreduce_error_feedback():
    """Over many steps the error-feedback compression is unbiased: the sum of
    dequantized transmissions converges to the sum of true gradients."""
    from repro.optim.compress import ef_int8_allreduce
    from jax.sharding import AxisType
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    g_true = [jnp.asarray(rng.standard_normal(32), jnp.float32) for _ in range(30)]
    err = {"g": jnp.zeros(32)}
    sent_total = jnp.zeros(32)
    for g in g_true:
        def body(g, e):
            return ef_int8_allreduce({"g": g}, e, "pod")

        (red, err) = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                               check_vma=False)(g, err)
        sent_total = sent_total + red["g"]
    true_total = sum(np.asarray(g) for g in g_true)
    np.testing.assert_allclose(np.asarray(sent_total), true_total, atol=0.2)
