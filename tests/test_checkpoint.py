"""Checkpoint manager: atomicity, async, retention, elastic restore."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore, save
from repro.checkpoint.manager import latest_step


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "layer": {"w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
                  "b": jnp.asarray(rng.standard_normal(8), jnp.bfloat16)},
        "step_count": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip_identity(tmp_path):
    tree = _tree()
    save(tmp_path, 10, tree)
    restored, step = restore(tmp_path, jax.tree_util.tree_map(jnp.zeros_like, tree))
    assert step == 10
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoints_ignored(tmp_path):
    tree = _tree()
    save(tmp_path, 5, tree)
    # forge a newer, uncommitted step
    d = tmp_path / "step_00000009"
    d.mkdir()
    (d / "manifest.json").write_text("{}")
    assert latest_step(tmp_path) == 5


def test_restore_validates_shapes(tmp_path):
    save(tmp_path, 1, _tree())
    bad = {"layer": {"w": jnp.zeros((3, 3)), "b": jnp.zeros(8, jnp.bfloat16)},
           "step_count": jnp.zeros((), jnp.int32)}
    with pytest.raises(ValueError):
        restore(tmp_path, bad)


def test_async_save_and_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        mgr.save_async(s, tree)
    mgr.wait()
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert steps == [3, 4]
    restored, step = mgr.restore_latest(jax.tree_util.tree_map(jnp.zeros_like, tree))
    assert step == 4


def _simulate_crash_mid_save(directory, step):
    """Forge the on-disk state of a save that died partway: shard partially
    written, manifest missing, no _COMMITTED — both in .tmp staging form and
    as a bare step dir (the pre-rename and post-partial-write crash points)."""
    directory = pathlib.Path(directory)
    staged = directory / f"step_{step:08d}.tmp"
    staged.mkdir(parents=True)
    (staged / "shard_00000.npz").write_bytes(b"PK\x03\x04 truncated")
    bare = directory / f"step_{step + 1:08d}"
    bare.mkdir(parents=True)
    (bare / "shard_00000.npz").write_bytes(b"PK\x03\x04 truncated")
    (bare / "manifest.json").write_text("{")


def test_crash_mid_save_restores_last_complete(tmp_path):
    tree = _tree()
    save(tmp_path, 5, tree)
    _simulate_crash_mid_save(tmp_path, 6)
    assert latest_step(tmp_path) == 5
    restored, step = restore(tmp_path,
                             jax.tree_util.tree_map(jnp.zeros_like, tree))
    assert step == 5
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_crash_mid_save_then_resave_recovers(tmp_path):
    """A later save over the wreckage clears the stale .tmp staging dir and
    commits cleanly."""
    tree = _tree()
    save(tmp_path, 5, tree)
    _simulate_crash_mid_save(tmp_path, 5)  # stale step_00000005.tmp + junk 6
    d = save(tmp_path, 5, _tree(seed=1))
    assert d.name == "step_00000005"
    assert latest_step(tmp_path) == 5
    restored, _ = restore(tmp_path,
                          jax.tree_util.tree_map(jnp.zeros_like, tree), step=5)
    exp = jax.tree_util.tree_leaves(_tree(seed=1))
    for a, b in zip(exp, jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retriever_load_survives_crash_mid_save(tmp_path):
    """Facade-level regression: a crash mid-``save()`` (partial shard, no
    committed manifest) must leave ``LemurRetriever.load()`` restoring the
    last complete checkpoint bit-identically."""
    from repro.core.config import LemurConfig
    from repro.data import synthetic
    from repro.retriever import LemurRetriever, SearchParams

    corpus = synthetic.make_corpus(m=48, d=8, avg_tokens=6, max_tokens=8,
                                   n_centers=6, seed=0)
    cfg = LemurConfig(d=8, d_prime=16, m_pretrain=32, n_train=512, n_ols=128,
                      epochs=1, k=5, k_prime=24, anns="bruteforce")
    r = LemurRetriever.build(corpus, cfg, key=jax.random.PRNGKey(0))
    r.save(tmp_path)
    _simulate_crash_mid_save(tmp_path, 0)   # wreck a would-be re-save
    r2 = LemurRetriever.load(tmp_path)
    q = np.asarray(corpus.doc_tokens[:4])
    qm = np.asarray(corpus.doc_mask[:4])
    p = SearchParams(k=5, k_prime=24)
    s1, i1 = r.search(q, qm, p)
    s2, i2 = r2.search(q, qm, p)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_elastic_restore_with_shardings(tmp_path):
    """Restore with explicit shardings places leaves on the (1-device) mesh —
    the same codepath a resized job uses."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    tree = _tree()
    save(tmp_path, 3, tree)
    sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)
    restored, _ = restore(tmp_path, jax.tree_util.tree_map(jnp.zeros_like, tree),
                          shardings=sh)
    w = restored["layer"]["w"]
    assert w.sharding == NamedSharding(mesh, P())
