"""Static sharding validation: every param leaf of every arch resolves to a
spec whose axes divide the production mesh — catches config/rule drift
without compiling (the cheap canary for the dry-run)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_arch
from repro.common.pytree import tree_map_with_name

MESHES = {"single": {"data": 16, "model": 16},
          "multi": {"pod": 2, "data": 16, "model": 16}}


def _check_divisible(name, shape, spec, mesh_shape):
    for dim, axis in zip(shape, tuple(spec)):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        factor = int(np.prod([mesh_shape[a] for a in axes]))
        assert dim % factor == 0, (
            f"{name}: dim {dim} not divisible by {factor} ({spec})"
        )


@pytest.mark.parametrize("mesh_name", ["single", "multi"])
@pytest.mark.parametrize(
    "arch", ["qwen2.5-32b", "granite-20b", "gemma-7b",
             "llama4-maverick-400b-a17b", "deepseek-v3-671b"]
)
def test_lm_param_shardings_divide(arch, mesh_name):
    from repro.dist.sharding import LM_RULES, LM_RULES_FFSLICE
    from repro.launch.cells import _resolve_spec
    from repro.models import lm

    cfg = get_arch(arch).CONFIG
    rules = LM_RULES_FFSLICE if cfg.moe_layout == "ffslice" and cfg.moe_n_experts else LM_RULES
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    mesh_shape = MESHES[mesh_name]

    def check(name, leaf):
        spec = _resolve_spec(rules, name, len(leaf.shape))
        _check_divisible(f"{arch}:{name}", leaf.shape, spec, mesh_shape)
        return leaf

    tree_map_with_name(check, params)


@pytest.mark.parametrize(
    "arch", ["deepfm", "xdeepfm", "bst", "two-tower-retrieval"]
)
def test_recsys_param_shardings_divide(arch):
    from repro.dist.sharding import RECSYS_RULES
    from repro.launch.cells import _resolve_spec
    from repro.models import recsys

    cfg = get_arch(arch).CONFIG
    params = jax.eval_shape(lambda: recsys.init_recsys(jax.random.PRNGKey(0), cfg))

    def check(name, leaf):
        spec = _resolve_spec(RECSYS_RULES, name, len(leaf.shape))
        _check_divisible(f"{arch}:{name}", leaf.shape, spec, MESHES["single"])
        return leaf

    tree_map_with_name(check, params)


def test_lm_shape_cells_batch_divisible():
    """Train/prefill batch dims divide the data axes on both meshes."""
    for arch in ("qwen2.5-32b", "granite-20b", "gemma-7b",
                 "llama4-maverick-400b-a17b", "deepseek-v3-671b"):
        shapes = get_arch(arch).SHAPES
        for name, spec in shapes.items():
            gb = spec["global_batch"]
            if spec["kind"] in ("train", "prefill"):
                assert gb % 32 == 0 or gb == 32, (arch, name, gb)
            seq = spec["seq"]
            assert seq % 16 == 0  # model-axis seq sharding


def test_cells_resolve_specs_for_lm_and_recsys():
    """Regression: launch/cells.py imports repro.dist.sharding and builds
    full cells — every in_sharding leaf resolves to a NamedSharding on the
    mesh — for one LM and one recsys config (no compilation, eval_shape
    only)."""
    from jax.sharding import NamedSharding

    from jax import make_mesh
    from repro.launch import cells
    from repro.models import lm, recsys

    mesh = make_mesh((1, 1), ("data", "model"))

    lm_cfg = get_arch("gemma-7b").CONFIG
    cell = cells.lm_prefill_cell("gemma-7b", lm_cfg, seq=128, global_batch=1,
                                 mesh=mesh)
    rs_cfg = get_arch("two-tower-retrieval").CONFIG
    rcell = cells.recsys_cell("two-tower-retrieval", rs_cfg, batch=32,
                              mesh=mesh, kind="train")
    for c in (cell, rcell):
        leaves = jax.tree_util.tree_leaves(
            c.in_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        assert leaves and all(isinstance(l, NamedSharding) for l in leaves), c.arch
        assert all(l.mesh == mesh for l in leaves), c.arch


def test_rules_first_match_wins():
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import ShardingRules

    rules = ShardingRules(rules=((r"special/w$", P("model")), (r".*", P())))
    assert rules.spec("special/w", 1) == P("model")
    assert rules.spec("other/w", 2) == P()


def test_rule_rank_overflow_raises():
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import ShardingRules

    rules = ShardingRules(rules=((r".*", P("data", "model")),))
    with pytest.raises(ValueError):
        rules.spec("w", 1)
