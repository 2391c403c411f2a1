"""Sharding-rule tests: every assigned arch gets divisible, well-formed
PartitionSpecs on the production mesh topology (AbstractMesh — no devices
needed, so these run on the 1-CPU test environment)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.data.pipeline import make_batch_specs
from repro.launch.sharding import (
    batch_shardings,
    decode_state_shardings,
    moment_shardings,
    param_shardings,
    pick_strategy,
)
from repro.models.config import ALL_SHAPES, DECODE_32K, LONG_500K, TRAIN_4K
from repro.models.transformer import Model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))

DRY_ARCHS = [a for a in ARCHS if a != "waste-pipeline"]


def _axis_sz(mesh, ax):
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def _check_divisible(shardings, shapes, mesh):
    flat_sh = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec")
    )
    flat_shape = jax.tree_util.tree_leaves(shapes)
    assert len(flat_sh) == len(flat_shape)
    for sh, leaf in zip(flat_sh, flat_shape):
        spec = sh.spec
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            assert leaf.shape[i] % _axis_sz(mesh, ax) == 0, (
                f"dim {i} of {leaf.shape} not divisible by {ax}"
            )


@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", DRY_ARCHS)
def test_param_shardings_divisible(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    for phase in ("train", "decode"):
        sh = param_shardings(mesh, cfg, shapes, phase=phase)
        _check_divisible(sh, shapes, mesh)


@pytest.mark.parametrize("arch", DRY_ARCHS)
@pytest.mark.parametrize("shape", [DECODE_32K, LONG_500K], ids=lambda s: s.name)
def test_decode_state_shardings_divisible(arch, shape):
    cfg = get_config(arch)
    model = Model(cfg)
    st = jax.eval_shape(
        lambda: model.init_decode_state(shape.global_batch, shape.seq_len)
    )
    sh = decode_state_shardings(MESH, cfg, shape, st)
    _check_divisible(sh, st, MESH)


def test_kv_cache_not_hd_sharded():
    """Regression for §Perf H2: hd-sharding the cache triggers a full-cache
    all-gather per decode step; qwen (2 kv heads) must shard S instead."""
    cfg = get_config("qwen2.5-3b")
    model = Model(cfg)
    st = jax.eval_shape(
        lambda: model.init_decode_state(DECODE_32K.global_batch, DECODE_32K.seq_len)
    )
    sh = decode_state_shardings(MESH, cfg, DECODE_32K, st)
    spec = sh["k"].spec
    # [L, B, S, K, hd]: model on S (idx 2), never on hd (idx 4)
    assert spec[4] is None
    assert spec[2] == "model"


def test_batch_shardings_replicate_indivisible():
    cfg = get_config("qwen2.5-3b")
    specs = {"tokens": jax.ShapeDtypeStruct((1,), jnp.int32)}
    sh = batch_shardings(MESH, cfg, LONG_500K, specs)
    assert sh["tokens"].spec == P(None)


def test_pick_strategy():
    assert pick_strategy(get_config("gemma2-2b"), "train") == "dp_zero1"
    assert pick_strategy(get_config("granite-8b"), "train") == "tp"
    assert pick_strategy(get_config("kimi-k2-1t-a32b"), "train") == "tp"
    assert pick_strategy(get_config("gemma2-2b"), "decode") == "tp"


def test_zero1_moments_sharded():
    cfg = get_config("gemma2-2b")
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    p_sh = param_shardings(MESH, cfg, shapes, strategy="dp_zero1")
    m_sh = moment_shardings(MESH, shapes, "dp_zero1", p_sh)
    # params replicated
    for sh in jax.tree_util.tree_leaves(p_sh, is_leaf=lambda x: hasattr(x, "spec")):
        assert sh.spec == P()
    # at least the embedding moment is sharded across all axes
    assert m_sh["embed"].spec != P()
    _check_divisible(m_sh, shapes, MESH)


def test_expert_weights_expert_parallel():
    cfg = get_config("deepseek-v2-236b")
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    sh = param_shardings(MESH, cfg, shapes, phase="train")
    wg = sh["stack"]["moe"]["wg"]
    assert wg.spec[1] == "model"  # [L, E, D, F]: experts over model
