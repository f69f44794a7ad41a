"""Sharding rules: TP/FSDP/EP translation, divisibility fallbacks."""
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as shd

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class _Shape:
    def __init__(self, *s):
        self.shape = s


def _spec(shape, logical, cfg, mesh=MESH1, **kw):
    rules = shd.logical_rules(cfg, **kw)
    return shd.spec_for_shape(shape, logical, rules, mesh)


def test_tp_and_fsdp_basic():
    cfg = get_config("qwen3-1.7b")
    # mlp weight [d, ff]: embed→data (FSDP), mlp→model (TP)
    assert _spec((2048, 6144), ("embed", "mlp"), cfg) == P("data", "model")
    # vocab divisible → model
    assert _spec((151936, 2048), ("vocab", "embed"), cfg) == P("model", "data")


def test_vocab_indivisible_falls_back():
    cfg = get_config("minicpm-2b")   # vocab 122753 is not divisible by 16
    spec = _spec((122753, 2304), ("vocab", "embed"), cfg)
    assert spec == P(None, "data")


def test_layers_axis_never_sharded():
    cfg = get_config("qwen3-1.7b")
    spec = _spec((28, 2048, 6144), ("layers", "embed", "mlp"), cfg)
    assert spec == P(None, "data", "model")


def test_moe_ep_vs_tp():
    ds = get_config("deepseek-v3-671b")     # 256 experts ≥ 16 → EP
    spec = _spec((256, 7168, 2048), ("expert", "embed", "expert_mlp"), ds)
    assert spec == P("model", "data", None)
    mx = get_config("mixtral-8x22b")        # 8 experts < 16 → TP on hidden
    spec = _spec((8, 6144, 16384), ("expert", "embed", "expert_mlp"), mx)
    assert spec == P(None, "data", "model")


def test_fsdp_over_pod():
    cfg = get_config("deepseek-v3-671b")
    spec = _spec((7168, 1536), ("embed", None), cfg, mesh=MESH2,
                 fsdp_over_pod=True)
    assert spec == P(("pod", "data"), None)
    # dim only divisible by data (not pod*data) degrades to data alone
    spec2 = _spec((48, 16), ("embed", None), cfg, mesh=MESH2,
                  fsdp_over_pod=True)
    assert spec2 == P("data", None)


def test_no_double_axis_use():
    cfg = get_config("qwen3-1.7b")
    spec = _spec((2048, 2048), ("embed", "embed"), cfg)
    assert spec == P("data", None)  # second 'data' suppressed


def test_batch_spec_degradation():
    assert shd.batch_spec(256, AbstractMesh((16, 16), ("data", "model"))) \
        == P(("data",), None)
    # batch=1 cannot shard → replicated
    assert shd.batch_spec(1, AbstractMesh((16, 16), ("data", "model"))) \
        == P(None, None)
    assert shd.batch_spec(256, MESH2) == P(("pod", "data"), None)


def test_param_specs_tree():
    import jax
    from repro.launch.steps import M_init_specs
    cfg = get_config("qwen3-1.7b")
    shapes, logical = M_init_specs(cfg)
    specs = shd.param_specs(shapes, logical, cfg, MESH1)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)
    # every spec's non-None axes divide the corresponding dim
    def check(shape_like, spec):
        for dim, ax in zip(shape_like.shape, spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            sz = int(np.prod([MESH1.shape[a] for a in axes]))
            assert dim % sz == 0, (shape_like.shape, spec)
    jax.tree.map(check, shapes, specs,
                 is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))
