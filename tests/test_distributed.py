"""Sharding rules and mesh helper tests (host mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (batch_specs, cache_specs,
                                        opt_state_specs, param_specs)
from repro.launch.mesh import data_axes
from repro.models.registry import init_params, make_decode_state


class _FakeMesh:
    """Shape-only mesh stand-in for spec-rule tests (no devices needed)."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = _FakeMesh({"data": 16, "model": 16})
MESH_MULTI = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_param_specs_dense_rules():
    cfg = get_config("llama3.2-3b")
    params = init_params(cfg, abstract=True)
    specs = param_specs(params, MESH)
    assert specs["embed"] == P("model", "data")
    assert specs["lm_head"] == P("data", "model")
    lay = specs["layers"]
    assert lay["attn"]["wq"] == P(None, "data", "model")
    assert lay["attn"]["wo"] == P(None, "model", "data")
    assert lay["ffn"]["w_gate"] == P(None, "data", "model")
    assert lay["ffn"]["w_down"] == P(None, "model", "data")
    assert lay["ln1"] == P()


def test_param_specs_moe_expert_sharding():
    cfg = get_config("qwen3-moe-235b-a22b")
    params = init_params(cfg, abstract=True)
    specs = param_specs(params, MESH)
    lay = specs["layers"]
    assert lay["ffn"]["w_gate"] == P(None, "model", "data", None)
    # router replicated (no sharded axes)
    assert all(a is None for a in tuple(lay["ffn"]["router"]))


def test_param_specs_divisibility_fallback():
    """Dims not divisible by an axis are replicated, never mis-sharded."""
    cfg = get_config("recurrentgemma-2b")   # 10 heads, kv=1
    params = init_params(cfg, abstract=True)
    specs = param_specs(params, MESH)
    sup = specs["supers"]
    # wk: (L, d, 1*256) -> 256 divisible by 16 => sharded on flat dim
    assert sup["attn"]["attn"]["wk"][-1] == "model"
    # lam: (L, 2560) with model=16 divides 2560
    assert sup["r0"]["rglru"]["lam"] == P(None, "model")


def test_opt_state_specs_add_dp_only_once():
    cfg = get_config("llama3.2-3b")
    params = init_params(cfg, abstract=True)
    o = opt_state_specs(params, MESH)
    flat = jax.tree_util.tree_leaves(
        o, is_leaf=lambda x: isinstance(x, P))
    for spec in flat:
        axes = [a for part in spec for a in
                (part if isinstance(part, tuple) else (part,))
                if a is not None]
        assert len(axes) == len(set(axes)), spec  # no duplicate mesh axes


def test_batch_and_cache_specs():
    cfg = get_config("llama3.2-3b")
    b = batch_specs(cfg, MESH_MULTI, "train")
    assert b["tokens"] == P(("pod", "data"), None)
    caches = make_decode_state(cfg, 128, 32768, abstract=True)
    cs = cache_specs(cfg, caches, MESH)
    k_spec = cs.kv[0]
    assert k_spec[1] == "data"      # batch dim
    assert "model" in tuple(k_spec)  # long seq dim sharded


def test_data_axes_helper():
    assert data_axes(MESH_MULTI) == ("pod", "data")
    assert data_axes(MESH) == ("data",)


def test_activation_constrain_noop_off_mesh():
    from repro.distributed.ctx import constrain
    x = jnp.ones((4, 8, 16))
    y = constrain(x, "bsd")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_grad_compression_error_feedback():
    from repro.optim import AdamW, Int8EF, apply_updates
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    opt = AdamW(lr=5e-2, weight_decay=0.0, compress=Int8EF())
    state = opt.init(params)
    assert state.ef is not None

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - 1.0))

    losses = []
    for _ in range(80):
        g = jax.grad(loss)(params)
        u, state = opt.update(g, state, params)
        params = apply_updates(params, u)
        losses.append(float(loss(params)))
    assert losses[-1] < losses[0] * 0.1   # converges despite int8 grads
