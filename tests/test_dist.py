"""Unit tests for the repro.dist rule/spec machinery.  These run in the
single-device main process: rule resolution is pure shape arithmetic, so
multi-device meshes are modeled with ``AbstractMesh`` (no devices touched);
the numerics of sharded execution live in test_distributed.py."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.dist import (ShardingRules, current_rules, default_rules,
                        divisible_spec, install_rules, maybe_shard,
                        replicated_serving_rules)


def _mesh(shape=(("data", 4), ("model", 2))):
    names, sizes = zip(*shape)
    return AbstractMesh(sizes, names)


# ---------------------------------------------------------------------------
# divisible_spec
# ---------------------------------------------------------------------------


def test_divisible_spec_basic():
    rules = default_rules(_mesh())
    assert divisible_spec(rules, ("batch", None), (8, 16)) == P("data", None)
    assert divisible_spec(rules, ("embed", "heads"), (64, 8)) == \
        P("data", "model")


def test_divisible_spec_drops_non_divisible_dim():
    rules = default_rules(_mesh())
    # batch of 6 does not divide the 4-way data axis -> replicated
    assert divisible_spec(rules, ("batch", None), (6, 16)) == P(None, None)
    # heads=3 does not divide model=2 -> replicated on that dim only
    assert divisible_spec(rules, ("embed", "heads"), (64, 3)) == \
        P("data", None)


def test_divisible_spec_no_duplicate_mesh_axes():
    # MoE weights: ("experts", "embed", "mlp") — when E divides the model
    # axis it takes it (expert parallelism) and the mlp dim must NOT reuse it
    rules = default_rules(_mesh())
    assert divisible_spec(rules, ("experts", "embed", "mlp"), (8, 64, 128)) \
        == P("model", "data", None)
    # granite-style: E=5 does not divide model=2 -> d_ff gets the axis
    assert divisible_spec(rules, ("experts", "embed", "mlp"), (5, 64, 128)) \
        == P(None, "data", "model")


def test_divisible_spec_multi_axis_dim():
    mesh = _mesh((("pod", 2), ("data", 4), ("model", 2)))
    rules = default_rules(mesh)
    # table rows shard over every axis when divisible by the full product
    assert divisible_spec(rules, ("table_rows", None), (512, 16)) == \
        P(("pod", "data", "model"), None)
    # 8 rows: pod(2) and data(4) fit (8 % 2, 8 % 8), model would need 16
    assert divisible_spec(rules, ("table_rows", None), (8, 16)) == \
        P(("pod", "data"), None)


def test_divisible_spec_unknown_logical_axis_replicates():
    rules = default_rules(_mesh())
    assert divisible_spec(rules, ("no_such_axis", None), (8, 8)) == \
        P(None, None)
    # annotation shorter than the rank pads with replicated dims
    assert divisible_spec(rules, ("batch",), (8, 8, 8)) == \
        P("data", None, None)


def test_replicated_serving_rules():
    rules = replicated_serving_rules(_mesh())
    assert divisible_spec(rules, ("batch", None), (8, 16)) == \
        P(("data", "model"), None)
    # weights replicate: "embed"/"mlp" are unmapped under serving rules
    assert divisible_spec(rules, ("embed", "mlp"), (64, 128)) == P(None, None)


# ---------------------------------------------------------------------------
# install_rules / current_rules
# ---------------------------------------------------------------------------


def test_install_rules_nesting_and_restoration():
    outer = default_rules(_mesh())
    inner = replicated_serving_rules(_mesh())
    assert current_rules() is None
    with install_rules(outer):
        assert current_rules() is outer
        with install_rules(inner):
            assert current_rules() is inner
        assert current_rules() is outer
    assert current_rules() is None


def test_install_rules_restores_on_error():
    rules = default_rules(_mesh())
    try:
        with install_rules(rules):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert current_rules() is None


# ---------------------------------------------------------------------------
# maybe_shard
# ---------------------------------------------------------------------------


def test_maybe_shard_noop_outside_rules():
    x = jnp.ones((8, 16))
    assert maybe_shard(x, ("batch", None)) is x


def test_maybe_shard_noop_on_trivial_mesh():
    # a 1-device mesh can be built for real in the single-device test proc
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    rules = ShardingRules(mesh, {"batch": ("data",)})
    x = jnp.ones((8, 16))
    with install_rules(rules):
        assert maybe_shard(x, ("batch", None)) is x


def test_maybe_shard_noop_when_nothing_maps():
    # rules installed, >1 device mesh, but no dim is shardable -> untouched
    rules = default_rules(_mesh())
    x = jnp.ones((7, 9))              # divides neither data=4 nor model=2
    with install_rules(rules):
        assert maybe_shard(x, ("batch", "embed_tp")) is x


def test_models_run_unsharded_with_no_rules():
    # the dist hooks must be invisible to plain single-device execution
    from repro.models.transformer import (TransformerConfig, causal_lm_loss,
                                          init_params)
    cfg = TransformerConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=64,
                            compute_dtype=jnp.float32, block_kv=8)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    loss = causal_lm_loss(params, cfg, toks[:, :-1], toks[:, 1:])
    assert jnp.isfinite(loss)
