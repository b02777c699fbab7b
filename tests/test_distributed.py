"""Multi-device numerical correctness, run in subprocesses with
``--xla_force_host_platform_device_count=8`` (the main test process stays
single-device).  These validate that the *sharded* execution paths compute
the same numbers as the single-device reference — the property the dry-run
alone (compile-only) cannot establish."""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(snippet: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # the forced host devices *are* CPU devices; pin the platform so jax
    # never probes for accelerators (TPU metadata probing hangs in CI)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_embedding_lookup_matches_take():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.dist.sharding import default_rules
    from repro.dist.context import install_rules
    from repro.models.recsys.embedding import sharded_lookup

    from repro.dist import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = default_rules(mesh)
    table = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    ids = jax.random.randint(jax.random.PRNGKey(1), (32,), 0, 64)
    ref = jnp.take(table, ids, axis=0)

    with mesh:
        tbl = jax.device_put(table, NamedSharding(mesh, P(("data","model"), None)))
        ids_s = jax.device_put(ids, NamedSharding(mesh, P("data")))
        def f(t, i):
            with install_rules(rules):
                return sharded_lookup(t, i, mesh, capacity_factor=8.0)
        out = jax.jit(f)(tbl, ids_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
    print("OK sharded_lookup")
    """)


def test_moe_grouped_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import default_rules
    from repro.dist.context import install_rules
    from repro.models.moe import init_moe, moe_ffn

    from repro.dist import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = default_rules(mesh)
    p, _ = init_moe(jax.random.PRNGKey(0), 32, 64, 8, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    ref, _ = moe_ffn(p, x, top_k=2, n_groups=1, capacity_factor=8.0)

    with mesh:
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        def f(p, x):
            with install_rules(rules):
                return moe_ffn(p, x, top_k=2, capacity_factor=8.0)[0]
        out = jax.jit(f)(p, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("OK grouped moe")
    """)


def test_sharded_transformer_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import default_rules
    from repro.dist.context import install_rules
    from repro.launch.steps import attach_shardings, eval_params
    from repro.models.transformer import TransformerConfig, init_params, \
        causal_lm_loss

    cfg = TransformerConfig(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                            d_ff=128, vocab_size=256,
                            compute_dtype=jnp.float32, remat_block=2,
                            block_kv=16, logits_chunk=8)
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
    ref = causal_lm_loss(params, cfg, toks[:, :-1], toks[:, 1:])

    from repro.dist import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = default_rules(mesh)
    shapes, ax = eval_params(lambda k: init_params(k, cfg))
    specs = attach_shardings(shapes, ax, rules)
    with mesh:
        ps = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                          params, specs)
        ts = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
        def f(p, t):
            with install_rules(rules):
                return causal_lm_loss(p, cfg, t[:, :-1], t[:, 1:])
        out = jax.jit(f)(ps, ts)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)
    print("OK sharded transformer", float(out), float(ref))
    """)


def test_compressed_psum_pod_axis():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.optim.compression import compressed_psum, init_error_feedback

    from repro.dist import make_mesh
    mesh = make_mesh((8,), ("pod",))
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 64))}
    fb = {"w": jnp.zeros((1, 64))}

    def f(g, e):
        out, new_e = compressed_psum(g, e, "pod")
        return out, new_e

    sm = shard_map(f, mesh=mesh,
                   in_specs=(P("pod", None), P("pod", None)),
                   out_specs=(P("pod", None), P("pod", None)))
    with mesh:
        out, new_fb = jax.jit(sm)(grads, {"w": jnp.zeros((8, 64))})
    # compressed mean-psum approximates the true mean across the pod axis
    ref = np.mean(np.asarray(grads["w"]), axis=0)
    got = np.asarray(out["w"])[0]
    err = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-9)
    assert err < 0.15, err      # int8 single-shot tolerance
    print("OK compressed psum, rel err", err)
    """)
