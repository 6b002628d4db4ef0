"""The fully-manual expert-parallel MoE path (shard_map over data x model)
must match the global-dispatch path numerically, gradients included.

Runs in a subprocess because the 4-device CPU mesh needs
XLA_FLAGS=--xla_force_host_platform_device_count=4 before jax initializes
(the main test process must keep the single real device).
"""
import os
import subprocess
import sys
import textwrap

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models.sharding import ShardingRules, DEFAULT_RULES
    from repro.models import model as M
    from repro.models.moe import moe_layer

    cfg = ModelConfig(name="t", family="moe", source="", num_layers=2,
                      d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                      vocab_size=64,
                      moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                                    capacity_factor=8.0))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda t: t[0], params["layers"])
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = ShardingRules(mesh, dict(DEFAULT_RULES))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)

    y_ref, _ = jax.jit(lambda x, p: moe_layer(x, p, cfg, None))(x, lp["moe"])
    with mesh:
        y_mesh, _ = jax.jit(lambda x, p: moe_layer(x, p, cfg, rules))(
            x, lp["moe"])
    assert np.allclose(np.asarray(y_ref), np.asarray(y_mesh), atol=2e-5), \
        np.abs(np.asarray(y_ref) - np.asarray(y_mesh)).max()

    g = jax.jit(jax.grad(lambda p: moe_layer(x, p, cfg, None)[0].sum()))(
        lp["moe"])
    with mesh:
        gm = jax.jit(jax.grad(lambda p: moe_layer(x, p, cfg, rules)[0].sum()))(
            lp["moe"])
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        g, gm)))
    assert err < 2e-3, f"grad mismatch {err}"
    print("OK")
""")


def test_moe_local_dispatch_matches_global():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", PROG], env=env,
                       capture_output=True, text=True, timeout=480)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


SERVED = textwrap.dedent("""
    import dataclasses, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models.sharding import ShardingRules, DEFAULT_RULES
    from repro.models import model as M
    from repro.models.moe import dense_experts, moe_served

    # capacity factor 1: the training dispatch would drop routes here
    cfg = ModelConfig(name="t", family="moe", source="", num_layers=2,
                      d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                      vocab_size=64,
                      moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                                    capacity_factor=1.0))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda t: t[0], params["layers"])
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # 8 positions a row: dense on one device and on every shard; 1100:
    # grouped on one device (4 held x 4400 tokens), dense on the shards'
    # 2 held; 2100: grouped on one device and on the replicated batch's
    # shards (2 x 8400), dense on a data shard's 4200 tokens
    paths = set()
    for s in (8, 1100, 2100):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, s, 32), jnp.float32)
        y_one, r_one = jax.jit(lambda x, p: moe_served(x, p, cfg))(
            x, lp["moe"])
        assert int(r_one.sum()) == 4 * s * 2
        paths.add((s, "one", dense_experts(4 * s, 2, 4, 4)))
        # batch sharded over data (prefill) and replicated (decode)
        for batch_axis, shard_t in (("data", 2 * s), (None, 4 * s)):
            paths.add((s, batch_axis, dense_experts(shard_t, 2, 4, 2)))
            rules = ShardingRules(mesh, {**DEFAULT_RULES,
                                         "act_batch": batch_axis})
            with mesh:
                y, r = jax.jit(lambda x, p: moe_served(x, p, cfg, rules))(
                    x, lp["moe"])
            assert np.allclose(np.asarray(y_one), np.asarray(y),
                               atol=2e-5), \\
                (s, batch_axis,
                 np.abs(np.asarray(y_one) - np.asarray(y)).max())
            assert np.array_equal(np.asarray(r_one), np.asarray(r)), \\
                (s, batch_axis, r_one, r)
    assert {(8, "one", True), (8, "data", True), (1100, "one", False),
            (1100, None, True), (2100, None, False),
            (2100, "data", True)} <= paths, paths
    # a layer holding a share of the experts is not split again
    held = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=2))
    try:
        with mesh:
            jax.jit(lambda x, p: moe_served(x, p, held, rules))(
                x, jax.tree.map(lambda t: t[:2] if t.shape[0] == 4
                                else t, lp["moe"]))
    except ValueError as e:
        assert "holds 2" in str(e), e
    else:
        raise AssertionError("a mesh split a held share")
    # the served prefill and decode programs of whole models on the mesh
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.specs import build_lowering
    rules = ShardingRules(mesh, dict(DEFAULT_RULES))
    for arch in ("dbrx-132b", "llama4-maverick-400b-a17b"):
        for shape in (InputShape("p", 64, 4, "prefill"),
                      InputShape("d", 64, 4, "decode")):
            fn, args, sh = build_lowering(get_config(arch).reduced(), shape,
                                          rules)
            with mesh:
                jax.jit(fn, in_shardings=sh).lower(*args).compile()
    print("OK")
""")


def test_served_moe_on_a_mesh_matches_one_device():
    """The drop-free served layer split over a mesh's expert axis gives the
    one-device layer's output and route counts, with the batch sharded
    (prefill) or replicated (decode), and whole MoE models' served
    programs compile on the mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SERVED], env=env,
                       capture_output=True, text=True, timeout=480)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]
