"""Multi-device test cases, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests/test_distributed.py
drives this; the main pytest process must stay single-device)."""

import sys

import numpy as np


def case_ca_matmul():
    import jax, jax.numpy as jnp
    from repro.core.ca_matmul import ca_matmul, summa_ca_matmul

    mesh = jax.make_mesh((2, 2, 2), ("kl", "tm", "tn"))
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(96, 64)), jnp.float32)
    want = np.asarray(a) @ np.asarray(b)
    for reduce in ("psum", "psum_scatter"):
        got = ca_matmul(a, b, mesh=mesh, tm_axis="tm", tn_axis="tn", kl_axis="kl", reduce=reduce)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    got = summa_ca_matmul(a, b, mesh=mesh, tm_axis="tm", tn_axis="tn", kl_axis="kl")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    got = ca_matmul(a, b, mesh=mesh, tm_axis="tm", tn_axis="tn", kl_axis=None)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def case_ca_matmul_backends():
    import jax, jax.numpy as jnp
    from repro.core.ca_matmul import ca_matmul, summa_ca_matmul

    mesh = jax.make_mesh((2, 2, 2), ("kl", "tm", "tn"))
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    want = np.asarray(a) @ np.asarray(b)
    axes = dict(mesh=mesh, tm_axis="tm", tn_axis="tn", kl_axis="kl")
    for backend in ("xla", "sfc_reference", "sfc_pallas"):
        for fn in (ca_matmul, summa_ca_matmul):
            got = fn(a, b, backend=backend, **axes)
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=1e-3, atol=1e-3
            )


def case_sharded_train_step():
    """Sharded vs single-device train step: identical loss and params."""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh_for
    from repro.launch.train import build_trainer

    cfg = get_config("yi_6b").reduced()
    # single device reference
    p1, o1, step1, batch_fn = build_trainer(cfg, batch=4, seq=16, lr=1e-3, total_steps=5)
    mesh = make_mesh_for(2, 2, 2)  # pod x data x model
    p2, o2, step2, _ = build_trainer(cfg, batch=4, seq=16, lr=1e-3, total_steps=5, mesh=mesh)

    for step in range(3):
        b = batch_fn(step)
        p1, o1, m1 = step1(p1, o1, b)
        p2, o2, m2 = step2(p2, o2, b)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-5)
    for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4)


def case_elastic_reshard():
    """Checkpoint on a 2x2 mesh, restore onto 4x1 and 1x1 — same values."""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train.checkpoint import restore, save

    devices = jax.devices()
    mesh_a = jax.make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    tree = {
        "w": jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh_a, P("data", "model")),
        )
    }
    save("/tmp/elastic_ckpt_test", 1, tree)

    mesh_b = jax.make_mesh((4, 1), ("data", "model"), devices=devices[:4])
    sh_b = {"w": NamedSharding(mesh_b, P(None, "data"))}
    got, _ = restore("/tmp/elastic_ckpt_test", 1, shardings=sh_b)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
    assert got["w"].sharding == sh_b["w"]

    got2, _ = restore("/tmp/elastic_ckpt_test", 1)  # host-local restore
    np.testing.assert_array_equal(np.asarray(got2["w"]), np.asarray(tree["w"]))


def case_compressed_gradient_sync():
    """Error-feedback int8 sync over a mesh axis: converges like f32."""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import compressed_psum_mean

    mesh = jax.make_mesh((4, 2), ("pod", "data"))
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)

    def body(g_loc):
        return compressed_psum_mean(g_loc, "pod")

    synced = jax.shard_map(
        body, mesh=mesh, in_specs=P("pod", None), out_specs=P("pod", None),
        check_vma=False,
    )(g)
    # each pod row receives the mean of all 4 shards (up to int8 quantization)
    want = np.asarray(g).reshape(4, 2, 32).mean(axis=0)
    got = np.asarray(synced).reshape(4, 2, 32)
    for i in range(4):
        np.testing.assert_allclose(got[i], want, rtol=0.06, atol=0.06)


def case_ca_25d_profile_lowers():
    """The beyond-paper ca_25d sharding profile lowers on a pod mesh."""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh_for
    from repro.models.registry import build_model, param_specs
    from repro.parallel.act_sharding import activation_sharding
    from repro.parallel.sharding import data_axes, make_shardings, spec_for_tree

    cfg = get_config("yi_6b").reduced()
    mesh = make_mesh_for(2, 2, 2)
    model = build_model(cfg)
    params_abs = param_specs(cfg)
    p_sh = make_shardings(mesh, spec_for_tree(params_abs, cfg, mesh, "ca_25d"))
    toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)

    def fwd(p, t):
        return model.forward(p, t, remat="none")[0]

    with mesh, activation_sharding(mesh, data_axes(mesh), "model"):
        lowered = jax.jit(fwd, in_shardings=(p_sh, None)).lower(params_abs, toks)
        lowered.compile()


def case_pipeline_parallel():
    """GPipe pipeline over a mesh axis == sequential stage application."""
    import jax, jax.numpy as jnp
    from repro.parallel.pipeline import pipeline_apply

    mesh = jax.make_mesh((4, 2), ("pipe", "data"))
    n_stages, n_micro, mb, d = 4, 6, 2, 8
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(n_stages, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

    def stage_fn(wi, h):
        return jnp.tanh(h @ wi)

    got = pipeline_apply(stage_fn, w, x, mesh=mesh, axis="pipe")
    want = x
    for sidx in range(n_stages):
        want = jnp.tanh(want @ w[sidx])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


CASES = {k[5:]: v for k, v in list(globals().items()) if k.startswith("case_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CASES[name]()
    print(f"DIST_CASE_OK {name}")
