"""Training substrate tests: loss decreases, grad accumulation equivalence,
checkpoint atomicity/integrity/elasticity, preemption-resume, compression
unbiasedness, data determinism."""
import json
import os
import shutil
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - CI installs hypothesis; shim elsewhere
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import get_config
from repro.models import build
from repro.train import checkpoint as ckpt
from repro.train.compression import compress_decompress_int8
from repro.train.data import DataConfig, ZipfBigramStream
from repro.train.optimizer import OptConfig
from repro.train.train_step import TrainConfig, init_train_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_model():
    cfg = get_config("smollm-360m").reduced()
    return build(cfg)


def _stream(model, batch=8, seq=32):
    return ZipfBigramStream(
        DataConfig(model.cfg.vocab_size, seq, batch, seed=7)
    )


@pytest.mark.slow
def test_loss_decreases():
    model = _tiny_model()
    tcfg = TrainConfig(opt=OptConfig(lr=3e-3, warmup_steps=5, total_steps=60))
    stream = _stream(model)
    step_fn = jax.jit(make_train_step(model, tcfg))
    params, opt = init_train_state(model, tcfg, jax.random.PRNGKey(0))
    losses = []
    for i in range(60):
        params, opt, m = step_fn(params, opt, stream.batch(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.5, losses[::10]


@pytest.mark.slow
def test_grad_accum_equivalence():
    """grad_accum=4 must match a single full-batch step numerically."""
    model = _tiny_model()
    base = TrainConfig(opt=OptConfig(lr=1e-3))
    accum = TrainConfig(opt=OptConfig(lr=1e-3), grad_accum=4)
    stream = _stream(model, batch=8)
    batch = stream.batch(0)
    params, opt = init_train_state(model, base, jax.random.PRNGKey(1))
    p1, _, m1 = jax.jit(make_train_step(model, base))(params, opt, batch)
    p2, _, m2 = jax.jit(make_train_step(model, accum))(params, opt, batch)
    # means of per-microbatch losses differ from full-batch loss only through
    # token-count weighting (equal here), grads through summation order
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2
    )
    assert max(jax.tree_util.tree_leaves(diffs)) < 5e-5


# ------------------------------------------------------------- checkpointing

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones(5, jnp.bfloat16)}}
    ckpt.save(tmp_path, 3, tree)
    step, restored = ckpt.restore(tmp_path, tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(tree["a"]), restored["a"])
    np.testing.assert_array_equal(
        np.asarray(tree["b"]["c"], np.float32), np.asarray(restored["b"]["c"], np.float32)
    )


def test_checkpoint_detects_corruption(tmp_path):
    tree = {"w": jnp.ones((4, 4))}
    ckpt.save(tmp_path, 1, tree, keep=5)
    ckpt.save(tmp_path, 2, jax.tree_util.tree_map(lambda a: a * 2, tree), keep=5)
    # corrupt the newest checkpoint
    leaf = next((tmp_path / "step_2").glob("*.npy"))
    np.save(leaf, np.zeros((4, 4)) + 99)
    step, restored = ckpt.restore(tmp_path, tree)
    assert step == 1  # fell back to the older valid checkpoint
    np.testing.assert_array_equal(restored["w"], np.ones((4, 4)))


def test_checkpoint_ignores_partial_tmp(tmp_path):
    tree = {"w": jnp.ones(3)}
    ckpt.save(tmp_path, 5, tree)
    (tmp_path / "step_9.tmp").mkdir()  # simulated crash mid-save
    step, _ = ckpt.restore(tmp_path, tree)
    assert step == 5


def test_checkpoint_gc_keeps_last(tmp_path):
    tree = {"w": jnp.ones(2)}
    for s in range(1, 6):
        ckpt.save(tmp_path, s, tree, keep=2)
    assert ckpt.available_steps(tmp_path) == [4, 5]


def test_async_saver(tmp_path):
    saver = ckpt.AsyncSaver()
    tree = {"w": jnp.arange(8.0)}
    saver.save(tmp_path, 7, tree)
    saver.wait()
    step, restored = ckpt.restore(tmp_path, tree)
    assert step == 7 and np.allclose(restored["w"], np.arange(8.0))


def test_elastic_restore_different_mesh(tmp_path):
    """Checkpoint written under one sharding restores onto another mesh
    (device-count change) — exercised in a subprocess with 8 host devices."""
    import subprocess, sys, textwrap

    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        x = jax.device_put(np.arange(64.).reshape(8, 8), NamedSharding(mesh_a, P("data", "model")))
        ckpt.save(r"{tmp_path}", 1, {{"x": x}})
        sh_b = {{"x": NamedSharding(mesh_b, P("data", "model"))}}
        step, restored = ckpt.restore(r"{tmp_path}", {{"x": x}}, shardings=sh_b)
        assert step == 1
        assert restored["x"].sharding.mesh.shape == {{"data": 2, "model": 4}}
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.arange(64.).reshape(8, 8))
        print("ELASTIC_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=str(Path(__file__).resolve().parent.parent),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_trainer_resume_after_kill(tmp_path):
    """Train 30 steps with checkpoints, 'crash', resume — the resumed run
    continues from the checkpoint and reaches the same total step count."""
    model = _tiny_model()
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3))
    stream = _stream(model)
    run_cfg = TrainerConfig(total_steps=30, ckpt_every=10, ckpt_dir=str(tmp_path), log_every=100)
    t1 = Trainer(model, tcfg, run_cfg, stream)
    # first run "crashes" after 20 steps: emulate by limiting total_steps
    t1.cfg.total_steps = 20
    r1 = t1.run()
    assert r1["final_step"] == 20
    assert ckpt.available_steps(tmp_path)  # checkpoints exist
    # resumed run picks up from step 20 (not 0) and finishes to 30
    t2 = Trainer(model, tcfg, TrainerConfig(total_steps=30, ckpt_every=10, ckpt_dir=str(tmp_path), log_every=100), stream)
    r2 = t2.run()
    assert r2["final_step"] == 30
    assert len(r2["history"]) == 10  # only the remaining 10 steps were run


# ------------------------------------------------------------- compression

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), scale=st.floats(1e-4, 1e3))
def test_int8_compression_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.normal(size=(64, 33)) * scale, jnp.float32)}
    out = compress_decompress_int8(g, jax.random.PRNGKey(seed))
    err = np.abs(np.asarray(out["w"]) - np.asarray(g["w"]))
    # block max / 127 bounds the quantisation step
    step = np.abs(np.asarray(g["w"])).max() / 127.0
    assert err.max() <= step + 1e-6


def test_int8_compression_unbiased():
    g = {"w": jnp.full((256, 64), 0.3, jnp.float32)}
    outs = [
        np.asarray(compress_decompress_int8(g, jax.random.PRNGKey(i))["w"]) for i in range(200)
    ]
    mean = np.mean(outs)
    assert abs(mean - 0.3) < 2e-3  # stochastic rounding is unbiased


@pytest.mark.slow
def test_compressed_training_still_learns():
    model = _tiny_model()
    tcfg = TrainConfig(opt=OptConfig(lr=3e-3, warmup_steps=5), compress_grads=True)
    stream = _stream(model)
    step_fn = jax.jit(make_train_step(model, tcfg))
    params, opt = init_train_state(model, tcfg, jax.random.PRNGKey(3))
    losses = []
    for i in range(40):
        params, opt, m = step_fn(params, opt, stream.batch(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


# ------------------------------------------------------------------- data

def test_data_deterministic_and_elastic():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8, seed=5)
    s = ZipfBigramStream(cfg)
    a = s.batch(3)["tokens"]
    b = ZipfBigramStream(cfg).batch(3)["tokens"]
    np.testing.assert_array_equal(a, b)  # restart-reproducible
    assert not np.array_equal(a, s.batch(4)["tokens"])  # steps differ


def test_data_is_zipf_skewed():
    cfg = DataConfig(vocab_size=512, seq_len=128, global_batch=16, seed=9)
    toks = ZipfBigramStream(cfg).batch(0)["tokens"].ravel()
    counts = np.bincount(toks, minlength=512)
    top = counts[:16].sum() / counts.sum()
    # head-heavy marginal (uniform would give 16/512 ~= 3%); the bigram
    # mixing flattens the pure Zipf(1.1) head somewhat
    assert top > 0.15