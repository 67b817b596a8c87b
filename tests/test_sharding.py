"""Sharding-rule unit tests + a real multi-device compile in a subprocess."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import logical_rules, partition_spec


@pytest.fixture(scope="module")
def mesh22():
    if jax.device_count() < 1:
        pytest.skip("no devices")
    # single-device "mesh" still exercises the rule logic via divisibility
    return jax.make_mesh((1, 1), ("data", "model"))


def _mesh_stub(shape):
    class M:
        pass

    m = M()
    m.shape = dict(shape)
    return m


TRAIN_RULES = logical_rules(kind="train", multi_pod=False, long_context=False)
DECODE_RULES = logical_rules(kind="decode", multi_pod=False, long_context=False)
MESH = _mesh_stub({"data": 16, "model": 16})
MESH_MP = _mesh_stub({"pod": 2, "data": 16, "model": 16})


def test_basic_param_sharding():
    # wq (d, H, hd): FSDP on d, TP on heads
    spec = partition_spec((2048, 32, 64), ("embed", "heads", "head_dim"), TRAIN_RULES, MESH)
    assert spec == P("data", "model")


def test_indivisible_head_fallback():
    # smollm: 15 heads don't divide 16 -> replicated heads, d/ff still shard
    spec = partition_spec((960, 15, 64), ("embed", "heads", "head_dim"), TRAIN_RULES, MESH)
    assert spec == P("data")


def test_vocab_fallback():
    # granite vocab 49155 % 16 != 0 -> replicated vocab, sharded embed dim
    spec = partition_spec((49155, 2048), ("vocab", "embed"), TRAIN_RULES, MESH)
    assert spec == P(None, "data")


def test_expert_fallbacks():
    # deepseek 160 experts -> EP over model; grok 8 -> TP inside experts
    ds = partition_spec((160, 5120, 1536), ("experts", "embed", "mlp"), TRAIN_RULES, MESH)
    assert ds == P("model", "data")
    gk = partition_spec((8, 6144, 32768), ("experts", "embed", "mlp"), TRAIN_RULES, MESH)
    assert gk == P(None, "data", "model")


def test_axis_used_once_per_tensor():
    # batch takes data; a later dim wanting data skips it
    spec = partition_spec((256, 4096, 2048), ("batch", "seq", "embed"), TRAIN_RULES, MESH)
    # batch->data, seq->model (candidate), embed wants data (used) -> None
    assert spec == P("data", "model")


def test_decode_kv_cache_sharding():
    # decode: kv_len unsharded, head_dim takes model when kv_heads can't
    spec = partition_spec(
        (128, 32768, 8, 128), ("batch", "kv_len", "kv_heads", "head_dim"), DECODE_RULES, MESH
    )
    assert spec == P("data", None, None, "model")


def test_long_context_batch1():
    rules = logical_rules(kind="decode", multi_pod=False, long_context=True)
    # batch=1 can't shard; decode caches shard head_dim over model
    spec = partition_spec(
        (1, 524288, 8, 128), ("batch", "kv_len", "kv_heads", "head_dim"), rules, MESH
    )
    assert spec == P(None, None, None, "model")


def test_multipod_batch():
    rules = logical_rules(kind="train", multi_pod=True, long_context=False)
    spec = partition_spec((256, 4096), ("batch", None), rules, MESH_MP)
    assert spec == P(("pod", "data"))


def test_candidate_list_order():
    rules = {"x": [("data", "model"), ("model",)], "y": ("data",)}
    # first candidate fits (trailing Nones are stripped)
    assert partition_spec((256, 32), ("x", "y"), rules, MESH) == P(("data", "model"))
    # y first consumes data -> x falls back to model-only
    assert partition_spec((32, 256), ("y", "x"), rules, MESH) == P("data", "model")


@pytest.mark.slow
def test_small_mesh_compile_with_rules():
    """Real 8-device SPMD compile of a reduced train step under the rules +
    activation hints (the dry-run path at toy scale)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import build
        from repro.models.model import input_specs
        from repro.configs.base import ShapeConfig
        from repro.sharding import rules as R
        from repro.sharding.ctx import activation_rules
        from repro.train.optimizer import OptConfig, adamw_init
        from repro.train.train_step import TrainConfig, make_train_step, init_train_state

        cfg = get_config("granite-3-2b").reduced()
        model = build(cfg)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rules = R.logical_rules(kind="train", multi_pod=False, long_context=False)
        tcfg = TrainConfig(grad_accum=2)
        step = make_train_step(model, tcfg)
        params, opt = init_train_state(model, tcfg, jax.random.PRNGKey(0))
        psh = R.param_shardings(model.param_specs, rules, mesh)
        params = jax.device_put(params, psh)
        opt = {"m": jax.device_put(opt["m"], psh), "v": jax.device_put(opt["v"], psh), "step": opt["step"]}
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32)}
        bsh = R.batch_shardings({"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}, rules, mesh)
        batch = {"tokens": jax.device_put(batch["tokens"], bsh["tokens"])}
        with activation_rules(mesh, rules):
            f = jax.jit(step, in_shardings=(psh, {"m": psh, "v": psh, "step": None}, bsh))
            p2, o2, m = f(params, opt, batch)
        assert bool(jnp.isfinite(m["loss"])), m
        print("SPMD_OK", float(m["loss"]))
    """)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "SPMD_OK" in out.stdout, (out.stdout[-1000:], out.stderr[-3000:])