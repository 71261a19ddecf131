"""Sharding rule unit tests (no devices needed — specs only)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import abstract_params
from repro.train import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    # Spec construction only — a fake 16x16 abstract device grid is fine.
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


def test_param_specs_qwen(mesh):
    cfg = get_config("qwen2-72b")
    params = abstract_params(cfg)
    specs, dropped = shd.param_specs(params, mesh)
    assert specs["embed"]["w"] == P("model", None)
    g = specs["groups"]["c0"]
    assert g["attn"]["wq"] == P(None, None, "model")   # group-stacked
    assert g["attn"]["wk"] == P(None, None, None)      # GQA KV replicated
    assert g["attn"]["wo"] == P(None, "model", None)
    assert g["mlp"]["w_gate"] == P(None, None, "model")
    assert not dropped   # padded vocab + aligned dims: nothing dropped


def test_param_specs_moe_expert_parallel(mesh):
    cfg = get_config("olmoe-1b-7b")
    specs, _ = shd.param_specs(abstract_params(cfg), mesh)
    g = specs["groups"]["c0"]["mlp"]
    assert g["we_gate"] == P(None, "model", None, None)  # experts over model
    assert g["router"] == P(None, None, None)


def test_divisibility_drops_are_recorded(mesh):
    cfg = get_config("recurrentgemma-2b")   # 10 heads: wq col dim 2560 OK
    specs, dropped = shd.param_specs(abstract_params(cfg), mesh)
    # whisper: 12 heads * 64 = 768 divisible; biases etc fine — check the
    # recording machinery with a synthetic case instead:
    fake = {"attn": {"wq": jax.ShapeDtypeStruct((100, 33), jnp.bfloat16)}}
    specs2, dropped2 = shd.param_specs(fake, mesh)
    assert specs2["attn"]["wq"] == P(None, None)
    assert dropped2 and "33" in dropped2[0]


def test_batch_specs(mesh):
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    specs = shd.batch_specs(batch, mesh)
    assert specs["tokens"] == P(("data",))
    odd = {"tokens": jax.ShapeDtypeStruct((3, 7), jnp.int32)}
    assert shd.batch_specs(odd, mesh)["tokens"] == P()


def test_cache_specs_batch_vs_seq_fallback(mesh):
    # decode_32k-like: batch shards
    caches = {"groups": {"k": jax.ShapeDtypeStruct((8, 128, 32768, 8, 128),
                                                   jnp.bfloat16)}}
    specs = shd.cache_specs(caches, mesh)
    assert specs["groups"]["k"] == P(None, ("data",), None, None, None)
    # long_500k-like: batch 1 -> sequence-parallel fallback
    caches2 = {"rest": ({"k": jax.ShapeDtypeStruct((1, 524288, 8, 128),
                                                   jnp.bfloat16)},)}
    specs2 = shd.cache_specs(caches2, mesh)
    assert specs2["rest"][0]["k"] == P(None, "data", None, None)


def test_zero1_and_fsdp_upgrade(mesh):
    cfg = get_config("qwen2-72b")
    params = abstract_params(cfg)
    specs, _ = shd.param_specs(params, mesh)
    up = shd.shard_over_data(specs, params, mesh)
    # a big replicated-dim tensor picked up the data axis
    assert up["groups"]["c0"]["attn"]["wk"] != specs["groups"]["c0"]["attn"]["wk"]
    # tiny tensors (the unstacked final norm) stay replicated
    assert up["final_norm"]["scale"] == P()
