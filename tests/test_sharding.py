"""Sharding planner unit tests on an abstract 16x16 production mesh
(no devices needed -- AbstractMesh carries only shape/axis names)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   param_shardings)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def spec_of(shardings):
    return jax.tree_util.tree_map(lambda s: tuple(s.spec), shardings,
                                  is_leaf=lambda x: hasattr(x, "spec"))


def test_megatron_column_and_row_parallel():
    tree = {"layers": {"attn": {"wq": sds(60, 7168, 7168)},
                       "mlp": {"w1": sds(60, 7168, 20480),
                               "w2": sds(60, 20480, 7168)}}}
    sp = spec_of(param_shardings(tree, MESH))
    # w1 column-parallel (ff out on model), FSDP on d_model
    assert sp["layers"]["mlp"]["w1"] == (None, "data", "model")
    # w2 row-parallel (ff in on model)
    assert sp["layers"]["mlp"]["w2"] == (None, "model", "data")
    # layer-stack axis never sharded
    for leaf in jax.tree_util.tree_leaves(sp, is_leaf=lambda x: isinstance(x, tuple)):
        assert leaf[0] is None


def test_expert_parallel_when_divisible():
    tree = {"layers": {"moe": {"w1": sds(60, 160, 5120, 1536)}}}
    sp = spec_of(param_shardings(tree, MESH))
    assert sp["layers"]["moe"]["w1"][1] == "model"  # 160 experts / 16


def test_expert_fallback_when_not_divisible():
    tree = {"layers": {"moe": {"w1": sds(24, 60, 2048, 1408)}}}
    sp = spec_of(param_shardings(tree, MESH))
    assert sp["layers"]["moe"]["w1"][1] is None     # 60 % 16 != 0
    assert "model" in sp["layers"]["moe"]["w1"]     # falls back to a feature dim


def test_small_out_rule_replicates_row_parallel_small_projection():
    tree = {"layers": {"attn": {"w_dkv": sds(60, 5120, 576)}}}
    sp0 = spec_of(param_shardings(tree, MESH))
    assert sp0["layers"]["attn"]["w_dkv"][1] == "model"   # baseline: row-parallel
    sp1 = spec_of(param_shardings(tree, MESH, small_out_threshold=1024))
    assert "model" not in sp1["layers"]["attn"]["w_dkv"]  # replicated over model


def test_embedding_vocab_sharded():
    tree = {"embed": {"tok": sds(152064, 5120)}}
    sp = spec_of(param_shardings(tree, MESH))
    assert sp["embed"]["tok"] == ("model", "data")


def test_non_divisible_dims_replicated():
    tree = {"x": sds(7, 13)}
    sp = spec_of(param_shardings(tree, MESH))
    assert sp["x"] == (None, None)


def test_batch_sharding_multipod():
    tree = {"tokens": sds(256, 4096, dtype=jnp.int32)}
    sp = spec_of(batch_shardings(tree, MESH3, 256))
    assert sp["tokens"][0] == ("pod", "data")


def test_cache_context_parallel():
    tree = {"ckv": sds(60, 128, 32768, 512)}
    sp0 = spec_of(cache_shardings(tree, MESH, 128, 32768))
    assert sp0["ckv"][3] == "model"               # baseline: latent dim
    sp1 = spec_of(cache_shardings(tree, MESH, 128, 32768,
                                  context_parallel=True))
    assert sp1["ckv"][2] == "model"               # opt: sequence dim
    assert sp1["ckv"][1] == "data"                # batch on data either way


def test_cache_batch1_context_parallel_over_data():
    tree = {"k": sds(40, 1, 8192, 4, 128)}
    sp = spec_of(cache_shardings(tree, MESH, 1, 8192))
    assert sp["k"][2] == "data"                   # seq over data when B=1


def test_recipes_follow_measured_guidance():
    from repro.launch.recipes import recommended_knobs
    # token-input training: full bundle incl chunked CE for 256k vocab
    k = recommended_knobs("nemotron-4-15b", "train_4k")
    assert k["remat_chunk"] and k["shard_acts"] and k["ce_chunk"] == 512
    # small vocab: no ce_chunk
    assert "ce_chunk" not in recommended_knobs("zamba2-2.7b", "train_4k")
    # embedding-input training: remat only (H5 regression fix)
    k = recommended_knobs("qwen2-vl-72b", "train_4k")
    assert k == dict(remat_chunk=True)
    # decode: context-parallel cache everywhere
    assert recommended_knobs("deepseek-v2-236b", "decode_32k") == dict(cp_cache=True)
