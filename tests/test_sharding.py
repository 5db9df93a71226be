"""Logical->physical sharding rules.  Uses an abstract 16x16 Mesh built
from the single CPU device via AbstractMesh (no 512-device requirement)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel.sharding import ShardingRules, batch_pspec, logical_to_pspec

MESH = AbstractMesh((16, 16), ("data", "model"))
RULES = ShardingRules()


def _ps(axes, shape, rules=RULES):
    return logical_to_pspec(tuple(axes), tuple(shape), MESH, rules)


def test_embed_table_vocab_tp_embed_fsdp():
    assert _ps(("vocab", "embed"), (151936, 896)) == P("model", "data")


def test_mlp_ffn_tp():
    assert _ps(("embed", "ffn"), (896, 4864)) == P("data", "model")


def test_moe_many_experts_ep():
    # qwen3-moe: 128 experts -> EP on model axis; embed FSDP; ffn replicated
    assert _ps(("experts", "embed", "ffn"), (128, 2048, 768)) == \
        P("model", "data", None)


def test_moe_few_experts_falls_to_ffn_tp():
    # mixtral: 8 experts %% 16 != 0 -> expert dim replicated, ffn gets TP
    assert _ps(("experts", "embed", "ffn"), (8, 6144, 16384)) == \
        P(None, "data", "model")


def test_mqa_kv_head_replicated():
    # gemma: kv=1 cannot shard; head_dim not a model-axis candidate
    assert _ps(("embed", "kv_heads", "head_dim"), (2048, 1, 256)) == \
        P("data", None, None)


def test_q_heads_tp_when_divisible():
    assert _ps(("embed", "q_heads", "head_dim"), (2560, 32, 128)) == \
        P("data", "model", None)


def test_q_heads_replicated_when_indivisible():
    # qwen2-0.5b: 14 heads %% 16 -> replicated; FSDP still on embed
    assert _ps(("embed", "q_heads", "head_dim"), (896, 14, 64)) == \
        P("data", None, None)


def test_kv_cache_heads_sharded_or_seq_sharded():
    # zamba2: kv=32 -> heads on model axis
    assert _ps(("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
               (9, 128, 32768, 32, 80)) == \
        P(None, "data", None, "model", None)
    # mixtral decode: kv=8 -> context-parallel seq sharding kicks in
    assert _ps(("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
               (56, 128, 4096, 8, 128)) == \
        P(None, "data", "model", None, None)


def test_kv_seq_shard_can_be_disabled():
    rules = ShardingRules(shard_kv_seq=False)
    assert _ps(("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
               (56, 128, 4096, 8, 128), rules) == \
        P(None, "data", None, None, None)


def test_no_fsdp_variant():
    rules = ShardingRules(fsdp=False)
    assert _ps(("embed", "ffn"), (896, 4864), rules) == P(None, "model")


def test_batch_replicated_when_indivisible():
    # long_500k: batch=1 cannot shard over data=16 -> replicated
    assert _ps(("batch", "ssm_heads", "head_dim"), (1, 32, 64)) == \
        P(None, "model", None)


def test_multipod_batch_axes():
    mesh3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    rules = ShardingRules(pod_axis="pod")
    got = logical_to_pspec(("batch", None), (256, 4096), mesh3, rules)
    assert got == P(("pod", "data"), None)


def test_batch_pspec_tree():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
             "labels": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    ps = batch_pspec(batch, MESH, RULES)
    assert ps["tokens"] == P("data", None)


def test_one_model_axis_per_tensor():
    """Never assign the same mesh axis twice in one PartitionSpec."""
    ps = _ps(("experts", "ffn", "vocab"), (128, 4864, 151936))
    axes = [a for a in ps if a is not None]
    flat = []
    for a in axes:
        flat.extend(a if isinstance(a, tuple) else [a])
    assert len(flat) == len(set(flat))
