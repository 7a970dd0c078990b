"""The one pipe a choice of kernel is told through: a pool's
`kernel_plans`, the engine's warm-up log line and `metrics_snapshot()`'s
eight plan keys, over a tiny model of each kind of layer on the fixed
pool and the dense one on the paged pool, at a geometry that walks its
block tables and at one that gathers them."""

import logging

import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (
    AttnSpec, TransformerLM, kernel_plans,
)
from horovod_tpu.parallel.latent_attention import LatentSpec
from horovod_tpu.parallel.state_space import SsmSpec
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import ServingEngine

FAMILIES = ("decode_attn", "moe_product", "state_step")
MAX_LEN = 32
# one layer where one kind is all the case needs (the suite's clock)
BASE = dict(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
            max_len=MAX_LEN, dtype=jnp.float32)
EXPERTS = dict(moe_every=1, moe_impl="dropless", num_experts=4, moe_k=2,
               moe_hidden=16)

# name: (model fields, engine keywords, each family's keys in order)
CASES = {
    "dense-gqa": (dict(num_heads=4, num_kv_heads=2), {},
                  (["attn"], [], [])),
    "full+window": (dict(
        num_layers=2, pos_emb="rope", layer_kinds=("attn", "swa"),
        attn_specs=(("attn", AttnSpec(num_heads=2)),
                    ("swa", AttnSpec(num_heads=2, window=8)))), {},
        (["attn", "swa"], [], [])),
    "latent": (dict(
        pos_emb="rope", layer_kinds=("mla",),
        latent=LatentSpec(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=4,
                          v_dim=8)), {},
        (["mla"], [], [])),
    "kda-hybrid": (dict(num_layers=2, pos_emb="none",
                        layer_kinds=("kda", "attn")), {},
                   (["attn"], [], ["kda"])),
    "state-space": (dict(
        num_layers=2, pos_emb="none", layer_kinds=("ssm", "attn"),
        ssm=SsmSpec(num_heads=2, head_dim=8, state_size=8, groups=1,
                    conv_taps=4, chunk=8)), {},
        (["attn"], [], ["ssm"])),
    "dropless-experts": (EXPERTS, {},
                         (["attn"], ["tick", "prefill"], [])),
    "paged-walks": (dict(decode_prefix_block=16),
                    dict(paged=True, kv_block_size=8),
                    (["attn"], [], [])),
    "paged-gathers": (dict(decode_prefix_block=12),
                      dict(paged=True, kv_block_size=8),
                      (["attn"], [], [])),
    "paged-experts": (dict(EXPERTS, decode_prefix_block=0),
                      dict(paged=True, kv_block_size=8),
                      (["attn"], ["tick", "prefill"], [])),
}
PAGED_WHY = {
    "paged-walks": ("walks filled blocks", "16 tokens a step"),
    "paged-gathers": ("gathers the lane's span",
                      "decode_prefix_block 12 is no multiple of the "
                      "block size 8"),
    "paged-experts": ("gathers the lane's span",
                      "decode_prefix_block is off"),
}


@pytest.mark.parametrize("name", CASES)
def test_pool_log_line_and_snapshot_tell_one_record(hvd, caplog, name):
    fields, engine_kw, keys = CASES[name]
    model = TransformerLM(**{**BASE, **fields})
    params = unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        eng = ServingEngine(model, params, num_slots=2, warmup=True,
                            prefill_chunk_budget=8, **engine_kw)
    with eng:
        snap = eng.metrics_snapshot()
        plans = eng.pool.kernel_plans(8)

    assert tuple(plans) == FAMILIES
    assert [list(plans[f]) for f in FAMILIES] == list(map(list, keys))
    for family, of in plans.items():
        assert snap[f"{family}_paths"] == {
            k: p.path for k, p in of.items()}
        assert snap[f"{family}_plans"] == {
            k: p.describe() for k, p in of.items()}
    first = next(iter(plans["decode_attn"].values()))
    assert snap["decode_attn_path"] == first.path
    assert snap["decode_attn_plan"] == first.describe()

    line, = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("serving warm-up:")]
    at = line.index("; decode attention: ")
    for family, words in zip(FAMILIES, ("decode attention",
                                        "expert products", "state step")):
        assert (f"; {words}: " in line) == bool(plans[family])
        for key, plan in plans[family].items():
            found = line.index(f"{key}: {plan.describe()}", at)
            assert found > at       # families and keys in order
            at = found

    if not engine_kw:
        # the fixed pool tells what the rules say of the model
        with eng.pool._ctx():
            rules = kernel_plans(model, 2, 8)
        assert {f: {k: p.describe() for k, p in of.items()}
                for f, of in rules.items()} == {
            f: snap[f"{f}_plans"] for f in FAMILIES}
    else:
        # the paged pool says which way it attends and why, in words
        way, reason = PAGED_WHY[name]
        assert first.path == "paged" == snap["decode_attn_path"]
        assert first.why.startswith(way) and reason in first.why
        assert "HVD_PAGED_KERNEL" not in first.describe()
        assert eng.pool.kernel_mode == (
            "lax" if way.startswith("walks") else "off")
