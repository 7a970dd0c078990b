"""Kind `serve_arch` with the `laguna` architecture module, rehearsed on the
CPU at a tiny size (as `test_hybrid_rehearsal.py` rehearses `solar_open2`),
the module's counts against hand-worked numbers, the new readers against
synthetic records and the fp8 control against the cell's limits.
Nothing here is a measurement.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
import tiny  # noqa: E402

from benchmarks.harness import tickscopes  # noqa: E402
from benchmarks.harness.cells import load_module  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
CELL = "laguna-s-2.1.code-closed64"
TINY = "tiny-laguna.code"
A = load_module(os.path.join(BENCH, "arch", "laguna.py"),
                "arch_laguna_for_bench_tests")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config(name=None):
    path = (os.path.join(BENCH, "configs", "laguna-s-2.1.json")
            if name is None else os.path.join(HERE, "tiny", name + ".json"))
    with open(path) as f:
        return json.load(f)


# ---- the rehearsal ---------------------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark's copy with the tiny cell added as files: its
    configuration and traffic from `tests/benchmark/tiny/`, limits of
    its own (a toy's logits are a tenth as wide as the cell's)."""
    root = tiny.make_copy(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(HERE, "tiny", "tiny-laguna.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(HERE, "tiny", "tiny-code.json"),
                os.path.join(bench, "traffic"))
    with open(os.path.join(bench, "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": {"gap_max": 0.05, "gap_mean": 0.005}}, f)
    tiny.add_cell(root, TINY, "tiny-laguna", "tiny-code", CELL)
    return root


def result_line(out):
    assert out, "the run printed nothing"
    return json.loads(out[-1])


def test_kind_end_to_end(copy):
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.5)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert sum("correct: " in x and "(limit " in x for x in out) >= 6
    # two kinds of K/V leaf in one pool: 2 full layers x 128 rows and
    # 3 sliding layers x 16 rows, 4 lanes, K and V of 2 x 16 in bf16
    row = 2 * 4 * 2 * 16 * 2
    assert any(f"pool bytes {{'kv': {2 * 128 * row}, 'kv_window': "
               f"{3 * 16 * row}, 'state': 0}}" in x for x in out)


def test_traced_run_reports_the_counters(copy):
    """A CPU trace has no device plane, so the readers of the device
    trace - the three new ones among them - find nothing and say so;
    the counters' readers report."""
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    m = line["metrics"]
    arch = config("tiny-laguna")["arch"]
    held, k = arch["experts_held"][1], arch["experts_per_token"]
    assert 0 < m["expert_pairs_per_expert"]["value"] <= 4 * k / held
    assert 1 <= m["expert_load_max_over_mean"]["value"] <= held
    for name in ("mixed_tick_roofline", "attn_full_share_of_tick",
                 "attn_window_share_of_tick", "moe_share_of_tick",
                 "decode_tick_device_ms"):
        assert name not in m
        assert any(f"per-layer {name}: nothing to read" in x for x in out)
    assert {"lanes_live_share", "lanes_free_share",
            "lanes_prefilling_share"} <= set(m)


BROKEN_SERVE = """
import dataclasses
import numpy as np
from horovod_tpu.serving import engine as E
_result = E.RequestHandle.result
def result(self, timeout=None):
    res = _result(self, timeout)
    toks = np.array(res.tokens)
    toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % 384   # one token altered
    return dataclasses.replace(res, tokens=toks)
E.RequestHandle.result = result
"""

# The rings are never written: a sliding layer sees its own chunk (or
# its own token) and nothing of the window before it. (A toy this
# narrow attends almost evenly, so a slot or a position that is merely
# wrong does not show in its logits; keys that are missing do.)
BROKEN_RING = """
import horovod_tpu.parallel.tensor as T
_write = T.ParallelSelfAttention._cache_write
def write(self, ck, cv, sk, sv, index, k, v, i, S, W):
    if self.window is None:
        return _write(self, ck, cv, sk, sv, index, k, v, i, S, W)
    index.value = i + S
T.ParallelSelfAttention._cache_write = write
"""


@pytest.mark.parametrize("patch", [BROKEN_SERVE, BROKEN_RING],
                         ids=["a-token-altered", "ring-never-written"])
def test_broken_timed_path_is_not_correct(copy, patch):
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0, patch=patch)
    assert rc == 0, err[-3000:]
    assert result_line(out)["correct"] is False
    failed = [x for x in out if "correct: " in x and "FAILED" in x]
    assert any("widest gap" in x for x in failed), "\n".join(out[-20:])


# ---- the control: one precision lower is NOT correct --------------------------
def test_fp8_control_fails_the_cell_limits():
    """The reference computed in fp8 in the program's place, at the
    published widths and a depth, expert count and vocabulary the CPU
    holds (the dense leading layer and a sliding sparse one, 8 of 64
    experts held, 2048 rows), on a sequence past the window: it fails
    the cell's limits - by one of them at least - and the reference
    itself passes both."""
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve_for_laguna")
    from benchmarks.harness import reference
    arch = dict(config()["arch"], num_layers=2,
                layer_kinds=["full", "sliding"], num_experts=64,
                experts_held=[0, 8], vocab_size=2048)
    params = A.make_params(arch, 1024, 11, "bfloat16")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 2048, 560, dtype=np.int32)
    served = rng.integers(0, 2048, 16, dtype=np.int32)
    kw = dict(seq_block=64, row_block=16)
    ref = A.served_logits(arch, params, prompt, served, **kw)
    low = A.served_logits(arch, params, prompt, served, quant="fp8", **kw)
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    sound = [reference.token_gaps(ref, np.asarray(ref).argmax(-1))]
    assert all(ok for _, _, _, ok in serve.compare(sound, limits))
    control = [reference.token_gaps(ref, np.asarray(low).argmax(-1))]
    rows = serve.compare(control, limits)
    assert not all(ok for _, _, _, ok in rows), rows


# ---- the module's counts, by hand ------------------------------------------------
def test_counts_of_the_cut_by_hand():
    """ISSUE 30's arithmetic: 1717 M parameters, 3.43 GB in bf16; the
    cache of 64 lanes; a tick's required bytes."""
    arch = config()["arch"]
    d, D, V = 3072, 128, 12544
    expert = 3 * d * 1024
    assert A.expert_params(arch) == expert == 9_437_184

    def attn(H):                    # q|k|v, the per-head gate, out
        return d * (H + 16) * D + d * H + H * D * d

    assert attn(48) == 44_187_648 and attn(72) == 63_135_744
    sparse = d * 256 + expert       # router + shared expert
    layer0 = attn(48) + 3 * d * 12288
    assert abs(layer0 / 1e6 - 157.4) < 0.05
    other = (layer0 + 3 * (attn(72) + sparse) + attn(48) + sparse + V * d)
    assert A.other_matmul_params(arch) == other
    total = A.count(arch)
    assert total == other + 4 * 32 * expert + V * d + 11 * d   # + norms
    assert abs(total / 1e6 - 1717.0) < 0.05
    assert abs(2 * total / 1e9 - 3.43) < 0.005
    assert A.layers_of(arch, "full") == 2
    assert A.layers_of(arch, "sliding") == 3 and A.expert_layers(arch) == 4
    assert A.kv_bytes_per_position(arch, "full") == 2 * 2 * 8 * D * 2 == 8192
    assert A.kv_bytes_per_position(arch, "sliding") == 12288
    # the pool: 64 lanes of 12288 positions, rings of 512
    assert abs(64 * 12288 * 8192 / 1e9 - 6.44) < 0.005
    assert abs(64 * 512 * 12288 / 1e9 - 0.40) < 0.005
    # a tick as ISSUE 30 counts it: 55 lanes at 2400 positions, 29 of
    # 32 experts hit a layer -> 4.6-4.9 GB, about 6 ms, bound by bytes
    asked = dict(lanes_decoding=55, context_sum=55 * 2400,
                 context_window_sum=55 * 512)
    byts = A.tick_bytes(arch, experts_hit=4 * 29, **asked)
    parts = (4 * 29 * expert * 2, other * 2, 55 * 2401 * 8192,
             55 * 513 * 12288)
    assert byts == sum(parts)
    assert [round(p / 1e9, 2) for p in parts] == [2.19, 0.94, 1.08, 0.35]
    assert 4.5e9 < byts < 4.9e9
    least, bound = A.tick_least_seconds(
        arch, PEAKS, experts_hit=4 * 29, pairs=4 * 55 * 10 // 8, **asked)
    assert bound == "bytes" and abs(least * 1e3 - 5.6) < 0.1
    # flops: each kind's own heads over the positions it sees
    flops = A.tick_flops(arch, pairs=0, **asked)
    assert flops == (2 * other * 55 + 4 * 2 * 48 * D * 55 * 2400
                     + 4 * 3 * 72 * D * 55 * 512)


def test_every_published_key_is_in_the_configuration_file():
    """The catalog row's values under the same keys, but the three
    reduced ones, whose published values stand beside them; the `arch`
    block says what the published keys say."""
    c = config()
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0,
    }
    period = ["full_attention", "sliding_attention", "sliding_attention",
              "sliding_attention"]
    published["layer_types"] = period * 12
    published["mlp_layer_types"] = ["dense"] + ["sparse"] * 47
    published["gating_types"] = ["per_head"] * 48
    published["num_attention_heads_per_layer"] = [48, 72, 72, 72] * 12
    published["rope_parameters"] = {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    assert sorted(c["reduced"]) == ["num_experts", "num_hidden_layers",
                                    "vocab_size"]
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] < value
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (5, 32, 12544)
    assert c["source"] == ("https://huggingface.co/poolside/Laguna-S-2.1/"
                           "blob/main/config.json")
    assert "8 chips share each layer" in c["deployment"]
    assert set(c["assumed"]) >= {"router", "shared_expert", "qk_norm",
                                 "gate", "yarn"}
    arch = c["arch"]
    assert (arch["num_layers"], arch["experts_held"][1],
            arch["vocab_size"]) == (5, 32, 12544)
    assert arch["layer_kinds"] == ["full", "sliding", "sliding", "sliding",
                                   "full"]
    full, sliding = arch["attention"]["full"], arch["attention"]["sliding"]
    assert (full["num_heads"], full["window"]) == (48, None)
    assert (sliding["num_heads"], sliding["window"]) == (72, 512)
    for mine, theirs in ((full, "full_attention"),
                         (sliding, "sliding_attention")):
        pub = dict(c["rope_parameters"][theirs])
        rope = dict(mine["rope"])
        assert rope.pop("type") == pub.pop("rope_type")
        assert rope.pop("theta") == pub.pop("rope_theta")
        assert rope == pub
    assert (arch["hidden_size"], arch["num_kv_heads"], arch["head_dim"],
            arch["dense_hidden"], arch["expert_hidden"],
            arch["shared_hidden"], arch["num_experts"],
            arch["experts_per_token"], arch["routed_scale"],
            arch["norm_eps"], arch["dense_layers"], arch["attn_gate"],
            arch["router"], arch["tied_head"]) == (
        3072, 8, 128, 12288, 1024, 1024, 256, 10, 2.5, 1e-06, [0], "head",
        "softmax", False)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [x for x in json.load(f)["configs"]
                 if x["name"] == "laguna-s-2.1"][0]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])


def test_the_traffic_file_holds_the_cell_as_the_issue_names_it():
    with open(os.path.join(BENCH, "traffic", "code-closed64.json")) as f:
        mix = json.load(f)
    mix.pop("what")
    assert mix == {
        "kind": "serve_arch", "loop": "closed", "clients": 64,
        "num_slots": 64, "cache_positions": 12288, "attn_impl": "flash",
        "prompt_len": {"dist": "lognormal", "median": 1536, "sigma": 0.8,
                       "min": 256, "max": 8192},
        "output_len": {"dist": "lognormal", "median": 768, "sigma": 0.6,
                       "min": 128, "max": 2048},
        "n_sizes": 64, "sizes_seed": 1, "check_requests": 6,
        "trace_seconds": 1.5, "poll_seconds": 0.0005}
    from benchmarks.harness import traffic
    sizes = traffic.sizes(mix)
    assert len(sizes) == 64 and max(p + n for p, n in sizes) <= 12288
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", "code-closed64", 1)
    reports = {m["name"] for m in b["per_layer"] + b["end_to_end"]
               if CELL in m.get("workloads", ())}
    # what this cell must report; what else lists it is not its business
    assert reports >= {
        "serve_tokens_per_s", "decode_tick_device_ms",
        "prefill_device_ms_per_1k", "lanes_live_share",
        "lanes_prefilling_share", "lanes_free_share",
        "device_idle_share.serve", "ttft_p95_ms.saturated",
        "tpot_p95_ms.saturated", "tpot_p50_ms.saturated",
        "sched_cpu_ms_per_tick", "expert_pairs_per_expert",
        "expert_load_max_over_mean", "moe_share_of_tick",
        "attn_full_share_of_tick", "attn_window_share_of_tick",
        "mixed_tick_roofline"}


# ---- the new readers on synthetic records ------------------------------------------
def _metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    mod = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                      "metric_" + name)
    return lambda ctx: mod.read(ctx, **spec["args"])


def test_scope_shares_by_kind_on_a_synthetic_trace(capsys):
    """The decode kernel's calls go to the layer whose scope the
    compiled text names: block_0/attn/ or block_1/swa/."""
    ms = 1_000_000
    ops, modules = [], []
    for t in (0, 20 * ms):              # two ticks of 10 ms
        modules.append(["jit_slot_decode_tick(7)", t, 10 * ms])
        ops += [["fusion.1", t, 1 * ms],                # attn projections
                ["_flash_decode.3", t + 1 * ms, 2 * ms],    # full kernel
                ["_flash_decode.4", t + 3 * ms, 1 * ms],    # ring kernel
                ["fusion.2", t + 4 * ms, 1 * ms],           # swa gate
                ["ragged-dot-none.5", t + 5 * ms, 2 * ms],
                ["fusion.9", t + 7 * ms, 1 * ms]]           # dense MLP
    modules.append(["jit_slot_prefill_chunk(9)", 12 * ms, 5 * ms])
    ops.append(["_flash_decode.3", 12 * ms, 5 * ms])    # another program's
    pre = "jit(slot_decode_tick)/vmap(TransformerLM)/"
    ctx = {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": modules}}, "host": []},
        "tick_op_scopes": {
            "fusion.1": pre + "block_0/attn/qkv/dot_general",
            "_flash_decode.3": pre + "block_0/attn/attn._decode_attention/"
            "attn._prefix_attention/jit(_flash_decode)/pallas_call",
            "_flash_decode.4": pre + "block_1/swa/swa._decode_attention/"
            "jit(_flash_decode)/pallas_call",
            "fusion.2": pre + "block_1/swa/mul",
            "ragged-dot-none.5": "ragged-dot-none",
            "fusion.9": pre + "block_0/mlp/down/dot_general"}}
    assert _metric("attn_full_share_of_tick")(ctx) == 30.0
    said = capsys.readouterr().out
    assert "/block_\\d+/swa/ 20.0 %" in said
    assert "/block_\\d+/mlp/ 10.0 %" in said
    assert "other operations 0.0 %" in said and "no operation 20.0 %" in said
    assert _metric("attn_window_share_of_tick")(ctx) == 20.0
    for name in ("attn_full_share_of_tick", "attn_window_share_of_tick"):
        assert _metric(name)({"trace": None}) is None
        assert _metric(name)(dict(ctx, tick_op_scopes={})) is None
    assert tickscopes.scope_share(
        ctx, "^jit_slot_decode_tick", r"/block_\d+/moe/|^ragged-dot") == 20.0


def test_mixed_tick_roofline_reader_on_synthetic_records():
    read = _metric("mixed_tick_roofline")
    ms = 1_000_000
    t0 = 1_700_000_000 * 10 ** 9        # the ring's clock
    ring, host, modules = [], [], []
    for i in range(4):
        s = i * 30 * ms
        ring.append({"name": "sched.step", "t0_ns": t0 + s,
                     "t1_ns": t0 + s + (20 + i) * ms, "attrs": {}})
        host.append(["sched.step", s, (20 + i) * ms])
        ring.append({"name": "sched.tick_dispatch", "t0_ns": t0 + s + ms,
                     "t1_ns": t0 + s + 2 * ms,
                     "attrs": {"lanes_decoding": 55,
                               "context_sum": 55 * 2400,
                               "context_window_sum": 55 * 512}})
        ring.append({"name": "sched.tick_sync", "t0_ns": t0 + s + 3 * ms,
                     "t1_ns": t0 + s + 4 * ms,
                     "attrs": {"moe_experts_hit": 116, "moe_pairs": 275}})
        modules.append(["jit_slot_decode_tick(1)", s + 2 * ms, 14 * ms])
    cell = type("Cell", (), {"config": config()})()
    ctx = {"trace": {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 0, ms]], "modules": modules}}, "host": host},
        "loop_ring": ring, "arch_module": A, "cell": cell, "peaks": PEAKS}
    # 4.56 GB / 819 GB/s = 5.57 ms of a 14 ms tick
    assert read(ctx) == pytest.approx(5.57 / 14 * 100, abs=0.2)
    assert read({"trace": None}) is None
    assert read(dict(ctx, arch_module=None)) is None
    # a program whose tick records lack the window sum (the parent's)
    old = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                          if k != "context_window_sum"}) for r in ring]
    fresh = {k: v for k, v in ctx.items() if not k.startswith("_")}
    assert read(dict(fresh, loop_ring=old)) is None
