"""Kind `serve_arch` with the `longcat` architecture module, rehearsed on
the CPU at a tiny size (as `test_laguna_rehearsal.py` rehearses `laguna`),
the module's counts against hand-worked numbers, the four new readers
against synthetic records and the fp8 control against the cell's limits.
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

from benchmarks.harness.cells import load_module  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
CELL = "longcat-flash-chat.assist-closed64"
TINY = "tiny-longcat.assist"
A = load_module(os.path.join(BENCH, "arch", "longcat.py"),
                "arch_longcat_for_bench_tests")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config(name=None):
    path = (os.path.join(BENCH, "configs", "longcat-flash-chat.json")
            if name is None else os.path.join(HERE, "tiny", name + ".json"))
    with open(path) as f:
        return json.load(f)


# ---- the rehearsal ---------------------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark's copy with the tiny cell added as files."""
    root = tiny.make_copy(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(HERE, "tiny", "tiny-longcat.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(HERE, "tiny", "tiny-assist.json"),
                os.path.join(bench, "traffic"))
    with open(os.path.join(bench, "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": {"gap_max": 0.05, "gap_mean": 0.005}}, f)
    tiny.add_cell(root, TINY, "tiny-longcat", "tiny-assist", CELL)
    return root


def result_line(out):
    assert out, "the run printed nothing"
    return json.loads(out[-1])


def test_traced_run_is_correct_and_reports_the_counters(copy):
    """One traced run of the kind end to end: `correct` against the
    reference, the latent pool's bytes, and the counters' readers -
    `zero_expert_share` among them. A CPU trace has no device plane, so
    the three readers of the device trace find nothing and say so."""
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sum("correct: " in x and "(limit " in x for x in out) >= 6
    # 2 layers x 2 sublayers x 4 lanes x 128 positions x 128 stored bf16
    assert any("pool bytes {'kv': 0, 'kv_window': 0, 'state': 0, "
               f"'latent': {2 * 2 * 4 * 128 * 128 * 2}}}" in x for x in out)
    m = line["metrics"]
    arch = config("tiny-longcat")["arch"]
    held, k = arch["experts_held"][1], arch["experts_per_token"]
    assert 0 < m["expert_pairs_per_expert"]["value"] <= 4 * k / held
    # 8 of 24 router outputs are identity experts: a third, give or take
    assert 15 < m["zero_expert_share"]["value"] < 55
    assert m["zero_expert_share"]["unit"] == "%"
    for name in ("mla_share_of_tick", "latent_tick_roofline",
                 "latent_decode_roofline", "moe_share_of_tick",
                 "decode_tick_device_ms"):
        assert name not in m
        assert any(f"per-layer {name}: nothing to read" in x for x in out)
    assert {"lanes_live_share", "lanes_free_share",
            "lanes_prefilling_share"} <= set(m)


# The latent rows are never written: a sublayer sees its own chunk (or
# token) and nothing of what came before.
BROKEN_ROWS = """
import horovod_tpu.parallel.latent_attention as L
def write(cached, index, rows, i, S):
    index.value = i + S
L.LatentAttention._write = staticmethod(write)
"""


def test_a_latent_cache_never_written_is_not_correct(copy):
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0,
                                 patch=BROKEN_ROWS)
    assert rc == 0, err[-3000:]
    assert result_line(out)["correct"] is False
    failed = [x for x in out if "correct: " in x and "FAILED" in x]
    assert any("gap" in x for x in failed), "\n".join(out[-20:])


# ---- the control: one precision lower is NOT correct --------------------------
def test_fp8_control_fails_the_cell_limits():
    """The reference computed in fp8 in the program's place, at the
    published widths and a depth, expert count and vocabulary the CPU
    holds (one layer, 2 of 512 experts held beside the 256 identity
    experts, 2048 rows): it fails the cell's limits - by one of them at
    least - and the reference itself passes both."""
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve_for_longcat")
    from benchmarks.harness import reference
    arch = dict(config()["arch"], num_layers=1, experts_held=[0, 2],
                vocab_size=2048)
    params = A.make_params(arch, 1024, 11, "bfloat16")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 2048, 112, dtype=np.int32)
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
    """ISSUE 32's arithmetic: 5172.7 M parameters, 10.35 GB in bf16;
    the pool's least bytes; a tick's required bytes and the kernel's."""
    arch = config()["arch"]
    d, H = 6144, 64
    expert = 3 * d * 2048
    assert A.expert_params(arch) == expert == 37_748_736
    mla = (d * 1536 + 1536 * H * 192 + d * 576 + 512 * H * 256
           + H * 128 * d)
    assert abs(mla / 1e6 - 90.6) < 0.05
    ffn = 3 * d * 12288
    assert abs(ffn / 1e6 - 226.5) < 0.05
    router = d * 768
    layer = 2 * mla + 2 * ffn + router
    assert abs(layer / 1e6 - 638.9) < 0.1
    other = 4 * layer + 16384 * d
    assert A.other_matmul_params(arch) == other
    norms = 4 * (4 * d + 2 * (1536 + 512) + 768) + d    # + the bias
    total = A.count(arch)
    assert total == other + 4 * 16 * expert + 16384 * d + norms
    assert abs(total / 1e6 - 5172.7) < 0.2
    assert abs(2 * total / 1e9 - 10.35) < 0.005
    assert A.sublayers(arch) == 8 and A.latent_row(arch) == 576
    assert A.latent_flops_per_position(arch) == 2 * H * (576 + 512)
    # the pool by ISSUE 32's count (the rows as asked, 576 numbers; the
    # leaf stores 640: 2.68 GB - `parallel.latent_attention`)
    assert abs(64 * 4096 * 8 * 576 * 2 / 1e9 - 2.42) < 0.005
    assert abs(64 * 4096 * 8 * 640 * 2 / 1e9 - 2.68) < 0.005
    # a tick as ISSUE 32 counts it: 58 lanes at 1300 positions, 10 of 16
    # experts hit a layer -> 9.2 GB, 11.2 ms, bound by bytes
    asked = dict(lanes_decoding=58, context_sum=58 * 1300)
    byts = A.tick_bytes(arch, experts_hit=40, **asked)
    parts = (40 * expert * 2, other * 2, 58 * 1301 * 8 * 576 * 2)
    assert byts == sum(parts)
    assert [round(p / 1e9, 2) for p in parts] == [3.02, 5.31, 0.70]
    least, bound = A.tick_least_seconds(arch, PEAKS, experts_hit=40,
                                        pairs=58, **asked)
    assert bound == "bytes" and abs(least * 1e3 - 11.02) < 0.05
    flops = A.tick_flops(arch, pairs=58, **asked)
    assert flops == (2 * other * 58 + 2 * expert * 58
                     + 8 * 2 * H * 1088 * 58 * 1300)
    # the kernel's call: the rows once against 121 flops a byte
    least, bound = A.latent_decode_least_seconds(arch, PEAKS, **asked)
    positions = 58 * 1301
    assert bound == "bytes"
    assert least == pytest.approx(
        (positions * 576 + 58 * H * 1088) * 2 / 819e9)
    assert 2 * H * 1088 / (576 * 2) == pytest.approx(120.9, abs=0.1)


def test_every_published_key_is_in_the_configuration_file():
    """The catalog row's values under the same keys, but the three
    reduced ones, whose published values stand beside them; the `arch`
    block says what the published keys say."""
    c = config()
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    assert c["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] < value
        else:
            assert c[key] == value, key
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) == (
        4, 16, 16384)
    assert c["source"] == ("https://huggingface.co/meituan-longcat/"
                           "LongCat-Flash-Chat/blob/main/config.json")
    assert "32 chips share each layer" in c["deployment"]
    assert set(c["assumed"]) >= {
        "scale_factors", "norm_topk_prob", "router", "rotation",
        "softmax_scale", "ffn", "identity_experts", "block", "head"}
    arch = c["arch"]
    assert (arch["hidden_size"], arch["num_heads"], arch["q_lora_rank"],
            arch["kv_lora_rank"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"],
            arch["dense_hidden"], arch["expert_hidden"],
            arch["num_experts"], arch["zero_experts"],
            arch["experts_per_token"], arch["routed_scale"],
            arch["norm_eps"], arch["rope_theta"], arch["router"],
            arch["router_bias"], arch["norm_topk"], arch["tied_head"]) == (
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 512, 256, 12, 6.0,
        1e-05, 10000000, "softmax", True, False, False)
    assert (arch["num_layers"], arch["experts_held"],
            arch["vocab_size"]) == (4, [0, 16], 16384)
    assert A.scales(arch) == pytest.approx((2.0, 12 ** 0.5))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [x for x in json.load(f)["configs"]
                 if x["name"] == "longcat-flash-chat"][0]
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"]


def test_the_traffic_file_holds_the_cell_as_the_issue_names_it():
    with open(os.path.join(BENCH, "traffic", "assist-closed64.json")) as f:
        mix = json.load(f)
    mix.pop("what")
    assert mix == {
        "kind": "serve_arch", "loop": "closed", "clients": 64,
        "num_slots": 64, "cache_positions": 4096, "attn_impl": "flash",
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                       "min": 64, "max": 2048},
        "output_len": {"dist": "lognormal", "median": 768, "sigma": 0.5,
                       "min": 192, "max": 1536},
        "n_sizes": 64, "sizes_seed": 1, "check_requests": 6,
        "trace_seconds": 1.5, "poll_seconds": 0.0005}
    from benchmarks.harness import traffic
    sizes = traffic.sizes(mix)
    assert len(sizes) == 64 and max(p + n for p, n in sizes) <= 3584
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-chat", "assist-closed64", 1)
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
        "mla_share_of_tick", "latent_tick_roofline",
        "latent_decode_roofline", "zero_expert_share"}
    new = {m["name"]: m for m in b["per_layer"] if m["name"] in (
        "mla_share_of_tick", "latent_tick_roofline",
        "latent_decode_roofline", "zero_expert_share")}
    assert len(new) == 4
    assert all(CELL in m["workloads"]
               and m["moves"] == "serve_tokens_per_s"
               for m in new.values())
    assert new["latent_decode_roofline"]["layer"] == "kernels"
    assert new["zero_expert_share"]["source"] == "program_counter"


# ---- the new readers on synthetic records ------------------------------------------
def _metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    mod = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                      "metric_" + name)
    return lambda ctx: mod.read(ctx, **spec.get("args", {}))


MS = 1_000_000
T0 = 1_700_000_000 * 10 ** 9            # the ring's clock


def synthetic(ticks=4):
    """A trace of `ticks` ticks of 16 ms - in each the tick program
    with two latent sublayers' operations, an expert layer's and a
    dense FFN's - and the loop ring of the same steps."""
    ring, host, modules, ops = [], [], [], []
    for i in range(ticks):
        s = i * 30 * MS
        ring.append({"name": "sched.step", "t0_ns": T0 + s,
                     "t1_ns": T0 + s + (20 + i) * MS, "attrs": {}})
        host.append(["sched.step", s, (20 + i) * MS])
        ring.append({"name": "sched.tick_dispatch", "t0_ns": T0 + s + MS,
                     "t1_ns": T0 + s + 2 * MS,
                     "attrs": {"lanes_decoding": 58,
                               "context_sum": 58 * 1300}})
        ring.append({"name": "sched.tick_sync", "t0_ns": T0 + s + 3 * MS,
                     "t1_ns": T0 + s + 4 * MS,
                     "attrs": {"moe_experts_hit": 40, "moe_pairs": 58,
                               "moe_zero_pairs": 58 * 4 * 4,
                               "moe_chosen_pairs": 58 * 4 * 12}})
        t = s + 2 * MS
        modules.append(["jit_slot_decode_tick(1)", t, 16 * MS])
        ops += [["fusion.1", t, 1 * MS],                 # mla_0: q_b
                ["_flash_append.8", t + 1 * MS, MS // 2],
                ["latent_decode.16", t + 2 * MS, MS // 2],
                ["latent_decode.17", t + 3 * MS, MS // 2],   # mla_1
                ["fusion.2", t + 4 * MS, MS // 2],       # mla_1: out
                ["ragged-dot-none.5", t + 5 * MS, 4 * MS],
                ["fusion.7", t + 9 * MS, 1 * MS],        # router
                ["fusion.9", t + 10 * MS, 4 * MS]]       # mlp_0/down
    modules.append(["jit_slot_prefill_chunk(9)", 17 * MS, 5 * MS])
    pre = "jit(slot_decode_tick)/vmap(TransformerLM)/"
    kern = "._decode_attention/jit(_flash_decode)/latent_decode/pallas_call"
    scopes = {
        "fusion.1": pre + "block_0/mla_0/q_b/dot_general",
        "_flash_append.8": pre + "block_0/mla_0/mla_0._decode_attention/"
        "jit(_flash_append)/pallas_call",
        "latent_decode.16": pre + "block_0/mla_0/mla_0" + kern,
        "latent_decode.17": pre + "block_0/mla_1/mla_1" + kern,
        "fusion.2": pre + "block_0/mla_1/out/dot_general",
        "ragged-dot-none.5": "ragged-dot-none",
        "fusion.7": pre + "block_0/moe/dot_general",
        "fusion.9": pre + "block_0/mlp_0/down/dot_general"}
    cell = type("Cell", (), {"config": config()})()
    return {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": modules}}, "host": host},
        "tick_op_scopes": scopes, "loop_ring": ring, "arch_module": A,
        "cell": cell, "peaks": PEAKS, "window_ticks": ticks}


def fresh(ctx, **kw):
    return dict({k: v for k, v in ctx.items() if not k.startswith("_")},
                **kw)


def test_mla_share_gives_the_kernel_s_calls_to_their_sublayers(capsys):
    ctx = synthetic()
    # 1 + 0.5 + 0.5 + 0.5 + 0.5 of 16 ms
    assert _metric("mla_share_of_tick")(ctx) == pytest.approx(18.75)
    said = capsys.readouterr().out
    assert "/block_\\d+/moe/|^ragged-dot 31.2 %" in said
    assert "/block_\\d+/mlp_\\d/ 25.0 %" in said
    assert "other operations 0.0 %" in said and "no operation 25.0 %" in said
    assert _metric("moe_share_of_tick")(ctx) == pytest.approx(31.25)
    assert _metric("mla_share_of_tick")({"trace": None}) is None
    assert _metric("mla_share_of_tick")(
        fresh(ctx, tick_op_scopes={})) is None


def test_latent_tick_roofline_reader_on_synthetic_records(capsys):
    read = _metric("latent_tick_roofline")
    ctx = synthetic()
    # 9.03 GB / 819 GB/s = 11.02 ms of a 16 ms tick
    assert read(ctx) == pytest.approx(11.02 / 16 * 100, abs=0.2)
    assert "bound by bytes" in capsys.readouterr().out
    assert read({"trace": None}) is None
    assert read(fresh(ctx, arch_module=None)) is None
    # a program whose sync records lack the experts' counters
    old = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                          if k != "moe_experts_hit"})
           for r in ctx["loop_ring"]]
    assert read(fresh(ctx, loop_ring=old)) is None


def test_latent_decode_roofline_reader_on_synthetic_records(capsys):
    read = _metric("latent_decode_roofline")
    ctx = synthetic()
    # a call's least: 58 x 1301 rows of 1152 bytes + q and o, 0.107 ms,
    # of the 0.5 ms a call took
    least = (58 * 1301 * 576 + 58 * 64 * 1088) * 2 / 819e9
    assert read(ctx) == pytest.approx(least / 0.5e-3 * 100, rel=1e-6)
    assert 22 < read(fresh(ctx)) < 24
    assert "8 calls" in capsys.readouterr().out
    assert read({"trace": None}) is None
    # a program without such a kernel (the parent's): nothing to read
    other = fresh(ctx)
    dev = dict(other["trace"]["devices"]["/device:TPU:0"])
    dev["ops"] = [o for o in dev["ops"]
                  if not o[0].startswith("latent_decode")]
    other["trace"] = dict(other["trace"],
                          devices={"/device:TPU:0": dev})
    assert read(other) is None
    assert read(fresh(ctx, arch_module=None)) is None


def test_zero_expert_share_reader_on_synthetic_records():
    read = _metric("zero_expert_share")
    ctx = synthetic()
    assert read(ctx) == pytest.approx(100 / 3)
    # the measured window is the LAST `window_ticks` records
    ring = list(ctx["loop_ring"])
    ring.insert(0, {"name": "sched.tick_sync", "t0_ns": T0 - 9 * MS,
                    "t1_ns": T0 - 8 * MS,
                    "attrs": {"moe_zero_pairs": 0,
                              "moe_chosen_pairs": 10 ** 6}})
    assert read(fresh(ctx, loop_ring=ring)) == pytest.approx(100 / 3)
    # a program whose records lack the counters (the parent's), or no
    # window: nothing to read
    old = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                          if not k.startswith("moe_z")
                          and not k.startswith("moe_c")}) for r in ring]
    assert read(fresh(ctx, loop_ring=old)) is None
    assert read(fresh(ctx, window_ticks=0)) is None
