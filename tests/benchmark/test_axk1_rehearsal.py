"""Kind `serve_arch` with the `axk1` architecture module, rehearsed on
the CPU at a tiny size (as `test_longcat_rehearsal.py` rehearses
`longcat`): the tiny cell run traced, and untraced with the timed path
broken underneath, ONCE each (side by side: the sound run is the traced
one, and the broken one's line is where the end-to-end metrics are
read); the no-YaRN and int8 controls against the cell's limits at the
published widths; the module's counts against
hand-worked numbers; the two new readers against synthetic records.
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
CELL = "a.x-k1.think-closed64"
TINY = "tiny-axk1.think"
A = load_module(os.path.join(BENCH, "arch", "axk1.py"),
                "arch_axk1_for_bench_tests")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config(name=None):
    path = (os.path.join(BENCH, "configs", "a.x-k1.json")
            if name is None else os.path.join(HERE, "tiny", name + ".json"))
    with open(path) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ---- the rehearsal: two runs, each once ----------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark's copy with the tiny cell added as files: the toy
    of `tiny-axk1.json` at two layers (the dense one and one expert
    layer: the child processes compile less)."""
    root = tiny.make_copy(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    toy = config("tiny-axk1")
    toy["arch"]["num_layers"] = 2
    with open(os.path.join(bench, "configs", "tiny-axk1.json"), "w") as f:
        json.dump(toy, f)
    shutil.copy(os.path.join(HERE, "tiny", "tiny-think.json"),
                os.path.join(bench, "traffic"))
    # The toy's limits, from the toy's own readings (CPU, PR 41): a sound
    # run's widest gap over ALL 67 requests a window finishes is 0.0009,
    # and 0 on every sample of three drawn (22 runs; which requests are
    # sampled follows how many finished); a latent cache never written
    # reads 0.043-0.166 and 0.0041-0.0238 (24 runs) - under the sibling
    # toys' 0.05 / 0.005 two of six such runs came out `correct`.
    with open(os.path.join(bench, "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": {"gap_max": 0.02, "gap_mean": 0.002}}, f)
    tiny.add_cell(root, TINY, "tiny-axk1", "tiny-think", CELL)
    return root


# The latent rows are never written: a layer sees its own chunk (or
# token) and nothing of what came before.
BROKEN_ROWS = """
import horovod_tpu.parallel.latent_attention as L
def write(cached, index, rows, i, S):
    index.value = i + S
L.LatentAttention._write = staticmethod(write)
"""


@pytest.fixture(scope="module")
def runs(copy):
    """The tiny cell run traced, and untraced and broken underneath at
    a shorter window, ONCE each - two child processes side by side (each is
    mostly one thread of tracing and compiling), so the module costs one
    run's time: {name: (stdout lines, result line)}."""
    from concurrent.futures import ThreadPoolExecutor
    asked = {"traced": dict(trace=1, seconds=1.0),
             "broken": dict(patch=BROKEN_ROWS, seconds=0.5)}
    with ThreadPoolExecutor(len(asked)) as pool:
        done = dict(zip(asked, pool.map(
            lambda kw: tiny.run_cell(copy, TINY, **kw), asked.values())))
    out = {}
    for name, (rc, lines, err) in done.items():
        assert rc == 0, (name, err[-3000:])
        assert lines, f"the {name} run printed nothing"
        out[name] = (lines, json.loads(lines[-1]))
    return out


@pytest.fixture(scope="module")
def traced(runs):
    return runs["traced"]


@pytest.fixture(scope="module")
def broken(runs):
    return runs["broken"]


def test_the_run_is_correct_by_every_row_of_the_comparison(traced):
    out, line = traced
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sum("correct: " in x and "(limit " in x for x in out) >= 6
    # 2 layers x 4 lanes x 32 positions x 128 stored bf16
    assert any("pool bytes {'kv': 0, 'kv_window': 0, 'state': 0, "
               f"'latent': {2 * 4 * 32 * 128 * 2}}}" in x for x in out)


def test_traced_run_reports_the_counters_and_the_new_metric(traced):
    """`expert_chips_per_token` among the per-layer metrics: 4 chips of
    6 experts are the 4 groups and 2 groups are kept, so 1 to 2. A CPU
    trace has no device plane: the device-trace readers - the new
    `latent_layer_share_of_tick` among them - find nothing and say so."""
    out, line = traced
    assert line["correct"] is True, "\n".join(out[-20:])
    m = line["metrics"]
    assert 1.0 <= m["expert_chips_per_token"]["value"] <= 2.0
    assert m["expert_chips_per_token"]["unit"] == "chips"
    arch = config("tiny-axk1")["arch"]
    held, k = arch["experts_held"][1], arch["experts_per_token"]
    assert 0 < m["expert_pairs_per_expert"]["value"] <= 4 * k / held
    for name in ("latent_layer_share_of_tick", "latent_tick_roofline",
                 "latent_decode_roofline", "moe_share_of_tick",
                 "decode_tick_device_ms"):
        assert name not in m
        assert any(f"per-layer {name}: nothing to read" in x for x in out)
    assert {"lanes_live_share", "lanes_free_share",
            "lanes_prefilling_share", "prefill_chunks_per_tick"} <= set(m)
    assert "mla_share_of_tick" not in m and "zero_expert_share" not in m


def test_a_latent_cache_never_written_is_not_correct(broken):
    """... and, untraced as the driver's measured runs are, its line
    carries the cell's end-to-end metrics (a traced line carries the
    per-layer ones)."""
    out, line = broken
    assert line["correct"] is False
    assert line["attempted"] > 0
    assert set(line["metrics"]) >= {"serve_tokens_per_s", "setup_s"}
    failed = [x for x in out if "correct: " in x and "FAILED" in x]
    assert any("gap" in x for x in failed), "\n".join(out[-20:])


# ---- the controls: without YaRN, and one precision lower, is NOT correct -------------
@pytest.fixture(scope="module")
def published_layer():
    """The reference at the published widths and a depth, expert count
    and vocabulary the CPU holds (one expert layer, 2 of 192 experts
    held, 2048 rows), on a prompt of 112 and 16 served positions."""
    arch = dict(config()["arch"], num_layers=1, dense_layers=[],
                experts_held=[0, 2], vocab_size=2048)
    params = A.make_params(arch, 1024, 11, "bfloat16")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 2048, 112, dtype=np.int32)
    served = rng.integers(0, 2048, 16, dtype=np.int32)

    def logits(quant=None):
        return np.asarray(A.served_logits(
            arch, params, prompt, served, quant=quant, seq_block=64,
            row_block=16))

    return logits, logits()


@pytest.mark.parametrize("control", ["no_yarn", "int8"])
def test_the_controls_fail_the_cell_s_limits(published_layer, control):
    """The reference with plain RoPE and no factor in the scale - the
    control only this model can fail - and the reference in int8, each
    in the program's place: not correct by the cell's limits, by one of
    them at least; the reference itself passes both. (At the toy's
    widths the comparison cannot see YaRN at all: seeded weights of
    0.02 make a toy's attention uniform - PERF.md §6, PR 41.)"""
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve_for_axk1")
    from benchmarks.harness import reference
    logits, ref = published_layer
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    sound = [reference.token_gaps(ref, ref.argmax(-1))]
    assert all(ok for _, _, _, ok in serve.compare(sound, limits))
    low = [reference.token_gaps(ref, logits(control).argmax(-1))]
    rows = serve.compare(low, limits)
    assert not all(ok for _, _, _, ok in rows), rows


# ---- the module's counts, by hand ------------------------------------------------
def test_counts_of_the_cut_by_hand():
    """ISSUE 41's arithmetic: 3.491 B parameters, 6.98 GB in bf16; the
    pool's bytes; a tick's required bytes and the kernel's."""
    arch = config()["arch"]
    d, H = 7168, 64
    expert = 3 * d * 2048
    assert A.expert_params(arch) == expert == 44_040_192
    mla = (d * 1536 + 1536 * H * 192 + d * 576 + 512 * H * 256
           + H * 128 * d)
    assert abs(mla / 1e6 - 101.12) < 0.01
    ffn = 3 * d * 18432
    assert abs(ffn / 1e6 - 396.36) < 0.01
    router = d * 192
    assert abs(router / 1e6 - 1.38) < 0.005
    other = 5 * mla + ffn + 4 * (router + expert) + 20480 * d
    assert A.other_matmul_params(arch) == other
    norms = 5 * (2 * d + 1536 + 512) + d
    total = A.count(arch)
    assert total == other + 4 * 12 * expert + 20480 * d + norms
    assert abs(total / 1e9 - 3.491) < 0.001
    assert abs(2 * total / 1e9 - 6.98) < 0.005
    assert A.expert_layers(arch) == 4 and A.latent_row(arch) == 576
    assert A.latent_flops_per_position(arch) == 2 * H * (576 + 512)
    # the pool: 64 lanes of 8192 rows stored 640 wide in five layers
    assert 64 * 8192 * 5 * 640 * 2 == 3_355_443_200
    # a tick as ISSUE 41 counts it: 64 lanes at 2070 positions, 11.3 of
    # 12 experts hit a layer -> 7.18 GB, 8.8 ms, bound by bytes (the
    # issue's 7.27 GB counts the rows as stored, 640 wide: 0.85 GB)
    asked = dict(lanes_decoding=64, context_sum=64 * 2070)
    parts = (45 * expert * 2, other * 2, 64 * 2071 * 5 * 576 * 2)
    assert A.tick_bytes(arch, experts_hit=45, **asked) == sum(parts)
    assert [round(p / 1e9, 2) for p in parts] == [3.96, 2.46, 0.76]
    least, bound = A.tick_least_seconds(arch, PEAKS, experts_hit=45,
                                        pairs=170, **asked)
    assert bound == "bytes" and abs(least * 1e3 - 8.77) < 0.05
    assert A.tick_flops(arch, pairs=170, **asked) == (
        2 * other * 64 + 2 * expert * 170
        + 5 * 2 * H * 1088 * 64 * 2070)
    # the kernel's call is LongCat's count: the same shape
    least, bound = A.latent_decode_least_seconds(arch, PEAKS, **asked)
    assert bound == "bytes" and least == pytest.approx(
        (64 * 2071 * 576 + 64 * H * 1088) * 2 / 819e9)
    # the three numbers of the rotary rule
    inv, on_cos_sin, on_scale = A.yarn(arch)
    plain = [1e4 ** (-2 * j / 64) for j in range(32)]
    assert inv[:11] == pytest.approx(plain[:11])
    assert inv[23:] == pytest.approx([f / 32 for f in plain[23:]])
    assert on_cos_sin == 1.0 and on_scale == pytest.approx(1.81326,
                                                           abs=1e-5)
    assert A.yarn(dict(arch, rope_scaling=None)) == (
        pytest.approx(plain), 1.0, 1.0)


def test_every_published_key_is_in_the_configuration_file():
    """The catalog row's values under the same keys, but the three
    reduced ones, whose published values stand beside them; the `arch`
    block says what the published keys say."""
    c = config()
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 192, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] < value
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (5, 12, 20480)
    assert c["source"] == ("https://huggingface.co/skt/A.X-K1/blob/main/"
                           "config.json")
    assert "16 chips share each layer" in c["deployment"]
    assert set(c["assumed"]) >= {
        "gate", "group_score", "rotation", "yarn", "kv_b", "ffn", "head",
        "cache", "weights", "published_values"}
    assert c["arch_module"] == "axk1"
    arch = c["arch"]
    assert (arch["hidden_size"], arch["num_heads"], arch["q_lora_rank"],
            arch["kv_lora_rank"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"],
            arch["dense_hidden"], arch["expert_hidden"],
            arch["shared_hidden"], arch["num_experts"],
            arch["experts_per_token"], arch["n_group"],
            arch["topk_group"], arch["routed_scale"], arch["norm_eps"],
            arch["rope_theta"], arch["router"], arch["router_bias"],
            arch["norm_topk"], arch["tied_head"]) == (
        7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 2048, 192, 8, 8,
        4, 2.5, 1e-06, 10000, "sigmoid", False, True, False)
    assert arch["rope_scaling"] == published["rope_scaling"]
    assert (arch["num_layers"], arch["dense_layers"],
            arch["experts_held"], arch["vocab_size"]) == (
        5, [0], [0, 12], 20480)
    entry = [x for x in benchmark()["configs"] if x["name"] == "a.x-k1"][0]
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"]


def test_the_traffic_file_holds_the_cell_as_the_issue_names_it():
    with open(os.path.join(BENCH, "traffic", "think-closed64.json")) as f:
        mix = json.load(f)
    mix.pop("what")
    assert mix == {
        "kind": "serve_arch", "loop": "closed", "clients": 64,
        "num_slots": 64, "cache_positions": 8192, "attn_impl": "flash",
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                       "min": 128, "max": 4096},
        "output_len": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                       "min": 256, "max": 2048},
        "n_sizes": 64, "sizes_seed": 1, "check_requests": 6,
        "trace_seconds": 1.5, "poll_seconds": 0.0005}
    from benchmarks.harness import traffic
    sizes = traffic.sizes(mix)
    assert len(sizes) == 64 and max(p + n for p, n in sizes) <= 6144
    assert sum(p for p, _ in sizes) / 64 == pytest.approx(1333, abs=1)
    assert sum(n for _, n in sizes) / 64 == pytest.approx(1112, abs=1)
    b = benchmark()
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "a.x-k1", "think-closed64", 1)
    reports = {m["name"] for m in b["per_layer"] + b["end_to_end"]
               if CELL in m.get("workloads", ())}
    # what this cell must report; what else lists it is not its business
    assert reports >= {
        "serve_tokens_per_s", "decode_tick_device_ms",
        "prefill_device_ms_per_1k", "lanes_live_share",
        "lanes_prefilling_share", "lanes_free_share",
        "device_idle_share.serve", "ttft_p95_ms.saturated",
        "tpot_p95_ms.saturated", "tpot_p50_ms.saturated",
        "sched_cpu_ms_per_tick", "sched_wait_ms_per_tick",
        "prefill_chunks_per_tick", "chunk_device_ms_per_tick",
        "expert_pairs_per_expert", "expert_load_max_over_mean",
        "moe_share_of_tick", "latent_tick_roofline",
        "latent_decode_roofline", "latent_layer_share_of_tick",
        "expert_chips_per_token"}
    # the two whose readers find nothing in this cell do not list it
    assert not reports & {"mla_share_of_tick", "zero_expert_share"}
    new = {m["name"]: m for m in b["per_layer"] if m["name"] in (
        "latent_layer_share_of_tick", "expert_chips_per_token")}
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s"
               for m in new.values()) and len(new) == 2
    assert new["expert_chips_per_token"]["source"] == "program_counter"
    assert new["expert_chips_per_token"]["layer"] == "expert layers"
    assert new["latent_layer_share_of_tick"]["source"] == "device_trace"


# ---- the two new readers on synthetic records ------------------------------------------
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
    with a latent layer's operations, an expert layer's (routed and
    shared) and the dense FFN's - and the loop ring of the same steps."""
    ring, host, modules, ops = [], [], [], []
    for i in range(ticks):
        s = i * 30 * MS
        ring.append({"name": "sched.step", "t0_ns": T0 + s,
                     "t1_ns": T0 + s + (20 + i) * MS, "attrs": {}})
        host.append(["sched.step", s, (20 + i) * MS])
        ring.append({"name": "sched.tick_dispatch", "t0_ns": T0 + s + MS,
                     "t1_ns": T0 + s + 2 * MS,
                     "attrs": {"lanes_decoding": 60,
                               "context_sum": 60 * 2000}})
        ring.append({"name": "sched.tick_sync", "t0_ns": T0 + s + 3 * MS,
                     "t1_ns": T0 + s + 4 * MS,
                     "attrs": {"moe_experts_hit": 44, "moe_pairs": 120,
                               "moe_layers": 4, "tokens": 60,
                               "moe_token_chips": 60 * 4 * 5 + 60 * i}})
        t = s + 2 * MS
        modules.append(["jit_slot_decode_tick(1)", t, 16 * MS])
        ops += [["fusion.1", t, 1 * MS],                 # mla: q_b
                ["_flash_append.8", t + 1 * MS, MS // 2],
                ["latent_decode.16", t + 2 * MS, 2 * MS],
                ["fusion.2", t + 4 * MS, MS // 2],       # mla: out
                ["grouped_swiglu.5", t + 5 * MS, 3 * MS],
                ["grouped_matmul.5", t + 8 * MS, 1 * MS],
                ["fusion.7", t + 9 * MS, 1 * MS],        # router
                ["fusion.8", t + 10 * MS, 1 * MS],       # shared/up
                ["fusion.9", t + 11 * MS, 2 * MS]]       # mlp/down
    pre = "jit(slot_decode_tick)/vmap(TransformerLM)/"
    scopes = {
        "fusion.1": pre + "block_1/mla/q_b/dot_general",
        "_flash_append.8": pre + "block_1/mla/mla._decode_attention/"
        "jit(_flash_append)/pallas_call",
        "latent_decode.16": pre + "block_1/mla/mla._decode_attention/"
        "jit(_flash_decode)/latent_decode/pallas_call",
        "fusion.2": pre + "block_1/mla/out/dot_general",
        "grouped_swiglu.5": pre + "block_1/moe/jit(_grouped_call)/"
        "grouped_swiglu/pallas_call",
        "grouped_matmul.5": pre + "block_1/moe/jit(_grouped_call)/"
        "grouped_matmul/pallas_call",
        "fusion.7": pre + "block_1/moe/dot_general",
        "fusion.8": pre + "block_1/moe/shared/up/dot_general",
        "fusion.9": pre + "block_0/mlp/down/dot_general"}
    cell = type("Cell", (), {"config": config()})()
    return {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": modules}}, "host": host},
        "tick_op_scopes": scopes, "loop_ring": ring, "arch_module": A,
        "cell": cell, "peaks": PEAKS, "window_ticks": ticks,
        "num_slots": 64}


def fresh(ctx, **kw):
    return dict({k: v for k, v in ctx.items() if not k.startswith("_")},
                **kw)


def test_latent_layer_share_reads_the_block_with_one_mixer(capsys):
    ctx = synthetic()
    # 1 + 0.5 + 2 + 0.5 of 16 ms
    assert _metric("latent_layer_share_of_tick")(ctx) == pytest.approx(25.0)
    said = capsys.readouterr().out
    assert "|^ragged-dot 31.2 %" in said            # 3 + 1 + 1 of 16
    assert "/block_\\d+/moe/shared/ 6.2 %" in said
    assert "/block_\\d+/mlp/ 12.5 %" in said
    assert "other operations 0.0 %" in said and "no operation 25.0 %" in said
    # the accepted share of the expert layers takes the shared expert too,
    # and LongCat's pattern (mla_<j>) finds nothing in this block
    assert _metric("moe_share_of_tick")(ctx) == pytest.approx(37.5)
    assert _metric("mla_share_of_tick")(fresh(ctx)) == pytest.approx(0.0)
    assert _metric("latent_layer_share_of_tick")({"trace": None}) is None
    assert _metric("latent_layer_share_of_tick")(
        fresh(ctx, tick_op_scopes={})) is None
    # the accepted rooflines read this cell through the module's counts
    assert 50 < _metric("latent_tick_roofline")(fresh(ctx)) < 60
    assert 8.5 < _metric("latent_decode_roofline")(fresh(ctx)) < 9.5


def test_expert_chips_per_token_reader_on_synthetic_records():
    read = _metric("expert_chips_per_token")
    ctx = synthetic()
    # (4 x 1200 + 60 x (0 + 1 + 2 + 3)) / (4 ticks x 60 lanes) / 4 layers
    assert read(ctx) == pytest.approx(5.375)
    # the measured window is the LAST `window_ticks` records
    ring = list(ctx["loop_ring"])
    ring.insert(0, {"name": "sched.tick_sync", "t0_ns": T0 - 9 * MS,
                    "t1_ns": T0 - 8 * MS,
                    "attrs": {"moe_token_chips": 10 ** 6,
                              "moe_layers": 4}})
    assert read(fresh(ctx, loop_ring=ring)) == pytest.approx(5.375)
    # a program whose records lack the counter (the parent's, or a model
    # that holds every expert), or no window: nothing to read
    old = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                          if k != "moe_token_chips"}) for r in ring]
    assert read(fresh(ctx, loop_ring=old)) is None
    assert read(fresh(ctx, window_ticks=0)) is None
    assert read({"trace": None}) is None


def test_routing_readings_lays_each_gap_beside_its_position_s_flips():
    """`tools/routing_readings.py`'s arithmetic on two requests by hand
    (the tool's run is a chip call's: PERF.md §6, PR 41)."""
    tool = load_module(os.path.join(BENCH, "tools", "routing_readings.py"),
                       "tool_routing_readings")
    gaps = [np.array([0.0, 1.0, 0.0, 0.25]), np.array([0.5, 0.0])]
    flips = [np.array([[0, 1, 0, 0], [0, 1, 0, 1]], bool),
             np.array([[0, 0], [0, 0]], bool)]
    held = [np.array([[0, 1, 0, 0], [0, 0, 0, 0]], bool),
            np.array([[0, 0], [0, 0]], bool)]
    got = tool.attribute(gaps, flips, held, top=3)
    assert got["tokens"] == 6 and got["gap_max"] == 1.0
    assert got["routing_differ_share"] == pytest.approx(3 / 12)
    assert got["routing_differ_held_share"] == pytest.approx(1 / 12)
    assert got["positions_flipped_share"] == pytest.approx(2 / 6)
    assert got["gap_mean_flipped"] == pytest.approx(0.625)
    assert got["gap_mean_not_flipped"] == pytest.approx(0.125)
    assert got["gap_max_not_flipped"] == 0.5
    assert (got["top"], got["top_on_flipped"]) == (3, 2)
    assert got["positions_held_flipped_share"] == pytest.approx(1 / 6)
    assert got["gap_mean_held_flipped"] == 1.0
    assert got["gap_max_not_held_flipped"] == 0.5
    assert got["top_on_held_flipped"] == 1
    assert got["widest"] == [
        {"gap": 1.0, "request": 0, "served_token": 1,
         "layers_flipped": [0, 1], "layers_held_flipped": [0]},
        {"gap": 0.5, "request": 1, "served_token": 0,
         "layers_flipped": [], "layers_held_flipped": []},
        {"gap": 0.25, "request": 0, "served_token": 3,
         "layers_flipped": [1], "layers_held_flipped": []}]
