"""Kind `serve_arch` and the `solar_open2` architecture module, rehearsed
on the CPU at a tiny size (as `test_rehearsal.py` rehearses `serve`), the
module's counts against hand-worked numbers, the scope reduction against
a synthetic trace and the fp8 control against the cell's limits.
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
CELL = "solar-open2-250b.reason-closed128"
TINY = "tiny-solar.reason"
A = load_module(os.path.join(BENCH, "arch", "solar_open2.py"),
                "arch_solar_open2_for_bench_tests")


def config(name=None):
    path = (os.path.join(BENCH, "configs", "solar-open2-250b.json")
            if name is None else os.path.join(HERE, "tiny", name + ".json"))
    with open(path) as f:
        return json.load(f)


# ---- the rehearsal ---------------------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark's copy with the tiny hybrid cell added as files:
    its configuration and traffic from `tests/benchmark/tiny/`, limits
    of its own (a toy's logits are a tenth as wide as the cell's)."""
    root = tiny.make_copy(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(HERE, "tiny", "tiny-solar.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(HERE, "tiny", "tiny-reason.json"),
                os.path.join(bench, "traffic"))
    with open(os.path.join(bench, "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": {"gap_max": 0.05, "gap_mean": 0.005}}, f)
    tiny.add_cell(root, TINY, "tiny-solar", "tiny-reason", CELL)
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
    assert any("pool bytes {'kv': " in x for x in out)


def test_traced_run_reports_the_expert_counters(copy):
    """A CPU trace has no device plane, so the three readers of the
    device trace find nothing; the counters' readers report."""
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    m = line["metrics"]
    held = config("tiny-solar")["arch"]["experts_held"][1]
    k = config("tiny-solar")["arch"]["experts_per_token"]
    # at most 4 lanes x k pairs a tick and layer, over the experts held
    assert 0 < m["expert_pairs_per_expert"]["value"] <= 4 * k / held
    assert 1 <= m["expert_load_max_over_mean"]["value"] <= held
    for name in ("hybrid_tick_roofline", "moe_share_of_tick",
                 "kda_share_of_tick", "decode_tick_device_ms"):
        assert name not in m
        assert any(f"per-layer {name}: nothing to read" in x for x in out)
    # the period's family reads the ring alone on a CPU: the thread's
    # own work a tick is there, the device's idle parts are not
    assert {"lanes_live_share", "lanes_free_share",
            "sched_cpu_ms_per_tick", "sched_wait_ms_per_tick"} <= set(m)
    assert m["sched_cpu_ms_per_tick"]["value"] > 0


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

# The state of a lane that does not advance is no longer kept: a tick
# between two chunks of a prompt (chunks of 8, so that prompts of 8-96
# take several steps) corrupts the half-built state.
BROKEN_FREEZE = """
import os
os.environ["HVD_PREFILL_CHUNK_BUDGET"] = "8"
import horovod_tpu.models.transformer as T
T.overwritten_leaf = lambda path: "index" in str(path)
"""


@pytest.mark.parametrize("patch", [BROKEN_SERVE, BROKEN_FREEZE],
                         ids=["a-token-altered", "state-not-frozen"])
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
    holds (GQA + KDA, 8 of 64 experts held, 4096 rows): it fails the
    cell's limits - by one of them at least - and the reference
    itself passes both."""
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve_for_hybrid")
    from benchmarks.harness import reference
    arch = dict(config()["arch"], num_layers=2,
                layer_kinds=["gqa", "kda"], num_experts=64,
                experts_held=[0, 8], vocab_size=4096)
    params = A.make_params(arch, 256, 11, "bfloat16")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 4096, 40, dtype=np.int32)
    served = rng.integers(0, 4096, 24, dtype=np.int32)
    kw = dict(seq_block=64, row_block=32)
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
    arch = config()["arch"]
    d, m, F = 4096, 1280, 64 * 128
    expert = 3 * d * m
    assert A.expert_params(arch) == expert == 15_728_640
    router_shared = d * 320 + expert
    kda = 3 * d * F + F * d + 2 * (d * 128 + 128 * F) + d * 64
    gqa = d * (64 + 16) * 128 + d * F + F * d
    other = 3 * (kda + router_shared) + gqa + router_shared + 24576 * d
    assert A.other_matmul_params(arch) == other
    # the configuration file's table: 3.31 B parameters in all
    total = A.count(arch)
    assert total == (other + 4 * 40 * expert + 24576 * d   # + embedding
                     + 3 * (4 * 3 * F + 64 + F + 128)      # conv, A, dt, norm
                     + 4 * 320 + 9 * d)                    # bias, norms
    assert abs(total / 1e9 - 3.31) < 0.005
    assert A.state_bytes_per_lane(arch) == 3 * (64 * 128 * 128 * 4
                                                + 3 * 3 * F * 2)
    assert A.kv_bytes_per_position(arch) == 2 * 8 * 128 * 2
    # a full tick as ISSUE 26 counts it: 128 lanes at 460 positions,
    # 38 of 40 experts hit a layer -> 9.7 GB, bound by bytes
    byts = A.tick_bytes(arch, 128, 128 * 460, 4 * 38)
    assert abs(byts / 1e9 - 9.74) < 0.01
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    least, bound = A.tick_least_seconds(
        arch, peaks, lanes_decoding=128, context_sum=128 * 460,
        experts_hit=4 * 38, pairs=4 * 128)
    assert bound == "bytes" and abs(least * 1e3 - 11.9) < 0.1


def test_every_published_key_is_in_the_configuration_file():
    """The catalog row's numbers under the same keys, but the three
    reduced ones, whose published values stand beside them."""
    c = config()
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_routed_experts": 320, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8}
    assert sorted(c["reduced"]) == ["n_routed_experts",
                                    "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] < value
        else:
            assert c[key] == value, key
    assert c["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert c["gqa_layers"] == list(range(0, 48, 4))
    arch = c["arch"]
    assert (arch["num_layers"], arch["experts_held"][1],
            arch["vocab_size"]) == (c["num_hidden_layers"],
                                    c["n_routed_experts"], c["vocab_size"])
    assert "8 chips share each layer" in c["deployment"]
    # what the program and the reference fix rather than read from
    # `arch` is what the published file says
    from horovod_tpu.parallel import linear_attention
    lin = c["linear_attn_config"]
    assert lin["short_conv_kernel_size"] == A.CONV_TAPS \
        == linear_attention.CONV_TAPS
    assert (lin["num_heads"], lin["head_dim"]) == (
        arch["num_heads"], arch["head_dim"])
    assert c["norm_topk_prob"] is True and c["routed_scaling_factor"] == 1


# ---- device time by scope -----------------------------------------------------------
def test_scope_shares_on_a_synthetic_trace(capsys):
    ms = 1_000_000
    ops, modules = [], []
    for t in (0, 20 * ms):              # two ticks of 10 ms
        modules.append(["jit_slot_decode_tick(7)", t, 10 * ms])
        ops += [["fusion.1", t, 2 * ms],
                # XLA's grouped-product kernels: the compiled text gives
                # the first its own name as op_name, the second none
                ["ragged-dot-none.5", t + 2 * ms, 1 * ms],
                ["ragged-dot-none.6", t + 3 * ms, 1 * ms],
                ["fusion.2", t + 4 * ms, 2 * ms],
                ["while.3", t + 6 * ms, 3 * ms],        # covers fusion.4
                ["fusion.4", t + 6 * ms, 3 * ms]]
    modules.append(["jit_slot_prefill_chunk(9)", 12 * ms, 5 * ms])
    ops.append(["fusion.1", 12 * ms, 5 * ms])           # another program's
    ctx = {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": modules}}, "host": []},
        "tick_op_scopes": {
            "fusion.1": "jit(slot_decode_tick)/vmap(TransformerLM)/block_1/moe/mul",
            "ragged-dot-none.5": "ragged-dot-none",
            "fusion.2": "jit(slot_decode_tick)/vmap(TransformerLM)/block_2/kda/add",
            "fusion.4": "jit(slot_decode_tick)/vmap(TransformerLM)/block_0/attn/while/body/dot",
            "while.3": "jit(slot_decode_tick)/vmap(TransformerLM)/block_0/attn/while"}}
    module = "^jit_slot_decode_tick"
    with open(os.path.join(BENCH, "layer_metrics",
                           "moe_share_of_tick.json")) as f:
        moe = json.load(f)["args"]["pattern"]   # the cell's own pattern
    assert tickscopes.scope_share(ctx, module, r"/block_\d+/moe/") == 20.0
    assert tickscopes.scope_share(ctx, module, moe) == 40.0
    assert tickscopes.scope_share(ctx, module, r"/block_\d+/kda/") == 20.0
    tickscopes.say_remainder(ctx, module, [r"/block_\d+/kda/", moe])
    said = capsys.readouterr().out
    assert "other operations 30.0 %" in said and "no operation 10.0 %" in said
    assert tickscopes.scope_share({"trace": None}, module, "x") is None
    assert tickscopes.scope_share(dict(ctx, tick_op_scopes={}), module,
                                  "x") is None


def test_tick_roofline_reader_on_synthetic_records():
    roof = load_module(os.path.join(BENCH, "layer_metrics",
                                    "hybrid_tick_roofline.py"), "roof_h")
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
                     "attrs": {"lanes_decoding": 128,
                               "context_sum": 128 * 460}})
        ring.append({"name": "sched.tick_sync", "t0_ns": t0 + s + 3 * ms,
                     "t1_ns": t0 + s + 4 * ms,
                     "attrs": {"moe_experts_hit": 152, "moe_pairs": 512}})
        modules.append(["jit_slot_decode_tick(1)", s + 2 * ms, 24 * ms])
    cell = type("Cell", (), {"config": config()})()
    ctx = {"trace": {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 0, ms]], "modules": modules}}, "host": host},
        "loop_ring": ring, "arch_module": A, "cell": cell,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    # 9.74 GB / 819 GB/s = 11.9 ms of a 24 ms tick
    assert roof.read(ctx, "^jit_slot_decode_tick") == pytest.approx(
        11.89 / 24 * 100, abs=0.2)
    assert roof.read({"trace": None}, "^jit_slot_decode_tick") is None
    assert roof.read(dict(ctx, arch_module=None),
                     "^jit_slot_decode_tick") is None
