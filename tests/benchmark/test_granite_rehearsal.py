"""The `granite_hybrid` architecture module and its cell, rehearsed on the
CPU at a tiny size (as `test_hybrid_rehearsal.py` rehearses `solar_open2`):
the tiny cell end to end untraced, traced and with the timed path broken
underneath, the controls against limits, the module's counts against
hand-worked numbers and the three readers on synthetic traces. It speaks
for its own cell, by name and with `>=`. Nothing here is a measurement.
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
CONFIG = "granite-4.0-h-micro"
CELL = CONFIG + ".chat-closed64"
TINY = "tiny-granite.chat"
A = load_module(os.path.join(BENCH, "arch", "granite_hybrid.py"),
                "arch_granite_hybrid_for_bench_tests")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config(name=None):
    path = (os.path.join(BENCH, "configs", CONFIG + ".json")
            if name is None else os.path.join(HERE, "tiny", name + ".json"))
    with open(path) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ---- the rehearsal ---------------------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark's copy with the tiny cell added as files: its
    configuration and traffic from `tests/benchmark/tiny/`, limits of
    its own (a toy's logits are a twentieth as wide as the cell's: a
    sound run reads 0 to 2e-5 and 0 to 8e-7, an altered token 4e-3)."""
    root = tiny.bare_copy(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(HERE, "tiny", "tiny-granite.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(HERE, "tiny", "tiny-chat.json"),
                os.path.join(bench, "traffic"))
    with open(os.path.join(bench, "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": {"gap_max": 2e-4, "gap_mean": 2e-5}}, f)
    tiny.add_cell(root, TINY, "tiny-granite", "tiny-chat", CELL)
    return root


def result_line(out):
    assert out, "the run printed nothing"
    return json.loads(out[-1])


def test_kind_end_to_end(copy):
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.5)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert sum("correct: " in x and "(limit " in x for x in out) >= 6
    # 3 state-space layers x 4 lanes x ([16, 128] + 3 rows of 160) f32
    assert any("'state': %d" % (3 * 4 * (16 * 128 + 3 * 160) * 4) in x
               for x in out)


def test_traced_run_leaves_the_device_readers_out(copy):
    """A CPU trace has no device plane, so the three new readers (and
    the accepted ones of the device trace) find nothing and the line
    leaves them out; the host loop's family reports."""
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    m = line["metrics"]
    for name in ("ssm_share_of_tick", "ssm_tick_roofline",
                 "ssm_step_roofline", "attn_full_share_of_tick",
                 "decode_tick_device_ms"):
        assert name not in m
        assert any(f"per-layer {name}: nothing to read" in x for x in out)
    assert {"lanes_live_share", "lanes_free_share",
            "sched_cpu_ms_per_tick", "sched_wait_ms_per_tick"} <= set(m)


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

# A tick no longer carries what a state-space layer keeps for its lane:
# the convolution's tail (every decode step convolves against three rows
# of zeros), or the state (every decode step starts from zeros and reads
# out its own position alone). Both through the tick's freeze, which
# every leaf of the cache passes. (Dropping the freeze itself, as the
# delta-rule rehearsal does, is no sure break here: whether a tick
# falls between two chunks of a sampled request is the scheduler's
# timing.)
BROKEN_LEAF = """
import jax
import jax.numpy as jnp
import horovod_tpu.models.transformer as T
_freeze = T._freeze_cache_indices
def freeze(new, old, advance, kept=()):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf)
        if "['ssm']['%s']" in jax.tree_util.keystr(path) else leaf,
        _freeze(new, old, advance, kept))
T._freeze_cache_indices = freeze
"""
BROKEN_TAIL, BROKEN_STATE = (BROKEN_LEAF % leaf
                             for leaf in ("conv_tail", "state"))


@pytest.mark.parametrize("patch", [BROKEN_SERVE, BROKEN_TAIL, BROKEN_STATE],
                         ids=["a-token-altered", "tail-not-carried",
                              "state-not-carried"])
def test_broken_timed_path_is_not_correct(copy, patch):
    rc, out, err = tiny.run_cell(copy, TINY, seconds=1.0, patch=patch)
    assert rc == 0, err[-3000:]
    assert result_line(out)["correct"] is False
    failed = [x for x in out if "correct: " in x and "FAILED" in x]
    assert any("widest gap" in x for x in failed), "\n".join(out[-20:])


TOOL = """
import sys
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_platforms", "cpu")
from benchmarks.harness.cells import load_module
load_module({root!r} + "/benchmarks/tools/control_readings.py", "tool").main(
    {argv!r}, accept_platform=("cpu",), peaks_kind="TPU v5 lite")
"""


def test_control_readings_names_its_controls(copy, tmp_path):
    """`tools/control_readings.py` on the toy: one sound run, then the
    kind's own comparison of the program's tokens and of each control
    named on the command line (the state's too, which the kind does
    not read by default), each beside the cell's limits."""
    import subprocess
    out_file = str(tmp_path / "lines.jsonl")
    argv = ["--workload", TINY, "--seeds", "7", "--seconds", "1",
            "--controls", "fp8,state_bf16,state_lost", "--out", out_file]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="0",
               JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, "-c", TOOL.format(root=copy, argv=argv)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    with open(out_file) as f:
        line = json.loads(f.read().strip().splitlines()[-1])
    assert line["seed"] == 7
    assert {"program", "control_fp8", "control_state_bf16",
            "control_state_lost"} <= set(line)
    assert {"gap_max", "gap_mean"} <= set(line["control_state_bf16"])
    assert line["within_limits"]["program"] is True
    assert line["within_limits"]["control_fp8"] is False


# ---- the controls: one precision lower is NOT correct ---------------------------
@pytest.fixture(scope="module")
def control_case():
    """The reference at the published widths and a depth and vocabulary
    the CPU holds (two periods: layers 3-22, two attention layers among
    them; 4096 rows), on a sequence of 64. A control's gap grows with
    the depth (int8's mean is 7.9e-4 to 1.1e-3 at the cell's 40 layers,
    my chip run, PR 39), so a few layers would not reach the cell's
    limits."""
    full = config()["arch"]
    arch = dict(full, num_layers=20, layer_kinds=full["layer_kinds"][3:23],
                vocab_size=4096)
    params = A.make_params(arch, 256, 11, "bfloat16")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 4096, 40, dtype=np.int32)
    served = rng.integers(0, 4096, 24, dtype=np.int32)
    run = lambda quant=None: np.asarray(A.served_logits(  # noqa: E731
        arch, params, prompt, served, quant=quant, seq_block=64,
        row_block=32))
    return run, run()


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_a_control_fails_the_cell_limits(control_case, quant):
    """The reference computed one precision lower in the program's
    place fails the cell's limits - by one of them at least - and the
    reference itself passes both."""
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve_for_granite")
    from benchmarks.harness import reference
    run, ref = control_case
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    sound = [reference.token_gaps(ref, ref.argmax(-1))]
    assert all(ok for _, _, _, ok in serve.compare(sound, limits))
    control = [reference.token_gaps(ref, run(quant).argmax(-1))]
    rows = serve.compare(control, limits)
    assert not all(ok for _, _, _, ok in rows), rows


@pytest.fixture(scope="module")
def state_case():
    """A long request (1024 + 256) through the last eight layers at the
    published widths, 4096 rows: a state's control shows where the
    state holds a long context (a bf16 state drops an update under 2^-9
    of what it holds; `state_lost` starts every 128th position from
    zeros, which a sequence of 64 never reaches)."""
    full = config()["arch"]
    arch = dict(full, num_layers=8, layer_kinds=full["layer_kinds"][32:],
                vocab_size=4096)
    params = A.make_params(arch, 2048, 11, "bfloat16")
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 4096, 1024, dtype=np.int32)
    served = rng.integers(0, 4096, 256, dtype=np.int32)
    run = lambda quant=None: np.asarray(A.served_logits(  # noqa: E731
        arch, params, prompt, served, quant=quant, seq_block=256,
        row_block=256))
    return run, run()


@pytest.mark.parametrize("quant", ["state_bf16", "state_lost"])
def test_a_state_control_fails_the_cell_limits(state_case, quant):
    """The reference with its state rounded to bf16, or lost at a
    chunk's start, in the program's place is NOT correct by the cell's
    limits (eight layers of the forty already read 1.8e-4 and 0.024
    against the limit of 5e-5) - the comparison sees the state, not the
    projections alone."""
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve_for_granite_state")
    from benchmarks.harness import reference
    run, ref = state_case
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    control = [reference.token_gaps(ref, run(quant).argmax(-1))]
    rows = serve.compare(control, limits)
    assert not all(ok for _, _, _, ok in rows), rows


def test_a_state_control_leaves_the_projections_alone(control_case):
    """`state_bf16` moves the logits, and over 64 positions less than
    fp8 does; `state_lost` never reaches its 128th position there."""
    run, ref = control_case
    moved = np.abs(run("state_bf16") - ref).max()
    assert 0 < moved < np.abs(run("fp8") - ref).max()
    np.testing.assert_array_equal(run("state_lost"), ref)


# ---- the module's counts, by hand ------------------------------------------------
def test_counts_of_the_whole_model_by_hand():
    arch = config()["arch"]
    d, m, inner, conv = 2048, 8192, 64 * 64, 64 * 64 + 2 * 128
    mlp = 3 * d * m
    mamba = d * (inner + conv + 64) + inner * d + mlp
    attn = d * (32 + 2 * 8) * 64 + 32 * 64 * d + mlp
    assert (mamba, attn) == (76_152_832, 60_817_408)
    matrices = 36 * mamba + 4 * attn + 100352 * d
    assert A.matmul_params(arch) == matrices
    total = A.count(arch)
    assert total == (matrices
                     + 36 * (4 * conv + conv + 3 * 64 + inner)  # taps, bias,
                     + 81 * d)                      # A, dt, D, norm; norms
    assert abs(total / 1e9 - 3.19) < 0.005
    assert A.state_bytes_per_lane(arch) == 36 * (64 * 64 * 128
                                                 + 3 * conv) * 4
    assert A.kv_bytes_per_position(arch) == 4 * 2 * 8 * 64 * 2
    # the pool of the cell: 64 lanes of state and tails, 2048 positions
    pool = 64 * (A.state_bytes_per_lane(arch)
                 + 2048 * A.kv_bytes_per_position(arch))
    assert abs(pool / 1e9 - 6.03) < 0.01
    # a full tick as ISSUE 39 counts it: 64 lanes at 400 positions ->
    # 6.38 GB of weights + 9.90 GB of state and tails + K/V, by bytes
    byts = A.tick_bytes(arch, 64, 64 * 400)
    assert abs(byts / 1e9 - (6.38 + 9.90 + 0.21)) < 0.01
    least, bound = A.tick_least_seconds(arch, PEAKS, lanes_decoding=64,
                                        context_sum=64 * 400)
    assert bound == "bytes" and abs(least * 1e3 - 20.1) < 0.1
    # one layer's step over 64 lanes: 2 x 2.10 MB a lane and the rows
    assert A.ssm_step_bytes(arch, 64) == 64 * (2 * 64 * 64 * 128
                                               + 3 * inner + 2 * 128) * 4
    least, bound = A.ssm_step_least_seconds(arch, PEAKS, lanes_decoding=64)
    assert bound == "bytes" and abs(least * 1e6 - 331.6) < 0.5


def test_every_published_key_is_in_the_configuration_file():
    """The catalog row's numbers under the same keys, nothing reduced;
    the `arch` block is read off them."""
    c = config()
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["reduced"] == [] and c["published"] == {}
    assert c["layer_types"] == [
        "attention" if i % 10 == 5 else "mamba" for i in range(40)]
    assert c["source"] == ("https://huggingface.co/ibm-granite/"
                           "granite-4.0-h-micro/blob/main/config.json")
    assert "One chip serves the whole model" in c["deployment"]
    assert {"cache_dtype", "in_proj_order", "gated_norm", "dt", "weights",
            "published_values"} <= set(c["assumed"])
    arch = c["arch"]
    assert arch["layer_kinds"] == c["layer_types"]
    assert (arch["num_layers"], arch["vocab_size"], arch["hidden_size"],
            arch["mlp_hidden"]) == (40, 100352, 2048, 8192)
    assert (arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"],
            arch["ssm_groups"], arch["ssm_conv"], arch["ssm_chunk"]) == (
        64, 64, 128, 1, 4, 256)
    assert arch["ssm_heads"] * arch["ssm_head_dim"] == (
        c["mamba_expand"] * c["hidden_size"])
    assert (arch["attn_scale"], arch["embed_scale"], arch["residual_scale"],
            arch["logits_divisor"]) == (1 / 64, 12, 0.22, 8)
    assert arch["head_dim"] * arch["num_heads"] == arch["hidden_size"]


def test_the_cell_is_entered_as_the_issue_names_it():
    """By name and with `>=`: the traffic file's parameters, the
    entries of BENCHMARK.json that speak of this cell."""
    with open(os.path.join(BENCH, "traffic", "chat-closed64.json")) as f:
        mix = json.load(f)
    want = {
        "kind": "serve_arch", "loop": "closed", "clients": 64,
        "num_slots": 64, "cache_positions": 2048, "attn_impl": "flash",
        "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                       "min": 32, "max": 1024},
        "output_len": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                       "min": 32, "max": 768},
        "n_sizes": 64, "sizes_seed": 1, "check_requests": 6,
        "trace_seconds": 1.5, "poll_seconds": 0.0005}
    assert {k: mix[k] for k in want} == want
    b = benchmark()
    entry = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "chat-closed64", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == [] and conf["file"].endswith(CONFIG + ".json")
    reports = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if CELL in m.get("workloads", ())}
    assert reports >= {
        "serve_tokens_per_s", "ssm_share_of_tick", "ssm_tick_roofline",
        "ssm_step_roofline", "attn_full_share_of_tick",
        "decode_tick_device_ms", "device_idle_share.serve",
        "sched_cpu_ms_per_tick", "idle_ms_per_tick.tick"}
    for name in ("ssm_share_of_tick", "ssm_tick_roofline",
                 "ssm_step_roofline"):
        m = {m["name"]: m for m in b["per_layer"]}[name]
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["unit"]) == ("serve_tokens_per_s", "%")


# ---- the three readers, by hand ------------------------------------------------------
MS = 1_000_000


def test_scope_share_on_a_synthetic_trace(capsys):
    """The cell's own pattern gives the state-space scopes their
    fusions AND the Mosaic call (its op_name is the layer's scope)."""
    ops, modules = [], []
    for t in (0, 20 * MS):              # two ticks of 10 ms
        modules.append(["jit_slot_decode_tick(7)", t, 10 * MS])
        ops += [["fusion.1", t, 2 * MS], ["ssm_step.3", t + 2 * MS, 3 * MS],
                ["fusion.2", t + 5 * MS, 2 * MS],
                ["fusion.4", t + 7 * MS, 1 * MS]]
    modules.append(["jit_slot_prefill_chunk(9)", 12 * MS, 5 * MS])
    ops.append(["ssm_step.3", 12 * MS, 5 * MS])         # another program's
    scope = "jit(slot_decode_tick)/vmap(TransformerLM)/block_%s"
    ctx = {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": modules}}, "host": []},
        "tick_op_scopes": {
            "fusion.1": scope % "1/ssm/in_proj/dot_general",
            "ssm_step.3": scope % "1/ssm/ssm_step/pallas_call",
            "fusion.2": scope % "5/attn/while/body/dot",
            "fusion.4": scope % "1/mlp/up/dot_general"}}
    with open(os.path.join(BENCH, "layer_metrics",
                           "ssm_share_of_tick.json")) as f:
        args = json.load(f)["args"]
    reader = load_module(os.path.join(BENCH, "layer_metrics",
                                      "ssm_share_of_tick.py"), "ssm_share")
    assert reader.read(ctx, **args) == 50.0
    said = capsys.readouterr().out
    assert "/attn/ 20.0 %" in said and "/mlp/ 10.0 %" in said
    assert "no operation 20.0 %" in said
    assert reader.read({"trace": None}, **args) is None
    assert tickscopes.scope_share(dict(ctx, tick_op_scopes={}),
                                  args["module"], args["pattern"]) is None


def synthetic_ticks(kernel=True):
    """Four ticks of 25 ms at 64 lanes and 400 positions a lane, a
    state step of 400 us a layer."""
    t0 = 1_700_000_000 * 10 ** 9        # the ring's clock
    ring, host, modules, ops = [], [], [], [["fusion.1", 0, MS]]
    for i in range(4):
        s = i * 30 * MS
        ring.append({"name": "sched.step", "t0_ns": t0 + s,
                     "t1_ns": t0 + s + (20 + i) * MS, "attrs": {}})
        host.append(["sched.step", s, (20 + i) * MS])
        ring.append({"name": "sched.tick_dispatch", "t0_ns": t0 + s + MS,
                     "t1_ns": t0 + s + 2 * MS,
                     "attrs": {"lanes_decoding": 64,
                               "context_sum": 64 * 400}})
        modules.append(["jit_slot_decode_tick(1)", s + 2 * MS, 25 * MS])
        if kernel:
            ops += [[f"ssm_step.{j}", s + 3 * MS + j * MS // 2, 400_000]
                    for j in range(36)]
            # a chunk of one token between the ticks: the call over ONE
            # lane, which the reader must leave out
            ops += [[f"ssm_step.{j}", s + 28 * MS + j * 10_000, 8_000]
                    for j in range(36)]
    cell = type("Cell", (), {"config": config()})()
    return {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": modules}}, "host": host},
        "loop_ring": ring, "arch_module": A, "cell": cell, "peaks": PEAKS}


def test_tick_roofline_reader_on_synthetic_records():
    roof = load_module(os.path.join(BENCH, "layer_metrics",
                                    "ssm_tick_roofline.py"), "roof_ssm")
    ctx = synthetic_ticks()
    # 16.49 GB / 819 GB/s = 20.1 ms of a 25 ms tick
    assert roof.read(ctx, "^jit_slot_decode_tick") == pytest.approx(
        20.13 / 25 * 100, abs=0.3)
    assert roof.read({"trace": None}, "^jit_slot_decode_tick") is None
    assert roof.read(dict(ctx, arch_module=None),
                     "^jit_slot_decode_tick") is None


def test_step_roofline_reader_on_synthetic_records():
    roof = load_module(os.path.join(BENCH, "layer_metrics",
                                    "ssm_step_roofline.py"), "roof_step")
    with open(os.path.join(BENCH, "layer_metrics",
                           "ssm_step_roofline.json")) as f:
        args = json.load(f)["args"]
    # 271.6 MB / 819 GB/s = 331.6 us of a 400 us call; the 8 us calls
    # of the one-token chunks lie outside the tick programs' runs
    ctx = synthetic_ticks()
    assert len(roof.calls_inside(ctx["trace"], **args)) == 4 * 36
    assert roof.read(ctx, **args) == pytest.approx(331.6 / 400 * 100,
                                                   abs=0.2)
    # a program without the kernel (the parent; the XLA form): nothing
    assert roof.read(synthetic_ticks(kernel=False), **args) is None
    assert roof.read({"trace": None}, **args) is None
    # an architecture module without the counts: nothing, and no raise
    assert roof.read(dict(synthetic_ticks(), arch_module=object()),
                     **args) is None
