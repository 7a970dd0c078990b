"""The yardstick itself: the FLOP/byte functions against hand-worked
numbers, the trace reduction against synthetic and recorded traces,
the traffic generator, and the plain reference against the program's
`TransformerLM` in float32 at a tiny width - for both configurations'
families.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks.harness import flops, readers, trace, traffic  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")


def arch_of(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["arch"]


# ---- operations and bytes ---------------------------------------------
def test_gpt2_medium_counts():
    a = arch_of("gpt2-medium")
    # per layer: qkv 1024x3072 + out 1024x1024 + mlp 2 x 1024x4096
    per_layer = 1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096
    assert per_layer == 12_582_912
    assert flops.matmul_params(a) == 24 * per_layer + 50257 * 1024
    assert flops.matmul_params(a) == 353_453_056
    # causal attention forward, one 1024-token sequence: per layer and
    # head QK^T and PV are 2*1024*1024*64 each, halved: 2^27 per head
    assert flops.attention_flops_fwd(a, 1024) == 24 * 16 * 2 ** 27
    per_token = 6 * 353_453_056 + 3 * 24 * 16 * 2 ** 27 / 1024
    assert flops.train_flops_per_token(a, 1024) == per_token
    assert round(per_token / 1e9, 3) == 2.272


def test_qwen_counts():
    a = arch_of("qwen2.5-1.5b")
    per_layer = (1536 * (12 + 4) * 128 + 1536 * 1536 + 3 * 1536 * 8960)
    assert flops.matmul_params(a) == 28 * per_layer + 151936 * 1536
    # one tick: every weight in bf16 + 32 lanes x 700 positions of
    # 28 layers x 2 (k, v) x 2 kv heads x 128 x 2 bytes
    kv = 28 * 2 * 2 * 128 * 2
    assert kv == 28672
    assert flops.decode_tick_bytes(a, 32, 700) == (
        2 * flops.matmul_params(a) + 32 * 700 * kv)


def test_flash_kernel_counts():
    # GPT-2-medium's call: B4 S1024 H16 D64
    f, b = flops.flash_fwd(4, 1024, 16, 16, 64)
    assert f == 4 * 16 * 2 ** 27          # 2 matmuls, lower triangle
    assert b == 4 * 1024 * 64 * 2 * (16 * 2 + 16 * 2)
    fb, bb = flops.flash_bwd(4, 1024, 16, 16, 64)
    assert fb == 2 * f and bb == 2 * b
    # Qwen2.5-1.5B's prefill call at S2048: 12 query heads on 2 kv heads
    f, b = flops.flash_fwd(1, 2048, 12, 2, 128)
    assert f == 2 * 2 * 12 * 2048 * 2048 * 128 / 2
    assert b == 2048 * 128 * 2 * (12 * 2 + 2 * 2)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "flops" and t == f / 197e12


# ---- the trace reduction ----------------------------------------------
def test_interval_union():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.covered((1, 6), [(0, 3), (5, 8)]) == 3


def synthetic():
    # two steps on one device: compute 0-40, an async all-reduce
    # 30-60 of which 30-40 is hidden, compute 60-100; then idle 100-120
    ops = [["fusion.1", 0, 40], ["all-reduce-start.1", 30, 2],
           ["all-reduce-done.1", 55, 5], ["fusion.2", 60, 40],
           ["fusion.1", 120, 40], ["all-reduce.7", 160, 10]]
    mods = [["jit_step(1)", 0, 100], ["jit_step(1)", 120, 50],
            ["jit_other(2)", 200, 5]]
    host = [["Execute", 95, 30_000], ["tiny", 100, 1]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": host}


def test_busy_idle_and_programs():
    t = synthetic()
    # union of ops and programs: 0-100, 120-170, 200-205
    assert trace.busy_seconds(t) == pytest.approx(155e-9)
    assert trace.module_times(t, "^jit_step") == [100e-9, 50e-9]
    assert trace.top_ops(t, 2) == [["fusion.1", 80e-9], ["fusion.2", 40e-9]]
    ctx = {"trace": t, "trace_window_s": 200e-9, "traced_steps": 2}
    assert readers.idle_share(ctx) == pytest.approx(22.5)
    assert readers.busy_ms_per_unit(ctx, "traced_steps") == pytest.approx(
        77.5e-6)
    assert readers.module_mean_ms(ctx, "^jit_step") == pytest.approx(75e-6)
    gaps = trace.idle_gaps(t, min_ns=1)
    assert gaps[0] == ["Execute", 50e-9]   # 100-120 and 170-200


def test_exposed_collective():
    ov = trace.collective_overlap(synthetic())
    # windows: 30-60 (start issue to done retire) and 160-170 (sync);
    # compute covers 30-40 of the first: exposed 20 + 10
    assert ov["n"] == 2
    assert ov["window_s"] == pytest.approx(40e-9)
    assert ov["exposed_s"] == pytest.approx(30e-9)
    ctx = {"trace": synthetic(), "traced_steps": 2}
    assert readers.exposed_collective_ms_per_unit(
        ctx, "traced_steps") == pytest.approx(15e-6)


def test_collectives_by_primitive_name_and_by_opcode():
    """The four-chip trace of PR 23 prints the gradient all-reduces as
    `psum.N` (synchronous: 27 a step); where the whole instruction is
    printed its opcode decides."""
    assert trace.collective_kind("psum.213") == ""
    assert trace.collective_kind("all-gather-done.3") == "-done"
    assert trace.collective_kind("multiply_reduce_fusion.121") is None
    assert trace.collective_kind(
        "bucket.7", "(f32[4]{0}, f32[8]{0}) all-reduce-start(f32[4]{0} "
        "%a), replica_groups={}") == "-start"
    assert trace.collective_kind(
        "fusion.3", "f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") is None
    t = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["fusion.1", 0, 50], ["psum.2", 50, 30], ["fusion.4", 80, 20]]}},
        "host": []}
    ov = trace.collective_overlap(t)
    assert ov == {"window_s": 30e-9, "exposed_s": 30e-9, "n": 1}


def test_readers_find_nothing_without_a_device_plane():
    ctx = {"trace": {"devices": {}, "host": []}, "trace_window_s": 1.0,
           "traced_steps": 3}
    assert readers.idle_share(ctx) is None
    assert readers.busy_ms_per_unit(ctx, "traced_steps") is None
    assert readers.module_mean_ms(ctx, "x") is None
    assert readers.exposed_collective_ms_per_unit(ctx, "traced_steps") is None


def test_recorded_trace_of_one_train_step():
    """One step of gpt2-medium.train-1chip as the chip traced it (PR
    23): what the reduction and the readers make of it."""
    import gzip
    import types
    from benchmarks.harness.cells import load_module
    with gzip.open(os.path.join(BENCH, "data",
                                "train_1chip_one_step.json.gz")) as f:
        t = json.load(f)
    assert len(t["devices"]["/device:TPU:0"]["ops"]) == 8588
    # the program covers its ops: busy is the module's 188.384 ms (the
    # op line alone unions to 188.345)
    assert trace.busy_seconds(t) == pytest.approx(0.188384283, rel=1e-9)
    assert trace.module_times(t, "^jit_step") == [0.188384283]
    # three Mosaic calls a layer, 24 layers
    seconds, n = trace.op_seconds(t, r"^attn\._dispatch_attn")
    assert n == 72 and seconds == pytest.approx(0.109222965, rel=1e-9)
    assert trace.top_ops(t, 1)[0][0] == "multiply_reduce_fusion.121"
    assert trace.collective_overlap(t) is None      # one chip
    with open(os.path.join(BENCH, "traffic", "train-1chip.json")) as f:
        job = json.load(f)
    cell = types.SimpleNamespace(
        config={"arch": arch_of("gpt2-medium")}, traffic=job)
    ctx = {"trace": t, "trace_window_s": t["window_ns"] / 1e9,
           "traced_steps": 1, "cell": cell,
           "peaks": {"bf16_flops_per_s": 197e12,
                     "hbm_bytes_per_s": 819e9}}
    assert readers.idle_share(ctx) == pytest.approx(0.00465, abs=1e-4)
    assert readers.busy_ms_per_unit(ctx, "traced_steps") == pytest.approx(
        188.384283)
    flash = load_module(os.path.join(BENCH, "layer_metrics",
                                     "flash_roofline.py"), "flash_reader")
    with open(os.path.join(BENCH, "layer_metrics",
                           "flash_roofline.json")) as f:
        args = json.load(f)["args"]
    # by hand: forward 8.59 GFLOP / 197 TFLOP/s = 43.60 us (its 33.6 MB
    # would take 40.97 us), backward twice that; 24 x 130.81 us over
    # the 109.22 ms the 72 kernels took
    assert flash.read(ctx, **args) == pytest.approx(2.8743, abs=1e-3)
    ctx["traced_steps"] = 2     # more calls claimed than the trace holds
    with pytest.raises(SystemExit):
        flash.read(ctx, **args)


# ---- traffic ----------------------------------------------------------
def test_every_seed_gets_the_same_sizes_in_another_order():
    with open(os.path.join(BENCH, "traffic", "serve-closed32.json")) as f:
        mix = json.load(f)
    sizes = traffic.sizes(mix)
    assert len(sizes) == mix["n_sizes"] == 32
    p, o = [a for a, _ in sizes], [b for _, b in sizes]
    assert min(p) >= 32 and max(p) == 3072 and min(o) >= 16 and max(o) == 512
    assert np.median(p) == pytest.approx(512, abs=20)
    assert np.median(o) == pytest.approx(128, abs=5)
    assert all(a + b - 1 <= mix["cache_positions"] for a, b in sizes)
    got = []
    for seed in (1, 2 ** 31 + 17):
        s = traffic.RequestStream(mix, seed, 1000)
        reqs = [s.next() for _ in range(len(sizes))]
        got.append([(len(pr), n) for pr, n in reqs])
    assert sorted(got[0]) == sorted(got[1]) == sorted(sizes)
    assert got[0] != got[1]
    assert traffic.percentile([1, 2, 3, 4, 5], 50) == 3
    assert traffic.percentile(list(range(101)), 95) == 95


# ---- the plain reference against the program, float32, tiny -----------
@pytest.mark.parametrize("config", ["tiny-gpt2", "tiny-qwen2"])
def test_reference_matches_transformer_lm_in_float32(config):
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import reference, weights
    from benchmarks.harness.model import program_model

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny", config + ".json")) as f:
        arch = json.load(f)["arch"]
    max_len = min(arch["max_positions"], 128)
    model = program_model(arch, max_len=max_len, attn_impl="dot",
                          dtype="float32")
    weights.check_layout(arch, max_len, model)
    params = weights.make_params(arch, max_len, 2 ** 31 + 5, "float32")
    toks = np.random.default_rng(3).integers(
        0, arch["vocab_size"], (2, 48), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, jnp.asarray(toks))
    want = reference.logits(arch, params, jnp.asarray(toks))
    # float32 against float32: only the order of summation differs
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
    # the loss and its gradient: optax's cross entropy of the program's
    # lm_loss against the reference's own, leaf by leaf
    from horovod_tpu.models.transformer import lm_loss

    def loss_fn(p):
        with jax.default_matmul_precision("highest"):
            return lm_loss(model.apply({"params": p}, jnp.asarray(toks)),
                           jnp.asarray(toks))

    l_got, g_got = jax.value_and_grad(loss_fn)(params)
    l_want, g_want = reference.loss_and_grad(arch, params,
                                             jnp.asarray(toks))
    assert float(l_got) == pytest.approx(float(l_want), abs=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=2e-4)
    # and the served-token path: padded, layer by layer, rows sliced
    prompt, served = toks[0, :20], toks[0, 20:31]
    rows = reference.served_logits(arch, params, prompt, served,
                                   seq_block=64, row_block=16)
    np.testing.assert_allclose(np.asarray(rows),
                               np.asarray(want[0, 19:30]),
                               atol=2e-5, rtol=2e-5)
    gaps = reference.token_gaps(rows, np.asarray(rows).argmax(-1))
    assert gaps.max() == 0


# ---- the controls: one precision lower is NOT correct ------------------
def _limits(cell):
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def test_fp8_control_fails_the_train_limits():
    """The reference computed in fp8 in the program's place, at a size
    the CPU holds, against the limits of the train cells: it fails the
    number that is first order in a rounding error, and only that."""
    from benchmarks.harness import reference, weights
    from benchmarks.harness.cells import load_module
    train = load_module(os.path.join(BENCH, "kinds", "train.py"),
                        "kind_train")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "tiny", "tiny-gpt2.json")) as f:
        arch = json.load(f)["arch"]
    feed = train.BatchFeed(2 ** 31 + 9, 2, 128, arch["vocab_size"])
    batches = [feed.next() for _ in range(3)]
    runs = {}
    for quant in (None, "fp8"):
        runs[quant] = reference.train_reference(
            arch, lambda: weights.make_params(arch, 128, 5, "float32"),
            batches, 3e-4, quant)
    for cell in ("gpt2-medium.train-1chip", "gpt2-medium.train-dp4"):
        rows = train.compare(runs["fp8"], runs[None], _limits(cell))
        failed = [what for what, _, _, ok in rows if not ok]
        assert len(failed) == 1 and "|program - reference|" in failed[0]
        same = train.compare(runs[None], runs[None], _limits(cell))
        assert all(ok for _, _, _, ok in same)


def test_fp8_control_fails_the_serve_limits():
    """The served model's control need not decode: at each position of
    the same prompts and tokens, the gap of the token that the fp8
    reference puts first. Qwen2.5-1.5B's widths at a depth, MLP and
    vocabulary the CPU holds (8 layers, 2048, 32768)."""
    from benchmarks.harness import reference, weights
    from benchmarks.harness.cells import load_module
    serve = load_module(os.path.join(BENCH, "kinds", "serve.py"),
                        "kind_serve")
    arch = dict(arch_of("qwen2.5-1.5b"), num_layers=8, mlp_hidden=2048,
                vocab_size=32768)
    params = weights.make_params(arch, 256, 11, "bfloat16")
    rng = np.random.default_rng(1)
    gaps = {None: [], "fp8": []}
    for _ in range(2):
        prompt = rng.integers(0, 32768, 64, dtype=np.int32)
        served = rng.integers(0, 32768, 64, dtype=np.int32)
        ref = reference.served_logits(arch, params, prompt, served,
                                      seq_block=128, row_block=64)
        low = reference.served_logits(arch, params, prompt, served,
                                      quant="fp8", seq_block=128,
                                      row_block=64)
        gaps[None].append(reference.token_gaps(
            ref, np.asarray(ref).argmax(-1)))
        gaps["fp8"].append(reference.token_gaps(
            ref, np.asarray(low).argmax(-1)))
    limits = _limits("qwen2.5-1.5b.serve-closed32")
    assert all(ok for _, _, _, ok in serve.compare(gaps[None], limits))
    rows = serve.compare(gaps["fp8"], limits)
    assert [ok for _, _, _, ok in rows] == [False, False], rows
