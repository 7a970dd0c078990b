"""kind `serve_arch`: kind `serve` for a configuration whose architecture
`harness/model.py`, `weights.py` and `reference.py` do not know.

The same system under test (`ServingEngine(model, serving_params(params),
num_slots=..., warmup=True)`), the same closed loop, phases, clocks and
`correct` as `kinds/serve.py` - its `Load`, `Client`, `make_engine`,
`latency_metrics`, `sample_for_check`, `numbers` and `compare` are taken
from that file by import, and only `drive`, `run` and `readings` are
written again here, because they name the model mapping, the weights and
the reference. Those come from the ARCHITECTURE MODULE the configuration
names:

    "arch_module": "<name>"     ->  benchmarks/arch/<name>.py

How a further architecture plugs in: bring `benchmarks/arch/<name>.py`
with `program_model(arch, max_len=, attn_impl=)`, `make_params(arch,
max_len, seed, matrix_dtype)`, `check_layout(arch, max_len, model)`
(names and shapes against `jax.eval_shape(model.init)`), `count(arch,
max_len)` and `served_logits(arch, params, prompt, served, quant=None)`
(the plain float32 reference, and with `quant` the control); a
configuration file whose `arch` block that module reads; and per-layer
metrics whose readers take their byte and flop counts from the same
module (`ctx["arch_module"]`). Nothing here names an architecture.

Beyond what `serve` puts into the readers' context, this kind adds the
expert layers' counters over the window and the traced window
(`<window>_moe_pairs`, `_moe_load_max`, `_moe_experts_hit`,
`_moe_layers_ticks`; absent where the program has none) and, with
`--trace 1`, `tick_op_scopes`: for each instruction of the compiled
decode tick the scope path the program gave it (`block_1/moe/...`), which
the trace's op line does not print.

Traffic parameters: those of `serve`.
"""

import gc
import os
import re
import time

import jax

from benchmarks.harness import cells, reference, traffic

CONTROLS = ("int8", "fp8")     # the steps below bf16

serve = cells.load_module(
    os.path.join(cells.BENCH_DIR, "kinds", "serve.py"),
    "benchmarks_kind_serve")


def arch_module(cell):
    name = cell.config["arch_module"]
    return cells.load_module(
        os.path.join(cell.bench_dir, "arch", name + ".py"),
        "benchmarks_arch_" + name)


def tick_op_scopes(eng):
    """{instruction name: op_name} of the compiled decode tick - the
    scope paths (`.../block_1/moe/shared/up/dot_general`) of the names
    the trace's op line prints (`fusion.12`). A tick that does not
    lower or compile raises: a traced run without its scopes would
    print "nothing to read" three times and still succeed."""
    from horovod_tpu.models.transformer import slot_decode_tick
    pool = eng.pool
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (pool.params, pool._cache, pool._toks, pool._temps,
         pool._top_ps, pool._rngs, pool._live, pool._done, pool._eos))
    text = slot_decode_tick.lower(pool.dec_model,
                                  *args).compile().as_text()
    return dict(re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', text,
        re.M))


def gaps_against_reference(cell, seed, sample, quant=None):
    """As `serve.gaps_against_reference`, with the architecture
    module's weights and reference."""
    import numpy as np
    arch_mod, arch = arch_module(cell), cell.config["arch"]
    params = arch_mod.make_params(
        arch, cell.traffic["cache_positions"], seed,
        arch["compute_dtype"])
    out = []
    for r in sample:
        ref = arch_mod.served_logits(arch, params, r["prompt"],
                                     r["tokens"])
        toks = r["tokens"]
        if quant is not None:
            low = arch_mod.served_logits(arch, params, r["prompt"],
                                         r["tokens"], quant=quant)
            toks = np.asarray(low).argmax(-1)
        out.append(reference.token_gaps(ref, toks))
    return out


def drive(cell, seed, seconds, trace, env):
    """Set-up, the phases and the window; the engine is shut down and
    freed on return."""
    say, mix, arch = env.say, cell.traffic, cell.config["arch"]
    arch_mod = arch_module(cell)
    env.phase("import")
    model = arch_mod.program_model(arch, max_len=mix["cache_positions"],
                                   attn_impl=mix["attn_impl"])
    arch_mod.check_layout(arch, model.max_len, model)
    params = arch_mod.make_params(arch, model.max_len, seed,
                                  arch["compute_dtype"])
    jax.block_until_ready(params)
    env.phase("weights")
    say(f"weights: {arch_mod.count(arch, model.max_len) / 1e6:.1f} M "
        f"parameters made on the device from the seed; traffic: "
        + traffic.describe(mix))
    eng = serve.make_engine(model, params, mix)
    del params
    info = eng.warmup_info or {}
    say(f"engine up: warm-up {info.get('seconds', 0):.1f} s, "
        f"{info.get('compiles')} program shapes, prefill chunks "
        f"{info.get('prefill_sizes')}")
    env.phase("engine_warmup")

    stream = traffic.RequestStream(mix, seed, arch["vocab_size"])
    load = serve.Load(eng, mix, stream, say)
    load.warm()
    env.phase("warm_period")
    setup_s = env.setup_done()

    traced = None
    if trace:
        env.start_trace()
        traced = load.window(mix["trace_seconds"])
        env.stop_trace(traced["seconds"])
        load.warm()
    compiles_before = env.compiles.n
    win = load.window(seconds)
    compiles = env.compiles.n - compiles_before
    load.stop()
    scopes = tick_op_scopes(eng) if trace else {}
    device = env.describe_device()
    eng.shutdown(drain=False, timeout=120)
    del eng, load
    gc.collect()
    return {"win": win, "traced": traced, "device": device,
            "setup_s": setup_s, "compiles": compiles, "scopes": scopes}


MOE_COUNTERS = {"moe_pairs": "moe_pairs",
                "moe_load_max": "moe_expert_load_max",
                "moe_experts_hit": "moe_experts_hit",
                "moe_layers_ticks": "moe_layers_ticks"}


def run(cell, args, env):
    say, mix = env.say, cell.traffic
    d = drive(cell, args.seed, args.seconds, args.trace, env)
    win, traced, compiles = d["win"], d["traced"], d["compiles"]
    snap, device, setup_s = win["snap1"], d["device"], d["setup_s"]
    done = win["done"]
    lat = serve.latency_metrics(done)
    rate = win["tokens"] / win["seconds"]
    wrong = sum(len(r["tokens"]) != r["want"] for r in done)
    say(f"window: {len(done)} requests finished, {win['tokens']} "
        f"tokens emitted in {win['seconds']:.3f} s -> {rate:.1f} "
        f"tokens/s; ttft p50 {lat['ttft_p50_ms']:.1f} p95 "
        f"{lat['ttft_p95_ms']:.1f} ms; tpot p50 "
        f"{lat['tpot_p50_ms']:.2f} p95 {lat['tpot_p95_ms']:.2f} ms; "
        f"failed {win['errors']}, wrong length {wrong}; compiles "
        f"inside the window: XLA {compiles}, engine-counted "
        f"{snap['compiles']}; pool bytes {snap.get('pool_bytes')}")

    sample = serve.sample_for_check(done, mix["check_requests"],
                                    args.seed)
    t0 = time.perf_counter()
    gaps = gaps_against_reference(cell, args.seed, sample)
    say(f"reference: {len(sample)} requests "
        f"({sum(len(g) for g in gaps)} served tokens; prompts "
        f"{[len(r['prompt']) for r in sample]}) in float32 at highest "
        f"precision took {time.perf_counter() - t0:.1f} s (not counted "
        f"in setup_s)")
    rows = serve.compare(gaps, cell.limits)
    rows.append(("requests of the wrong length", wrong, 0, wrong == 0))
    rows.append(("requests that failed", win["errors"], 0,
                 win["errors"] == 0))
    rows.append(("XLA compiles inside the window", compiles, 0,
                 compiles == 0))
    rows.append(("engine-counted compiles after warm-up",
                 snap["compiles"], 0, snap["compiles"] == 0))

    def delta(a, b, key):
        return (b.get(key) or 0) - (a.get(key) or 0)

    ctx = {"num_slots": mix["num_slots"],
           "arch_module": arch_module(cell),
           "tick_op_scopes": d["scopes"]}
    for name, w in (("window", win), ("traced", traced)):
        if w is None:
            continue
        a, b = w["snap0"], w["snap1"]
        ctx[name + "_ticks"] = delta(a, b, "ticks")
        ctx[name + "_decode_tokens"] = (
            delta(a, b, "tokens_out") - delta(a, b, "prefill_first_tokens"))
        ctx[name + "_prefill_tokens"] = delta(a, b, "prefill_tokens")
        for short, counter in MOE_COUNTERS.items():
            if counter in b:
                ctx[f"{name}_{short}"] = delta(a, b, counter)
    ctx.update(lat)
    if ctx["window_ticks"]:
        ctx["lanes_live_share"] = (ctx["window_decode_tokens"]
                                   / ctx["window_ticks"]
                                   / mix["num_slots"])
    return {
        "rows": rows, "attempted": len(done) + win["errors"],
        "failed": win["errors"] + wrong, "device": device,
        "values": {"serve_tokens_per_s": rate, "setup_s": setup_s},
        "ctx": ctx,
    }


def routing_flips(cell, seed, sample):
    """How often the program's chosen experts differ from the
    reference's: over the sample's sequences (prompt ++ served), per
    layer and token, the share of tokens whose set of chosen experts
    differs, and of those the share in which an expert HELD here is
    among the difference. The program's choice is read from its own
    full forward pass in the serving precision (`intermediates`), the
    reference's from the architecture module's `reference_routing`;
    {} where either is missing."""
    import jax.numpy as jnp
    import numpy as np
    arch_mod, arch = arch_module(cell), cell.config["arch"]
    if not hasattr(arch_mod, "reference_routing"):
        return {}
    from horovod_tpu.models.transformer import serving_params
    model = arch_mod.program_model(
        arch, max_len=cell.traffic["cache_positions"], attn_impl="dot")
    params = arch_mod.make_params(
        arch, model.max_len, seed, arch["compute_dtype"])
    first, n = arch["experts_held"]
    apply = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"])[1])
    ids = np.arange(first, first + n)

    def member(chosen):                 # [layers, S, k] -> [layers, S, n]
        return (chosen[..., None] == ids).any(-2)

    tokens = differ = held = 0
    for r in sample:
        seq = np.concatenate([r["prompt"], r["tokens"]]).astype(np.int32)
        ref = arch_mod.reference_routing(arch, params, seq)
        padded = np.zeros(-(-len(seq) // 512) * 512, np.int32)
        padded[:len(seq)] = seq         # causal: the tail reaches nothing
        sown = apply(serving_params(params), jnp.asarray(padded)[None])
        got = np.stack([np.sort(np.asarray(
            sown["intermediates"][f"block_{i}"]["moe"]["chosen"]), -1)
            [:len(seq)] for i in range(arch["num_layers"])])
        diff = (got != ref).any(-1)                     # [layers, S]
        # a differing token matters here when the experts HELD here
        # among its chosen differ
        held_diff = (member(got) != member(ref)).any(-1)
        tokens += diff.size
        differ += int(diff.sum())
        held += int(held_diff.sum())
    return {"routing_tokens": tokens,
            "routing_differ_share": differ / max(tokens, 1),
            "routing_differ_held_share": held / max(tokens, 1)}


def readings(cell, seed, seconds, env, program=True):
    """For setting the limits, as `serve.readings`: the gaps of a sound
    run's served tokens and of the controls, all against the reference,
    on one seed - and how often the program's routing differs from the
    reference's (`routing_flips`)."""
    if not program:
        raise SystemExit("a served model's control is read at the "
                         "positions the program served: it needs the "
                         "program")
    d = drive(cell, seed, seconds, 0, env)
    sample = serve.sample_for_check(
        d["win"]["done"], cell.traffic["check_requests"], seed)
    prog = gaps_against_reference(cell, seed, sample)
    out = {"program": {k: v for k, (_, v) in
                       serve.numbers(prog).items()}}
    out["program"].update(routing_flips(cell, seed, sample))
    for quant in CONTROLS:
        control = gaps_against_reference(cell, seed, sample, quant=quant)
        out["control_" + quant] = {
            k: v for k, (_, v) in serve.numbers(control).items()}
    return out
