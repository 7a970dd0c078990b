"""kind `train`: the README's five lines, timed.

`hvd.init()`, `hvd.DistributedOptimizer(optax.adamw(lr))`,
`hvd.make_train_step(loss_fn, tx)` with `lm_loss`, as `chip_smoke.py`'s
`phase_dp` builds route 2. One object - the compiled step with its
state - is built in set-up, driven from the seed through its first
steps (whose losses, first gradient and parameter change `correct`
holds against the plain reference), warmed, and handed to the window.

Traffic parameters (`traffic/<mix>.json`): `per_chip_batch`, `seq_len`,
`learning_rate`, `attn_impl`, `loss_fetch_every`, `check_steps`,
`warm_steps`, `trace_steps`.
"""

import gc
import math
import time

import jax
import numpy as np

from benchmarks.harness import reference, weights
from benchmarks.harness.model import program_model


CONTROLS = ("int8", "fp8")     # the steps below bf16


class BatchFeed:
    """A seeded host stream of [global_batch, seq_len] token batches;
    every row of every batch differs."""

    def __init__(self, seed, global_batch, seq_len, vocab):
        self.rng = np.random.default_rng([int(seed), 0x7261696E])
        self.shape, self.vocab = (global_batch, seq_len), vocab

    def next(self):
        return self.rng.integers(0, self.vocab, self.shape,
                                 dtype=np.int32)


def _find_adam_mu(opt_state):
    """The first-moment tree of the adam state inside `opt_state`."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return opt_state.mu
    if isinstance(opt_state, dict):
        opt_state = tuple(opt_state.values())
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            mu = _find_adam_mu(s)
            if mu is not None:
                return mu
    for name in ("inner_state", "inner_opt_state", "state"):
        if hasattr(opt_state, name):
            return _find_adam_mu(getattr(opt_state, name))
    return None


def build(cell, seed, say):
    """The one object the window drives and what feeds it: (step,
    state, feed, place). `state` is the list [params, opt_state];
    `place` puts a host batch onto the mesh."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import lm_loss
    from horovod_tpu.parallel.mesh import shard_batch

    arch, job = cell.config["arch"], cell.traffic
    hvd.init()
    say(f"hvd.init(): size {hvd.size()}")
    if hvd.size() != cell.chips:
        raise SystemExit(f"hvd.size() is {hvd.size()}, the cell asks "
                         f"for {cell.chips} chip(s)")
    model = program_model(arch, attn_impl=job["attn_impl"])
    t0 = time.perf_counter()
    params = weights.make_params(arch, model.max_len, seed, "float32")
    params = hvd.broadcast_global_variables(params, 0)
    jax.block_until_ready(params)
    say(f"weights: {weights.count(arch, model.max_len) / 1e6:.1f} M "
        f"parameters made on the device from the seed in "
        f"{time.perf_counter() - t0:.2f} s")

    def loss_fn(p, batch):
        return lm_loss(model.apply({"params": p}, batch), batch)

    tx = hvd.DistributedOptimizer(optax.adamw(job["learning_rate"]))
    opt_state = tx.init(params)
    step = hvd.make_train_step(loss_fn, tx)
    feed = BatchFeed(seed, job["per_chip_batch"] * cell.chips,
                     job["seq_len"], arch["vocab_size"])
    mesh = hvd.mesh()

    def place(toks):
        return shard_batch(mesh, toks)

    return step, [params, opt_state], feed, place


def first_steps(step, state, feed, place, n, seed, arch, max_len, env):
    """Drive the step through its first `n` steps on the seed's data.
    Returns what `correct` compares: the losses, the per-leaf norms of
    the first gradient (from the adam state after one step: mu = (1 -
    b1) g) and of the parameters' change after the n steps - and the
    batches, for the reference to follow."""
    say = env.say
    batches, losses, grad_norms = [], [], None
    b1 = reference.ADAMW["b1"]
    t0 = time.perf_counter()
    for i in range(n):
        toks = feed.next()
        batches.append(toks)
        state[0], state[1], loss = step(state[0], state[1], place(toks))
        if i == 0:
            say(f"first call of the step (trace, lower, compile or "
                f"cache load, dispatch) returned after "
                f"{time.perf_counter() - t0:.2f} s")
            mu = _find_adam_mu(state[1])
            if mu is None:
                raise SystemExit("no adam state found in the "
                                 "optimizer's state")
            grad_norms = reference.leaf_norms(mu)
            # the first gradient itself, kept on the host until the
            # reference has its own (the copy is the comparison's
            # cost, not set-up)
            t1 = time.perf_counter()
            first_grad = jax.tree.map(
                lambda m: m / (1 - b1), jax.device_get(mu))
            env.exclude(time.perf_counter() - t1)
        losses.append(loss)
    losses = [float(x) for x in losses]
    say(f"first {n} steps done after {time.perf_counter() - t0:.2f} s")
    start = weights.make_params(arch, max_len, seed, "float32")
    change = np.asarray(reference.leaf_diff_norms(state[0], start))
    del start
    return {"losses": losses,
            "grad_norms": np.asarray(grad_norms) / (1 - b1),
            "first_grad": first_grad,
            "change_norms": change, "batches": batches}


def window(step, state, feed, place, seconds, fetch_every,
           max_steps=None):
    """Steps for `seconds` (or `max_steps`): a fresh batch every step,
    the loss fetched every `fetch_every` steps as a training loop logs
    it; that fetch is the fence the window ends on. Returns (steps,
    seconds, losses)."""
    jax.block_until_ready(state[0])
    steps, fetched = 0, []
    t0 = time.perf_counter()
    while True:
        for _ in range(fetch_every):
            state[0], state[1], loss = step(state[0], state[1],
                                            place(feed.next()))
            steps += 1
        fetched.append(float(loss))
        now = time.perf_counter()
        if now - t0 >= seconds or (max_steps and steps >= max_steps):
            return steps, now - t0, fetched


def worst_leaf_gap(got, want):
    """max over leaves of |got - want| / max(want, median(want)): the
    gap between two norms, against the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.maximum(want, np.median(want))
    rel = np.abs(got - want) / floor
    i = int(np.argmax(rel))
    return float(rel[i]), i


def numbers(prog, ref):
    """{limit's name: (what, value)} - the numbers `correct` compares."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_abs.{i + 1}"] = (
            f"loss step {i + 1}: program {a:.5f} reference {b:.5f} "
            f"|diff|", abs(a - b) if math.isfinite(a) else math.inf)
    g, gi = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    out["grad_norm_gap"] = (
        f"first gradient, worst leaf (#{gi}) norm gap", g)
    out["grad_rel_diff"] = (
        "first gradient, |program - reference| / |reference| over all "
        "leaves", float(reference.relative_difference(
            prog["first_grad"], ref["first_grad"])))
    c, ci = worst_leaf_gap(prog["change_norms"], ref["change_norms"])
    out["change_norm_gap"] = (
        f"parameter change after {len(prog['losses'])} steps, worst "
        f"leaf (#{ci}) norm gap", c)
    return out


def compare(prog, ref, limits):
    """[(what, value, limit, ok)] - each number beside its limit."""
    rows = []
    for name, (what, value) in numbers(prog, ref).items():
        limit = limits[name.split(".")[0]]
        rows.append((what, value, limit, value <= limit))
    return rows


def run_reference(cell, seed, batches, quant=None):
    """The plain reference over the same first steps, on one device,
    from its own copy of the seed's weights."""
    arch = cell.config["arch"]
    return reference.train_reference(
        arch, lambda: weights.make_params(arch, arch["max_positions"],
                                          seed, "float32"),
        batches, cell.traffic["learning_rate"], quant)


def run(cell, args, env):
    say, job, arch = env.say, cell.traffic, cell.config["arch"]
    env.phase("import")
    step, state, feed, place = build(cell, args.seed, say)
    env.phase("weights_and_state")
    prog = first_steps(step, state, feed, place, job["check_steps"],
                       args.seed, arch, arch["max_positions"], env)
    env.phase("compile_and_first_steps")
    for _ in range(job["warm_steps"]):
        state[0], state[1], loss = step(state[0], state[1],
                                        place(feed.next()))
    float(loss)
    env.phase("warm_steps")

    tokens_per_step = (job["per_chip_batch"] * cell.chips
                       * job["seq_len"])
    compiles_before = env.compiles.n
    setup_s = env.setup_done()
    traced = None
    if args.trace:
        # a short window of its own under the profiler, then the
        # measured window with the profiler off
        env.start_trace()
        traced = window(step, state, feed, place, args.seconds,
                        job["loss_fetch_every"], job["trace_steps"])
        env.stop_trace(traced[1])
    steps, secs, fetched = window(step, state, feed, place, args.seconds,
                                  job["loss_fetch_every"])
    compiles = env.compiles.n - compiles_before
    rate = steps * tokens_per_step / secs / cell.chips
    say(f"window: {steps} steps of {tokens_per_step} tokens in "
        f"{secs:.3f} s -> {secs / steps * 1e3:.2f} ms a step, "
        f"{rate:.1f} tokens/s/chip; losses fetched "
        + " ".join(f"{x:.3f}" for x in fetched[:3]) + " ... "
        + f"{fetched[-1]:.3f}; XLA compiles inside the window: "
          f"{compiles}")
    device = env.describe_device()

    hbm = None
    if args.trace:
        ma = step.__wrapped__.lower(
            state[0], state[1], place(feed.next())).compile(
            ).memory_analysis()
        hbm = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    del state[:], step
    gc.collect()

    t0 = time.perf_counter()
    ref = run_reference(cell, args.seed, prog["batches"])
    say(f"reference: {len(prog['batches'])} steps in float32 at "
        f"highest precision took {time.perf_counter() - t0:.1f} s "
        f"(not counted in setup_s)")
    rows = compare(prog, ref, cell.limits)
    finite = all(math.isfinite(x) for x in fetched)
    rows.append(("non-finite losses fetched in the window",
                 sum(not math.isfinite(x) for x in fetched), 0, finite))
    rows.append(("XLA compiles inside the window", compiles, 0,
                 compiles == 0))
    return {
        "rows": rows, "attempted": steps,
        "failed": 0 if finite else steps,
        "device": device,
        "values": {"train_tokens_per_s_per_chip": rate,
                   "setup_s": setup_s},
        "ctx": {"steps": steps, "window_s": secs,
                "tokens_per_step": tokens_per_step,
                "tokens_per_s_per_chip": rate,
                "traced_steps": traced[0] if traced else None,
                "program_hbm_bytes": hbm},
    }


def readings(cell, seed, _seconds, env, program=True):
    """For setting the limits: the numbers of a sound run of the
    program and of the controls (the reference computed one precision
    lower in the program's place), all against the reference, on one
    seed. Without `program`, the controls alone, on the batches the
    seed's feed would give the program: they run on one device, so a
    four-chip cell's controls can be read on one chip."""
    arch, job = cell.config["arch"], cell.traffic
    out = {}
    if program:
        step, state, feed, place = build(cell, seed, env.say)
        prog = first_steps(step, state, feed, place, job["check_steps"],
                           seed, arch, arch["max_positions"], env)
        del state[:], step
        gc.collect()
        batches = prog["batches"]
    else:
        feed = BatchFeed(seed, job["per_chip_batch"] * cell.chips,
                         job["seq_len"], arch["vocab_size"])
        batches = [feed.next() for _ in range(job["check_steps"])]
    ref = run_reference(cell, seed, batches)
    if program:
        out["program"] = {k: v for k, (_, v)
                          in numbers(prog, ref).items()}
    for quant in CONTROLS:
        control = run_reference(cell, seed, batches, quant=quant)
        out["control_" + quant] = {
            k: v for k, (_, v) in numbers(control, ref).items()}
    return out
