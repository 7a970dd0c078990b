"""kind `serve`: `ServingEngine` under clients that wait for replies.

`ServingEngine(model, serving_params(params), num_slots=..., warmup=True)`
with the engine's defaults otherwise. One load-generator thread (this
one) plays every client: a closed loop submits a client's next request
when its last completes. Times are the benchmark's own: submit and
completion on `time.perf_counter()`, the first token by polling
`tokens_so_far()` every `poll_seconds`.

Phases: a warm period until as many requests have finished as there
are clients (set-up); with `--trace 1` a traced window of `trace_seconds`
and a second warm period; then the measured window of `--seconds`.
Metrics are over the requests that complete inside the window, tokens/s
over every token emitted inside it.

After the window the engine is shut down and freed, and `correct`
holds a sample of the finished requests (drawn from the seed, the
longest among them) against the plain reference: for every served
token, how far its logit lies below the reference's best at that
position (teacher-forced on the served tokens). Greedy tokens only.

Traffic parameters (`traffic/<mix>.json`): `clients`, `num_slots`,
`cache_positions`, `attn_impl`, `prompt_len`, `output_len`, `n_sizes`,
`sizes_seed`, `check_requests`, `trace_seconds`, `poll_seconds`.
"""

import gc
import time

import jax
import numpy as np

from benchmarks.harness import reference, traffic, weights
from benchmarks.harness.model import program_model


CONTROLS = ("int8", "fp8")     # the steps below bf16


class Client:
    __slots__ = ("handle", "prompt", "want", "t_submit", "t_first",
                 "t_done", "finished")

    def __init__(self):
        self.handle, self.finished = None, 0

    def submit(self, eng, prompt, want):
        self.prompt, self.want = prompt, want
        self.t_first = self.t_done = None
        self.t_submit = time.perf_counter()
        self.handle = eng.submit(prompt, want)
        self.handle.future.add_done_callback(self._done)

    def _done(self, _future):
        self.t_done = time.perf_counter()


def make_engine(model, params, mix):
    """The system under test (the broken-path test wraps this)."""
    from horovod_tpu.models.transformer import serving_params
    from horovod_tpu.serving import ServingEngine
    return ServingEngine(model, serving_params(params),
                         num_slots=mix["num_slots"], warmup=True)


class Load:
    """The clients, the phases and the records of one run."""

    def __init__(self, eng, mix, stream, say):
        self.eng, self.mix, self.stream, self.say = eng, mix, stream, say
        self.clients = [Client() for _ in range(mix["clients"])]
        self.records = []        # finished requests, in order
        self.errors = self.finished = 0
        self.accepting = True

    # -- one pass over the clients -------------------------------------
    def poll(self):
        now = time.perf_counter()
        for c in self.clients:
            if c.handle is None:
                continue
            if c.t_done is not None:
                self._finish(c)
            elif c.t_first is None and c.handle.tokens_so_far():
                c.t_first = now
        # a client whose request finished submits its next one - held
        # back only while the admission queue (16 by default) is half
        # full, which a steady closed loop never reaches: nothing is
        # ever refused
        for c in self.clients:
            if (c.handle is None and self.accepting
                    and self.eng.queue_depth < 8):
                c.submit(self.eng, *self.stream.next())
        return now

    def _finish(self, c):
        try:
            res = c.handle.result(timeout=0)
            toks = np.asarray(res.tokens)
        except Exception as e:      # a failed request is a finding
            self.errors += 1
            self.say(f"request failed: {type(e).__name__}: {e}")
            toks = None
        if toks is not None:
            t_first = c.t_first if c.t_first is not None else c.t_done
            self.records.append({
                "prompt": c.prompt, "tokens": toks, "want": c.want,
                "t_submit": c.t_submit, "t_first": t_first,
                "t_done": c.t_done})
        c.finished += 1
        c.handle = None
        self.finished += 1

    def emitted(self):
        """Tokens the requests in flight have emitted so far."""
        return sum(len(c.handle.tokens_so_far()) for c in self.clients
                   if c.handle is not None)

    # -- phases ----------------------------------------------------------
    def warm(self):
        """Until as many requests have finished as there are clients
        and every client has one in flight: about one mean request's
        time, after which the requests in flight are a steady mix."""
        target = self.finished + len(self.clients)
        while (self.finished < target
               or any(c.handle is None for c in self.clients)):
            self.poll()
            time.sleep(self.mix["poll_seconds"])

    def window(self, seconds):
        """Run for `seconds`; returns what the window saw."""
        snap0 = self.eng.metrics_snapshot()
        n0, in_flight0 = len(self.records), self.emitted()
        err0 = self.errors
        t0 = time.perf_counter()
        while self.poll() - t0 < seconds:
            time.sleep(self.mix["poll_seconds"])
        t1 = time.perf_counter()
        done = self.records[n0:]
        tokens = (sum(len(r["tokens"]) for r in done) - in_flight0
                  + self.emitted())
        return {"t0": t0, "seconds": t1 - t0, "done": done,
                "tokens": tokens, "errors": self.errors - err0,
                "snap0": snap0, "snap1": self.eng.metrics_snapshot()}

    def stop(self):
        """No new requests; cancel what is in flight and wait for it."""
        self.accepting = False
        for c in self.clients:
            if c.handle is not None:
                c.handle.cancel()
        deadline = time.perf_counter() + 60
        while (any(c.handle is not None and c.t_done is None
                   for c in self.clients)
               and time.perf_counter() < deadline):
            time.sleep(0.005)


def latency_metrics(done):
    ttft = [(r["t_first"] - r["t_submit"]) * 1e3 for r in done]
    tpot = [(r["t_done"] - r["t_first"]) * 1e3 / (len(r["tokens"]) - 1)
            for r in done if len(r["tokens"]) > 1]
    return {"ttft_p95_ms": traffic.percentile(ttft, 95),
            "ttft_p50_ms": traffic.percentile(ttft, 50),
            "tpot_p95_ms": traffic.percentile(tpot, 95),
            "tpot_p50_ms": traffic.percentile(tpot, 50)}


def sample_for_check(done, k, seed):
    """k finished requests: the longest, and k-1 drawn from the seed."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"])
                  + len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed), 0x636865636B])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [done[longest]] + [done[rest[i]] for i in pick]


def gaps_against_reference(cell, seed, sample, quant=None):
    """Per served token of the sample, the gap below the reference's
    best logit. With `quant` (the control), the gap of the token that
    the reference computed in that precision puts first at the same
    positions of the same prompts and tokens."""
    arch = cell.config["arch"]
    params = weights.make_params(
        arch, cell.traffic["cache_positions"], seed,
        arch["compute_dtype"])
    out = []
    for r in sample:
        ref = reference.served_logits(arch, params, r["prompt"],
                                      r["tokens"])
        toks = r["tokens"]
        if quant is not None:
            low = reference.served_logits(arch, params, r["prompt"],
                                          r["tokens"], quant=quant)
            toks = np.asarray(low).argmax(-1)
        out.append(reference.token_gaps(ref, toks))
    return out


def numbers(gaps):
    """{limit's name: (what, value)} - the numbers `correct` compares."""
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    n = len(allg)
    return {
        "gap_max": (f"widest gap of a served token below the "
                    f"reference's best logit ({n} tokens of {len(gaps)} "
                    f"requests)", float(allg.max()) if n else np.inf),
        "gap_mean": (f"mean gap over the same {n} tokens",
                     float(allg.mean()) if n else np.inf)}


def compare(gaps, limits):
    return [(what, value, limits[name], value <= limits[name])
            for name, (what, value) in numbers(gaps).items()]


def drive(cell, seed, seconds, trace, env):
    """Set-up, the phases and the window; the engine is shut down and
    freed on return."""

    say, mix, arch = env.say, cell.traffic, cell.config["arch"]
    env.phase("import")
    model = program_model(arch, max_len=mix["cache_positions"],
                          attn_impl=mix["attn_impl"])
    params = weights.make_params(arch, model.max_len, seed,
                                 arch["compute_dtype"])
    jax.block_until_ready(params)
    env.phase("weights")
    say(f"weights: {weights.count(arch, model.max_len) / 1e6:.1f} M "
        f"parameters made on the device from the seed; traffic: "
        + traffic.describe(mix))
    eng = make_engine(model, params, mix)
    del params
    info = eng.warmup_info or {}
    say(f"engine up: warm-up {info.get('seconds', 0):.1f} s, "
        f"{info.get('compiles')} program shapes, prefill chunks "
        f"{info.get('prefill_sizes')}")
    env.phase("engine_warmup")

    stream = traffic.RequestStream(mix, seed, arch["vocab_size"])
    load = Load(eng, mix, stream, say)
    load.warm()
    env.phase("warm_period")
    setup_s = env.setup_done()

    traced = None
    if trace:
        # a short window of its own under the profiler, lanes filled
        # again, then the measured window with the profiler off
        env.start_trace()
        traced = load.window(mix["trace_seconds"])
        env.stop_trace(traced["seconds"])
        load.warm()
    compiles_before = env.compiles.n
    win = load.window(seconds)
    compiles = env.compiles.n - compiles_before
    load.stop()
    device = env.describe_device()
    eng.shutdown(drain=False, timeout=120)
    del eng, load
    gc.collect()
    return {"win": win, "traced": traced, "device": device,
            "setup_s": setup_s, "compiles": compiles}


def run(cell, args, env):
    say, mix = env.say, cell.traffic
    d = drive(cell, args.seed, args.seconds, args.trace, env)
    win, traced, compiles = d["win"], d["traced"], d["compiles"]
    snap, device, setup_s = win["snap1"], d["device"], d["setup_s"]
    done = win["done"]
    lat = latency_metrics(done)
    rate = win["tokens"] / win["seconds"]
    wrong = sum(len(r["tokens"]) != r["want"] for r in done)
    say(f"window: {len(done)} requests finished, {win['tokens']} "
        f"tokens emitted in {win['seconds']:.3f} s -> {rate:.1f} "
        f"tokens/s; ttft p50 {lat['ttft_p50_ms']:.1f} p95 "
        f"{lat['ttft_p95_ms']:.1f} ms; tpot p50 "
        f"{lat['tpot_p50_ms']:.2f} p95 {lat['tpot_p95_ms']:.2f} ms; "
        f"failed {win['errors']}, wrong length {wrong}; compiles "
        f"inside the window: XLA {compiles}, engine-counted "
        f"{snap['compiles']}")

    sample = sample_for_check(done, mix["check_requests"], args.seed)
    t0 = time.perf_counter()
    gaps = gaps_against_reference(cell, args.seed, sample)
    say(f"reference: {len(sample)} requests "
        f"({sum(len(g) for g in gaps)} served tokens; prompts "
        f"{[len(r['prompt']) for r in sample]}) in float32 at highest "
        f"precision took {time.perf_counter() - t0:.1f} s (not counted "
        f"in setup_s)")
    rows = compare(gaps, cell.limits)
    rows.append(("requests of the wrong length", wrong, 0, wrong == 0))
    rows.append(("requests that failed", win["errors"], 0,
                 win["errors"] == 0))
    rows.append(("XLA compiles inside the window", compiles, 0,
                 compiles == 0))
    rows.append(("engine-counted compiles after warm-up",
                 snap["compiles"], 0, snap["compiles"] == 0))

    def delta(a, b, key):
        return (b.get(key) or 0) - (a.get(key) or 0)

    ctx = {"num_slots": mix["num_slots"]}
    for name, w in (("window", win), ("traced", traced)):
        if w is None:
            continue
        a, b = w["snap0"], w["snap1"]
        ctx[name + "_ticks"] = delta(a, b, "ticks")
        ctx[name + "_decode_tokens"] = (
            delta(a, b, "tokens_out") - delta(a, b, "prefill_first_tokens"))
        ctx[name + "_prefill_tokens"] = delta(a, b, "prefill_tokens")
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


def readings(cell, seed, seconds, env, program=True):
    """For setting the limits: the gaps of a sound run's served tokens
    and of the controls (the reference computed one precision lower:
    at the same positions of the same prompts and tokens, the token
    it puts first), all against the reference, on one seed."""
    if not program:
        raise SystemExit("a served model's control is read at the "
                         "positions the program served: it needs the "
                         "program")
    d = drive(cell, seed, seconds, 0, env)
    sample = sample_for_check(d["win"]["done"],
                              cell.traffic["check_requests"], seed)
    prog = gaps_against_reference(cell, seed, sample)
    out = {"program": {k: v for k, (_, v) in numbers(prog).items()}}
    for quant in CONTROLS:
        control = gaps_against_reference(cell, seed, sample, quant=quant)
        out["control_" + quant] = {
            k: v for k, (_, v) in numbers(control).items()}
    return out
