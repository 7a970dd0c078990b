"""Where the widest gaps of `correct` sit: on each seed ONE sound run of
the program, and then, per served token of the sampled requests, its
gap below the reference's best logit BESIDE whether the program's
chosen experts differ from the reference's at the position that
predicted it:

    python3 benchmarks/tools/routing_readings.py --workload <cell> \\
        --seeds 1,2 [--seconds 12] [--top 20] [--out <file>]

For a cell whose architecture module offers `expert_routing(arch,
params, tokens)` -> [expert layers, S, k] (sorted per token; a row an
EXPERT layer, so a dense leading layer is no obstacle, as it is to
`kinds/serve_arch.py::routing_flips`). The program's choice is read
from its own full forward pass in the serving precision (the
`intermediates` its expert layers sow), as `routing_flips` reads it.
A served token i of a request with a prompt of P tokens is predicted
at position P - 1 + i: that position's routing is the one its gap is
laid beside.

One JSON line a seed: `gap_max`, `gap_mean` (the numbers of
`correct`); `routing_differ_share` (layer-positions whose chosen set
differs) and `positions_flipped_share` (predicting positions with a
differing layer); the mean gap on flipped and on unflipped positions;
of the `--top` widest gaps how many sit on a flipped position, and
those gaps themselves with the layers that flipped - and all of it once
more for the flips that touch an expert HELD on this chip
(`experts_held`: `_held_flipped`), the only ones its share of a layer
computes with. A benchmark run never runs this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PAD = 2048      # the program's forward compiles once a multiple of this


def attribute(gaps, flips, held, top=20):
    """`gaps`: per request, float [N] (a served token's gap); `flips`
    and `held`: per request, bool [expert layers, N] - the predicting
    position's chosen set differs in that layer, and differs in an
    expert HELD here (the only difference this chip's share of the
    layer computes with). -> the line's numbers."""
    import numpy as np
    g = np.concatenate(gaps)
    f, h = (np.concatenate(x, axis=1) for x in (flips, held))
    order = np.argsort(-g)[:top]
    request = np.concatenate([np.full(len(x), i) for i, x in
                              enumerate(gaps)])
    token = np.concatenate([np.arange(len(x)) for x in gaps])

    def split(name, mask):
        on = mask.any(0)
        return {
            f"positions_{name}_share": float(on.mean()),
            f"gap_mean_{name}": float(g[on].mean()) if on.any() else None,
            f"gap_mean_not_{name}": (float(g[~on].mean())
                                     if (~on).any() else None),
            f"gap_max_not_{name}": (float(g[~on].max())
                                    if (~on).any() else None),
            f"top_on_{name}": int(on[order].sum())}

    return {
        "tokens": int(g.size),
        "gap_max": float(g.max()), "gap_mean": float(g.mean()),
        "routing_differ_share": float(f.mean()),
        "routing_differ_held_share": float(h.mean()),
        **split("flipped", f), **split("held_flipped", h),
        "top": int(len(order)),
        "widest": [{"gap": float(g[j]), "request": int(request[j]),
                    "served_token": int(token[j]),
                    "layers_flipped": np.flatnonzero(f[:, j]).tolist(),
                    "layers_held_flipped":
                        np.flatnonzero(h[:, j]).tolist()}
                   for j in order],
    }


def program_routing(apply, num_layers, params, seq):
    """The program's chosen ids on `seq`, sorted per token:
    [expert layers, len(seq), k] - its full forward in the serving
    precision (`apply`: tokens [1, S] -> what the model sowed), the
    blocks that sowed a choice in layer order."""
    import jax.numpy as jnp
    import numpy as np
    padded = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
    padded[:len(seq)] = seq             # causal: the tail reaches nothing
    sown = apply(params, jnp.asarray(padded)[None])["intermediates"]
    blocks = [f"block_{i}" for i in range(num_layers)
              if "moe" in sown.get(f"block_{i}", {})]
    return np.stack([np.sort(np.asarray(
        sown[b]["moe"]["chosen"]), -1).reshape(len(padded), -1)[:len(seq)]
        for b in blocks])


def read_seed(cell, seed, seconds, env, top):
    import jax
    import numpy as np
    from benchmarks.harness import reference
    from horovod_tpu.models.transformer import serving_params
    driver = cell.driver()
    arch_mod, arch = driver.arch_module(cell), cell.config["arch"]
    d = driver.drive(cell, seed, seconds, 0, env)
    sample = driver.serve.sample_for_check(
        d["win"]["done"], cell.traffic["check_requests"], seed)
    params = arch_mod.make_params(
        arch, cell.traffic["cache_positions"], seed,
        arch["compute_dtype"])
    model = arch_mod.program_model(
        arch, max_len=cell.traffic["cache_positions"], attn_impl="dot")
    served = serving_params(params)
    apply = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"])[1])
    first, n = arch["experts_held"]
    ids = np.arange(first, first + n)

    def member(chosen):                 # [layers, S, k] -> [layers, S, n]
        return (chosen[..., None] == ids).any(-2)

    gaps, flips, held = [], [], []
    for r in sample:
        prompt = np.asarray(r["prompt"], np.int32)
        toks = np.asarray(r["tokens"], np.int32)
        ref = arch_mod.served_logits(arch, params, prompt, toks)
        gaps.append(reference.token_gaps(ref, toks))
        seq = np.concatenate([prompt, toks])
        want = arch_mod.expert_routing(arch, params, seq)
        got = program_routing(apply, arch["num_layers"], served, seq)
        at = len(prompt) - 1 + np.arange(len(toks))
        flips.append((got != want).any(-1)[:, at])
        held.append((member(got) != member(want)).any(-1)[:, at])
    return {"seed": seed,
            "prompts": [len(r["prompt"]) for r in sample],
            **attribute(gaps, flips, held, top)}


def main(argv=None, *, accept_platform=("tpu",), peaks_kind=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from benchmarks.harness.runenv import open_cell

    for seed in (int(x) for x in args.seeds.split(",")):
        cell, env = open_cell(args.workload, time.time(),
                              accept_platform=accept_platform,
                              peaks_kind=peaks_kind)
        line = json.dumps(read_seed(cell, seed, args.seconds, env,
                                    args.top))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
