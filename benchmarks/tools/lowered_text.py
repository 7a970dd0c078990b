"""The lowered text of the serving programs of every configuration a
tree has, as digests, so that two trees can be compared without a chip:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/lowered_text.py <tree root> <out.json>

run once on the parent's tree (`git archive` into a scratch directory)
and once on the change's, then compare the two files' entries. For each
serving configuration of `BENCHMARK.json` (at its cell's lanes and cache
positions) and each toy under `tests/benchmark/tiny/` (4 lanes of 128):
`slot_decode_tick` and `slot_prefill_chunk` of 1, 16 and 128 tokens,
lowered (`lower()` only, nothing compiled) for the described v5e with
the rules on their TPU branch. An entry holds `raw`, the digest of the
text as it is, and `stripped`, the digest with each custom call's
`backend_config` blanked: a Mosaic body carries the checkout's path and
its call sites' line numbers, so a program with a kernel differs in
`raw` between two trees whose kernels are the same. A configuration the
tree cannot build (the parent, of a configuration the change adds) is
skipped and said so. A benchmark run never runs this.
"""

import hashlib
import json
import os
import re
import sys

SIZES = (1, 16, 128)


def strip(text):
    return re.sub(r'backend_config\s*=\s*"[^"]*"', 'backend_config="..."',
                  text)


def configurations(root):
    """{name: (configuration file, lanes, cache positions)}."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = {}
    for cell in bench["workloads"]:
        with open(os.path.join(root, "benchmarks", "traffic",
                               cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        if "num_slots" in mix and cell["config"] not in out:
            out[cell["config"]] = (files[cell["config"]], mix["num_slots"],
                                   mix["cache_positions"])
    tiny = os.path.join("tests", "benchmark", "tiny")
    for name in sorted(os.listdir(os.path.join(root, tiny))):
        with open(os.path.join(root, tiny, name)) as f:
            if "arch" in json.load(f):
                out[name[:-5]] = (os.path.join(tiny, name), 4, 128)
    return out


def main(root, out):
    root = os.path.abspath(root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    from benchmarks.harness import model as harness_model
    from benchmarks.harness.cells import load_module
    from horovod_tpu.models.transformer import (
        init_slot_cache, serving_params, slot_decode_model,
        slot_decode_tick, slot_prefill_chunk)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.tensor import unbox
    flash_attention._auto_interpret = lambda: False

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    res = {}
    for name, (path, lanes, W) in configurations(root).items():
        with open(path) as f:
            cfg = json.load(f)
        if "train" in cfg.get("kind", "") or "arch" not in cfg:
            continue
        try:
            if "arch_module" in cfg:
                mod = load_module(
                    f"benchmarks/arch/{cfg['arch_module']}.py",
                    "lowered_" + re.sub(r"\W", "_", name))
                model = mod.program_model(cfg["arch"], max_len=W,
                                          attn_impl="flash")
            else:
                model = harness_model.program_model(
                    cfg["arch"], max_len=W, attn_impl="flash")
        except Exception as e:  # noqa: BLE001 - said, and skipped
            print(f"cannot build {name}: {e!r}"[:240], flush=True)
            continue
        dec = slot_decode_model(model)
        params = place(jax.eval_shape(
            lambda r: serving_params(unbox(model.init(
                r, jnp.zeros((1, 64), jnp.int32))["params"])),
            jax.random.PRNGKey(0)))
        cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))

        def vec(dt):
            return sds((lanes,), dt)

        programs = {"tick": lambda: slot_decode_tick.lower(
            dec, params, cache, vec(jnp.int32), vec(jnp.float32),
            vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
            vec(bool), sds((), jnp.int32))}
        for n in SIZES:
            programs[f"chunk-{n}"] = lambda n=n: slot_prefill_chunk.lower(
                dec, params, cache, sds((), jnp.int32),
                sds((n,), jnp.int32))
        for which, lower in programs.items():
            text = lower().as_text()
            res[f"{name}/{which}"] = {
                "raw": hashlib.sha256(text.encode()).hexdigest()[:16],
                "stripped": hashlib.sha256(
                    strip(text).encode()).hexdigest()[:16],
                "lines": text.count("\n"),
                "kernels": text.count("tpu_custom_call")}
            print(name, which, res[f"{name}/{which}"], flush=True)
    with open(out, "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:3])
