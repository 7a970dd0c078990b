"""Sharded serving tests: the pod-scale decode contract.

The whole contract is ONE sentence: a serving mesh changes WHERE the
hot path runs, never WHAT it produces. Every test here pins the
sharded engine's token streams BITWISE against the single-device
program across {fixed, paged} x {fp32, int8} x {greedy, seeded} x
mesh {1, 2, 4} on the virtual CPU mesh (conftest forces 8 devices),
plus the seams where sharding could plausibly leak: prefix-cache hits
whose blocks are mesh-wide shard sets, speculative decoding composed
with the mesh, and forced-prefix migration BETWEEN sharded and
unsharded replicas (docs/serving.md "Sharded serving").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import TransformerLM
from horovod_tpu.parallel.mesh import make_mesh, safe_spec
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import ServingEngine
from jax.sharding import PartitionSpec as P

VOCAB = 64
MAX_LEN = 32


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_state():
    # The GSPMD compiles below segfault inside XLA:CPU when they land
    # on top of the full suite's ~700 accumulated executables (every
    # sub-slice of the suite passes; only the complete run crashes, at
    # the first int8-paged partitioned compile). Dropping jax's traced/
    # compiled caches releases the dead modules' executables first.
    jax.clear_caches()


def _model(num_heads=4, num_layers=2):
    return TransformerLM(vocab_size=VOCAB, num_layers=num_layers,
                         num_heads=num_heads, head_dim=8,
                         max_len=MAX_LEN, dtype=jnp.float32)


@pytest.fixture(scope="module")
def lm(hvd):
    model = _model()
    params = unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


@pytest.fixture(scope="module")
def draft(hvd):
    model = _model(num_heads=2, num_layers=1)
    params = unbox(model.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


def _mesh(n):
    return make_mesh(devices=jax.devices()[:n], model=n)


def _prompts(n, seed=0, lo=2, hi=8):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (int(rs.randint(lo, hi)),))
            for _ in range(n)]


def _streams(model, params, prompts, steps, *, seeded=False, **kw):
    with ServingEngine(model, params, num_slots=2, **kw) as eng:
        hs = [eng.submit(p, steps,
                         **({"temperature": 0.9, "seed": 100 + i}
                            if seeded else {}))
              for i, p in enumerate(prompts)]
        out = [list(h.result(timeout=300).tokens) for h in hs]
        snap = eng.metrics_snapshot()
    return out, snap


class TestShardedBitwise:
    """The acceptance sweep: sharded == single-device token streams."""

    @pytest.mark.parametrize("paged", [False, True],
                             ids=["fixed", "paged"])
    @pytest.mark.parametrize("quant", [None, "int8"],
                             ids=["fp32", "int8"])
    @pytest.mark.parametrize("seeded", [False, True],
                             ids=["greedy", "seeded"])
    def test_sharded_matches_single_device(self, lm, paged, quant,
                                           seeded):
        model, params = lm
        prompts = _prompts(3, seed=11)
        steps = 7
        kw = dict(paged=paged, weight_quant=quant)
        if paged:
            kw["kv_block_size"] = 8
        ref, _ = _streams(model, params, prompts, steps,
                          seeded=seeded, **kw)
        for n in (1, 2, 4):
            got, snap = _streams(model, params, prompts, steps,
                                 seeded=seeded, mesh=_mesh(n), **kw)
            assert got == ref, (paged, quant, seeded, n)
            assert snap["mesh_devices"] == n

    def test_gqa_degrade_replicates_undividable_heads(self, hvd):
        """heads=3 over model=2: `safe_spec` keeps the KV leaves
        replicated (the axis doesn't divide the heads dim) instead of
        erroring or sharding unevenly — and the stream is still
        bitwise the single-device one."""
        model = _model(num_heads=3, num_layers=1)
        params = unbox(model.init(
            jax.random.PRNGKey(2),
            jnp.zeros((1, 16), jnp.int32))["params"])
        prompts = _prompts(2, seed=3)
        ref, _ = _streams(model, params, prompts, 6, paged=True,
                          kv_block_size=8)
        got, _ = _streams(model, params, prompts, 6, paged=True,
                          kv_block_size=8, mesh=_mesh(2))
        assert got == ref

    def test_safe_spec_drops_axes_that_do_not_fit(self, hvd):
        mesh = _mesh(4)
        spec = P(None, None, None, "model")
        # 4 heads / model=4 shards; 3 heads doesn't divide -> dropped;
        # unknown axis name -> dropped.
        assert safe_spec(mesh, spec, (2, 1, 32, 4, 8)) == spec
        assert safe_spec(mesh, spec, (2, 1, 32, 3, 8)) == P(
            None, None, None, None)
        assert safe_spec(mesh, P("nope", "model"), (8, 8)) == P(
            None, "model")


class TestReallySharded:
    """Replication must never again pass as sharding: a fully
    replicated engine emits the same tokens, so the bitwise tests
    above cannot tell. These assert what each device HOLDS."""

    def test_safe_spec_reads_a_partition_spec(self, hvd):
        # A PartitionSpec is a sequence, not a tuple subclass: it must
        # be read entry by entry, never wrapped whole as one entry.
        mesh = _mesh(4)
        assert not isinstance(P(), tuple)
        assert safe_spec(mesh, P(None, "model"), (8, 8)) == P(
            None, "model")
        assert safe_spec(mesh, P("model"), (8, 8)) == P("model")
        assert safe_spec(mesh, "model", (8,)) == P("model")
        assert safe_spec(mesh, P(("data", "model"), None),
                         (8, 8)) == P(("data", "model"), None)

    def test_place_with_specs_shards_params(self, lm):
        from horovod_tpu.models.transformer import lm_param_specs
        from horovod_tpu.parallel.mesh import place_with_specs
        model, params = lm
        mesh = _mesh(4)
        specs = lm_param_specs(model, jax.random.PRNGKey(0),
                               jnp.zeros((1, MAX_LEN), jnp.int32))
        placed = place_with_specs(mesh, params, specs)
        flat, _ = jax.tree_util.tree_flatten_with_path(placed)
        split = 0
        for path, leaf in flat:
            name = jax.tree_util.keystr(path)
            shards = leaf.addressable_shards
            assert len({s.device for s in shards}) == 4
            if "kernel" in name and ("attn" in name or "mlp" in name):
                # Every attention / MLP matmul kernel: 1/4 per device.
                assert shards[0].data.size * 4 == leaf.size, (
                    name, leaf.shape, shards[0].data.shape)
                split += 1
        assert split >= 4 * model.num_layers
        held = sum(s.data.nbytes for _, leaf in flat
                   for s in leaf.addressable_shards[:1])
        total = sum(leaf.nbytes for _, leaf in flat)
        assert held < 0.6 * total, (held, total)

    @pytest.mark.parametrize("paged", [False, True],
                             ids=["fixed", "paged"])
    def test_engine_kv_holds_a_quarter_per_device(self, lm, paged):
        model, params = lm
        kw = dict(paged=True, kv_block_size=8) if paged else {}
        eng = ServingEngine(model, params, num_slots=2, mesh=_mesh(4),
                            **kw)
        try:
            if paged:
                leaves = list(eng.pool._pools)
            else:
                flat, _ = jax.tree_util.tree_flatten_with_path(
                    eng.pool._cache)
                leaves = [leaf for path, leaf in flat
                          if "index" not in jax.tree_util.keystr(path)]
            assert leaves
            for leaf in leaves:
                shard = leaf.addressable_shards[0].data
                # [rows, 1, len, Hkv, D]: the heads axis splits 4 ways.
                assert shard.shape[3] * 4 == leaf.shape[3], (
                    leaf.shape, shard.shape)
                assert len({s.device
                            for s in leaf.addressable_shards}) == 4
            kernels = [
                leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(
                    eng.pool.params)[0]
                if "kernel" in jax.tree_util.keystr(path)
                and "attn" in jax.tree_util.keystr(path)]
            assert kernels and all(
                k.addressable_shards[0].data.size * 4 == k.size
                for k in kernels)
        finally:
            eng.shutdown()


class TestShardedSeams:
    """Where sharding could leak: prefix cache, spec decode,
    migration, accounting."""

    def test_prefix_hits_across_shard_boundaries(self, lm):
        """A prefix published by one sharded request is reusable by
        the next: the host block ids name mesh-wide block SHARD sets,
        so a hit skips prefill on EVERY shard at once. Streams stay
        bitwise the unsharded engine's, which runs the same prompts
        without any cache geometry."""
        model, params = lm
        BS = 8
        rs = np.random.RandomState(5)
        sysp = rs.randint(0, VOCAB, (2 * BS,))
        prompts = [np.concatenate([sysp, rs.randint(0, VOCAB, (2,))])
                   for _ in range(3)]
        steps = 5
        ref, _ = _streams(model, params, prompts, steps, paged=True,
                          kv_block_size=BS)
        with ServingEngine(model, params, num_slots=2, paged=True,
                           kv_block_size=BS, mesh=_mesh(4)) as eng:
            first = eng.submit(prompts[0], steps).result(timeout=300)
            rest = [eng.submit(p, steps).result(timeout=300)
                    for p in prompts[1:]]
            snap = eng.metrics_snapshot()
        assert first.prefix_tokens_cached == 0
        for r in rest:
            assert r.prefix_tokens_cached == 2 * BS
        assert snap["prefix_hits"] >= 4
        got = [list(r.tokens) for r in [first] + rest]
        assert got == ref

    def test_spec_decode_composes_with_mesh(self, lm, draft):
        """Speculative decoding under the mesh: the draft-verify
        round runs with BOTH caches sharded, and the greedy
        acceptance rule keeps the stream bitwise the plain target's
        — spec x mesh composes rather than being mutually
        exclusive."""
        model, params = lm
        dm, dp = draft
        prompts = _prompts(2, seed=17)
        steps = 8
        plain, _ = _streams(model, params, prompts, steps)
        for paged in (False, True):
            kw = dict(spec_draft=(dm, dp), spec_k=3, paged=paged)
            if paged:
                kw["kv_block_size"] = 8
            got, snap = _streams(model, params, prompts, steps,
                                 mesh=_mesh(4), **kw)
            assert got == plain, paged
            assert snap["spec_rounds"] > 0

    def test_forced_prefix_migration_across_layouts(self, lm):
        """Token-exact migration BETWEEN a sharded and an unsharded
        replica, both directions: the forced prefix teacher-forces the
        tokens the dead replica already emitted, and the survivor —
        whatever its mesh — continues the exact greedy stream."""
        model, params = lm
        prompt = _prompts(1, seed=23)[0]
        steps = 9
        ref, _ = _streams(model, params, [prompt], steps)
        k = 4
        for src_mesh, dst_mesh in ((None, _mesh(4)), (_mesh(4), None)):
            with ServingEngine(model, params, num_slots=1,
                               mesh=src_mesh) as eng:
                head = list(eng.submit(
                    prompt, k).result(timeout=300).tokens)
            assert head == ref[0][:k]
            with ServingEngine(model, params, num_slots=1,
                               mesh=dst_mesh) as eng:
                tail = list(eng.submit(
                    prompt, steps,
                    forced_prefix=head).result(timeout=300).tokens)
            assert tail == ref[0]

    def test_mesh_forms_env_and_stamp(self, lm, monkeypatch):
        """Engine mesh resolution: int / 'axis=N' str / HVD_SERVE_MESH
        env all build the same layout, and the mesh stamp reaches
        /healthz and the metrics snapshot (the obs gauge row rides
        `hvd_serving_mesh_devices`)."""
        from horovod_tpu.runtime.config import config
        model, params = lm
        with ServingEngine(model, params, num_slots=1,
                           mesh="model=2") as eng:
            assert eng.mesh_devices == 2
            assert eng._health()["mesh"] == {"model": 2}
        with ServingEngine(model, params, num_slots=1, mesh=2) as eng:
            assert eng.mesh_devices == 2
        monkeypatch.setenv("HVD_SERVE_MESH", "2")
        config.refresh()
        try:
            with ServingEngine(model, params, num_slots=1) as eng:
                assert eng.mesh_devices == 2
                snap = eng.metrics_snapshot()
                assert snap["mesh_devices"] == 2
                assert snap["mesh"] == {"model": 2}
        finally:
            monkeypatch.delenv("HVD_SERVE_MESH")
            config.refresh()
        with pytest.raises(ValueError):
            ServingEngine(model, params, num_slots=1, mesh=99)

    def test_per_shard_kv_gauges(self, lm):
        """Paged engine on a mesh emits per-shard block-occupancy
        rows — one per device, all agreeing (one host allocator
        decision drives every shard) — and removes them on close."""
        from horovod_tpu.obs.catalog import serving_metrics
        model, params = lm
        cat = serving_metrics()
        with ServingEngine(model, params, num_slots=2, paged=True,
                           kv_block_size=8, mesh=_mesh(2)) as eng:
            eng.submit(_prompts(1, seed=31)[0], 4).result(timeout=300)
            label = str(eng._engine_id)
            free0 = cat["kv_blocks_free_shard"].value(
                engine=label, shard="0")
            free1 = cat["kv_blocks_free_shard"].value(
                engine=label, shard="1")
            assert free0 > 0 and free0 == free1
            assert cat["mesh_devices"].value(engine=label) == 2

        def rows(metric):
            return [lbl for lbl, _ in metric.samples()
                    if lbl.get("engine") == label]

        # close() removed every row this engine owned.
        assert not rows(cat["kv_blocks_free_shard"])
        assert not rows(cat["mesh_devices"])
