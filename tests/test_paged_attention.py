"""Paged-attention kernel tests (docs/serving.md "Decode fast path").

The contract stack:

* **Walk == gather, bitwise.** The lax block-table walk
  (`ops.paged_attention.paged_prefix_attention`, what a pool takes
  wherever its geometry allows: `kernel_mode == "lax"`) reads the same
  bytes in the same accumulation order as the gathered-view program,
  so prefill logits and token streams are BITWISE those of a pool
  that gathers (`kernel_mode == "off"`) — across fill patterns, block
  sizes, prompt lengths, eos stops, and int8-KV scale pools. The
  geometry here walks; the reference is had by steering the pool's
  rule in the test (`gathers`): the program has no option for it.
* **No full-span gather.** The fused tick's traced jaxpr contains no
  gather whose output covers the whole table span — the kernel path
  walks only filled blocks. The same detector FINDS the full-span
  gather in the legacy program (positive control), so the assert
  cannot rot into vacuity.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (
    TransformerLM, generate, paged_cache_spec, paged_decode_tick,
)
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import ServingEngine
from horovod_tpu.serving import paging
from horovod_tpu.serving.paging import (
    PagedSlotPool, _paged_attention_way,
)

VOCAB = 64
MAX_LEN = 32


def _model(**kw):
    return TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=4,
                         head_dim=8, max_len=MAX_LEN,
                         dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def lm(hvd):
    model = _model()
    params = unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


@pytest.fixture
def steer(monkeypatch):
    """steer("off") makes every pool built after it gather its lanes'
    whole spans, the reference, at a geometry the rule would walk;
    steer("lax") gives the rule back."""
    def to(kernel):
        if kernel == "off":
            monkeypatch.setattr(
                paging, "_paged_attention_way",
                lambda model, block_size: ("off", "the test's reference"))
        else:
            monkeypatch.setattr(paging, "_paged_attention_way",
                                _paged_attention_way)
    return to


def _prompts(n, seed=0, lo=1, hi=12):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (int(rs.randint(lo, hi)),))
            for _ in range(n)]


def _pool_streams(steer, model, params, kernel, prompts, steps, *,
                  block_size=8, eos_id=None, num_slots=3,
                  collect_logits=False):
    """Drive a PagedSlotPool directly (interleaved admissions so fill
    patterns differ per lane), walking ("lax") or steered to the
    gather ("off"), and return per-prompt token streams (and
    optionally each prefill's final logits)."""
    steer(kernel)
    pool = PagedSlotPool(model, params, num_slots,
                         block_size=block_size, eos_id=eos_id)
    assert pool.kernel_mode == kernel
    streams, logits_out = [], []
    for p in prompts:
        adm = pool.admit(np.asarray(p), steps)
        slot = adm.slot
        pool.begin_prefill(slot)
        off, logits = adm.skipped, None
        from horovod_tpu.models.transformer import prefill_chunks
        for c in prefill_chunks(len(p) - adm.skipped):
            logits = pool.prefill_chunk(slot, np.asarray(p)[off:off + c])
            off += c
        if collect_logits:
            logits_out.append(np.asarray(logits))
        toks = [pool.finish_prefill(slot, logits, 0.0, None, 0)]
        for _ in range(steps - 1):
            toks.append(int(pool.tick()[slot]))
        streams.append(toks)
        pool.free(slot)
    return (streams, logits_out) if collect_logits else streams


class TestWalkVsGather:
    @pytest.mark.parametrize("block_size", [4, 8, 16])
    def test_streams_and_logits_bitwise(self, lm, steer, block_size):
        """The walk == the gather, bitwise, across block sizes
        and mixed fill patterns — and both equal `generate`."""
        model, params = lm
        prompts = _prompts(5, seed=0)
        steps = 6
        off, lo = _pool_streams(steer, model, params, "off", prompts, steps,
                                block_size=block_size,
                                collect_logits=True)
        lax_, ll = _pool_streams(steer, model, params, "lax", prompts, steps,
                                 block_size=block_size,
                                 collect_logits=True)
        assert off == lax_
        for a, b in zip(lo, ll):
            np.testing.assert_array_equal(a, b)   # bitwise logits
        for p, s in zip(prompts, off):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p)[None], steps))[0]
            np.testing.assert_array_equal(ref[len(p):], s)

    def test_eos_stop_bitwise(self, lm, steer):
        model, params = lm
        prompt = _prompts(1, seed=3)[0]
        probe = _pool_streams(steer, model, params, "off", [prompt], 10)[0]
        eos = probe[len(probe) // 2]
        a = _pool_streams(steer, model, params, "off", [prompt], 10, eos_id=eos)
        b = _pool_streams(steer, model, params, "lax", [prompt], 10, eos_id=eos)
        assert a == b

    def test_int8_kv_scale_pools_walk(self, lm, steer):
        """int8 KV: the scale pools ride the paged collection and the
        walk's per-block dequant matches the gathered view's."""
        model, params = lm
        kvm = model.clone(kv_quant="int8")
        prompts = _prompts(3, seed=5)
        a = _pool_streams(steer, kvm, params, "off", prompts, 6)
        b = _pool_streams(steer, kvm, params, "lax", prompts, 6)
        assert a == b

    def test_engine_kernel_token_exact(self, lm):
        """ServingEngine(paged) on the walk end to end == generate."""
        model, params = lm
        prompts = _prompts(6, seed=7)
        steps = 6
        with ServingEngine(model, params, num_slots=3, paged=True,
                           kv_block_size=8) as eng:
            assert eng.pool.kernel_mode == "lax"
            out = [list(eng.submit(p, steps).result(timeout=300)
                        .tokens) for p in prompts]
        for p, s in zip(prompts, out):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p)[None], steps))[0]
            np.testing.assert_array_equal(ref[len(p):], s)

    def test_prefix_hit_fill_pattern_bitwise(self, lm, steer):
        """A prefix-cache hit starts the lane's fill mid-table — the
        walk must be bitwise the gather from that offset too."""
        model, params = lm
        rs = np.random.RandomState(11)
        sys_p = rs.randint(0, VOCAB, (16,))
        prompts = [np.concatenate([sys_p, rs.randint(0, VOCAB, (3,))])
                   for _ in range(2)]
        outs = {}
        for kern in ("off", "lax"):
            steer(kern)
            with ServingEngine(model, params, num_slots=2, paged=True,
                               kv_block_size=8) as e:
                assert e.pool.kernel_mode == kern
                outs[kern] = [
                    list(e.submit(p, 5).result(timeout=300).tokens)
                    for p in prompts]
                snap = e.metrics_snapshot()
                assert snap["prefill_tokens_skipped"] > 0  # hit path
        assert outs["off"] == outs["lax"]


def _gather_ops(jaxpr, acc):
    """Every gather/dynamic-slice-family eqn in a closed jaxpr,
    recursively through sub-jaxprs (scan/while/pjit/custom_*)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            acc.append(eqn)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                sub = v.jaxpr if hasattr(v.jaxpr, "eqns") else v
                _gather_ops(sub if hasattr(sub, "eqns")
                            else sub.jaxpr, acc)
            elif isinstance(v, (list, tuple)):
                for w in v:
                    if hasattr(w, "jaxpr"):
                        sub = w.jaxpr
                        _gather_ops(sub if hasattr(sub, "eqns")
                                    else sub.jaxpr, acc)
    return acc


class TestNoFullSpanGather:
    """The acceptance assert: the kernel path's traced program never
    gathers a lane's whole table span from a pool; the legacy program
    does (positive control proving the detector sees such gathers)."""

    def _tick_pool_gathers(self, model, params, fused):
        """Blocks-gathered-per-lane for every gather whose operand is
        a KV pool, from the traced tick's jaxpr. The model walks at
        decode_prefix_block=8 (< max_len) so the fused walk's bounded
        per-step take is distinguishable from the full-span gather."""
        import math
        from horovod_tpu.models.transformer import (
            init_paged_pools, slot_decode_model)
        model = model.clone(decode_prefix_block=8)
        spec = paged_cache_spec(model, 8)
        num_blocks = 2 * spec.blocks_per_seq + 1
        pools = init_paged_pools(model, spec, num_blocks)
        L = 2
        dec = slot_decode_model(model)
        args = (pools, params,
                jnp.zeros((L, spec.blocks_per_seq), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.zeros((L,), jnp.float32),
                jnp.ones((L,), jnp.float32),
                jnp.stack([jax.random.PRNGKey(i) for i in range(L)]),
                jnp.zeros((L,), bool), jnp.zeros((L,), bool),
                jnp.int32(-1))
        jaxpr = jax.make_jaxpr(
            lambda *a: paged_decode_tick(dec, spec, *a, fused=fused)
        )(*args)
        gathers = _gather_ops(jaxpr.jaxpr, [])
        pool_shapes = {tuple(p.shape): math.prod(p.shape[1:])
                       for p in pools}
        per_lane = []
        for eqn in gathers:
            op = tuple(eqn.invars[0].aval.shape)
            out = eqn.outvars[0].aval
            if op in pool_shapes and out.shape:
                per_lane.append(
                    math.prod(out.shape) // (L * pool_shapes[op]))
        assert per_lane, "no pool gathers found — detector broken?"
        return per_lane, spec.blocks_per_seq

    def test_fused_walks_filled_blocks_only(self, lm):
        model, params = lm
        per_lane, nb = self._tick_pool_gathers(model, params,
                                               fused=True)
        assert max(per_lane) < nb, per_lane

    def test_detector_sees_legacy_full_gather(self, lm):
        model, params = lm
        per_lane, nb = self._tick_pool_gathers(model, params,
                                               fused=False)
        assert max(per_lane) >= nb, per_lane


class TestKernelModeResolution:
    def test_auto_defaults_to_walk(self, lm):
        """The geometry alone decides: the walk where
        decode_prefix_block fits the block size and max_len, the
        gather where it does not, each with its reason in words."""
        model, params = lm
        assert _paged_attention_way(model, 8)[0] == "lax"
        assert _paged_attention_way(
            model.clone(decode_prefix_block=0), 8)[0] == "off"
        assert PagedSlotPool(model, params, 1,
                             block_size=8).kernel_mode == "lax"
