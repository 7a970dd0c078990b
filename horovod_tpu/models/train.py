"""SPMD training-step builder for flax CNN models (BatchNorm state).

The CNN analogue of `horovod_tpu.jax.make_train_step` for models with
mutable `batch_stats` and dropout RNG — the training loop shape of the
reference's `examples/tensorflow_mnist.py` / tf_cnn_benchmarks runs,
built the TPU way: one jitted shard_map over the `data` axis with fused
gradient psum (tensor fusion) and donated state.

BatchNorm stats stay per-replica-local and are then allreduce-averaged
like the reference's effective behavior under checkpoint-on-rank-0 (each
GPU keeps local stats; averaging keeps replicas consistent so the
rank-0 checkpoint contract of SURVEY §5.4 holds).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops.fusion import (exchange_for, fused_allreduce_tree,
                                    step_compiler_options)
from horovod_tpu.runtime import state as _state


def make_cnn_train_step(model, tx: optax.GradientTransformation,
                        *, mesh=None, axis_name: Optional[str] = None,
                        fusion_threshold: Optional[int] = None,
                        reduce_dtype: Optional[Any] = None,
                        donate: bool = True,
                        remat: bool = False,
                        examples_per_step: Optional[float] = None,
                        flops_per_step: Optional[float] = None
                        ) -> Callable:
    """Returns step(train_state, batch, rng) -> (train_state, loss) where
    train_state = {params, batch_stats, opt_state} (a plain dict pytree,
    replicated) and batch = (images, labels) sharded on dim 0.

    remat=True wraps the forward pass in jax.checkpoint, trading FLOPs
    for HBM — the standard TPU recipe for deep CNNs at large batch.

    Every returned step is bracketed by the observability plane
    (docs/observability.md): the `hvd_training_steps_total` counter
    and `hvd_training_step_seconds` cadence histogram always record;
    declaring the step's work turns on the throughput gauges —
    ``examples_per_step`` drives `hvd_training_tokens_per_s` and
    ``flops_per_step`` (analytic, from the model's shapes)
    the `hvd_training_mfu` gauge against the device's known peak
    (`utils/profile_analysis.py` math).
    """
    st = _state.check_initialized()
    mesh = mesh or st.mesh
    axis = axis_name or st.axis_name
    # An hvd.DistributedOptimizer performs its own gradient allreduce
    # (possibly compressed — PowerSGD must see RAW local grads, and a
    # second mean would also waste a bucket pass); the step factory
    # only reduces for plain optax transforms. The factory's own wire
    # knobs would then be silently dead — refuse instead of letting a
    # caller believe their reduce_dtype took effect.
    from horovod_tpu.jax import _DistributedTransformation
    tx_distributed = isinstance(tx, _DistributedTransformation)
    if tx_distributed and (fusion_threshold is not None
                           or reduce_dtype is not None):
        raise ValueError(
            "tx is an hvd.DistributedOptimizer, which owns the "
            "gradient allreduce — pass fusion_threshold/reduce_dtype "
            "to DistributedOptimizer(...) instead of the step factory")

    def loss_fn(params, batch_stats, images, labels, rng):
        def fwd(p, imgs):
            return model.apply(
                {"params": p, "batch_stats": batch_stats},
                imgs, train=True, mutable=["batch_stats"],
                rngs={"dropout": rng})
        if remat:
            fwd = jax.checkpoint(fwd)
        logits, mutated = fwd(params, images)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    def step(state, batch, rng):
        images, labels = batch
        rng = jax.random.fold_in(rng, lax.axis_index(axis))
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], state["batch_stats"],
                                   images, labels, rng)
        with exchange_for(mesh, axis):
            if not tx_distributed:
                grads = fused_allreduce_tree(
                    grads, axis_name=axis, average=True,
                    threshold=fusion_threshold, reduce_dtype=reduce_dtype)
            loss = lax.pmean(loss, axis)
            new_stats = jax.tree.map(lambda x: lax.pmean(x, axis),
                                     new_stats)
            updates, new_opt = tx.update(grads, state["opt_state"],
                                         state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "batch_stats": new_stats,
                 "opt_state": new_opt}, loss)

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    donate_argnums = (0,) if donate else ()
    from horovod_tpu.jax import commit_step_state
    from horovod_tpu.utils.timeline import step_bracket
    compiled = jax.jit(
        sharded, donate_argnums=donate_argnums,
        compiler_options=step_compiler_options(mesh, axis) or None)

    def placed(state, batch, rng):
        return compiled(commit_step_state(mesh, state), batch, rng)

    jitted = step_bracket(placed)
    jitted.__wrapped__ = compiled
    return _obs_step(_chaos_step(jitted),
                     tokens_per_step=examples_per_step,
                     flops_per_step=flops_per_step)


def _chaos_step(step_fn):
    """Chaos sites for one train-step invocation (host-side wrapper;
    disarmed cost is one global None check per step):

    * ``step_exception`` — a worker dies mid-step (the reference's
      "one rank raised" scenario): raises `ChaosError` before the
      dispatch, so the step never ran and state was not consumed.
    * ``grad_nan`` — a diverged step: the returned loss AND params are
      poisoned with NaN, exactly what an inf/NaN gradient produces
      after `apply_updates` — the `NaNGuard` rollback path's fault.
    """
    from horovod_tpu.resilience import chaos

    def stepped(state, batch, rng):
        if chaos.fires("step_exception"):
            raise chaos.ChaosError(
                "injected worker exception mid-step "
                "(site step_exception)")
        new_state, loss = step_fn(state, batch, rng)
        if chaos.fires("grad_nan"):
            nan = jnp.float32(jnp.nan)
            new_state = dict(
                new_state,
                params=jax.tree.map(lambda x: x * nan.astype(x.dtype),
                                    new_state["params"]))
            loss = loss * nan
        return new_state, loss

    # `__wrapped__` keeps resolving to the innermost JITTED step (the
    # contract step_bracket established and tests/test_fusion.py's HLO
    # introspection relies on: `step.__wrapped__.lower(...)`).
    stepped.__wrapped__ = getattr(step_fn, "__wrapped__", step_fn)
    return stepped


def _obs_step(step_fn, *, tokens_per_step=None, flops_per_step=None,
              name: str = "train_step"):
    """Observability bracket around one train-step invocation: step
    cadence into `hvd_training_step_seconds`/`hvd_training_steps_total`
    and, when the work per step is declared, the tokens-per-second and
    MFU gauges (obs/profiling.StepProfiler). Failed steps (a chaos
    `step_exception`, a real fault) are NOT recorded — the cadence
    histogram is the healthy-step distribution."""
    import time as _time

    from horovod_tpu.obs import straggler as _straggler
    from horovod_tpu.obs.profiling import StepProfiler
    prof = StepProfiler(name, tokens_per_step=tokens_per_step,
                        flops_per_step=flops_per_step)

    def stepped(state, batch, rng):
        t_enter = _time.time()
        with prof.step():
            out = step_fn(state, batch, rng)
        # The fusion-buffer cycle's straggler leg (obs/straggler.py):
        # each step hosts one bucketed-allreduce cycle, and its
        # host-side enter/exit pair is the per-rank timestamp the
        # cross-rank skew report is built from. Failed steps (the
        # chaos step_exception above raised) are skipped, like the
        # cadence histogram.
        _straggler.tracker().record("fusion_cycle",
                                    _time.time() - t_enter)
        return out

    stepped.__wrapped__ = getattr(step_fn, "__wrapped__", step_fn)
    stepped.__obs_profiler__ = prof
    return stepped


def init_cnn_state(model, tx: optax.GradientTransformation, rng,
                   sample_input) -> dict:
    """Initialize {params, batch_stats, opt_state} for a CNN model.

    init is jitted: eager tracing dispatches every initializer op
    individually, which takes minutes for Inception-sized models."""
    # hvd: disable=HVD003(one-shot model init at setup — jitted for tracing speed, not reused)
    variables = jax.jit(lambda r, x: model.init(r, x, train=False))(
        rng, sample_input)
    # Strip nn.Partitioned boxes (TP-annotated models like ViT): the
    # train step passes plain arrays through apply, same as the LM
    # path; CNN models without annotations are untouched.
    from horovod_tpu.parallel.tensor import unbox
    params = unbox(variables["params"])
    batch_stats = variables.get("batch_stats", {})
    return {"params": params, "batch_stats": batch_stats,
            "opt_state": tx.init(params)}
