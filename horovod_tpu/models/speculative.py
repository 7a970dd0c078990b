"""Speculative decoding (greedy): draft proposes, target verifies.

No reference equivalent — serving-side decode acceleration postdates
the reference. A small DRAFT model autoregressively proposes ``k``
tokens (k cheap ticks), then the TARGET model scores the whole
``[pending, p_1..p_k]`` block in ONE ``chunked_prefill`` append (the
S>1-onto-a-non-empty-cache path built for exactly this); the longest
prefix of proposals matching the target's argmax is accepted, plus
the target's own next token — between 1 and k+1 tokens per target
forward. Greedy acceptance makes the output EXACTLY the target
model's greedy decode — the draft only changes how many target
forward passes are spent per token (oracle:
`tests/test_speculative.py` pins token equality with
`models.generate`).

The cache trick: verifying writes K/V for all proposed positions; on
a rejection at offset ``a`` the caches must forget the rejected tail.
With the LINEAR cache that is just rewinding the per-layer
``cache_index`` (and ``pos_index``) scalars — entries past the index
are invisible to the attention mask and get overwritten by later
appends. Rolling-window caches physically overwrite slots, so
``window`` models are rejected (use plain `generate`).

Execution model: a HOST loop (acceptance length is data-dependent)
over per-shape jitted apply steps — the draft tick, the k-wide
verify, and single-tick tail each compile once per shape and are
cached across calls (`_jitted_step` keys on the flax module's
dataclass fields). The draft ticks chain device-side (no per-tick
host sync); one readback per ROUND (the proposals, when the verify
comparison needs them on host) is inherent to host-side control
flow.

Scope: batch 1 (the cache index is one scalar per layer — per-row
acceptance divergence cannot share it), greedy only (sampling needs
rejection-resampling; the greedy case has an exact-equality oracle).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _rewind(cache: Any, n: int) -> Any:
    """Every per-layer ``cache_index`` / ``pos_index`` scalar set to
    ``n`` — the rejected tail becomes invisible (mask) and will be
    overwritten by the next append."""
    def fix(path, leaf):
        key = getattr(path[-1], "key", None)
        if key in ("cache_index", "pos_index"):
            return jnp.asarray(n, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def _zeros_cache(model, B, prompt_dtype):
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((B, model.max_len), prompt_dtype))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


@functools.lru_cache(maxsize=None)
def _jitted_step(model, mode: str):
    """Compiled decode-apply for one model config.

    ``mode``: "last" — logits for the final position only (ticks,
    prefill: never materializes [1, S, vocab]); "all" — logits for
    every fed position (the verify block); "advance" — no head math
    at all (the draft's prompt prefill only warms its cache)."""

    def f(params, cache, toks):
        (hidden, head), mut = model.apply(
            {"params": params, "cache": cache}, toks,
            return_hidden=True, mutable=["cache"])
        if mode == "advance":
            return mut["cache"]
        h = hidden[:, -1:] if mode == "last" else hidden
        logits = jnp.einsum("bsd,vd->bsv", h, head.astype(h.dtype))
        return logits.astype(jnp.float32), mut["cache"]

    return jax.jit(f)


def generate_speculative(draft_model, draft_params, target_model,
                         target_params, prompt, steps: int, *,
                         k: int = 4,
                         return_stats: bool = False):
    """Greedy generation from ``target_model`` accelerated by
    ``draft_model`` proposals; returns ``[1, P + steps]`` tokens
    identical to `generate(target_model, ..., temperature=0)`.

    ``k``: proposals per round. Each round costs k draft ticks + ONE
    target forward over k+1 positions and yields between 1 and k+1
    tokens — the target's sequential-tick count drops by the
    acceptance rate, which is the entire speedup.
    """
    prompt = jnp.asarray(prompt)
    if prompt.ndim != 2 or prompt.shape[0] != 1:
        raise ValueError(
            f"speculative decoding is batch-1 (got {prompt.shape}); "
            "the per-layer cache index cannot diverge per row")
    if target_model.has_rolling_cache or draft_model.has_rolling_cache:
        raise ValueError(
            "sliding-window (rolling-cache) models cannot rewind "
            "rejected proposals; use models.generate")
    if draft_model.vocab_size != target_model.vocab_size:
        raise ValueError("draft and target vocab sizes differ")
    stats = {"rounds": 0, "draft_accepted": 0, "tokens": 0}
    if steps <= 0:
        return (prompt, stats) if return_stats else prompt
    P = prompt.shape[1]
    # Same bound as models.generate: the final token is never fed.
    for m, name in ((target_model, "target"), (draft_model, "draft")):
        if P + steps - 1 > m.max_len:
            raise ValueError(
                f"prompt+steps-1={P + steps - 1} exceeds {name} "
                f"max_len={m.max_len}")

    # chunked_prefill=True: the S>1-onto-non-empty-cache verify path.
    # The PREFILL itself runs through the cp=False clone so prompt
    # numerics are identical to models.generate's one-pass prefill.
    tgt = target_model.clone(decode=True, chunked_prefill=True)
    tgt_pre = target_model.clone(decode=True, chunked_prefill=False)
    drf = draft_model.clone(decode=True, chunked_prefill=True)
    drf_pre = draft_model.clone(decode=True, chunked_prefill=False)

    t_cache = _zeros_cache(tgt, 1, prompt.dtype)
    d_cache = _zeros_cache(drf, 1, prompt.dtype)
    tl, t_cache = _jitted_step(tgt_pre, "last")(
        target_params, t_cache, prompt)
    d_cache = _jitted_step(drf_pre, "advance")(
        draft_params, d_cache, prompt)
    pending = jnp.argmax(tl[:, -1], axis=-1).astype(prompt.dtype)

    draft_tick = _jitted_step(drf, "last")
    target_tick = _jitted_step(tgt, "last")

    out = [int(pending[0])]
    consumed = P          # tokens whose K/V both caches hold
    max_fill = min(target_model.max_len, draft_model.max_len)
    while len(out) < steps:
        # Verify appends k_eff+1 entries; keep them within the cache.
        k_eff = min(k, steps - len(out), max_fill - consumed - 1)
        if k_eff < 1:
            # Cache nearly full: finish with plain target ticks (the
            # final token never needs to be fed).
            while len(out) < steps:
                tl, t_cache = target_tick(
                    target_params, t_cache, pending[:, None])
                pending = jnp.argmax(tl[:, -1], axis=-1).astype(
                    prompt.dtype)
                out.append(int(pending[0]))
                consumed += 1
            break
        # Draft proposes k_eff tokens, one tick each, starting from
        # the pending (not-yet-fed) token. `cur` stays a DEVICE array
        # across the chain — no host sync until the whole round's
        # proposals are needed for the acceptance comparison.
        dev_proposals = []
        cur = pending[:, None]
        for _ in range(k_eff):
            dl, d_cache = draft_tick(draft_params, d_cache, cur)
            cur = jnp.argmax(dl[:, -1:], axis=-1).astype(prompt.dtype)
            dev_proposals.append(cur)
        proposals = [int(c[0, 0]) for c in dev_proposals]
        # Target verifies the whole round in one forward: feeding
        # [pending, p_1..p_k] yields its greedy choice AFTER each.
        block = jnp.asarray([[int(pending[0])] + proposals],
                            prompt.dtype)
        tl, t_cache = _jitted_step(tgt, "all")(
            target_params, t_cache, block)
        greedy = np.asarray(jnp.argmax(tl[0], axis=-1))  # [k_eff+1]
        a = 0
        while a < k_eff and int(greedy[a]) == proposals[a]:
            a += 1
        # Accept p_1..p_a plus the target's own token (a == k_eff:
        # every proposal matched and greedy[k_eff] is the free bonus).
        new = proposals[:a] + [int(greedy[a])]
        out.extend(new)
        stats["rounds"] += 1
        stats["draft_accepted"] += a
        consumed += 1 + a      # pending + accepted proposals
        pending = jnp.asarray([new[-1]], prompt.dtype)
        if a == k_eff:
            # Full acceptance: p_k entered the TARGET cache via the
            # verify block but was never fed to the draft (its ticks
            # stop at p_{k-1}), so the draft cache lacks position
            # consumed-1 — write it before the forward rewind admits
            # that slot.
            d_cache = _jitted_step(drf, "advance")(
                draft_params, d_cache,
                jnp.asarray([[proposals[-1]]], prompt.dtype))
        t_cache = _rewind(t_cache, consumed)
        d_cache = _rewind(d_cache, consumed)

    tokens = jnp.concatenate(
        [prompt, jnp.asarray([out[:steps]], prompt.dtype)], axis=1)
    stats["tokens"] = len(out[:steps])
    if return_stats:
        return tokens, stats
    return tokens
