"""Multi-token verification for self-speculative decoding (DESIGN.md §10).

One jitted call per engine round runs the whole ``(slots, k+1)`` window —
the newest committed token plus the draft's ``k`` proposals — through the
target's ``LM.decode_step``. The window forward is *bitwise* equal to
``k+1`` sequential single-token decodes (pinned in tests/test_spec.py for
dense and paged caches): every window token's logits are exactly what
sequential greedy decode at its position would have produced, so the
longest-prefix-match acceptance below emits, by construction, a prefix of
the sequential engine's token stream — speculative serving is token-exact,
not approximately so.

Shape note for the kernels: the verify forward's GEMMs are M = slots·(k+1)
— the small-GEMM regime where the paper's sparse ternary kernels beat the
GEMV-shaped plain decode (the entire point of converting decode into
verify). The engine traces this call under ``serving_phase("verify")`` so
those dispatches autotune separately from the M = slots decode entries.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["longest_prefix_match", "make_verify_step"]


def longest_prefix_match(window: jnp.ndarray, greedy: jnp.ndarray):
    """Greedy (exact-match) acceptance, jit-safe.

    ``window`` (B, k+1): the fed tokens ``[t, d_1..d_k]``; ``greedy``
    (B, k+1): the target's argmax at each window position (``greedy[:, j]``
    is the target's next token after ``window[:, j]``). Draft token
    ``d_{j+1}`` is accepted iff it equals ``greedy[:, j]`` *and* every
    earlier draft token was accepted. Returns ``(n_acc (B,), bonus (B,))``:
    the per-slot accepted count in [0, k] and the bonus token
    ``greedy[b, n_acc[b]]`` — the target's continuation after the last
    accepted token, emitted for free (so a round always emits
    ``n_acc + 1`` tokens)."""
    match = (window[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    bonus = jnp.take_along_axis(greedy, n_acc[:, None], axis=1)[:, 0]
    return n_acc, bonus


def make_verify_step(model, max_len: int, k: int, *, paged: bool = False,
                     guard: bool = False):
    """Build the jitted verify step for a target ``LM``.

    Dense: ``(params, layers, pos, window) ->
    (layers, greedy (B, k+1), n_acc (B,), bonus (B,))``; paged takes the
    device block table after ``layers``. The cache-position clamp keeps
    free slots' garbage window writes in range — live rows never clamp
    (the engine reserves ``k`` positions of headroom at submit).

    ``guard=True`` is the fault-hardened variant (DESIGN.md §11): the call
    takes a trailing ``nan_mask (B,)`` bool (fault injection corrupts the
    masked slots' window logits to NaN *before* the guard, so the guard is
    exercised end to end; the all-false mask is a bitwise no-op) and
    returns a trailing ``ok (B,)`` finite-check over each slot's whole
    window — the engine quarantines ``~ok`` slots instead of committing
    their garbage."""

    def verify(params, layers, pos, window, table=None, nan_mask=None):
        cache = {"layers": layers, "pos": jnp.minimum(pos, max_len - 1 - k)}
        if table is not None:
            cache["block_table"] = table
        logits, new_cache = model.decode_step(params, cache, window)
        if nan_mask is not None:
            logits = jnp.where(nan_mask[:, None, None], jnp.nan, logits)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        n_acc, bonus = longest_prefix_match(window, greedy)
        out = (new_cache["layers"], greedy, n_acc, bonus)
        if nan_mask is not None:
            ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            out = out + (ok,)
        return out

    # the function name names the program in a profiler trace
    if paged and guard:
        def engine_verify(params, layers, table, pos, window, mask):
            return verify(params, layers, pos, window, table, mask)
    elif paged:
        def engine_verify(params, layers, table, pos, window):
            return verify(params, layers, pos, window, table)
    elif guard:
        def engine_verify(params, layers, pos, window, mask):
            return verify(params, layers, pos, window, None, mask)
    else:
        def engine_verify(params, layers, pos, window):
            return verify(params, layers, pos, window)
    return jax.jit(engine_verify, donate_argnums=(1,))
