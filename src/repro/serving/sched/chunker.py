"""Chunked-prefill forward pass (DESIGN.md §14).

One engine step advances every mid-prefill slot by its planned chunk in
a single batched forward, reusing the (B, S) decode window that
speculative verify proved bitwise-equal to sequential decode
(DESIGN.md §10): the window scatters each row's S token K/V entries at
its own position offset *before* any query attends, and causal masking
keeps queries off positions at or beyond their own — so prefilling a
prompt 64 tokens at a time commits exactly the same cache bytes and
logits as the one-shot whole-prompt prefill. Token exactness vs
whole-prompt admission follows by greedy determinism.

Window packing: jobs are rectangularized to ``S = max(chunk)``; shorter
rows pad by repeating their last real token. Padded positions write
garbage K/V *inside the row's own slot/pages* at positions the row's
next chunk (or its first decode steps) overwrites before any real query
can attend there — the same overwrite-before-read invariant free-slot
garbage lanes already rely on. ``plan_chunks`` caps S so no padded row
writes past ``max_len`` (no reliance on XLA out-of-bounds scatter
semantics).

Compile discipline: a fresh XLA compile mid-traffic costs seconds — a
p99 disaster — so the window shape space is pinned small and warmed
ahead of time. The row dimension is always padded to the full slot
count (pad rows are write-discarded: dense rows live only in the
gathered copy that is never inserted back; paged pad rows carry an
all-zeros block table, routing every write to the trash page), and
``plan_chunks`` rounds S down to a power of two — so the only shapes
that exist are (max_slots, pow2), and ``warmup`` compiles them all at
``load()`` time.

The forward traces under ``ops.serving_phase("chunk")``: flattened GEMM
M = P·S rows — bigger than decode's GEMV, smaller than a grouped
prefill — gets its own autotune phase so chunk plans never thrash the
decode or prefill entries. Paged attention is not flattened on the chip:
``ops.paged_window_attention`` walks each row's pages once for all S
tokens (one grid step per row and page); off the chip its ``jax``
lowering runs the window as P·S single-query rows.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

__all__ = ["ChunkRunner", "checks_and_stats"]


def checks_and_stats(ok, moe_stats):
    """The one int32 vector a step's program returns for its readback:
    each row's finite check, then its MoE stats ((row, held expert) pairs
    computed, held experts with a row)."""
    return jnp.concatenate([ok.astype(jnp.int32).reshape(-1),
                            moe_stats.astype(jnp.int32)])


class ChunkRunner:
    """Jit'd chunk forward over dense slot rows or paged block tables,
    at a fixed row count (``rows`` = the engine's slot count)."""

    def __init__(self, model, max_len: int, paged: bool, rows: int):
        self.model = model
        self.max_len = max_len
        self.paged = paged
        self.rows = rows

        # the function names name the programs in a profiler trace
        def engine_chunk_window(params, layers, pos, toks, real,
                                table=None):
            # tokens not ``real`` (pad rows, repeat-last padding) route to
            # no expert
            cache = {"layers": layers, "pos": pos, "token_mask": real}
            if table is not None:
                cache["block_table"] = table
            logits, new_cache, stats = model.decode_step(
                params, cache, toks, with_stats=True)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            return (new_cache["layers"], greedy,
                    checks_and_stats(ok, stats))

        def engine_chunk_gather(layers, idx):
            return jax.tree.map(lambda x: x[:, idx], layers)

        # dense: donates the *gathered* P-row copy, never the pool tree
        self._fwd = jax.jit(engine_chunk_window, donate_argnums=(1,))
        if not paged:
            self._gather = jax.jit(engine_chunk_gather)

    # ------------------------------------------------------------------
    def pack_window(self, jobs) -> Tuple[List[int], np.ndarray, np.ndarray,
                                         np.ndarray]:
        """Rectangularize ``[(slot, req, c)]`` into the fixed-row batched
        window: real slot list, (rows,) start positions, (rows, S) tokens
        with repeat-last padding, and the (rows, S) mask of real tokens.
        Rows beyond ``len(jobs)`` are pad lanes (position 0, token 0)
        whose writes the caller discards."""
        slots = [s for s, _, _ in jobs]
        pos = np.zeros(self.rows, np.int32)
        s_max = max(c for _, _, c in jobs)
        toks = np.zeros((self.rows, s_max), np.int32)
        real = np.zeros((self.rows, s_max), bool)
        for i, (_, req, c) in enumerate(jobs):
            a = req.prefill_pos
            pos[i] = a
            toks[i, :c] = req.prompt[a:a + c]
            toks[i, c:] = req.prompt[a + c - 1]
            real[i, :c] = True
        return slots, pos, toks, real

    def _pad_table(self, pool, slots) -> jnp.ndarray:
        """(rows, T) block table: real rows from the pool, pad rows all
        zeros — page 0 is the trash page, so pad-lane writes vanish by
        the same mechanism shared-prefix COW relies on."""
        table = np.zeros((self.rows, pool.table.shape[1]),
                         pool.table.dtype)
        if slots:
            table[:len(slots)] = pool.table[slots]
        return jnp.asarray(table)

    def advance(self, params, pool, jobs) -> Tuple[jax.Array, jax.Array]:
        """Dispatch one chunk window over ``pool`` (mutating its cache
        tree in place) and return ``(greedy, ok)`` as device arrays whose
        first ``len(jobs)`` rows follow ``jobs`` order: greedy[i, j] is
        the argmax after job i's token j (a completing row reads its
        first output token at its last real chunk position), ok[i] the
        per-row finite-logits guard followed by the window's MoE stats
        (``checks_and_stats``). ``greedy`` has one entry per row the
        window computes, pad rows included."""
        slots, pos, toks, real = self.pack_window(jobs)
        dev_pos = jnp.asarray(pos)
        dev_toks = jnp.asarray(toks)
        dev_real = jnp.asarray(real)
        if self.paged:
            with kops.serving_phase("chunk"):
                pool.layers, greedy, ok = self._fwd(
                    params, pool.layers, dev_pos, dev_toks, dev_real,
                    self._pad_table(pool, slots))
        else:
            # pad lanes gather slot 0's rows; the garbage they compute
            # stays in the gathered copy, which is inserted back only at
            # the real slots
            idx = np.zeros(self.rows, np.int32)
            idx[:len(slots)] = slots
            gathered = self._gather(pool.layers, jnp.asarray(idx))
            with kops.serving_phase("chunk"):
                gathered, greedy, ok = self._fwd(
                    params, gathered, dev_pos, dev_toks, dev_real)
            pool.insert(slots, jax.tree.map(lambda x: x[:, :len(slots)],
                                            gathered))
        return greedy, ok

    def warmup(self, params, pool, windows) -> None:
        """Compile every (rows, S) window shape ahead of traffic: one
        all-pad forward per S in ``windows``. Pad-lane writes are
        discarded (dense) or routed to the trash page (paged), so the
        pool's cache content is untouched."""
        for s in windows:
            pos = jnp.zeros(self.rows, jnp.int32)
            toks = jnp.zeros((self.rows, int(s)), jnp.int32)
            real = jnp.zeros((self.rows, int(s)), jnp.bool_)
            with kops.serving_phase("chunk"):
                if self.paged:
                    pool.layers, _, _ = self._fwd(
                        params, pool.layers, pos, toks, real,
                        self._pad_table(pool, []))
                else:
                    gathered = self._gather(
                        pool.layers, jnp.zeros(self.rows, jnp.int32))
                    self._fwd(params, gathered, pos, toks, real)
