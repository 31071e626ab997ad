"""Block-shape autotuner for the ternary Pallas kernels.

The TPU analogue of the paper's unroll-factor grid search (Figs 2-4): for a
given (M, K, N, sparsity, impl) problem, sweep candidate
(block_m, block_n, block_k) shapes and keep the winner. Two scoring modes:

* ``measure``  -- wall-clock the compiled kernel (only meaningful on a real
                  TPU backend; interpret-mode timing is Python-bound noise);
* ``model``    -- deterministic analytic score: modeled HBM-bound time for
                  the tile traffic (X re-reads per N-tile, packed W re-reads
                  per M-tile, output write) plus grid-overhead and
                  VMEM-pressure penalties. Used automatically off-TPU so the
                  tuner is reproducible in CI.

Winners are cached twice: in-process (dict) and on disk as JSON so tuning
survives across processes. Cache file format (DESIGN.md §5)::

    {"version": 1,
     "entries": {"dense:m128:k4096:n4096:s0.25": [128, 128, 512], ...}}

Keys bucket M to the next power of two and sparsity to the paper's grid
{1, 1/2, 1/4, 1/8, 1/16, 1/32}, so serving shapes that differ only in batch
hit the same entry. Consumers: ``ops.ternary_gemm`` (block args default to
the tuned shape), the ternary linear in ``models/layers.py``,
``benchmarks/kernel_bench.py``, and ``scripts/hillclimb.py``.

**Cross-op fusion keys** (DESIGN.md §12): the fused MLP lowering plans one
shared ``block_m`` plus per-projection (block_n, block_k) pairs for both
weights of the chain. Those live under ``fused:...`` keys — five-int
entries (``FusedBlockConfig``) in the same cache file, keyed on *both*
weights' shapes under the existing phase keys::

    "fused:m128:k1024:f4096:n1024:s1.0x1.0:pprefill": [128, 128, 512,
                                                       128, 512]

A fused entry is composed from the two per-GEMM entries on miss, so the
fused kernel's K/N tiling always agrees with what the unfused chain would
have used — that agreement is what makes the fused output bitwise equal.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Callable, Dict, List, Optional, Tuple

from repro.kernels.ternary_gemm import K_PER_WORD

__all__ = ["BlockConfig", "FusedBlockConfig", "Autotuner", "get_tuner",
           "DEFAULT_CACHE_PATH"]

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE_PATH = os.path.join("experiments", "autotune_cache.json")

# Modeled v5e-class machine — the single source for these numbers
# (benchmarks/kernel_bench.py imports them from here). Published peaks of
# one TPU v5e chip (Google Cloud, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s.
HBM_BW = 819e9
PEAK_FLOPS = 197e12
VMEM_BYTES = 16 * 2**20
MODELED_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")


def describes_device() -> bool:
    """Whether the constants above describe the attached chip. Off the
    TPU there is no chip to describe and the model stands alone."""
    import jax
    return (jax.default_backend() != "tpu"
            or jax.devices()[0].device_kind in MODELED_DEVICE_KINDS)


def check_roofline_device() -> None:
    """Raise on a TPU that is not a v5e: a roofline against v5e peaks
    would be wrong there, and no table of peaks per device exists yet."""
    if not describes_device():
        import jax
        raise RuntimeError(
            f"the roofline model holds TPU v5e peaks; this device is "
            f"{jax.devices()[0].device_kind!r}, for which no peaks are "
            f"recorded")


# Candidate grid: the shapes the paper-style search sweeps. block_k spans
# the K-reuse axis, block_m/n the MXU tile axes.
CANDIDATE_BLOCKS: Tuple[Tuple[int, int, int], ...] = (
    (128, 128, 256), (128, 128, 512), (128, 128, 1024),
    (128, 256, 512), (256, 128, 512), (256, 256, 512),
    (64, 128, 512), (8, 128, 512), (8, 256, 512),
)

# Extra candidates considered for the serving decode phase: M = slots is
# GEMV-shaped (tiny block_m), so trade the M tile for deeper K reuse. The
# speculative-decoding verify phase (M = slots·(k+1), still small-M but
# GEMM-shaped) shares the widened grid so its own cache entries can land
# on the GEMV-leaning shapes when the model scores them best.
DECODE_CANDIDATE_BLOCKS: Tuple[Tuple[int, int, int], ...] = (
    (8, 128, 1024), (8, 256, 1024), (8, 512, 512), (16, 256, 512),
)

SPARSITY_GRID = (1.0, 0.5, 0.25, 0.125, 0.0625, 0.03125)


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    block_m: int
    block_n: int
    block_k: int

    def as_list(self) -> List[int]:
        return [self.block_m, self.block_n, self.block_k]

    def vmem_bytes(self, dtype_bytes: int = 2) -> int:
        x = self.block_m * self.block_k * dtype_bytes
        w = (self.block_k // K_PER_WORD) * self.block_n * 4
        dec = self.block_k * self.block_n * dtype_bytes
        acc = self.block_m * self.block_n * 4
        out = self.block_m * self.block_n * dtype_bytes
        return x + w + dec + acc + out


@dataclasses.dataclass(frozen=True)
class FusedBlockConfig:
    """Block plan for one fused MLP pair: a shared M tile plus the up- and
    down-projection's own (N, K) tiles. Serialized as a five-int cache
    entry (the arity is what distinguishes it from ``BlockConfig`` on
    load)."""

    block_m: int
    block_n1: int
    block_k1: int
    block_n2: int
    block_k2: int

    def as_list(self) -> List[int]:
        return [self.block_m, self.block_n1, self.block_k1,
                self.block_n2, self.block_k2]

    def up(self) -> BlockConfig:
        return BlockConfig(self.block_m, self.block_n1, self.block_k1)

    def down(self) -> BlockConfig:
        return BlockConfig(self.block_m, self.block_n2, self.block_k2)


def _pow2_bucket(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


def _sparsity_bucket(s: float) -> float:
    return min(SPARSITY_GRID, key=lambda g: abs(g - max(min(s, 1.0), 0.0)))


def cache_key(m: int, k: int, n: int, sparsity: float = 1.0,
              impl: str = "dense", fixed_n: Optional[int] = None,
              fixed_k: Optional[int] = None,
              phase: Optional[str] = None) -> str:
    """Layout-pinned block shapes (TiledTernary tile_n/tile_k) are part of
    the problem identity — two packs of the same logical shape with
    different tiles must not share (and thrash) one entry. Likewise the
    serving phase: decode (M=slots, GEMV-shaped) and prefill (M=B·L,
    GEMM-shaped) problems tune separately even at equal bucketed M."""
    key = (f"{impl}:m{_pow2_bucket(m)}:k{k}:n{n}"
           f":s{_sparsity_bucket(sparsity)}")
    if fixed_n is not None:
        key += f":bn{fixed_n}"
    if fixed_k is not None:
        key += f":bk{fixed_k}"
    if phase is not None:
        key += f":p{phase}"
    return key


def fused_cache_key(m: int, k: int, ff: int, n: int,
                    sparsity_up: float = 1.0, sparsity_down: float = 1.0,
                    phase: Optional[str] = None) -> str:
    """Key for a fused MLP pair: both weights' shapes (K->FF up, FF->N
    down) and both occupancies are the problem identity, under the same
    phase suffix the per-GEMM keys use."""
    key = (f"fused:m{_pow2_bucket(m)}:k{k}:f{ff}:n{n}"
           f":s{_sparsity_bucket(sparsity_up)}"
           f"x{_sparsity_bucket(sparsity_down)}")
    if phase is not None:
        key += f":p{phase}"
    return key


class Autotuner:
    """Process-wide block-shape cache with JSON persistence."""

    def __init__(self, path: Optional[str] = None, mode: str = "auto"):
        self._path = path if path is not None else os.environ.get(
            CACHE_ENV, DEFAULT_CACHE_PATH)
        self._mode = mode          # auto | model | measure
        self._cache: Dict[str, BlockConfig] = {}
        self._lock = threading.Lock()
        self._loaded = False

    # --- persistence ------------------------------------------------------
    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self._path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return            # unreadable / corrupt file: degrade to re-tune
        for key, blk in data.get("entries", {}).items():
            # arity decides the entry type: 3 ints = one GEMM, 5 = a fused
            # pair. A malformed entry drops alone — it must not take the
            # rest of the cache down with it.
            try:
                ints = [int(v) for v in blk]
            except (ValueError, TypeError):
                continue
            if len(ints) == 3:
                self._cache[key] = BlockConfig(*ints)
            elif len(ints) == 5:
                self._cache[key] = FusedBlockConfig(*ints)

    def save(self) -> None:
        entries = {key: cfg.as_list() for key, cfg in sorted(
            self._cache.items())}
        d = os.path.dirname(self._path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self._path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": entries}, f, indent=1)
        os.replace(tmp, self._path)

    # --- candidate generation / scoring ----------------------------------
    def candidates(self, m: int, k: int, n: int,
                   fixed_n: Optional[int] = None,
                   fixed_k: Optional[int] = None,
                   phase: Optional[str] = None) -> List[BlockConfig]:
        """VMEM-feasible candidates; fixed_n/fixed_k pin block shapes that
        are dictated by the data layout (TiledTernary tile shapes). The
        decode phase widens the grid with GEMV-shaped candidates."""
        grid = CANDIDATE_BLOCKS
        if phase in ("decode", "verify"):
            grid = grid + DECODE_CANDIDATE_BLOCKS
        out, seen = [], set()
        for bm, bn, bk in grid:
            bm = min(bm, _pow2_bucket(max(m, 8)))
            bn = fixed_n if fixed_n is not None else bn
            bk = fixed_k if fixed_k is not None else bk
            cfg = BlockConfig(bm, bn, bk)
            if cfg in seen or cfg.vmem_bytes() > VMEM_BYTES:
                continue
            seen.add(cfg)
            out.append(cfg)
        if not out:   # degenerate fallback: smallest legal tile
            out.append(BlockConfig(min(8, _pow2_bucket(max(m, 8))),
                                   fixed_n or 128, fixed_k or 256))
        return out

    def _model_score(self, cfg: BlockConfig, m: int, k: int, n: int,
                     sparsity: float) -> float:
        """Modeled seconds for one GEMM pass, lower is better. Occupied
        fraction scales the K-dimension traffic (the skip path's lever)."""
        occ = max(min(sparsity, 1.0), 1.0 / 64)
        mp = -(-m // cfg.block_m) * cfg.block_m
        npad = -(-n // cfg.block_n) * cfg.block_n
        kp = -(-k // cfg.block_k) * cfg.block_k
        n_tiles = npad // cfg.block_n
        m_tiles = mp // cfg.block_m
        k_steps = max(1, round((kp // cfg.block_k) * occ))
        x_bytes = m_tiles * n_tiles * k_steps * cfg.block_m * cfg.block_k * 2
        w_bytes = (m_tiles * n_tiles * k_steps
                   * (cfg.block_k // K_PER_WORD) * cfg.block_n * 4)
        out_bytes = mp * npad * 2
        t_mem = (x_bytes + w_bytes + out_bytes) / HBM_BW
        grid = m_tiles * n_tiles * k_steps
        t_grid = grid * 1e-6          # per-step dispatch/DMA-setup overhead
        # mild pressure penalty as the working set approaches VMEM capacity
        t_vmem = t_mem * 0.25 * (cfg.vmem_bytes() / VMEM_BYTES)
        return t_mem + t_grid + t_vmem

    def _measure(self, cfg: BlockConfig, run: Callable[[BlockConfig], None],
                 repeats: int = 3) -> float:
        import time
        run(cfg)                      # compile + warm
        t0 = time.perf_counter()
        for _ in range(repeats):
            run(cfg)
        return (time.perf_counter() - t0) / repeats

    # --- the public entry -------------------------------------------------
    def lookup(self, m: int, k: int, n: int, sparsity: float = 1.0,
               impl: str = "dense", fixed_n: Optional[int] = None,
               fixed_k: Optional[int] = None,
               run: Optional[Callable[[BlockConfig], None]] = None,
               phase: Optional[str] = None) -> BlockConfig:
        """Best block shape for the problem; tunes and persists on miss.

        ``run``, if given and the mode resolves to ``measure``, is called
        per candidate to produce a wall-clock score; otherwise the analytic
        model decides (deterministic, CI-safe). ``phase`` ("prefill" /
        "decode" / None) separates serving-phase entries.
        """
        key = cache_key(m, k, n, sparsity, impl, fixed_n=fixed_n,
                        fixed_k=fixed_k, phase=phase)
        with self._lock:
            self._load()
            hit = self._cache.get(key)
        if isinstance(hit, BlockConfig) \
                and (fixed_n is None or hit.block_n == fixed_n) \
                and (fixed_k is None or hit.block_k == fixed_k):
            return hit

        mode = self._mode
        if mode == "auto":
            import jax
            mode = ("measure"
                    if run is not None and jax.default_backend() == "tpu"
                    else "model")
        cands = self.candidates(m, k, n, fixed_n=fixed_n, fixed_k=fixed_k,
                                phase=phase)
        if mode == "measure" and run is not None:
            scored = [(self._measure(c, run), c) for c in cands]
        else:
            scored = [(self._model_score(c, m, k, n, sparsity), c)
                      for c in cands]
        best = min(scored, key=lambda sc: sc[0])[1]
        with self._lock:
            self._cache[key] = best
            try:
                self.save()
            except OSError:
                pass      # read-only FS: in-process cache still works
        return best

    def lookup_fused(self, m: int, k: int, ff: int, n: int,
                     sparsity_up: float = 1.0, sparsity_down: float = 1.0,
                     fixed_n1: Optional[int] = None,
                     fixed_k1: Optional[int] = None,
                     fixed_n2: Optional[int] = None,
                     fixed_k2: Optional[int] = None,
                     phase: Optional[str] = None) -> FusedBlockConfig:
        """Block plan for a fused ``(K->FF) -> act -> (FF->N)`` MLP pair.

        On a miss the entry is *composed* from the two per-GEMM ``lookup``
        results (so fused and unfused chains always tile K/N identically —
        the bitwise-equality contract) with the shared M tile taken as the
        smaller of the two, then persisted under the fused key so later
        plans are a single cache hit. ``fixed_n1``/``fixed_k1`` pin the
        up-projection tiles when the pack layout dictates them (Tiled
        weights)."""
        key = fused_cache_key(m, k, ff, n, sparsity_up, sparsity_down,
                              phase=phase)
        with self._lock:
            self._load()
            hit = self._cache.get(key)
        if isinstance(hit, FusedBlockConfig) \
                and (fixed_n1 is None or hit.block_n1 == fixed_n1) \
                and (fixed_k1 is None or hit.block_k1 == fixed_k1) \
                and (fixed_n2 is None or hit.block_n2 == fixed_n2) \
                and (fixed_k2 is None or hit.block_k2 == fixed_k2):
            return hit
        up = self.lookup(m, k, ff, sparsity=sparsity_up,
                         impl="skip" if fixed_n1 is not None else "dense",
                         fixed_n=fixed_n1, fixed_k=fixed_k1, phase=phase)
        down = self.lookup(m, ff, n, sparsity=sparsity_down,
                           impl="skip" if fixed_n2 is not None else "dense",
                           fixed_n=fixed_n2, fixed_k=fixed_k2, phase=phase)
        best = FusedBlockConfig(min(up.block_m, down.block_m),
                                up.block_n, up.block_k,
                                down.block_n, down.block_k)
        with self._lock:
            self._cache[key] = best
            try:
                self.save()
            except OSError:
                pass
        return best

    def entries(self) -> Dict[str, BlockConfig]:
        with self._lock:
            self._load()
            return dict(self._cache)


_GLOBAL: Optional[Autotuner] = None
_GLOBAL_LOCK = threading.Lock()


def get_tuner() -> Autotuner:
    """The process-wide tuner (path from $REPRO_AUTOTUNE_CACHE)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = Autotuner()
        return _GLOBAL
