"""First-class ternary-weight containers (the typed replacement for the old
untyped ``ternary_gemm`` weight-operand union).

A ``TernaryWeight`` is a JAX pytree: jit/vmap/scan-safe and
``jax.device_put``-table. Array payloads (packed codes, occupancy metadata,
per-channel scale, bias) are pytree *leaves*; everything a kernel planner
needs at trace time (logical shape, tile shapes, pack-time occupancy
summaries) is static auxiliary data, so planning works even when the leaves
are tracers (weights passed as jit arguments) and the container survives
``jax.lax.scan`` slicing of stacked parameter trees unchanged.

One subclass per storage format, registered by name in ``FORMATS``:

* ``Dense2Bit`` -- 2-bit codes, 16 weights per uint32 word (the dense
  Pallas kernel format). Supports stacked leading dims for scan-stacked /
  per-expert weights.
* ``Tiled``     -- 2-bit codes + per-(K-tile, N-tile) occupancy metadata
  (the sparsity-adaptive skipping kernel format, DESIGN.md §3).
* ``Bitplane``  -- plus/minus uint8 bit-masks (structural sign encoding,
  DESIGN.md §4).
* ``Base3``     -- 5 trits per byte (the paper's value-compression format;
  LUT-gather decode, reference kernel only).

Uniform interface::

    wc = weights.pack(w, format="tiled", tile_k=256)   # float or ternary in
    wc.shape, wc.k, wc.n          # logical (K, N)
    wc.occupancy()                # static nnz / tile-occupancy fraction
    wc.nbytes                     # payload bytes (leaves)
    wc.materialize(jnp.float32)   # decoded {-1,0,+1} dense matrix
    kernels.ops.ternary_gemm(x, wc)

New formats register in one place (``@register_format``) and become
dispatchable once a kernel lowering is registered for them in
``repro.kernels.ops`` (see ``register_kernel`` there).

Sharding convention: parameter spec trees mirror the container structure —
build the spec twin with ``dataclasses.replace(wc, packed=P(...), ...)`` so
the two trees flatten identically (``models/layers.py`` does this).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats, quantize

__all__ = [
    "TernaryWeight",
    "Dense2Bit",
    "Tiled",
    "Bitplane",
    "Base3",
    "FORMATS",
    "register_format",
    "pack",
    "ternarize_stacked",
    "validate_spec_twin",
]

# name -> container class; the single place new layouts register.
FORMATS: Dict[str, Type["TernaryWeight"]] = {}


class _PackStat(int):
    """Pack-time statistic (nnz / occupied-tile count) riding in pytree aux
    data. It survives flatten/unflatten but is excluded from treedef
    *identity* (always-equal under ``==``, constant hash): a packed-from-
    latent container (real nnz) stays structurally compatible with its
    init-time sharding-spec twin (nnz=-1) and with other packs of the same
    layout — ``tree_map``/``resolve_specs``/scan stacking never see a
    mismatch. Safe because every registered kernel lowering computes the
    same Y: statistics steer impl *choice*, never numerics."""

    __slots__ = ()

    def __eq__(self, other):
        return isinstance(other, _PackStat)

    def __ne__(self, other):
        return not isinstance(other, _PackStat)

    def __hash__(self):
        return 0


def register_format(name: str):
    """Class decorator: register a ``TernaryWeight`` subclass under ``name``
    and make it a JAX pytree (with named key paths, so checkpoints get
    readable leaf keys like ``.../w_packed/packed``).

    The subclass declares its array fields in ``_leaves``; every other
    dataclass field is static aux data (must be hashable). Fields named in
    ``_stats`` are wrapped in ``_PackStat`` so they ride along without
    contributing to treedef identity."""

    def deco(cls):
        cls.format_name = name
        FORMATS[name] = cls
        field_names = [f.name for f in dataclasses.fields(cls)]
        leaf_names = tuple(cls._leaves)
        stat_names = frozenset(cls._stats)
        static_names = tuple(n for n in field_names if n not in leaf_names)

        def aux_of(obj):
            return tuple(
                _PackStat(getattr(obj, n)) if n in stat_names
                else getattr(obj, n) for n in static_names)

        def flatten_with_keys(obj):
            children = [(jax.tree_util.GetAttrKey(n), getattr(obj, n))
                        for n in leaf_names]
            return children, aux_of(obj)

        def flatten(obj):
            return [getattr(obj, n) for n in leaf_names], aux_of(obj)

        def unflatten(aux, children):
            kw = dict(zip(leaf_names, children))
            # unwrap stats back to plain ints: _PackStat's always-equal
            # semantics belong to treedef aux only, never to the fields
            # user code compares against
            kw.update((n, int(v) if n in stat_names else v)
                      for n, v in zip(static_names, aux))
            return cls(**kw)

        jax.tree_util.register_pytree_with_keys(
            cls, flatten_with_keys, unflatten, flatten)
        return cls

    return deco


def _nbytes(v) -> int:
    if v is None:
        return 0
    if hasattr(v, "nbytes"):
        return int(v.nbytes)
    size = getattr(v, "size", None)
    dt = getattr(v, "dtype", None)
    if size is not None and dt is not None:        # tracers / shape structs
        return int(size) * np.dtype(dt).itemsize
    return 0


class TernaryWeight:
    """Base class: common derived views over the per-format dataclasses.

    Subclasses are frozen dataclasses with fields split into array leaves
    (``_leaves``) and static aux metadata. All carry:

    * ``shape`` -- logical (K, N) of the encoded ternary matrix (leading
      stack dims of the leaves, if any, are *not* part of ``shape``);
    * ``nnz``   -- pack-time nonzero count (-1 when unknown, e.g. a wrapped
      pre-packed buffer);
    * ``scale`` / ``bias`` -- optional per-output-channel epilogue operands
      consumed by ``ternary_gemm`` when the caller passes none explicitly;
    * ``tp_dim`` -- which logical axis (``"k"`` / ``"n"``) a mesh's
      ``"model"`` axis splits, set by ``distributed.tp.shard_params`` from
      the placed arrays (None: unsplit); static aux data.
    """

    format_name = "abstract"
    _leaves: Tuple[str, ...] = ()
    _stats: Tuple[str, ...] = ("nnz",)

    # --- logical geometry -------------------------------------------------
    @property
    def k(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    @property
    def nbytes(self) -> int:
        """Total payload bytes across array leaves (codes + metadata +
        scale/bias), the serving-memory figure of merit."""
        return sum(_nbytes(getattr(self, f)) for f in self._leaves)

    def occupancy(self) -> float:
        """Nonzero fraction recorded at pack time (1.0 when unknown — the
        dense assumption). ``Tiled`` overrides with the tile-occupancy
        fraction the skip planner consumes."""
        if self.nnz < 0:
            return 1.0
        return self.nnz / max(self.k * self.n, 1)

    def shard_constraints(self) -> Dict[str, Tuple[int, int]]:
        """Tensor-parallel shard-boundary constraints of the *physical*
        encoding: ``{"k": (extent, multiple), "n": (extent, multiple)}``.

        ``extent`` is the physical size of that logical axis as stored
        (tile-padded for ``Tiled``) and ``multiple`` the value count one
        indivisible pack unit covers (a 2-bit uint32 word spans 16 K
        values, a bitplane byte 8, a base-3 byte 5, a skip tile
        ``tile_k``/``tile_n``). A mesh shard boundary that does not land
        on ``multiple`` would split a pack word/tile across devices —
        ``validate_spec_twin`` rejects such specs at placement time."""
        return {"k": (self.k, 1), "n": (self.n, 1)}

    # --- conversions ------------------------------------------------------
    def materialize(self, dtype=jnp.float32, with_scale: bool = False):
        """Decode to the dense {-1,0,+1} matrix (stacked leading dims of the
        leaves are preserved). ``with_scale`` multiplies the per-channel
        scale in, yielding the effective float weight."""
        raise NotImplementedError

    def replace(self, **kw) -> "TernaryWeight":
        """``dataclasses.replace`` passthrough (handy for attaching
        scale/bias after construction, or building sharding-spec twins)."""
        return dataclasses.replace(self, **kw)

    def device_put(self, device=None) -> "TernaryWeight":
        return jax.device_put(self, device)

    def _apply_scale(self, t, with_scale: bool, dtype):
        if with_scale and self.scale is not None:
            t = t * jnp.asarray(self.scale).astype(dtype)[..., None, :]
        return t

    def __repr__(self) -> str:  # leaves may be tracers; keep repr static
        return (f"{type(self).__name__}(shape={self.shape}, "
                f"nnz={self.nnz}, nbytes={self.nbytes})")


def _pack_stacked(t: np.ndarray, pack_fn) -> np.ndarray:
    """Apply a 2-D host packer over arbitrary leading stack dims."""
    lead = t.shape[:-2]
    t2 = t.reshape((-1,) + t.shape[-2:])
    packed = np.stack([pack_fn(t2[i]) for i in range(t2.shape[0])])
    return packed.reshape(lead + packed.shape[-2:])


def _decode_stacked(packed, decode_fn, k: int, dtype):
    """vmap a 2-D decoder over arbitrary leading stack dims."""
    p = jnp.asarray(packed)
    lead = p.shape[:-2]
    p2 = p.reshape((-1,) + p.shape[-2:])
    dec = jax.vmap(lambda q: decode_fn(q, k, dtype))(p2)
    return dec.reshape(lead + dec.shape[-2:])


# ---------------------------------------------------------------------------
# Dense2Bit — 16 weights / uint32 word (the dense Pallas kernel format)
# ---------------------------------------------------------------------------

@register_format("dense2bit")
@dataclasses.dataclass(frozen=True)
class Dense2Bit(TernaryWeight):
    packed: Any                       # (..., ceil(K/16), N) uint32
    scale: Optional[Any]              # (..., N) or None
    bias: Optional[Any]               # (..., N) or None
    shape: Tuple[int, int]            # logical (K, N)
    nnz: int = -1
    tp_dim: Optional[str] = None      # "k" | "n" | None (see base class)

    _leaves = ("packed", "scale", "bias")

    @classmethod
    def from_dense(cls, t, scale=None, bias=None) -> "Dense2Bit":
        """Host-side pack of a {-1,0,+1} matrix (any leading stack dims).
        ``nnz`` records the *mean per-matrix* count so ``occupancy()`` stays
        a fraction of the logical (K, N) both stacked and scan-sliced."""
        t = np.asarray(t)
        n_stack = max(int(np.prod(t.shape[:-2], dtype=np.int64)), 1)
        return cls(packed=jnp.asarray(_pack_stacked(t, formats.pack_2bit)),
                   scale=scale, bias=bias, shape=t.shape[-2:],
                   nnz=int(round(np.count_nonzero(t) / n_stack)))

    @classmethod
    def from_packed(cls, packed, k: int, scale=None, bias=None,
                    nnz: int = -1) -> "Dense2Bit":
        """Wrap an existing packed word buffer (``formats.pack_2bit``
        layout). ``k`` is the logical K; words may be K-padded beyond it."""
        kw, n = packed.shape[-2:]
        if kw * 16 < k:
            raise ValueError(
                f"packed words cover K={kw * 16} < logical k={k}")
        return cls(packed=packed, scale=scale, bias=bias, shape=(k, n),
                   nnz=nnz)

    def materialize(self, dtype=jnp.float32, with_scale: bool = False):
        t = _decode_stacked(self.packed, formats.decode_2bit, self.k, dtype)
        return self._apply_scale(t[..., :self.n], with_scale, dtype)

    def shard_constraints(self) -> Dict[str, Tuple[int, int]]:
        return {"k": (self.k, 16), "n": (self.n, 1)}


# ---------------------------------------------------------------------------
# Tiled — 2-bit codes + per-tile occupancy metadata (skip kernel format)
# ---------------------------------------------------------------------------

@register_format("tiled")
@dataclasses.dataclass(frozen=True)
class Tiled(TernaryWeight):
    packed: Any                       # (Kp/16, Np) uint32 (K/N tile-padded)
    kt_indices: Any                   # (n_ntiles, max_occ) int32
    kt_counts: Any                    # (n_ntiles,) int32
    scale: Optional[Any]
    bias: Optional[Any]
    shape: Tuple[int, int]            # logical (K, N)
    tile_k: int = 256
    tile_n: int = 128
    nnz: int = -1
    occupied_tiles: int = 0           # pack-time occupied-tile count
    tp_dim: Optional[str] = None

    _leaves = ("packed", "kt_indices", "kt_counts", "scale", "bias")
    _stats = ("nnz", "occupied_tiles")

    @classmethod
    def from_tiled(cls, tt: formats.TiledTernary, scale=None,
                   bias=None) -> "Tiled":
        return cls(packed=jnp.asarray(tt.packed),
                   kt_indices=jnp.asarray(tt.kt_indices),
                   kt_counts=jnp.asarray(tt.kt_counts),
                   scale=scale, bias=bias, shape=tt.shape,
                   tile_k=tt.tile_k, tile_n=tt.tile_n,
                   nnz=int(tt.tile_nnz.sum()),
                   occupied_tiles=tt.occupied_tiles())

    @classmethod
    def from_dense(cls, t, scale=None, bias=None, tile_k: int = 256,
                   tile_n: int = 128) -> "Tiled":
        tt = formats.TiledTernary.from_dense(np.asarray(t), tile_k=tile_k,
                                             tile_n=tile_n)
        return cls.from_tiled(tt, scale=scale, bias=bias)

    # --- tile geometry (all static: derived from shapes + aux) -----------
    @property
    def n_ktiles(self) -> int:
        return self.packed.shape[-2] * 16 // self.tile_k

    @property
    def n_ntiles(self) -> int:
        return self.packed.shape[-1] // self.tile_n

    @property
    def max_occ(self) -> int:
        return self.kt_indices.shape[-1]

    def total_tiles(self) -> int:
        return self.n_ktiles * self.n_ntiles

    def visited_tiles(self) -> int:
        """Static grid bound of the skip kernel: N-tiles x max occupancy."""
        return self.n_ntiles * self.max_occ

    def occupancy(self) -> float:
        """Occupied-tile fraction — the skip/dense planning signal."""
        return self.occupied_tiles / max(self.total_tiles(), 1)

    def materialize(self, dtype=jnp.float32, with_scale: bool = False):
        kp = self.packed.shape[-2] * 16
        t = formats.decode_2bit(jnp.asarray(self.packed), kp, dtype)
        return self._apply_scale(t[:self.k, :self.n], with_scale, dtype)

    def shard_constraints(self) -> Dict[str, Tuple[int, int]]:
        # the occupancy metadata (kt_indices/kt_counts) is per (K-tile,
        # N-tile): shard boundaries must land on whole tiles of the
        # *padded* grid, not just on pack words
        return {"k": (self.n_ktiles * self.tile_k, self.tile_k),
                "n": (self.n_ntiles * self.tile_n, self.tile_n)}


# ---------------------------------------------------------------------------
# Bitplane — plus/minus uint8 masks (structural sign encoding)
# ---------------------------------------------------------------------------

@register_format("bitplane")
@dataclasses.dataclass(frozen=True)
class Bitplane(TernaryWeight):
    plus: Any                         # (ceil(K/8), N) uint8
    minus: Any                        # (ceil(K/8), N) uint8
    scale: Optional[Any]
    bias: Optional[Any]
    shape: Tuple[int, int]
    nnz: int = -1
    tp_dim: Optional[str] = None

    _leaves = ("plus", "minus", "scale", "bias")

    @classmethod
    def from_dense(cls, t, scale=None, bias=None) -> "Bitplane":
        t = np.asarray(t)
        plus, minus = formats.pack_bitplanes(t)
        return cls(plus=jnp.asarray(plus), minus=jnp.asarray(minus),
                   scale=scale, bias=bias, shape=t.shape,
                   nnz=int(np.count_nonzero(t)))

    @classmethod
    def from_planes(cls, plus, minus, k: int, scale=None, bias=None,
                    nnz: int = -1) -> "Bitplane":
        if plus.shape != minus.shape:
            raise ValueError(f"plane shapes differ: {plus.shape} vs "
                             f"{minus.shape}")
        kb, n = plus.shape[-2:]
        if kb * 8 < k:
            raise ValueError(f"bitplanes cover K={kb * 8} < logical k={k}")
        return cls(plus=plus, minus=minus, scale=scale, bias=bias,
                   shape=(k, n), nnz=nnz)

    def materialize(self, dtype=jnp.float32, with_scale: bool = False):
        t = formats.decode_bitplanes(jnp.asarray(self.plus),
                                     jnp.asarray(self.minus), self.k,
                                     dtype=dtype)
        return self._apply_scale(t[..., :self.n], with_scale, dtype)

    def shard_constraints(self) -> Dict[str, Tuple[int, int]]:
        return {"k": (self.k, 8), "n": (self.n, 1)}


# ---------------------------------------------------------------------------
# Base3 — 5 trits / byte (paper's value compression; ref kernel only)
# ---------------------------------------------------------------------------

@register_format("base3")
@dataclasses.dataclass(frozen=True)
class Base3(TernaryWeight):
    packed: Any                       # (ceil(K/5), N) uint8
    scale: Optional[Any]
    bias: Optional[Any]
    shape: Tuple[int, int]
    nnz: int = -1
    tp_dim: Optional[str] = None

    _leaves = ("packed", "scale", "bias")

    @classmethod
    def from_dense(cls, t, scale=None, bias=None) -> "Base3":
        t = np.asarray(t)
        return cls(packed=jnp.asarray(formats.pack_base3(t)),
                   scale=scale, bias=bias, shape=t.shape,
                   nnz=int(np.count_nonzero(t)))

    def materialize(self, dtype=jnp.float32, with_scale: bool = False):
        t = formats.decode_base3(jnp.asarray(self.packed), self.k,
                                 dtype=dtype)
        return self._apply_scale(t[..., :self.n], with_scale, dtype)

    def shard_constraints(self) -> Dict[str, Tuple[int, int]]:
        return {"k": (self.k, 5), "n": (self.n, 1)}


# ---------------------------------------------------------------------------
# pack — the one entry point producers use
# ---------------------------------------------------------------------------

def ternarize_stacked(w, threshold: float = 0.7):
    """Host-side per-matrix ternarization (TWN absmean) over arbitrary
    leading stack dims: (..., K, N) float -> ({-1,0,1} (..., K, N) int8,
    per-channel scales (..., N) f32)."""
    w = np.asarray(w)
    lead, (k, n) = w.shape[:-2], w.shape[-2:]
    w2 = w.reshape((-1, k, n))
    ts, scales = [], []
    for i in range(w2.shape[0]):
        t, alpha = quantize.ternarize(jnp.asarray(w2[i], jnp.float32),
                                      threshold)
        ts.append(np.asarray(t))
        scales.append(np.asarray(alpha, np.float32).reshape(-1))
    return (np.stack(ts).reshape(lead + (k, n)),
            np.stack(scales).reshape(lead + (n,)))


def pack(w, format: str = "dense2bit", *, scale=None, bias=None,
         threshold: float = 0.7, **opts) -> TernaryWeight:
    """Pack a weight matrix into the requested ternary container.

    ``w`` is either an already-ternary {-1,0,+1} integer matrix, or a float
    matrix which is first ternarized per-matrix (TWN absmean,
    ``core.quantize``; leading stack dims supported where the format
    supports them) — in the float case the per-channel ternarization scale
    becomes the container's ``scale`` unless one is passed explicitly.
    ``**opts`` are format-specific (e.g. ``tile_k``/``tile_n`` for
    ``"tiled"``).
    """
    if format not in FORMATS:
        raise ValueError(f"unknown ternary format {format!r}; registered: "
                         f"{sorted(FORMATS)}")
    w = np.asarray(w)
    if np.issubdtype(w.dtype, np.floating) or w.dtype.kind == "V":
        t, scales = ternarize_stacked(w, threshold)
        if scale is None:
            scale = jnp.asarray(scales)
    else:
        t = w
    return FORMATS[format].from_dense(t, scale=scale, bias=bias, **opts)


# ---------------------------------------------------------------------------
# Spec-twin validation — pack-boundary enforcement for tensor parallelism
# ---------------------------------------------------------------------------

def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    """Accept a ``jax.sharding.Mesh`` (or anything with ``.shape``
    mapping axis name -> size) or a plain ``{name: size}`` dict."""
    shape = getattr(mesh, "shape", mesh)
    return dict(shape)


def _resolve_split(ax, sizes: Dict[str, int], used: set, fsdp: bool):
    """Mirror ``distributed.sharding.resolve_spec``'s axis-name resolution
    (logical "fsdp"/"expert" names, tuples, literal mesh names, the
    no-reuse rule) *without* its silent replicate-on-indivisible fallback —
    return (split size, resolved axis names)."""
    if ax is None:
        return 1, ()
    if ax == "fsdp":
        axes = (tuple(a for a in ("pod", "data") if a in sizes)
                if fsdp else ())
    elif ax == "expert":
        axes = ("model",) if "model" in sizes else ()
    elif isinstance(ax, (tuple, list)):
        axes = tuple(a for a in ax if a in sizes)
    else:
        axes = (ax,) if ax in sizes else ()
    axes = tuple(a for a in axes if a not in used)
    size = 1
    for a in axes:
        size *= sizes[a]
    used.update(axes)
    return size, axes


def validate_spec_twin(wc: TernaryWeight, twin, mesh, *,
                       fsdp: bool = False) -> None:
    """Reject a PartitionSpec spec twin whose shard boundaries would split
    a pack word or skip tile across devices.

    ``twin`` is the container's sharding-spec twin (the same dataclass with
    PartitionSpec leaves, as built by ``models.layers.linear_init``);
    ``mesh`` supplies the axis sizes. The physical encodings are
    indivisible below their pack unit — 16 values per 2-bit uint32 word,
    8 per bitplane byte, 5 per base-3 byte, a whole ``tile_k x tile_n``
    tile for the skip format — so a K (or N, for tiled) shard boundary off
    that multiple has no representable per-device layout. Today such specs
    would be silently replicated at resolve time; serving placement calls
    this first so they fail loudly with the offending axis and the nearest
    legal boundary instead.

    Raises ``ValueError``; returns ``None`` when the twin is legal.
    """
    spec = None
    for name in ("packed", "plus"):
        cand = getattr(twin, name, None)
        if cand is not None and not isinstance(cand, TernaryWeight):
            spec = cand
            break
    if spec is None:                      # nothing sharded -> nothing to do
        return
    sizes = _mesh_axis_sizes(mesh)
    cons = wc.shard_constraints()
    # align the spec to the primary leaf's trailing (K-pack, N) axes —
    # scan-stacked twins carry leading None entries (transformer._stack_specs)
    entries = tuple(spec)
    if len(entries) < 2:
        entries = (None,) * (2 - len(entries)) + entries
    used: set = set()
    splits = []
    for ax in entries[:-2]:               # leading stack dims burn axes too
        _resolve_split(ax, sizes, used, fsdp)
    for ax in entries[-2:]:
        splits.append(_resolve_split(ax, sizes, used, fsdp))
    for (tp, axes), dim in zip(splits, ("k", "n")):
        if tp <= 1:
            continue
        extent, multiple = cons[dim]
        if extent % (tp * multiple) == 0:
            continue
        per_shard = extent / tp
        legal = max(multiple, int(round(per_shard / multiple)) * multiple)
        raise ValueError(
            f"{wc.format_name} spec twin: sharding {dim.upper()} over mesh "
            f"axis {axes if len(axes) > 1 else axes[0]!r} ({tp}-way) puts "
            f"shard boundaries every {per_shard:g} of {extent} values — "
            f"off the {multiple}-value pack multiple of {wc!r}. Per-shard "
            f"{dim.upper()} must be a multiple of {multiple} that divides "
            f"{extent}; nearest legal boundary is {legal}.")
