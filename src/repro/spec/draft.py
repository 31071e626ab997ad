"""Draft-model construction for self-speculative decoding (DESIGN.md §10).

A *draft* is any cheap model whose greedy continuations of the target's
token stream are often the target's own — the verify step (``spec.verify``)
accepts the longest matching prefix, so draft quality moves throughput,
never correctness. Three construction strategies live behind the one
``DraftModel`` protocol (a name + an ``LM`` + its params):

* ``resparsify`` — re-ternarize the target's packed ``TernaryWeight``
  containers at a *higher sparsity* (lower nnz fraction) into fresh
  containers of the same registered format. The paper's sparsity-stability
  observation is the bet: a ternary network keeps most of its argmax
  behaviour as small-magnitude columns are dropped, while every sparse
  kernel in this repo gets faster as occupancy falls. The draft shares the
  target's architecture, embeddings and lm_head; only the GEMM operands
  shrink.
* ``layer_skip`` — run a *prefix* of the target's stack (sliced scan
  groups) plus the shared final norm + lm_head. The residual stream makes
  truncated-depth logits a decent predictor of full-depth logits; draft
  cost scales with the kept fraction of layers.
* ``external`` — any smaller ``ModelConfig`` with its own params (a
  distilled or otherwise-trained drafter).

Drafting itself (``make_draft_round``) is a single jitted call per engine
round: one *re-sync* feed (writes the draft's K/V for the newest committed
token — exactly the catch-up token when the previous round accepted the
whole window, and an idempotent rewrite otherwise) followed by ``k``
chained greedy feeds producing the proposal tokens. The draft owns its own
dense KV cache (``LM.init_cache`` slot rows managed by the engine); it
never touches the target's paged pool, so rollback only ever concerns the
target cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import weights
from repro.models import LM

__all__ = ["DraftModel", "Draft", "SpecConfig", "build_draft",
           "resparsify", "layer_skip", "external", "make_draft_round"]


@runtime_checkable
class DraftModel(Protocol):
    """What the engine needs from a draft: a display name, the draft
    ``LM`` (its config may differ from the target's) and its params."""

    name: str
    model: LM
    params: Any


@dataclasses.dataclass
class Draft:
    name: str
    model: LM
    params: Any


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs for ``ContinuousScheduler(spec=...)``.

    ``draft`` is a strategy name (``"resparsify"`` / ``"layer_skip"`` /
    ``"external"``) resolved against the loaded params by ``build_draft``,
    or a ready ``DraftModel`` instance. ``k`` is the proposal depth: each
    engine round drafts ``k`` tokens and verifies the ``k+1``-token window
    in one target forward (the engine reserves ``k`` cache positions of
    headroom per slot)."""

    draft: Any = "layer_skip"
    k: int = 4
    draft_sparsity: float = 0.125      # resparsify: target nnz fraction
    draft_layers: int = 0              # layer_skip: 0 = half, period-rounded
    draft_cfg: Optional[ModelConfig] = None   # external
    draft_params: Any = None                  # external


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

def _reternarize(eff: np.ndarray, sparsity: float):
    """Re-ternarize one effective (scale-applied) ternary matrix at a lower
    nnz fraction. Ranking is *global* |w|: within a ternary matrix every
    nonzero of column n shares magnitude alpha_n, so a per-channel quantile
    (``quantize.ternarize_target_sparsity``'s default) is degenerate here —
    the global quantile instead drops whole low-scale columns' weight mass
    first. Survivor scales are the TWN L1-optimal per-channel mean, exactly
    as ``core.quantize.ternarize`` computes them."""
    absw = np.abs(eff)
    delta = np.quantile(absw.reshape(-1), 1.0 - sparsity)
    mask = (absw >= delta) & (absw > 0)
    t = (np.sign(eff) * mask).astype(np.int8)
    cnt = np.maximum(mask.sum(axis=0), 1)
    alpha = ((absw * mask).sum(axis=0) / cnt).astype(np.float32)
    return t, alpha


def _resparsify_container(w: weights.TernaryWeight, sparsity: float,
                          ) -> weights.TernaryWeight:
    eff = np.asarray(w.materialize(jnp.float32, with_scale=True))
    lead, (kk, n) = eff.shape[:-2], eff.shape[-2:]
    e2 = eff.reshape((-1, kk, n))
    ts, alphas = zip(*(_reternarize(e2[i], sparsity)
                       for i in range(e2.shape[0])))
    t = np.stack(ts).reshape(lead + (kk, n))
    alpha = np.stack(alphas).reshape(lead + (n,))
    cls = weights.FORMATS[w.format_name]
    return cls.from_dense(t, scale=jnp.asarray(alpha), bias=w.bias)


def resparsify(model: LM, params, sparsity: float) -> Draft:
    """Higher-sparsity re-ternarization of the target's packed weights: a
    draft that shares the target's config, embeddings and unpacked params
    and replaces every ``TernaryWeight`` container with a fresh pack at
    ``sparsity`` nnz fraction (same registered format -> same kernels,
    lower occupancy -> cheaper skip/sparse dispatch)."""
    if not 0.0 < sparsity <= 1.0:
        raise ValueError(f"draft sparsity {sparsity} not in (0, 1]")
    n_packed = 0

    def conv(v):
        nonlocal n_packed
        if isinstance(v, weights.TernaryWeight):
            n_packed += 1
            return _resparsify_container(v, sparsity)
        return v

    dparams = jax.tree_util.tree_map(
        conv, params, is_leaf=lambda v: isinstance(v, weights.TernaryWeight))
    if n_packed == 0:
        raise ValueError(
            "resparsify found no TernaryWeight containers in the params — "
            "pack them first (models.layers.pack_params / --packed), or use "
            "the layer_skip/external draft strategies")
    return Draft(name=f"resparsify(s={sparsity:g})", model=model,
                 params=dparams)


def layer_skip(model: LM, params, n_layers: int) -> Draft:
    """Depth-truncated self-draft: the first ``n_layers`` of the target
    stack (sliced scan groups — params are shared, not copied) + the
    target's own final norm and lm_head."""
    cfg = model.cfg
    if not 0 < n_layers < cfg.num_layers:
        raise ValueError(f"layer_skip needs 0 < n_layers < {cfg.num_layers},"
                         f" got {n_layers}")
    if n_layers % model.period:
        raise ValueError(f"n_layers={n_layers} must be a multiple of the "
                         f"stack period {model.period} (scan groups slice "
                         f"whole periods)")
    g = n_layers // model.period
    dmodel = LM(dataclasses.replace(cfg, num_layers=n_layers))
    dparams = dict(params)
    for j in range(len(model.block_kinds)):
        dparams[f"block{j}"] = jax.tree.map(lambda x: x[:g],
                                            params[f"block{j}"])
    return Draft(name=f"layer_skip({n_layers}/{cfg.num_layers})",
                 model=dmodel, params=dparams)


def external(cfg: ModelConfig, params=None, *, key=None) -> Draft:
    """Any independent (typically smaller) model as the drafter. ``params``
    default to a fresh init — useful only for plumbing tests; real use
    passes a trained/distilled checkpoint."""
    m = LM(cfg)
    if params is None:
        params = m.init(key if key is not None else jax.random.PRNGKey(0))
    return Draft(name=f"external({cfg.name})", model=m, params=params)


def build_draft(spec: SpecConfig, model: LM, params) -> DraftModel:
    """Resolve a ``SpecConfig`` against the loaded target params."""
    if not isinstance(spec.draft, str):
        return spec.draft
    if spec.draft == "resparsify":
        return resparsify(model, params, spec.draft_sparsity)
    if spec.draft == "layer_skip":
        n = spec.draft_layers
        if not n:
            n = max(model.period,
                    (model.cfg.num_layers // 2)
                    // model.period * model.period)
        return layer_skip(model, params, n)
    if spec.draft == "external":
        if spec.draft_cfg is None:
            raise ValueError("draft='external' needs SpecConfig.draft_cfg")
        return external(spec.draft_cfg, spec.draft_params)
    raise ValueError(f"unknown draft strategy {spec.draft!r}; expected "
                     f"'resparsify', 'layer_skip', 'external' or a "
                     f"DraftModel instance")


# ---------------------------------------------------------------------------
# The drafting loop (one jitted call per engine round)
# ---------------------------------------------------------------------------

def make_draft_round(draft: DraftModel, max_len: int, k: int):
    """Jitted per-round drafter: re-sync feed + ``k`` chained greedy feeds.

    ``(params, layers, pos, prev_tok, tok) -> (layers, drafts (B, k))``
    where ``pos``/``prev_tok``/``tok`` are the engine's per-slot position /
    second-newest / newest committed-token vectors. The re-sync feed writes
    ``prev_tok``'s K/V at ``pos - 1``: after a fully-accepted round that is
    exactly the one committed token the draft never fed (the catch-up);
    otherwise it rewrites a value the draft already holds. Free slots
    (pos 0) compute garbage into rows the next admission overwrites."""
    dlm = draft.model

    def engine_draft(params, layers, pos, prev_tok, tok):
        pos_c = jnp.minimum(pos, max_len - 1 - k)
        cache = {"layers": layers, "pos": jnp.maximum(pos_c - 1, 0)}
        _, cache = dlm.decode_step(params, cache, prev_tok[:, None])
        cur, drafts = tok, []
        for _ in range(k):
            logits, cache = dlm.decode_step(params, cache, cur[:, None])
            cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            drafts.append(cur)
        return cache["layers"], jnp.stack(drafts, axis=1)

    # the function name names the program in a profiler trace
    return jax.jit(engine_draft, donate_argnums=(1,))
