"""The DMuon optimizer orchestrator: layout → orthogonalize → update rule.

This module is the thin composition point of the three optimizer layers:

* ``core/owner_comms.py``    — WHERE matrices live: the owner-major packed
  layout, the staged all-to-all resharding, the owner sharding (§3.2).
* ``core/orthogonalize.py``  — HOW updates are orthogonalized: batched Gram
  NS, bucket-fused NS, full-matrix NS, and the NorMuon / MuonBP variant
  backends, all behind one protocol.
* ``core/update_rules.py``   — WHAT scalar math wraps them: momentum,
  RMS-matching scale, weight decay / lr, and elementwise AdamW.

Execution modes (``MuonConfig.mode``):

* ``owner``  — DMuon.  Matrix gradients are packed into owner-major stacked
  buffers whose leading axis is sharded over the owner mesh axes (the SPMD
  realization of "reduce to the owner").  Momentum lives permanently in this
  layout; the orthogonalizer runs on the local slice only and the updates
  are published back to each parameter's training sharding.
* ``gather`` — Muon-AG baseline: momentum in training layout, full-matrix NS
  replicated on every device.
* ``adamw``  — element-wise baseline for step-time comparisons.

Variants (``MuonConfig.variant``; registry in ``core/api.py``): ``muon``,
``normuon``, ``muonbp``, ``dion2``, ``adamuon``, ``adamw`` — all sharing the
owner-layout pipeline, differing only in the orthogonalizer backend (and its
per-group state, threaded through ``MuonState.variant_state``).

Non-matrix parameters always take AdamW (Alg. 1 line 16).  All modes produce
*identical* updates up to NS-iteration rounding for variant='muon' —
tests/test_muon.py + tests/dist_check.py check owner == gather == per-matrix
reference exactly.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.dedication import DedicationPlan
from repro.core.gram_ns import GramNSConfig
from repro.core.orthogonalize import make_orthogonalizer
from repro.core.owner_comms import (  # noqa: F401 — stable re-exports
    OwnerLayout, _from_owner_staged, _lead_perm, _stacked_spec,
    _to_owner_staged, group_key_str, owner_sharding, pack_group, unpack_group)
from repro.core.update_rules import (  # noqa: F401 — stable re-exports
    AdamWState, adamw_init, adamw_update, apply_wd_and_lr, momentum_update,
    scale_factor)

# Backwards-compatible aliases (pre-refactor private names).
_group_key_str = group_key_str
_scale_factor = scale_factor
_apply_wd_and_lr = apply_wd_and_lr


@dataclass(frozen=True)
class MuonConfig:
    learning_rate: float = 0.02
    momentum: float = 0.95
    nesterov: bool = True
    weight_decay: float = 0.0
    ns: GramNSConfig = GramNSConfig()
    # update scale: 'match_rms_adam' = 0.2·sqrt(max(m,n)) (Moonlight),
    # 'spectral' = sqrt(max(1, m/n)), 'none' = 1.0
    scale_mode: str = "match_rms_adam"
    mode: str = "owner"                  # 'owner' | 'gather' | 'adamw'
    # optimizer variant by name (registry in core/api.py):
    #   'muon'    — plain orthogonalized updates (the paper's optimizer)
    #   'normuon' — + neuron-wise second-moment normalization (NorMuon)
    #   'muonbp'  — block-periodic NS refresh every `muonbp_period` steps
    #   'dion2'   — Gram NS on a warm-started rank-r factor only (Dion2)
    #   'adamuon' — + elementwise second-moment adaptation (AdaMuon)
    #   'adamw'   — elementwise baseline (equivalent to mode='adamw')
    variant: str = "muon"
    # optimizer-step schedule for mode='owner' (core/pipeline.py):
    #   'fused'    — one post-backward phase: pack all → NS all → publish all
    #   'bucketed' — per-Gram-bucket stage_in/compute/publish pipeline with
    #                double-buffered staging (bit-exact with 'fused';
    #                docs/DESIGN.md §6)
    pipeline: str = "fused"
    # keep the bucketed schedule's optimization_barrier ties (disable to let
    # XLA schedule freely — changes overlap/memory, never values)
    pipeline_barriers: bool = True
    # pre-warm the kernel autotune cache for every shape in the dedication
    # plan at optimizer construction (paper §3.3 workflow)
    autotune_prewarm: bool = True
    momentum_dtype: str = "float32"
    # dtype of the packed owner-layout gradient/momentum math; bf16 for
    # trillion-param configs (memory policy, docs/DESIGN.md §8)
    pack_dtype: str = "float32"
    # AdamW settings for non-matrix params (and for mode='adamw')
    adam_lr: float = 3e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    adam_weight_decay: float = 0.0
    # gradient-transpose compression: reduce to owners in bf16 with fp32
    # error-feedback accumulator (docs/DESIGN.md §7)
    compress_grads: bool = False
    # variant knobs
    normuon_beta2: float = 0.95          # NorMuon neuron second-moment decay
    normuon_eps: float = 1e-8
    muonbp_period: int = 4               # full-NS refresh period (1 = every step)
    # Dion2: rank fraction r/m of the shrunken factor the Gram NS runs on
    # (1.0 = full-rank; the update then matches plain muon up to rounding)
    dion2_rank_frac: float = 0.25
    adamuon_beta2: float = 0.95          # AdaMuon entry second-moment decay
    adamuon_eps: float = 1e-8


def _resolve(cfg: MuonConfig):
    """(variant_spec, effective_mode) for ``cfg`` — validates the combo."""
    from repro.core.api import get_variant   # lazy: api imports this module
    spec = get_variant(cfg.variant)
    mode = "adamw" if spec.elementwise else cfg.mode
    if cfg.mode == "gather" and not spec.elementwise and spec.name != "muon":
        raise ValueError(
            f"variant {spec.name!r} requires the owner pipeline "
            "(mode='owner'); the gather baseline only supports 'muon'")
    if cfg.pipeline not in ("fused", "bucketed"):
        raise ValueError(f"unknown pipeline {cfg.pipeline!r}; "
                         "known: 'fused', 'bucketed'")
    if cfg.pipeline == "bucketed" and mode == "gather":
        raise ValueError(
            "pipeline='bucketed' schedules the owner-layout stages; the "
            "gather baseline has no staged comms to pipeline (mode='owner')")
    return spec, mode


def compress_with_error_feedback(gm, error_feedback, cfg: MuonConfig):
    """bf16 gradient transpose with fp32 error feedback (docs/DESIGN.md §7):
    compressed = bf16(g + e); residual e' = (g + e) - compressed stays in the
    training layout.  Identity when compression is off.  Returns
    ``(grads_for_pack, new_error_feedback)``."""
    if not (cfg.compress_grads and error_feedback is not None):
        return gm, error_feedback
    compressed, new_ef = {}, {}
    for p, g in gm.items():
        acc = g.astype(jnp.float32) + error_feedback[p]
        cg = acc.astype(jnp.bfloat16)
        new_ef[p] = acc - cg.astype(jnp.float32)
        compressed[p] = cg
    return compressed, new_ef


# --------------------------------------------------------------------------
# Optimizer state
# --------------------------------------------------------------------------

class MuonState(NamedTuple):
    step: jax.Array
    # mode='owner': {group_key_str: (D*cap, m, n) owner-major momentum}
    # mode='gather': momentum pytree in training layout (matrix leaves only)
    momentum: Any
    adamw: AdamWState            # state for non-matrix leaves
    error_feedback: Any = None   # fp32 residual for compressed grad transpose
    # per-variant orthogonalizer state (owner-major buffers), e.g. NorMuon's
    # neuron-wise second moments or MuonBP's cached polar accumulators
    variant_state: Any = None


def _matrix_and_rest(plan: DedicationPlan, tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree_util.tree_structure(tree)
    from repro.core.dedication import _key_str
    matrix, rest = {}, {}
    for kp, leaf in flat:
        path = "/".join(_key_str(k) for k in kp)
        (matrix if path in plan.leaves else rest)[path] = leaf
    return matrix, rest, treedef


def _rebuild(tree_like, matrix: Dict[str, Any], rest: Dict[str, Any]):
    """Reassemble a pytree of the same structure from the two path dicts."""
    from repro.core.dedication import _key_str
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
    leaves = []
    for kp, _ in flat:
        path = "/".join(_key_str(k) for k in kp)
        leaves.append(matrix[path] if path in matrix else rest[path])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def muon_init(plan: DedicationPlan, params, cfg: MuonConfig, mesh=None
              ) -> MuonState:
    matrix, rest, _ = _matrix_and_rest(plan, params)
    spec, mode = _resolve(cfg)
    layout = OwnerLayout(plan, mesh)
    mdt = jnp.dtype(cfg.momentum_dtype)
    variant_state = None
    if mode == "owner":
        momentum = {group_key_str(key): layout.zeros(key, mdt)
                    for key in plan.groups}
        if spec.stateful:
            ortho = make_orthogonalizer(spec.orthogonalizer, cfg)
            variant_state = ortho.init_state(layout, cfg)
    elif mode == "gather":
        momentum = {p: jnp.zeros(v.shape, mdt) for p, v in matrix.items()}
    else:  # adamw for everything
        momentum = {}
        rest = {**rest, **matrix}
    ef = None
    if cfg.compress_grads and mode == "owner":
        ef = {p: jnp.zeros(v.shape, jnp.float32) for p, v in matrix.items()}
    return MuonState(step=jnp.zeros((), jnp.int32), momentum=momentum,
                     adamw=adamw_init(rest), error_feedback=ef,
                     variant_state=variant_state)


def muon_update(plan: DedicationPlan, grads, state: MuonState, params,
                cfg: MuonConfig, mesh=None):
    """One optimizer step. Returns (updates, new_state); updates are deltas
    to be *added* to params (optax convention)."""
    gm, gr, _ = _matrix_and_rest(plan, grads)
    pm, pr, _ = _matrix_and_rest(plan, params)
    spec, mode = _resolve(cfg)

    if mode == "adamw":
        gr, pr = {**gr, **gm}, {**pr, **pm}
        adam_updates, adamw_state = adamw_update(gr, state.adamw, pr,
                                                 state.step, cfg)
        updates = _rebuild(grads, {}, adam_updates)
        return updates, MuonState(state.step + 1, state.momentum, adamw_state,
                                  state.error_feedback, state.variant_state)

    adam_updates, adamw_state = adamw_update(gr, state.adamw, pr, state.step,
                                             cfg)

    if mode == "owner":
        matrix_updates, new_momentum, new_ef, new_vstate = _owner_update(
            plan, gm, pm, state, cfg, mesh, spec)
    elif mode == "gather":
        matrix_updates, new_momentum = _gather_update(plan, gm, pm, state,
                                                      cfg, mesh)
        new_ef, new_vstate = state.error_feedback, state.variant_state
    else:
        raise ValueError(f"unknown mode {cfg.mode!r}")

    updates = _rebuild(grads, matrix_updates, adam_updates)
    return updates, MuonState(state.step + 1, new_momentum, adamw_state,
                              new_ef, new_vstate)


def _owner_update(plan: DedicationPlan, gm, pm, state: MuonState,
                  cfg: MuonConfig, mesh, spec):
    """DMuon path: pack → momentum → orthogonalize (pluggable backend) →
    unpack/publish.  Alg. 1 lines 10–15 in SPMD form.

    ``cfg.pipeline`` selects the schedule: 'fused' is the one-phase
    post-backward program below; 'bucketed' delegates to the per-Gram-bucket
    stage_in/compute/publish pipeline (core/pipeline.py) — same math, ordered
    so the staged comms overlap the compute wavefront.

    The three sections run under the named scopes ``dmuon.stage_in``,
    ``dmuon.orthogonalize`` and ``dmuon.publish``.  In this fused path the
    momentum update lies inside ``dmuon.stage_in``, with the compression,
    pack and owner constraint (the all-to-all to owners on a mesh)."""
    if cfg.pipeline == "bucketed":
        from repro.core.pipeline import BucketPipeline
        pipe = BucketPipeline(plan, cfg, mesh, spec)
        return pipe.run_from_grads(gm, pm, state)

    layout = OwnerLayout(plan, mesh)
    new_momentum: Dict[str, jax.Array] = {}

    # --- gradient routing: training layout -> owner layout (reduce-to-owner)
    pdt = jnp.dtype(cfg.pack_dtype)
    packed_mom: Dict[str, jax.Array] = {}
    skey_to_key = {group_key_str(key): key for key in plan.groups}
    with jax.named_scope("dmuon.stage_in"):
        grads_for_pack, new_ef = compress_with_error_feedback(
            gm, state.error_feedback, cfg)
        for key, g in plan.groups.items():
            g_packed = layout.pack(key, {p: grads_for_pack[p].astype(pdt)
                                         for p in g.leaf_paths})
            skey = group_key_str(key)
            mom = state.momentum[skey].astype(pdt)
            mom, eff = momentum_update(mom, g_packed, cfg)
            new_momentum[skey] = layout.constrain(
                mom.astype(jnp.dtype(cfg.momentum_dtype)))
            packed_mom[skey] = layout.constrain(eff)

    # --- owner-side orthogonalization via the variant's pluggable backend
    # (batched Gram NS by default; bucket-fused / NorMuon / MuonBP by name).
    ortho_fn = make_orthogonalizer(spec.orthogonalizer, cfg)
    with jax.named_scope("dmuon.orthogonalize"):
        ortho, new_vstate = ortho_fn(packed_mom, step=state.step,
                                     state=state.variant_state,
                                     layout=layout, cfg=cfg)

    # --- publication: owner layout -> training layout + scale/wd/lr.
    # The resharded tensor stays in pack_dtype; fp32 casting before the
    # all-to-all would double the publish volume (and at 1T scale the fp32
    # temp alone exceeds HBM).
    matrix_updates: Dict[str, jax.Array] = {}
    with jax.named_scope("dmuon.publish"):
        for skey, o in ortho.items():
            key = skey_to_key[skey]
            m, n = plan.groups[key].key
            s = scale_factor(m, n, cfg.scale_mode)
            per_leaf = layout.unpack(key, o.astype(pdt) * s)
            for p, upd in per_leaf.items():
                matrix_updates[p] = apply_wd_and_lr(upd, pm[p], cfg)
    return matrix_updates, new_momentum, new_ef, new_vstate


def muon_update_staged(plan: DedicationPlan, staged, rest_grads,
                       state: MuonState, params, cfg: MuonConfig, mesh=None):
    """One optimizer step from PRE-STAGED owner-layout matrix gradients.

    ``staged`` is {group_key_str: (D·cap, m, n) owner-major gradient stack}
    (already averaged over microbatches); ``rest_grads`` is the {path: grad}
    dict of the non-matrix (AdamW) leaves.  This is the entry point of the
    accumulation-overlapped bucketed pipeline: ``train/step.py`` packs each
    microbatch's gradients to owners inside the ``lax.scan`` (stage_in under
    the backward pass), then calls this to run compute + publish only.

    Bit-exact with ``muon_update`` on the packed-then-averaged gradients:
    packing is a permutation + zero-pad, so it commutes with the microbatch
    sum, the 1/accum scaling, and the pack-dtype cast.

    Incompatible with ``compress_grads`` (error feedback needs the summed
    gradient in the training layout) — callers fall back to the unstaged
    path; enforced here.
    """
    spec, mode = _resolve(cfg)
    if mode != "owner":
        raise ValueError(f"muon_update_staged requires mode='owner' "
                         f"(got {mode!r})")
    if cfg.compress_grads:
        raise ValueError("pre-staged gradients are incompatible with "
                         "compress_grads (error feedback is a training-layout "
                         "residual)")
    pm, pr, _ = _matrix_and_rest(plan, params)
    adam_updates, adamw_state = adamw_update(rest_grads, state.adamw, pr,
                                             state.step, cfg)
    from repro.core.pipeline import BucketPipeline
    pipe = BucketPipeline(plan, cfg, mesh, spec)
    matrix_updates, new_momentum, new_vstate = pipe.run_staged(
        staged, pm, state)
    updates = _rebuild(params, matrix_updates, adam_updates)
    return updates, MuonState(state.step + 1, new_momentum, adamw_state,
                              state.error_feedback, new_vstate)


def _gather_update(plan: DedicationPlan, gm, pm, state: MuonState,
                   cfg: MuonConfig, mesh=None):
    """Muon-AG baseline: momentum in training layout; full-matrix standard NS
    computed redundantly on every device (SPMD: replicated compute)."""
    from repro.core.orthogonalize import FullMatrixNS
    layout = OwnerLayout(plan, mesh)
    new_momentum: Dict[str, jax.Array] = {}
    eff_all: Dict[str, jax.Array] = {}
    for p, g in gm.items():
        g32 = g.astype(jnp.float32)
        mom, eff = momentum_update(state.momentum[p].astype(jnp.float32),
                                   g32, cfg)
        new_momentum[p] = mom.astype(jnp.dtype(cfg.momentum_dtype))
        eff_all[p] = eff
    ortho, _ = FullMatrixNS()(eff_all, step=state.step, state=None,
                              layout=layout, cfg=cfg)
    matrix_updates: Dict[str, jax.Array] = {}
    for p, o in ortho.items():
        m, n = plan.leaves[p].group
        s = scale_factor(m, n, cfg.scale_mode)
        matrix_updates[p] = apply_wd_and_lr(o * s, pm[p], cfg)
    return matrix_updates, new_momentum
