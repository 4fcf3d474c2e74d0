"""Bucket-pipelined optimizer schedule: stage_in → compute → publish.

The fused owner path (``core/muon.py:_owner_update``) runs the optimizer as
one post-backward phase: pack EVERY group, orthogonalize EVERY group, publish
EVERY group.  Nothing in that program tells XLA's latency-hiding scheduler
that group k+1's staged all-to-all could fly while group k's Gram recurrence
occupies the MXU — and at the memory level all staging buffers are live at
once.

This module decomposes the step into an explicitly schedulable pipeline over
*Gram buckets* (``plan.buckets``: groups sharing a Gram dimension m — the
granularity at which the iterate phase fuses, docs/DESIGN.md §6):

  ``stage_in(b)``  pack the bucket's gradients + staged all-to-all to owners
  ``compute(b)``   momentum + the variant's orthogonalizer on the local slice
  ``publish(b)``   staged reshard back to the training layout + scale/wd/lr

and software-pipelines them with double-buffered staging:

    stage_in(b₀) → [stage_in(b₁) ‖ compute(b₀)]
                 → [stage_in(b₂) ‖ compute(b₁) ‖ publish(b₀)] → …

The schedule is enforced with ``lax.optimization_barrier`` ties: bucket k+1's
staging buffers are grouped with bucket k's compute output, so the partitioner
can neither hoist every all-to-all to the front (unbounded staging memory) nor
sink them all to the back (zero overlap) — at most one staging buffer is in
flight ahead of the compute wavefront.

Gradients can also arrive *pre-staged*: with gradient accumulation,
``train/step.py`` packs each microbatch's matrix gradients into the owner
layout inside the ``lax.scan`` and accumulates there, so the owner transposes
ride under the next microbatch's forward/backward instead of forming one
post-backward barrier.  ``run_staged`` then starts the pipeline at
``compute``.  Pack is a (linear) permutation + zero-pad, so accumulating
packed microbatch gradients is bit-exact with packing the accumulated
gradient — ``tests/test_pipeline.py`` pins this down for every registry
variant.

All four registry variants (muon / normuon / muonbp / adamw) ride the
pipeline unchanged: the orthogonalizer protocol already takes a dict of
stacks, so each bucket's compute is one backend call on the bucket's slice of
``MuonState.variant_state`` (sliced/merged per field by ``_slice_state`` /
``_merge_state`` — the same {field: {group: buffer}} shape the elastic
resharder walks).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.dedication import DedicationPlan
from repro.core.orthogonalize import make_orthogonalizer
from repro.core.owner_comms import OwnerLayout, group_key_str, repack_rows
from repro.core.update_rules import (apply_wd_and_lr, momentum_update,
                                     scale_factor)


def _tie(*trees):
    """Group ``trees`` into one scheduling unit (identity semantics).

    Consumers of any returned leaf wait for every input leaf, which is how
    the bucket schedule expresses "stage_in(k+1) completes alongside
    compute(k)" to XLA's scheduler without changing a single value.
    """
    flat, defs, sizes = [], [], []
    for t in trees:
        leaves, tdef = jax.tree_util.tree_flatten(t)
        flat.extend(leaves)
        defs.append(tdef)
        sizes.append(len(leaves))
    out = jax.lax.optimization_barrier(tuple(flat))
    res, off = [], 0
    for tdef, n in zip(defs, sizes):
        res.append(jax.tree_util.tree_unflatten(tdef, list(out[off:off + n])))
        off += n
    return tuple(res)


def _after(tree, dep):
    """Return ``tree`` unchanged but data-dependent on ``dep``: producers of
    the returned leaves cannot be scheduled before ``dep`` is computed."""
    if dep is None:
        return tree
    out, _ = _tie(tree, dep)
    return out


def _slice_state(state: Optional[dict], skeys: List[str]) -> Optional[dict]:
    """Per-bucket view of a variant state ({field: {skey: buf} | other})."""
    if state is None:
        return None
    return {field: ({k: bufs[k] for k in skeys if k in bufs}
                    if isinstance(bufs, dict) else bufs)
            for field, bufs in state.items()}


def _merge_state(acc: Optional[dict], part: Optional[dict]) -> Optional[dict]:
    """Fold one bucket's updated state slice back into the full state."""
    if part is None:
        return acc
    if acc is None:
        acc = {}
    for field, bufs in part.items():
        if isinstance(bufs, dict):
            acc.setdefault(field, {}).update(bufs)
        else:
            acc[field] = bufs
    return acc


def reshard_staged(staged: Dict[str, jax.Array], old_plan: DedicationPlan,
                   new_plan: DedicationPlan, new_mesh=None
                   ) -> Dict[str, jax.Array]:
    """Re-layout in-flight staged gradient stacks across dedication plans.

    A preemption mid-accumulation leaves owner-major staging buffers (partial
    gradient sums) that, like every owner buffer, are padded to the OLD plan's
    ``D·cap`` rows.  This repacks their logical rows under the new plan so an
    elastic restart can finish the interrupted step at a different owner
    count (tests/test_pipeline.py::test_staged_state_elastic_reshard).
    """
    from repro.core.owner_comms import owner_sharding
    skey_to_key = {group_key_str(k): k for k in old_plan.groups}
    out = {}
    for skey, buf in staged.items():
        key = skey_to_key[skey]
        packed = repack_rows(old_plan.groups[key], new_plan.groups[key], buf)
        shard = owner_sharding(new_plan, new_mesh, ndim=packed.ndim)
        if shard is not None:
            packed = jax.device_put(packed, shard)
        out[skey] = packed
    return out


class BucketPipeline:
    """The schedulable per-bucket realization of the owner update.

    One instance per (plan, config, mesh) triple; every method is pure and
    jit-traceable.  ``run_from_grads`` is the drop-in replacement for the
    fused ``_owner_update`` body; ``stage_in`` + ``run_staged`` split the
    step around the backward pass for the accumulation-overlapped mode.
    """

    def __init__(self, plan: DedicationPlan, cfg, mesh=None, spec=None):
        if spec is None:
            from repro.core.api import get_variant
            spec = get_variant(cfg.variant)
        self.plan = plan
        self.cfg = cfg
        self.mesh = mesh
        self.spec = spec
        self.layout = OwnerLayout(plan, mesh)
        self.ortho = make_orthogonalizer(spec.orthogonalizer, cfg)
        # Schedule order: Gram buckets, largest m first — the longest compute
        # goes first so later (cheaper) buckets have the most staging overlap
        # to hide behind.  Values are unaffected (buckets are independent).
        self.schedule: List[Tuple[int, List[Any]]] = sorted(
            plan.buckets.items(), key=lambda kv: -kv[0])
        # Schedule ties only pay for themselves when there are owner
        # transfers to overlap; on a single device they just fence XLA's
        # fusion.  Identity semantics either way — values are unaffected.
        multi = mesh is not None and mesh.devices.size > 1
        self.barriers = bool(getattr(cfg, "pipeline_barriers", True)) and multi

    # ------------------------------------------------------------ stages

    def stage_in(self, keys, grads: Dict[str, jax.Array], *,
                 dtype=None) -> Dict[str, jax.Array]:
        """Pack one bucket's gradients and issue the staged all-to-all to the
        owner layout.  ``dtype`` casts the leaves before packing (pack_dtype
        on the direct path; the accumulator dtype when pre-staging).

        Runs under the named scope ``dmuon.stage_in``, also inside the
        microbatch scan of the pre-staging path.  Unlike the fused path's
        stage_in, it holds no momentum: that runs in ``compute``."""
        out = {}
        with jax.named_scope("dmuon.stage_in"):
            for key in keys:
                g = self.plan.groups[key]
                leaves = {p: (grads[p] if dtype is None
                              else grads[p].astype(dtype))
                          for p in g.leaf_paths}
                out[group_key_str(key)] = self.layout.pack(key, leaves)
        return out

    def stage_in_all(self, grads: Dict[str, jax.Array], *,
                     dtype=None) -> Dict[str, jax.Array]:
        """stage_in over every bucket (the pre-staging path inside the
        microbatch scan, where the schedule is the scan itself)."""
        out = {}
        for _, keys in self.schedule:
            out.update(self.stage_in(keys, grads, dtype=dtype))
        return out

    def zeros_staged(self, dtype) -> Dict[str, jax.Array]:
        """Owner-sharded zero staging accumulators for every group."""
        return {group_key_str(k): self.layout.zeros(k, dtype)
                for k in self.plan.groups}

    def compute(self, keys, staged: Dict[str, jax.Array], momentum, step,
                vstate):
        """Momentum + the variant's orthogonalizer for one bucket, on the
        owner-local slice only.

        The orthogonalizer runs under the named scope
        ``dmuon.orthogonalize``; the momentum update before it lies under
        no ``dmuon.*`` scope in this bucketed path (in the fused path it is
        part of ``dmuon.stage_in``)."""
        cfg = self.cfg
        pdt = jnp.dtype(cfg.pack_dtype)
        mdt = jnp.dtype(cfg.momentum_dtype)
        new_mom, eff = {}, {}
        for key in keys:
            skey = group_key_str(key)
            mom = momentum[skey].astype(pdt)
            mom, e = momentum_update(mom, staged[skey].astype(pdt), cfg)
            new_mom[skey] = self.layout.constrain(mom.astype(mdt))
            eff[skey] = self.layout.constrain(e)
        skeys = [group_key_str(k) for k in keys]
        with jax.named_scope("dmuon.orthogonalize"):
            ortho, new_sub = self.ortho(eff, step=step,
                                        state=_slice_state(vstate, skeys),
                                        layout=self.layout, cfg=cfg)
        return ortho, new_mom, new_sub

    def publish(self, keys, ortho: Dict[str, jax.Array], params_matrix):
        """Staged reshard back to the training layout + scale / wd / lr,
        under the named scope ``dmuon.publish``."""
        cfg = self.cfg
        pdt = jnp.dtype(cfg.pack_dtype)
        updates = {}
        with jax.named_scope("dmuon.publish"):
            for key in keys:
                skey = group_key_str(key)
                m, n = self.plan.groups[key].key
                s = scale_factor(m, n, cfg.scale_mode)
                per_leaf = self.layout.unpack(key,
                                              ortho[skey].astype(pdt) * s)
                for p, upd in per_leaf.items():
                    updates[p] = apply_wd_and_lr(upd, params_matrix[p], cfg)
        return updates

    # ---------------------------------------------------------- schedules

    def run_from_grads(self, gm, pm, state):
        """Full pipelined step from training-layout gradients.

        Drop-in for the fused owner update: same math per group, but staged
        per bucket with the double-buffered schedule.  Returns
        ``(matrix_updates, new_momentum, new_error_feedback, new_vstate)``.
        """
        from repro.core.muon import compress_with_error_feedback
        grads_for_pack, new_ef = compress_with_error_feedback(
            gm, state.error_feedback, self.cfg)
        pdt = jnp.dtype(self.cfg.pack_dtype)

        sched = self.schedule
        n = len(sched)
        matrix_updates: Dict[str, jax.Array] = {}
        new_momentum: Dict[str, jax.Array] = {}
        new_vstate: Optional[dict] = None
        cur = self.stage_in(sched[0][1], grads_for_pack, dtype=pdt) \
            if n else {}
        prev_ortho = None
        for i, (_, keys) in enumerate(sched):
            nxt = None
            if i + 1 < n:
                # Issue bucket i+1's staging while bucket i computes; the
                # _after tie keeps it from launching before bucket i-1's
                # compute retired (double buffering, not all-at-once).
                nxt = self.stage_in(
                    sched[i + 1][1],
                    _after(
                        {p: grads_for_pack[p]
                         for k in sched[i + 1][1]
                         for p in self.plan.groups[k].leaf_paths},
                        prev_ortho) if self.barriers else grads_for_pack,
                    dtype=pdt)
            ortho, mom_b, vs_b = self.compute(keys, cur, state.momentum,
                                              state.step, state.variant_state)
            if self.barriers and nxt is not None:
                nxt, ortho = _tie(nxt, ortho)
            matrix_updates.update(self.publish(keys, ortho, pm))
            new_momentum.update(mom_b)
            new_vstate = _merge_state(new_vstate, vs_b)
            prev_ortho = ortho
            cur = nxt
        return matrix_updates, new_momentum, new_ef, new_vstate

    def run_staged(self, staged: Dict[str, jax.Array], pm, state):
        """Compute + publish pipeline over pre-staged owner-layout gradients
        (stage_in already happened inside the microbatch scan).  Returns
        ``(matrix_updates, new_momentum, new_vstate)``."""
        matrix_updates: Dict[str, jax.Array] = {}
        new_momentum: Dict[str, jax.Array] = {}
        new_vstate: Optional[dict] = None
        prev_ortho = None
        for _, keys in self.schedule:
            bucket_staged = {group_key_str(k): staged[group_key_str(k)]
                             for k in keys}
            if self.barriers and prev_ortho is not None:
                # publish(k-1) rides alongside compute(k)
                bucket_staged = _after(bucket_staged, prev_ortho)
            ortho, mom_b, vs_b = self.compute(keys, bucket_staged,
                                              state.momentum, state.step,
                                              state.variant_state)
            matrix_updates.update(self.publish(keys, ortho, pm))
            new_momentum.update(mom_b)
            new_vstate = _merge_state(new_vstate, vs_b)
            prev_ortho = ortho
        return matrix_updates, new_momentum, new_vstate
