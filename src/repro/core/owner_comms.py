"""Owner-layout communication machinery (the SPMD pattern of §3.2).

This module owns everything about *where matrices live*: the leaf↔matrix
reshapes, the owner-major packed stacking, and the staged resharding that
lowers the owner transpose to same-shape all-to-alls instead of XLA's
"involuntary full rematerialization" (whole-tensor all-gathers).

It deliberately knows nothing about optimization: no momentum, no
Newton-Schulz, no learning rates.  ``core/muon.py`` composes an
:class:`OwnerLayout` with an orthogonalizer (``core/orthogonalize.py``) and an
update rule (``core/update_rules.py``); tests exercise the layout in
isolation (tests/test_owner_comms.py).

Module-level functions are the stable primitive API (kept for callers that
carry an explicit ``(plan, mesh)`` pair); ``OwnerLayout`` binds them once so
optimizer code reads as ``layout.pack(key, leaves)`` / ``layout.unpack(...)``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dedication import DedicationPlan


def group_key_str(key) -> str:
    """Sanitize a group key for use as a state-dict key ('/' would collide
    with the checkpoint manifest's path separator)."""
    return key.replace("/", ".") if isinstance(key, str) else \
        f"{key[0]}x{key[1]}"


def _lead_perm(info, spec) -> tuple:
    """Permutation of the leaf's leading dims putting sharded dims first
    (major).  Flattening a sharded-MAJOR axis keeps the merged-axis sharding
    expressible and every reshape local — the property that lets the owner
    transpose lower to one same-shape all-to-all instead of XLA's
    "involuntary full rematerialization" (whole-tensor all-gather)."""
    n_lead = len(info.shape) - 2
    if spec is None or n_lead <= 1:
        return tuple(range(n_lead))
    lead = list(spec)[:n_lead] if len(spec) >= n_lead else [None] * n_lead
    return tuple(sorted(range(n_lead), key=lambda i: (lead[i] is None, i)))


def _stacked_spec(info, spec):
    """Training-layout PartitionSpec of the (count, m, n) stacked view."""
    from jax.sharding import PartitionSpec as P
    if spec is None:
        return None
    n_lead = len(info.shape) - 2
    lead = list(spec)[:n_lead]
    perm = _lead_perm(info, spec)
    major = lead[perm[0]] if n_lead and perm and lead[perm[0]] is not None \
        else None
    m_spec = spec[-2] if len(spec) >= 2 else None
    n_spec = spec[-1] if len(spec) >= 1 else None
    if info.transpose:
        m_spec, n_spec = n_spec, m_spec
    return P(major, m_spec, n_spec)


def _leaf_to_matrices(arr: jax.Array, info, spec=None) -> jax.Array:
    """(lead..., m0, n0) -> (count, m, n) with m <= n, sharded-major order."""
    m0, n0 = info.shape[-2:]
    perm = _lead_perm(info, spec)
    n_lead = arr.ndim - 2
    if perm != tuple(range(n_lead)):
        arr = jnp.transpose(arr, perm + (n_lead, n_lead + 1))
    flat = arr.reshape((-1, m0, n0))
    return flat.mT if info.transpose else flat


def _matrices_to_leaf(flat: jax.Array, info, spec=None) -> jax.Array:
    if info.transpose:
        flat = flat.mT
    perm = _lead_perm(info, spec)
    n_lead = len(info.shape) - 2
    if perm != tuple(range(n_lead)):
        permuted_shape = tuple(info.shape[i] for i in perm) + info.shape[-2:]
        inv = tuple(np.argsort(perm)) + (n_lead, n_lead + 1)
        return jnp.transpose(flat.reshape(permuted_shape), inv)
    return flat.reshape(info.shape)


def pack_group(plan: DedicationPlan, key, leaf_values: Dict[str, jax.Array],
               mesh=None) -> jax.Array:
    """Stack a shape group's matrices into the owner-major padded layout.

    Output: (num_owners * capacity, m, n); position p belongs to owner
    p // capacity.  With known training specs the stacked view is explicitly
    constrained so the only communication is the same-shape axis-0
    redistribution applied afterwards by the owner constraint.
    """
    g = plan.groups[key]
    specs = getattr(plan, "train_specs", None) or {}
    parts = []
    for p in g.leaf_paths:
        spec = specs.get(p)
        part = _leaf_to_matrices(leaf_values[p], plan.leaves[p], spec)
        st_spec = _stacked_spec(plan.leaves[p], spec)
        if mesh is not None and st_spec is not None:
            from jax.sharding import NamedSharding
            part = jax.lax.with_sharding_constraint(
                part, NamedSharding(mesh, st_spec))
        parts.append(part)
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    m, n = g.key
    n_pad = g.packed_size - g.count
    if np.array_equal(g.pack_index[:g.count], np.arange(g.count)):
        # contiguous physical layout: pure pad — partitions as a local op
        if n_pad == 0:
            return flat
        return jnp.concatenate(
            [flat, jnp.zeros((n_pad, m, n), flat.dtype)], axis=0)
    pad = jnp.zeros((1, m, n), flat.dtype)
    flat_ext = jnp.concatenate([flat, pad], axis=0)
    idx = np.where(g.pack_index < 0, g.count, g.pack_index)
    return jnp.take(flat_ext, jnp.asarray(idx), axis=0)


def unpack_group(plan: DedicationPlan, key, packed: jax.Array,
                 mesh=None) -> Dict[str, jax.Array]:
    """Inverse of pack_group: owner-major stack -> per-leaf arrays.

    The publish reshard (owner layout -> training layout) happens HERE at the
    padded stacked shape — a same-shape axis redistribution (all-to-all) —
    before any slice/transpose/reshape, all of which are then sharding-local.
    """
    g = plan.groups[key]
    specs = getattr(plan, "train_specs", None) or {}
    if len(g.leaf_paths) == 1 and mesh is not None:
        p = g.leaf_paths[0]
        st_spec = _stacked_spec(plan.leaves[p], specs.get(p))
        if st_spec is not None:
            packed = _from_owner_staged(packed, st_spec, plan, mesh)
    if np.array_equal(g.unpack_index, np.arange(g.count)):
        flat = packed[:g.count]            # contiguous layout: pure slice
    else:
        flat = jnp.take(packed, jnp.asarray(g.unpack_index), axis=0)
    out: Dict[str, jax.Array] = {}
    start = 0
    for p in g.leaf_paths:
        info = plan.leaves[p]
        out[p] = _matrices_to_leaf(flat[start:start + info.count], info,
                                   specs.get(p))
        start += info.count
    return out


def owner_sharding(plan: DedicationPlan, mesh, ndim: int = 3):
    """NamedSharding for owner-major state buffers: axis 0 over the owner
    mesh axes, trailing ``ndim - 1`` dims replicated.  ``ndim=3`` covers the
    (D·cap, m, n) momentum stacks; variant state may carry (D·cap, m)
    buffers (e.g. NorMuon's neuron-wise second moments) with ``ndim=2``."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = plan.owner_axes or tuple(mesh.axis_names)
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))


def _constrain(x, sharding):
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def _to_owner_staged(x, stacked_spec, plan, mesh):
    """Training-stacked layout -> owner layout, one mesh axis per stage.

    Each stage moves a single mesh axis from a matrix dim onto the stack
    axis — a reshard GSPMD lowers as a true all-to-all.  Jumping directly to
    the owner spec lets XLA resolve the two-axis move "through replication"
    (full-tensor all-gathers), a TB-scale temp at 340B+ scale; see
    docs/DESIGN.md §2 and §9 (nemotron train iteration).
    """
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = plan.owner_axes or tuple(mesh.axis_names)
    cur = list(stacked_spec) if stacked_spec is not None else [None] * 3
    while len(cur) < 3:
        cur.append(None)
    front = list(cur[0]) if isinstance(cur[0], tuple) else \
        ([cur[0]] if cur[0] is not None else [])
    for ax in axes:
        if ax in front:
            continue
        rest = [None if d == ax else d for d in cur[1:]]
        front = front + [ax]
        cur = [tuple(front)] + rest
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*cur)))
    return x


def _from_owner_staged(x, stacked_spec, plan, mesh):
    """Owner layout -> training-stacked layout (publish), staged in reverse:
    one axis leaves the stack dim per stage (an all-to-all back to its matrix
    dim, or an all-gather when the training layout doesn't use it)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = list(plan.owner_axes or tuple(mesh.axis_names))
    target = list(stacked_spec) if stacked_spec is not None else [None] * 3
    while len(target) < 3:
        target.append(None)
    front = list(axes)
    rest = [None, None]
    for ax in reversed(axes):
        front = [a for a in front if a != ax]
        for di in (1, 2):
            if target[di] == ax:
                rest[di - 1] = ax
        lead = tuple(front) if front else target[0]
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(lead, rest[0], rest[1])))
    return x


def repack_rows(old_g, new_g, buf: jax.Array) -> jax.Array:
    """Re-layout one owner-major buffer across plans: unpack the logical rows
    under ``old_g`` (a GroupPlan), repack/pad under ``new_g``.  Works on any
    (packed_size, ...) buffer — momentum stacks, NorMuon (D·cap, m) moments,
    MuonBP (D·cap, m, m) caches — so elastic restart reshards every piece of
    owner state with the same code path."""
    if np.array_equal(old_g.unpack_index, np.arange(old_g.count)):
        rows = buf[:old_g.count]
    else:
        rows = jnp.take(buf, jnp.asarray(old_g.unpack_index), axis=0)
    n_pad = new_g.packed_size - new_g.count
    if np.array_equal(new_g.pack_index[:new_g.count],
                      np.arange(new_g.count)):
        if n_pad == 0:
            return rows
        return jnp.concatenate(
            [rows, jnp.zeros((n_pad,) + rows.shape[1:], rows.dtype)], 0)
    ext = jnp.concatenate(
        [rows, jnp.zeros((1,) + rows.shape[1:], rows.dtype)], 0)
    idx = np.where(new_g.pack_index < 0, new_g.count, new_g.pack_index)
    return jnp.take(ext, jnp.asarray(idx), axis=0)


class OwnerLayout:
    """The pack/reshard half of the optimizer, bound to a (plan, mesh) pair.

    One instance per optimizer; all methods are pure and jit-traceable.  The
    optimizer core never touches PartitionSpecs directly — it asks the layout
    to move tensors between the training layout and the owner layout.
    """

    def __init__(self, plan: DedicationPlan, mesh=None):
        self.plan = plan
        self.mesh = mesh
        self.sharding = owner_sharding(plan, mesh)

    # ---------------------------------------------------------- structure

    @property
    def group_keys(self):
        return list(self.plan.groups.keys())

    def packed_shape(self, key) -> tuple:
        g = self.plan.groups[key]
        return (g.packed_size,) + g.key

    def buffer_sharding(self, ndim: int = 3):
        """Sharding for an owner-major state buffer of rank ``ndim``."""
        return owner_sharding(self.plan, self.mesh, ndim)

    def zeros(self, key, dtype, trailing: tuple = None) -> jax.Array:
        """Owner-sharded zero state buffer for group ``key``.  ``trailing``
        overrides the per-row shape (default: the (m, n) matrix)."""
        g = self.plan.groups[key]
        shape = (g.packed_size,) + (g.key if trailing is None
                                    else tuple(trailing))
        buf = jnp.zeros(shape, dtype)
        return _constrain(buf, self.buffer_sharding(len(shape)))

    # -------------------------------------------------------- movement

    def stacked_spec(self, key):
        """Training-layout spec of the stacked view (single-leaf groups)."""
        g = self.plan.groups[key]
        if len(g.leaf_paths) != 1:
            return None
        p = g.leaf_paths[0]
        specs = getattr(self.plan, "train_specs", None) or {}
        return _stacked_spec(self.plan.leaves[p], specs.get(p))

    def pack(self, key, leaf_values: Dict[str, jax.Array]) -> jax.Array:
        """Training layout -> owner-major stack (reduce-to-owner direction):
        stack + stage the all-to-alls + pin the owner sharding."""
        packed = pack_group(self.plan, key, leaf_values, mesh=self.mesh)
        packed = _to_owner_staged(packed, self.stacked_spec(key), self.plan,
                                  self.mesh)
        return _constrain(packed, self.sharding)

    def unpack(self, key, packed: jax.Array) -> Dict[str, jax.Array]:
        """Owner-major stack -> training layout (publish direction)."""
        return unpack_group(self.plan, key, packed, mesh=self.mesh)

    def constrain(self, x: jax.Array) -> jax.Array:
        """Pin ``x`` (an owner-major stack) to the owner sharding."""
        return _constrain(x, self.sharding)

    def constrain_buffer(self, x: jax.Array) -> jax.Array:
        """Pin an owner-major state buffer of any rank (axis 0 = stack)."""
        return _constrain(x, self.buffer_sharding(x.ndim))

    # ---------------------------------------------------------- local map

    def shard_local(self, fn, tree_in):
        """Run ``fn`` over owner-sharded stacks with provably local compute.

        ``tree_in`` is a (nested) dict of owner-major buffers; under a mesh
        the call is wrapped in shard_map with the stack axis sharded over the
        owner axes (no collectives inside — each device handles its own
        matrices); without one, ``fn`` runs directly (unit tests).
        shard_map infers the per-leaf specs from leaf ranks.
        """
        if self.mesh is None:
            return fn(tree_in)
        from jax.sharding import PartitionSpec as P
        axes = self.plan.owner_axes or tuple(self.mesh.axis_names)

        def spec_of(leaf):
            return P(axes, *([None] * (leaf.ndim - 1)))
        in_specs = jax.tree.map(spec_of, tree_in)
        out_shape = jax.eval_shape(fn, tree_in)
        out_specs = jax.tree.map(spec_of, out_shape)
        # check_vma=False: the Gram kernels' pallas_calls inside ``fn`` do not
        # type-check under vma (no out_shape vma; the interpreter's slices
        # reject mixed vma).  ``fn`` is local per stack row, so the check
        # has nothing to catch here.
        return jax.shard_map(fn, mesh=self.mesh, in_specs=(in_specs,),
                             out_specs=out_specs, check_vma=False)(tree_in)
