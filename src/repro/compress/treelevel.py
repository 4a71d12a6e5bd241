"""Pytree adapter for the compression subsystem (DESIGN.md §3-§5).

The flat layers (:mod:`plan` / :mod:`backends`) think in (n, d) matrices;
model training thinks in parameter pytrees whose leaves carry a leading node
axis and GSPMD shardings.  This module is the ONE place that bridges them:

* :func:`leaf_keys`          — per-leaf RNG key fanout;
* :func:`bernoulli_compress` — tree-level independent / shared_coords modes;
* :func:`permk_compress`     — tree-level PermK with exact aggregate;
* :func:`fused_tree_update`  — the Pallas fused path, now covering ALL modes
  (independent | shared_coords | permk) x variants (dasha | mvr), which lets
  :mod:`repro.optim.distributed` drop its old "kernel only if not permk and
  not mvr" restriction.

All masks come from :mod:`repro.compress.plan`, so the dense and fused paths
are parity-testable under the same key.  The fused path draws a leaf's
``independent`` u8-threshold mask inside the kernel from the leaf key
(:func:`kernel_draw_threshold`, counted by :func:`kernel_draw_count`)
and streams each leaf in its own layout where the kernel's blocks tile it
(:func:`kernel_layout_count`), its gradient in the oracle's dtype
(:func:`kernel_grad_dtype_count`); every other mask draw runs under the
``dasha.compress`` named scope (the mean over nodes under
``dasha.aggregate``), which names its ops in a device trace.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compress.plan import draw_mask, permk_owner, u8_threshold

PyTree = Any


def leaf_keys(key: jax.Array, tree: PyTree) -> PyTree:
    """Split one round key into one key per leaf (same treedef)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = list(jax.random.split(key, len(leaves)))
    return jax.tree_util.tree_unflatten(treedef, keys)


def _spec_leaf(t) -> bool:
    from jax.sharding import PartitionSpec
    return t is None or isinstance(t, (jax.Array, PartitionSpec))


def _is_pair(t) -> bool:
    return isinstance(t, tuple) and len(t) == 2


def _is_triple(t) -> bool:
    return isinstance(t, tuple) and len(t) == 3


def _none_specs(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x: None, tree)


# ---------------------------------------------------------------------------
# dense tree-level execution
# ---------------------------------------------------------------------------

def bernoulli_compress(key: jax.Array, delta: PyTree, p: float,
                       specs: Optional[PyTree] = None,
                       shared: bool = False) -> PyTree:
    """delta leaves: (n, *shape). Independent mask per node per coordinate;
    ``shared=True`` draws ONE mask per leaf shared by all nodes (the
    aggregate is then supported on ~p*d coords with a common index set —
    the `shared_coords` execution mode; loses the omega/n variance
    averaging across nodes, see DESIGN.md §3).

    ``specs``: optional PartitionSpecs (WITH the node axis) pinned onto the
    Bernoulli masks — forces the partitionable threefry RNG to generate its
    bits sharded instead of materialising an unsharded d-size mask."""
    def leaf(k, x, spec):
        shp = x.shape[1:] if shared else x.shape
        mask = draw_mask(k, shp, p)
        if shared:
            mask = jnp.broadcast_to(mask[None], x.shape)
        if spec is not None:
            mask = jax.lax.with_sharding_constraint(mask, spec)
        return jnp.where(mask, x / p, 0.0).astype(x.dtype)

    if specs is None:
        specs = _none_specs(delta)
    with jax.named_scope("dasha.compress"):
        return jax.tree_util.tree_map(leaf, leaf_keys(key, delta), delta,
                                      specs, is_leaf=_spec_leaf)


def permk_compress(key: jax.Array, delta: PyTree, n: int,
                   specs: Optional[PyTree] = None) -> Tuple[PyTree, PyTree]:
    """Returns (messages m_i (n,*shape), exact aggregate mean_i m_i (*shape)).

    PermK partitioning via the shared cyclically-shifted ownership map
    (:func:`repro.compress.plan.permk_owner`) — iota masks only, no
    (n, n, blk) intermediates, no rolls — so GSPMD keeps every tensor at the
    (n, d) footprint (the roll formulation compiled to 5x peak memory; see
    EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec

    def leaf(k, x, spec):
        nloc = x.shape[0]
        owner = permk_owner(k, x.shape[1:], nloc)
        if spec is not None:              # shard the ownership iota too
            owner = jax.lax.with_sharding_constraint(
                owner, PartitionSpec(*tuple(spec)[1:]))
        ids = jnp.arange(nloc).reshape((nloc,) + (1,) * (x.ndim - 1))
        m = x * (owner[None] == ids).astype(x.dtype) * nloc
        if spec is not None:
            m = jax.lax.with_sharding_constraint(m, spec)
        # disjoint supports => the mean recovers exactly node owner(c)'s
        # value at c; computed as a plain mean so GSPMD emits ONE reduce
        # over the node axis.
        with jax.named_scope("dasha.aggregate"):
            return m, jnp.mean(m.astype(jnp.float32), 0)

    if specs is None:
        specs = _none_specs(delta)
    with jax.named_scope("dasha.compress"):
        pairs = jax.tree_util.tree_map(leaf, leaf_keys(key, delta), delta,
                                       specs, is_leaf=_spec_leaf)
    m = jax.tree_util.tree_map(lambda p_: p_[0], pairs, is_leaf=_is_pair)
    agg = jax.tree_util.tree_map(lambda p_: p_[1], pairs, is_leaf=_is_pair)
    return m, agg


# ---------------------------------------------------------------------------
# fused (Pallas) tree-level execution — full mode x variant coverage
# ---------------------------------------------------------------------------

def _leaf_mask(k, x, spec, *, mode: str, p: float, n: int) -> jax.Array:
    """One leaf's (n, *shape) f32 {0,1} mask (see :func:`tree_masks`)."""
    if mode == "permk":
        nloc = x.shape[0]
        # the returned scale is the tree-wide n: a leaf whose node axis
        # disagrees would get silently mis-scaled (biased estimator)
        assert nloc == n, (f"permk leaf node axis {nloc} != n={n}; "
                           "masks and scale would disagree")
        owner = permk_owner(k, x.shape[1:], nloc)
        ids = jnp.arange(nloc).reshape((nloc,) + (1,) * (x.ndim - 1))
        mask = (owner[None] == ids).astype(jnp.float32)
    elif mode == "shared_coords":
        mask = jnp.broadcast_to(draw_mask(k, x.shape[1:], p)[None],
                                x.shape).astype(jnp.float32)
    else:
        mask = draw_mask(k, x.shape, p).astype(jnp.float32)
    if spec is not None:
        mask = jax.lax.with_sharding_constraint(mask, spec)
    return mask


def _scale(mode: str, p: float, n: int) -> float:
    return float(n) if mode == "permk" else 1.0 / p


def tree_masks(key: jax.Array, tree: PyTree, *, mode: str, p: float, n: int,
               specs: Optional[PyTree] = None) -> Tuple[PyTree, float]:
    """One (n, *shape) f32 {0,1} mask per leaf + the unbiasedness scale.

    Draws the SAME randomness as the dense paths above (same per-leaf key
    fanout, same primitives), so fused-vs-dense trajectories are
    parity-testable under a shared round key."""
    if specs is None:
        specs = _none_specs(tree)
    with jax.named_scope("dasha.compress"):
        masks = jax.tree_util.tree_map(
            lambda k, x, spec: _leaf_mask(k, x, spec, mode=mode, p=p, n=n),
            leaf_keys(key, tree), tree, specs, is_leaf=_spec_leaf)
    return masks, _scale(mode, p, n)


#: the largest leaf whose flat indices the keyed kernels count exactly in
#: u32 arithmetic
KERNEL_DRAW_MAX_ELEMENTS = 2 ** 32


def _draws_threefry(key: Optional[jax.Array]) -> bool:
    """``key`` (a raw key where ``None``) draws ``draw_mask``'s bits with
    the partitionable threefry: one u32 counter per element, which the
    keyed kernels replay."""
    if key is not None and jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        impl = str(jax.random.key_impl(key))
    else:
        impl = jax.config.jax_default_prng_impl
    return impl == "threefry2x32" and jax.config.jax_threefry_partitionable


def kernel_draw_threshold(x, spec, *, mode: str, p: float, mesh=None,
                          key: Optional[jax.Array] = None) -> Optional[int]:
    """The u8 threshold at which the fused kernel draws leaf ``x``'s mask
    itself, or ``None`` where :func:`tree_masks` must draw it.

    The kernel replays ``draw_mask``'s u8 path over the whole
    ``(n, *shape)`` leaf, so it takes a leaf only when that is the draw:
    ``independent`` masks, ``256 p`` an integer in (0, 256), a threefry
    ``key`` (raw where ``None``), the size within
    :data:`KERNEL_DRAW_MAX_ELEMENTS`, and the leaf not split over ``mesh``
    (a shard's elements are not a contiguous run of the leaf's flat
    indices)."""
    if mode != "independent" or not _draws_threefry(key):
        return None
    if spec is not None and mesh is not None and not mesh.empty:
        return None
    if int(x.size) > KERNEL_DRAW_MAX_ELEMENTS:
        return None
    return u8_threshold(p)


class KernelDraws(NamedTuple):
    """How much of a tree's mask draw the fused kernel makes."""

    leaves: int
    of_leaves: int
    elements: int
    of_elements: int

    #: what the counted leaves have, in the printed line
    label = "in kernel"

    def __str__(self) -> str:
        return (f"{self.label} {self.leaves}/{self.of_leaves} leaves, "
                f"{self.elements / 1e6:.1f}M/{self.of_elements / 1e6:.1f}M"
                " elements")


class KernelLayouts(KernelDraws):
    """How much of a tree the fused kernel updates in its own layout."""

    __slots__ = ()
    label = "own layout"


def kernel_draw_count(tree: PyTree, *, mode: str, p: float,
                      specs: Optional[PyTree] = None,
                      mesh=None) -> KernelDraws:
    """Leaves and elements of ``tree`` (leaves ``(n, *shape)``, arrays or
    shapes) whose masks :func:`fused_tree_update` draws in the kernel,
    under ``specs`` on ``mesh`` (default: the current abstract mesh)."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    leaves, spec_leaves = _spec_leaves(tree, specs)
    drawn = [int(x.size) for x, spec in zip(leaves, spec_leaves)
             if kernel_draw_threshold(x, spec, mode=mode, p=p,
                                      mesh=mesh) is not None]
    return KernelDraws(len(drawn), len(leaves), sum(drawn),
                       sum(int(x.size) for x in leaves))


def _spec_leaves(tree: PyTree, specs: Optional[PyTree]):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, ([None] * len(leaves) if specs is None
                    else treedef.flatten_up_to(specs))


def kernel_layout_count(tree: PyTree, *, specs: Optional[PyTree] = None,
                        mesh=None) -> KernelLayouts:
    """Leaves and elements of ``tree`` (leaves ``(n, *shape)``, arrays or
    shapes) that :func:`fused_tree_update` streams in their own layout
    (:func:`repro.kernels.ops.node_update_view`) rather than in lane rows:
    each device's shard where ``specs`` split a leaf over ``mesh``
    (default: the current abstract mesh)."""
    from jax.sharding import NamedSharding

    from repro.kernels.ops import node_update_view
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    leaves, spec_leaves = _spec_leaves(tree, specs)

    def shape(x, spec):
        if spec is None or mesh.empty:
            return x.shape
        return NamedSharding(mesh, spec).shard_shape(x.shape)

    own = [int(x.size) for x, spec in zip(leaves, spec_leaves)
           if node_update_view(shape(x, spec)) is not None]
    return KernelLayouts(len(own), len(leaves), sum(own),
                         sum(int(x.size) for x in leaves))


class KernelGradDtypes(KernelDraws):
    """How much of a tree's gradient the fused kernel reads in the dtype the
    oracle made it in, with no cast pass before the kernel."""

    __slots__ = ()
    label = "own dtype"


def kernel_grad_dtype_count(grads: PyTree) -> KernelGradDtypes:
    """Leaves and elements of the gradient tree ``grads`` (leaves ``(n,
    *shape)``, arrays or shapes, in the oracle's dtypes) that
    :func:`fused_tree_update` hands to its kernels as they are
    (:func:`repro.kernels.ops.kernel_grad_dtype`), rather than as an f32
    copy."""
    from repro.kernels.ops import kernel_grad_dtype
    leaves = jax.tree_util.tree_leaves(grads)
    own = [int(x.size) for x in leaves
           if kernel_grad_dtype(x.dtype) == x.dtype]
    return KernelGradDtypes(len(own), len(leaves), sum(own),
                            sum(int(x.size) for x in leaves))


def fused_tree_update(key: jax.Array, grads_new: PyTree, h: PyTree,
                      g_local: PyTree, *, mode: str, a: float, p: float,
                      n: int, variant: str = "dasha", b: float = 0.0,
                      grads_old: Optional[PyTree] = None,
                      specs: Optional[PyTree] = None
                      ) -> Tuple[PyTree, PyTree, PyTree]:
    """Alg. 1 lines 8-10 per leaf in ONE Pallas HBM pass, for every mode.

    ``variant="dasha"``: h_new = grads_new.  ``variant="mvr"``: the kernel
    fuses the momentum h-update h_new = gn + (1-b)(h - go) as well
    (``grads_old`` required).  Returns (m, h_new, g_local_new) trees.

    A leaf whose mask is ``draw_mask``'s u8 draw over the whole leaf
    (:func:`kernel_draw_threshold`) has it drawn inside the kernel from its
    leaf key, so the mask never reaches HBM; every other leaf gets its mask
    from :func:`tree_masks`' per-leaf draw.  Both give the same mask under
    the same key.

    Under a mesh (``jax.set_mesh``) with ``specs`` given, each leaf's
    kernel runs on every device's own shard (``shard_map``): the update
    is elementwise, and XLA cannot partition a Mosaic kernel."""
    from repro.kernels import ops as kops

    if specs is None:
        specs = _none_specs(grads_new)
    mesh = jax.sharding.get_abstract_mesh()
    scale = _scale(mode, p, n)
    with jax.named_scope("dasha.compress"):
        keys = leaf_keys(key, grads_new)

    if variant == "mvr":
        assert grads_old is not None, "mvr fused path needs grads_old"

        def update(mask, gn, go, hh, gl):
            return kops.dasha_mvr_update(gn, go, hh, gl, mask, a, b, scale)

        def keyed(k, thresh, gn, go, hh, gl):
            return kops.dasha_mvr_update_keyed(gn, go, hh, gl, k, a, b,
                                               scale, thresh)

        operands = (grads_new, grads_old, h, g_local)
    else:
        def update(mask, gn, hh, gl):
            return kops.dasha_update(gn, hh, gl, mask, a, scale)

        def keyed(k, thresh, gn, hh, gl):
            return kops.dasha_update_keyed(gn, hh, gl, k, a, scale, thresh)

        operands = (grads_new, h, g_local)

    def leaf(spec, k, *xs):
        thresh = kernel_draw_threshold(xs[0], spec, mode=mode, p=p,
                                       mesh=mesh, key=key)
        if thresh is not None:
            return keyed(k, thresh, *xs)
        with jax.named_scope("dasha.compress"):
            mask = _leaf_mask(k, xs[0], spec, mode=mode, p=p, n=n)
        if spec is None or mesh.empty:
            return update(mask, *xs)
        return jax.shard_map(update, in_specs=(spec,) * (len(xs) + 1),
                             out_specs=(spec,) * 3, check_vma=False)(mask,
                                                                     *xs)

    trips = jax.tree_util.tree_map(leaf, specs, keys, *operands,
                                   is_leaf=_spec_leaf)

    def pick(i):
        return jax.tree_util.tree_map(lambda t: t[i], trips,
                                      is_leaf=_is_triple)

    return pick(0), pick(1), pick(2)
