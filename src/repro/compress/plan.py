"""Layer 2 of the compression subsystem: per-round randomness ("plans").

Every compressor draws its randomness HERE, exactly once per round, through
one of four primitives:

* :func:`draw_mask`       — Bernoulli(p) 0/1 mask (u8-threshold fast path,
                            :func:`u8_threshold`);
* :func:`randk_indices`   — uniform K-subset without replacement (RandK);
* :func:`perm_partition`  — a shared permutation split into n node blocks
                            (PermK, flat path);
* :func:`permk_owner`     — the cyclic-shift ownership map (PermK, pytree /
                            GSPMD path: iota only, no d-sized permutation).

The resulting :class:`Plan` is backend-agnostic: the dense, sparse and fused
execution backends (see :mod:`repro.compress.backends`) all consume the same
plan, which is what makes sparse-vs-dense messages bit-identical under the
same key and lets the fused Pallas kernels reuse the masks.  See DESIGN.md
§5 (execution backends) and §6 (payload accounting).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

#: sentinel index value used to pad ragged PermK blocks (>= d, dropped by
#: ``mode="drop"`` scatters and masked out of gathers)
PAD = jnp.iinfo(jnp.int32).max


class Plan(NamedTuple):
    """Per-round compression randomness, shared by every backend.

    ``kind`` selects the execution family:

    * ``"sparsify"``    — coordinate selection; ``indices`` (static-K
      compressors: RandK / PermK) and/or ``mask`` (Bernoulli) carry the
      support, ``scale`` the unbiasedness rescale.
    * ``"dither"``      — stochastic quantization; ``dither_u`` carries the
      external uniforms (so dense / fused paths quantize identically).
    * ``"passthrough"`` — identity.

    ``payload_coords`` counts fp32-equivalent scalars per node message under
    ideal entropy coding (Definition 1.3 accounting); ``wire_coords`` counts
    what the sparse wire format actually moves (values + indices).
    """

    kind: str
    scale: Union[float, jax.Array]
    indices: Optional[jax.Array] = None       # (n, k) int32, PAD-padded
    mask: Optional[jax.Array] = None          # (n, d) 0/1, or None
    dither_u: Optional[jax.Array] = None      # (n, d) uniforms
    levels: int = 0                           # dither levels s
    payload_coords: float = 0.0
    wire_coords: float = 0.0


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def u8_threshold(p: float) -> Optional[int]:
    """``round(256 p)`` where the Bernoulli(p) mask is exactly
    ``uint8 bits < round(256 p)``: p a multiple of 1/256 in (0, 1).  Else
    ``None`` (p = 1.0 among them: uint8(256) would overflow)."""
    thresh256 = p * 256.0
    if abs(thresh256 - round(thresh256)) < 1e-9 and 0 < round(thresh256) < 256:
        return round(thresh256)
    return None


def draw_mask(k: jax.Array, shape, p: float) -> jax.Array:
    """Bernoulli(p) mask; u8-threshold path (exact when p is a multiple of
    1/256) avoids materialising u32 bits + f32 uniforms over d elements."""
    thresh = u8_threshold(p)
    if thresh is not None:
        return jax.random.bits(k, shape, jnp.uint8) < jnp.uint8(thresh)
    return jax.random.bernoulli(k, p, shape)


def randk_indices(key: jax.Array, d: int, k: int) -> jax.Array:
    """Uniform K-subset of [d] without replacement, as (k,) int32 indices.

    Top-k of iid uniforms == uniform K-subset without replacement."""
    u = jax.random.uniform(key, (d,))
    return jax.lax.top_k(u, k)[1].astype(jnp.int32)


def perm_partition(key: jax.Array, d: int, n: int) -> jax.Array:
    """PermK partition of [d] into n node blocks: (n, ceil(d/n)) indices.

    The inverse view of :func:`permk_owner` (SAME shift draw, so the flat
    and pytree PermK paths agree bit-for-bit under one key): node i owns
    ``c = (i*blk + j - shift) mod n*blk`` for j in [0, blk).  O(d) iota
    arithmetic — no d-sized permutation/sort, which costs ~17 s at d=1e7 on
    CPU and is why the cyclic-shift partition is this repo's PermK
    everywhere (per-coordinate ownership marginals stay exactly 1/n, so
    unbiasedness and omega = n-1 are unchanged; beyond-paper adaptation,
    DESIGN.md §3).  When ``d % n != 0`` out-of-range slots carry the
    :data:`PAD` sentinel; backends drop / zero them."""
    blk = -(-d // n)                          # ceil
    shift = jax.random.randint(key, (), 0, n * blk)
    c = (jnp.arange(n * blk, dtype=jnp.int32).reshape(n, blk) - shift) \
        % (n * blk)
    return jnp.where(c < d, c, PAD)


def permk_owner(key: jax.Array, shape, n: int) -> jax.Array:
    """PermK ownership map for one leaf of shape ``shape`` (no node axis):
    coordinate c belongs to node ``owner(c) = ((c + shift) // blk) % n``.

    Iota + cyclic shift only — no (n, n, blk) intermediates, no rolls, no
    d-sized permutation — so GSPMD keeps every tensor at its own footprint
    (the roll formulation compiled to 5x peak memory; EXPERIMENTS.md §Perf).
    """
    L = 1
    for s in shape:
        L *= int(s)
    blk = -(-L // n)                          # ceil
    shift = jax.random.randint(key, (), 0, n * blk)
    owner = ((jnp.arange(L) + shift) // blk) % n
    return owner.reshape(shape)


def indices_to_masks(indices: jax.Array, d: int,
                     dtype=jnp.float32) -> jax.Array:
    """(n, k) PAD-padded indices -> (n, d) 0/1 masks (PAD slots dropped)."""
    def one(idx):
        return jnp.zeros((d,), dtype).at[idx].set(1.0, mode="drop")
    return jax.vmap(one)(indices)


def participation_coins(key: jax.Array, n: int, p: float) -> jax.Array:
    """Per-node Bernoulli(p) participation coins as a (n, 1) f32 factor of
    ``coin / p`` (Appendix D wrapper C_{p'}): multiply into any plan's scale
    or mask to get the partial-participation variant."""
    coins = jax.random.bernoulli(key, p, (n,))
    return (coins.astype(jnp.float32) / p)[:, None]
