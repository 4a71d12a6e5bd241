"""State substrates: the handful of ops the method skeleton needs, twice.

A substrate answers "what shape is the per-node state and how do I act on
it":

* :class:`FlatSubstrate` — stacked ``(n, d)`` arrays, vmap on one host (the
  research loop of :mod:`repro.core.dasha`); compression through a
  :class:`repro.compress.RoundCompressor` (dense | sparse | fused backends);
* :class:`TreeSubstrate` — params-shaped pytrees with a leading node axis,
  GSPMD-sharding aware (the trainer of :mod:`repro.optim.distributed`);
  compression either tree-native (:class:`TreeCompression` →
  :mod:`repro.compress.treelevel`, incl. the fused Pallas path) or per-leaf
  through the same RoundCompressor specs (:class:`LeafSpecCompressor`).

Oracles are pluggable on the tree side: :class:`BatchLossOracle` derives
per-node gradients from a loss function (training), while
:class:`LeafProblemOracle` adapts a flat Section-1.2 problem to a
single-leaf tree — under it, a single-leaf TreeSubstrate is BIT-IDENTICAL
to FlatSubstrate (same RNG, same compressor plan), which is the substrate-
parity contract tested in tests/test_methods_api.py.

RNG contract: the engine hands each substrate the same round keys; per-leaf
fanout is ``split(key, n_leaves)`` EXCEPT a single-leaf tree uses the round
key directly (the degenerate tree *is* the flat substrate).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.tags import COHORT_TAG
from repro.compress import as_round_compressor
from repro.compress.backends import RoundCompressor
from repro.compress.treelevel import (bernoulli_compress, fused_tree_update,
                                      permk_compress)
from repro.methods.rules import MvrFusion

PyTree = Any


# ---------------------------------------------------------------------------
# shared oracle semantics over the Section 1.2 problem classes
# ---------------------------------------------------------------------------

def _problem_grad(problem, key, x, size):
    """Finite-sum: the exact nabla f_i; stochastic: a fresh size-B batch."""
    if hasattr(problem, "full_grad"):
        return problem.full_grad(x)
    return problem.stoch_grad(key, x, size)


def _problem_grad_pair(problem, key, x_new, x_old, size):
    """Same-sample gradients at two points (MVR / SARAH)."""
    if hasattr(problem, "stoch_grad_pair"):
        return problem.stoch_grad_pair(key, x_new, x_old, size)
    # finite-sum: the SAME key draws the same multiset at both points
    return (problem.minibatch_grad(key, x_new, size),
            problem.minibatch_grad(key, x_old, size))


def _problem_grad_diff(problem, key, x_new, x_old, size):
    """Shared-sample difference (PAGE / MARINA).  ``size == 0`` requests the
    exact full-gradient difference (plain MARINA on finite sums)."""
    if hasattr(problem, "minibatch_diff"):
        if size == 0:
            return problem.full_grad(x_new) - problem.full_grad(x_old)
        return problem.minibatch_diff(key, x_new, x_old, size)
    gn, go = problem.stoch_grad_pair(key, x_new, x_old, size)
    return gn - go


def _problem_megabatch(problem, key, x, size):
    """The sync round's dense upload: exact gradient when the oracle has
    one, else a fresh B' megabatch."""
    if hasattr(problem, "full_grad"):
        return problem.full_grad(x)
    return problem.stoch_grad(key, x, size)


def _problem_grad_minibatch(problem, key, x, size):
    """An honest size-B minibatch gradient on EITHER oracle (the Cor.
    6.8/6.10 B_init initialisation; never silently the exact gradient)."""
    if hasattr(problem, "stoch_grad"):
        return problem.stoch_grad(key, x, size)
    return problem.minibatch_grad(key, x, size)


# ---------------------------------------------------------------------------
# FlatSubstrate
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatSubstrate:
    """Stacked (n, d) per-node state on one host (vmap-ed oracles)."""

    problem: Any
    n: int
    d: int
    rc: Optional[RoundCompressor] = None

    def with_compressor(self, comp) -> "FlatSubstrate":
        rc = as_round_compressor(comp)
        return dataclasses.replace(self, rc=rc)

    # -- oracle ops --------------------------------------------------------
    def grad(self, key, x, data=None, size: int = 1):
        return _problem_grad(self.problem, key, x, size)

    def grad_pair(self, key, x_new, x_old, size: int, data=None):
        return _problem_grad_pair(self.problem, key, x_new, x_old, size)

    def grad_diff(self, key, x_new, x_old, size: int, data=None):
        return _problem_grad_diff(self.problem, key, x_new, x_old, size)

    def megabatch(self, key, x, size: int, data=None):
        return _problem_megabatch(self.problem, key, x, size)

    def grad_minibatch(self, key, x, size: int, data=None):
        return _problem_grad_minibatch(self.problem, key, x, size)

    # -- arithmetic --------------------------------------------------------
    def lin(self, fn: Callable, *arrays):
        return fn(*arrays)

    def where(self, coin, a, b):
        return jnp.where(coin, a, b)

    def mean_nodes(self, per_node):
        return jnp.mean(per_node, 0)

    def add_server(self, g, agg):
        return g + agg

    def sub_deficit(self, g, deficit):
        """g minus the in-flight message sum (async pipelining, DESIGN.md
        §14): what the server has actually RECEIVED.  Exact because g is a
        sum — subtracting the unlanded terms commutes with every landing."""
        return g - deficit

    def zeros_per_node(self, x0):
        return jnp.zeros((self.n, self.d), x0.dtype)

    def dense_coords(self, per_node_tree=None) -> float:
        return float(self.d)

    # -- server ------------------------------------------------------------
    def init_opt(self, x0):
        return ()

    def server_update(self, x, g, opt_state, hp):
        return x - hp.gamma * g, opt_state

    # -- compression (Alg. 1 lines 9-10) -----------------------------------
    def estimator_update(self, key, h_new, h, g_local, a: float, aux=None):
        return self.estimator_update_full(key, h_new, h, g_local, a,
                                          aux)[:4]

    def estimator_update_full(self, key, h_new, h, g_local, a: float,
                              aux=None):
        """``estimator_update`` plus the wire observables: the per-node
        message container and the Appendix-D participation coins (None at
        full participation).  Recomputing the plan from the same key is
        free under jit (pure + CSE) and keeps the two entry points
        bit-identical."""
        msgs, h_out, gl = self.rc.estimator_update(key, h_new, h, g_local, a)
        present = None
        if self.rc.spec.p_participate < 1.0:
            # the participation wrapper folds coin/p' into the plan's
            # per-node scale; a zero scale row IS an absent node
            scale = self.rc.plan(key).scale
            present = jnp.ravel(scale) != 0
        return (msgs.mean(), h_out, gl, self.rc.payload_per_node, msgs,
                present)

    def round_present(self, state_key):
        """(n,) Appendix-D participation for the round whose pre-step
        MethodState key is ``state_key`` — the same plan derivation
        ``estimator_update_full`` performs (``k_c = split(key, 4)[2]``),
        recomputable by observers without running the step.  All-ones at
        full participation.  The fault layer needs it to distinguish a
        crashed-but-absent client (nothing expected, nothing lost) from a
        crashed participant (the server waits, then degrades)."""
        if self.rc.spec.p_participate >= 1.0:
            return jnp.ones((self.n,), bool)
        k_c = jax.random.split(state_key, 4)[2]
        return jnp.ravel(self.rc.plan(k_c).scale) != 0

    def round_wire_counts(self, state_key):
        """Per-node shipped value-scalar counts for the round whose
        MethodState key is ``state_key`` (the engine derives
        ``k_c = split(key, 4)[2]``).  Only mask (Bernoulli) plans have
        data-dependent counts — every other format's count is static and
        classified by :func:`repro.fed.wire.wire_schema`."""
        k_c = jax.random.split(state_key, 4)[2]
        plan = self.rc.plan(k_c)
        if plan.mask is None:
            raise ValueError("round_wire_counts is only defined for mask "
                             "(Bernoulli) plans; static-count formats come "
                             "from repro.fed.wire.wire_schema")
        return jnp.sum(plan.mask != 0, axis=1).astype(jnp.int32)

    # -- metrics -----------------------------------------------------------
    def default_metric(self):
        # memoized: callers key compile caches on the metric's identity
        # (driver/sim `(length, metric_fn)` dicts), so returning a fresh
        # closure per call would force a retrace per run (the PR 5 bug).
        cached = self.__dict__.get("_default_metric")
        if cached is not None:
            return cached
        p = self.problem
        if hasattr(p, "grad_f"):
            def metric(s):
                return jnp.sum(p.grad_f(s.x) ** 2)
        elif getattr(p, "true_grad", None) is not None:
            def metric(s):
                return jnp.sum(p.true_grad(s.x) ** 2)
        else:
            def metric(s):
                return jnp.float32(0)
        object.__setattr__(self, "_default_metric", metric)
        return metric


# ---------------------------------------------------------------------------
# SampledFlatSubstrate — the cross-device O(C*d) round (DESIGN.md §13)
# ---------------------------------------------------------------------------

# COHORT_TAG (the fold_in tag deriving the cohort-draw key from the
# round's k_c without consuming from the engine's key stream) lives in
# repro.analysis.tags — the registry is the single source of truth for
# fold_in namespaces, and is imported above so existing consumers keep
# reading substrates.COHORT_TAG.


def cohort_indices(k_round: jax.Array, n: int, c: int) -> jax.Array:
    """The round's uniform C-of-n cohort (without replacement), derived from
    the engine round key ``k_c`` via :data:`COHORT_TAG` — recomputable by
    observers (the federated simulator) from ``state.key`` alone."""
    k_sel = jax.random.fold_in(k_round, COHORT_TAG)
    return jax.random.permutation(k_sel, n)[:c]


# ---------------------------------------------------------------------------
# host-side schedule precompute: the bit-exact permutation head
# ---------------------------------------------------------------------------
#
# jax.random.permutation is a multi-round sort-by-random-u32-keys shuffle
# (jax._src.random._shuffle: ``num_rounds = ceil(3 ln n / ln(2^32-1))``
# rounds of ``key, sub = split(key); bits = random_bits(sub, 32, (n,));
# _, x = lax.sort_key_val(bits, x)`` with is_stable=True).  A full sort is
# O(n log n) and, at n = 10^5, dominates the sampled round (~67 ms/round on
# one CPU core) — yet the campaign driver only ever needs the FIRST c
# entries.  Because the per-round sort is STABLE, sorting by u32 bits is
# exactly ascending order of the composite u64 key ``(bits << 32) | pos``
# (position breaks ties), which is collision-free — so the head of the
# permutation is recoverable by ORDER-STATISTIC SELECTION: the c smallest
# composite keys of the last round give the output positions, and each
# earlier round only needs the identity of its k-th smallest key at c given
# ranks (``np.argpartition`` with a kth vector), O(n) per round instead of
# a sort.  The threefry bit streams themselves stay in jax (exact), so the
# result is BIT-IDENTICAL to ``jax.random.permutation(key, n)[:c]`` —
# asserted once per process per n against the reference (guarding against
# upstream algorithm drift) and exhaustively in tests/test_slab_store.py.

def _shuffle_num_rounds(n: int) -> int:
    """Round count of jax's sort-based shuffle for a size-``n`` range."""
    if n <= 1:
        return 0
    u32max = float(np.iinfo(np.uint32).max)
    return int(np.ceil(3 * np.log(n) / np.log(u32max)))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _shuffle_bits(key: jax.Array, n: int, num_rounds: int) -> jax.Array:
    """The (num_rounds, n) u32 sort-key streams _shuffle would draw."""
    outs = []
    for _ in range(num_rounds):
        key, sub = jax.random.split(key)
        outs.append(jax.random.bits(sub, (n,), jnp.uint32))
    return jnp.stack(outs)


def _perm_head_from_bits(bits: np.ndarray, c: int) -> np.ndarray:
    """First ``c`` entries of the stable sort-by-bits shuffle of arange(n).

    Pure numpy selection over the composite keys ``(bits[r] << 32) | pos``;
    unit-tested against a stable-argsort reference on crafted collision
    inputs (the composite key makes ties positional, matching
    ``lax.sort_key_val(..., is_stable=True)``)."""
    num_rounds, n = bits.shape
    pos = np.arange(n, dtype=np.uint64)
    b = bits.astype(np.uint64)
    # last round: positions of the c smallest composite keys, in key order
    ck = (b[-1] << np.uint64(32)) | pos
    idx = np.argpartition(ck, c - 1)[:c] if c < n else np.arange(n)
    sel = idx[np.argsort(ck[idx], kind="stable")]
    # walk earlier rounds backwards: the value at rank j of round r is the
    # index of round r's j-th smallest composite key
    for r in range(num_rounds - 2, -1, -1):
        ck = (b[r] << np.uint64(32)) | pos
        kth = np.unique(sel)
        part = np.argpartition(ck, kth)
        sel = part[sel]
    return sel.astype(np.int32)


_PERM_HEAD_VERIFIED: set = set()


def permutation_head(key: jax.Array, n: int, c: int) -> np.ndarray:
    """Host-side ``np.asarray(jax.random.permutation(key, n)[:c])``,
    bit-identical, via threefry bit replay + O(n) selection (no sort).

    The first call per (process, n) cross-checks a reference permutation
    so any upstream change to jax's shuffle algorithm fails loudly instead
    of silently desynchronizing the cohort schedule."""
    if not 0 < c <= n:
        raise ValueError(f"need 0 < c <= n, got c={c} n={n}")
    num_rounds = _shuffle_num_rounds(n)
    if num_rounds == 0:
        return np.arange(c, dtype=np.int32)
    if n not in _PERM_HEAD_VERIFIED:
        _PERM_HEAD_VERIFIED.add(n)
        probe = jax.random.PRNGKey(0x5e1ec7)
        ref = np.asarray(jax.random.permutation(probe, n)[:min(c, n)])
        got = _perm_head_from_bits(
            np.asarray(_shuffle_bits(probe, n, num_rounds)), min(c, n))
        if not np.array_equal(ref, got):
            raise RuntimeError(
                "permutation_head disagrees with jax.random.permutation "
                f"at n={n} — jax's shuffle algorithm changed; fall back to "
                "the in-jit scatter store")
    bits = np.asarray(_shuffle_bits(key, n, num_rounds))
    return _perm_head_from_bits(bits, c)


@jax.jit
def gather_slab_rows(full: jax.Array, idx: jax.Array) -> jax.Array:
    """Slab gather: rows of ``full`` at ``idx``; the pad sentinel (== n,
    one past the end) reads as zeros and is never addressed by a loc."""
    return jnp.take(full, idx, axis=0, mode="fill", fill_value=0)


def slab_layout(sels: np.ndarray, n: int):
    """The chunk's slab layout from its (length, C) cohort schedule.

    Returns ``(uniq_pad, loc)``: ``uniq_pad`` (U_pad,) int32 — the sorted
    union of touched global rows, padded to the STATIC length
    ``U_pad = min(length*C, n)`` with the sentinel ``n`` so every chunk of
    the same length compiles once; ``loc`` (length, C) int32 — each
    round's cohort as slab-row indices (``uniq_pad[loc[t]] == sels[t]``).
    """
    length, c = sels.shape
    u_pad = min(length * c, n)
    uniq = np.unique(sels)
    loc = np.searchsorted(uniq, sels).astype(np.int32)
    uniq_pad = np.full((u_pad,), n, np.int32)
    uniq_pad[:uniq.size] = uniq
    return uniq_pad, loc


@functools.partial(jax.jit, static_argnums=(1,))
def _cohort_key_chain(state_key: jax.Array, length: int) -> jax.Array:
    """Replay the engine's per-round ``split(key, 4)`` chain for ``length``
    rounds, returning the COHORT_TAG-folded cohort-draw keys (length, ...)
    — the observer-side contract of :meth:`SampledFlatSubstrate.
    round_cohort`, batched."""
    def step(k, _):
        ks = jax.random.split(k, 4)
        return ks[0], jax.random.fold_in(ks[2], COHORT_TAG)
    return jax.lax.scan(step, state_key, None, length=length)[1]


def _rows_stoch_grad(problem, key, x, batch, rows):
    """Row-restricted ``StochasticProblem.stoch_grad``: per-client keys stay
    CLIENT-ID keyed (``split(key, n)[rows]``), so the cohort draws the same
    noise its clients would draw under full participation."""
    gfun = jax.grad(problem.loss)
    keys = jax.random.split(key, problem.n)[rows]

    def node(i, k):
        xi = problem.sample(k, i, batch)
        return jnp.mean(jax.vmap(lambda s: gfun(x, s, i))(xi), 0)

    return jax.vmap(node)(rows, keys)


def _rows_stoch_grad_pair(problem, key, x_new, x_old, batch, rows):
    gfun = jax.grad(problem.loss)
    keys = jax.random.split(key, problem.n)[rows]

    def node(i, k):
        xi = problem.sample(k, i, batch)
        gn = jnp.mean(jax.vmap(lambda s: gfun(x_new, s, i))(xi), 0)
        go = jnp.mean(jax.vmap(lambda s: gfun(x_old, s, i))(xi), 0)
        return gn, go

    return jax.vmap(node)(rows, keys)


class _CohortView:
    """One round's (C, d) window onto a :class:`SampledFlatSubstrate`.

    Built inside the traced step (``sel`` is a traced (C,) index vector), it
    exposes the same ops the variant rules consume — but every oracle call
    and the estimator update run on the gathered cohort slice only, so the
    round costs O(C*d) FLOPs/activations while the (n, d) client state stays
    persistent.  ``scatter_nodes`` writes the cohort rows back; unsampled
    rows FREEZE (an offline cross-device client computes nothing — unlike
    the Appendix-D wrapper, where every client refreshes h locally and only
    the transmission is coin-gated).

    Under the chunk-resident slab store (DESIGN.md §16) the view carries a
    second index vector ``loc``: ``sel`` stays the GLOBAL client ids (every
    oracle draw, data gather and participation mask is client-id keyed so
    the cohort computes exactly what it would under the scatter store),
    while ``gather_nodes`` / ``scatter_nodes`` address ``loc`` — the
    cohort's rows inside the compact (U, d) slab that replaces the (n, d)
    arrays in the scan carry."""

    def __init__(self, base: "SampledFlatSubstrate", sel: jax.Array,
                 loc: Optional[jax.Array] = None):
        self.base = base
        self.sel = sel
        self.loc = loc

    # -- node-axis windowing ----------------------------------------------
    def gather_nodes(self, per_node):
        idx = self.sel if self.loc is None else self.loc
        return per_node[idx]

    def scatter_nodes(self, full, rows):
        idx = self.sel if self.loc is None else self.loc
        return full.at[idx].set(rows)

    def _rows_problem(self):
        """The finite-sum problem restricted to the cohort's data rows."""
        p = self.base.problem
        return dataclasses.replace(p, features=p.features[self.sel],
                                   labels=p.labels[self.sel])

    # -- oracle ops (cohort rows only) ------------------------------------
    def grad(self, key, x, data=None, size: int = 1):
        p = self.base.problem
        if hasattr(p, "full_grad"):
            return self._rows_problem().full_grad(x)
        return _rows_stoch_grad(p, key, x, size, self.sel)

    def grad_pair(self, key, x_new, x_old, size: int, data=None):
        p = self.base.problem
        if hasattr(p, "stoch_grad_pair"):
            return _rows_stoch_grad_pair(p, key, x_new, x_old, size,
                                         self.sel)
        rp = self._rows_problem()
        return (rp.minibatch_grad(key, x_new, size),
                rp.minibatch_grad(key, x_old, size))

    def grad_diff(self, key, x_new, x_old, size: int, data=None):
        p = self.base.problem
        if hasattr(p, "minibatch_diff"):
            rp = self._rows_problem()
            if size == 0:
                return rp.full_grad(x_new) - rp.full_grad(x_old)
            return rp.minibatch_diff(key, x_new, x_old, size)
        gn, go = self.grad_pair(key, x_new, x_old, size, data)
        return gn - go

    def megabatch(self, key, x, size: int, data=None):
        p = self.base.problem
        if hasattr(p, "full_grad"):
            return self._rows_problem().full_grad(x)
        return _rows_stoch_grad(p, key, x, size, self.sel)

    def grad_minibatch(self, key, x, size: int, data=None):
        p = self.base.problem
        if hasattr(p, "stoch_grad"):
            return _rows_stoch_grad(p, key, x, size, self.sel)
        return self._rows_problem().minibatch_grad(key, x, size)

    # -- arithmetic (shape-agnostic, same as FlatSubstrate) ----------------
    def lin(self, fn: Callable, *arrays):
        return fn(*arrays)

    def where(self, coin, a, b):
        return jnp.where(coin, a, b)

    # -- compression (cohort slice; inflation folded into the plan) --------
    def estimator_update_full(self, key, h_new, h, g_local, a: float,
                              aux=None):
        from repro.compress.backends import estimator_update_with_plan
        base = self.base
        rc = base.cohort_rc
        plan = rc.plan(key)
        # the unbiasedness inflation n/C (Theorem D.1 with p' = C/n) folds
        # into the plan scale, exactly like Appendix-D coins do — messages
        # carry it, so g_i += m_i keeps g = mean_i(g_i) invariant
        plan = plan._replace(scale=plan.scale * (base.n / float(base.c)))
        msgs, h_out, gl = estimator_update_with_plan(
            rc.backend, plan, h_new, h, g_local, a)
        # server aggregate (1/n) sum_{i in S} m_i = (C/n) * mean_S(m_i)
        agg = msgs.mean() * (float(base.c) / base.n)
        present = jnp.zeros((base.n,), bool).at[self.sel].set(True)
        payload = rc.payload_per_node * (float(base.c) / base.n)
        return agg, h_out, gl, payload, msgs, present


@dataclasses.dataclass(frozen=True)
class SampledFlatSubstrate(FlatSubstrate):
    """Cross-device FlatSubstrate: each round a uniform cohort of ``c`` of
    the ``n`` clients is gathered, stepped, and scattered back.

    Per-round gradient compute, compression and estimator updates touch only
    the (c, d) cohort slice — O(c*d) FLOPs and activations against the
    persistent (n, d) state — while unsampled clients freeze (they compute
    and send NOTHING; zero bytes on the simulated wire, and the variance
    cost is the Theorem-D.1 omega inflation with p' = c/n, see
    :func:`repro.compress.spec.omega_participation`).  With ``c == n`` the
    substrate IS FlatSubstrate (``round_view`` returns ``self`` and the
    engine takes the unsliced path), which is the bit-identical parity
    anchor tested in tests/test_fed_scale.py.  Rules with a client
    synchronization barrier (``sync_requires_all``: MARINA, SYNC-MVR) are
    rejected at ``Method.build`` time — a sampled cohort can never answer
    an all-client dense round."""

    c: int = 0

    def __post_init__(self):
        if not 0 < self.c <= self.n:
            raise ValueError(f"cohort size c={self.c} must be in [1, "
                             f"n={self.n}]")
        if self.rc is not None and self.rc.spec.p_participate < 1.0:
            raise ValueError(
                "SampledFlatSubstrate IS the participation model — combine "
                "it with a p_participate < 1 compressor and clients would "
                "be sampled twice; use one or the other")

    @property
    def samples_clients(self) -> bool:
        return self.c < self.n

    @property
    def participation_frac(self) -> float:
        return self.c / float(self.n)

    @property
    def cohort_rc(self) -> RoundCompressor:
        """The round's compressor over the cohort: same spec/mode/backend,
        re-dimensioned to c nodes (PermK partitions [d] over the ACTIVE
        cohort, so its collection omega becomes c - 1)."""
        rc = self.rc
        spec = rc.spec
        if spec.name == "permk":
            spec = dataclasses.replace(spec, n=self.c)
        return RoundCompressor(spec, self.c, rc.mode, rc.backend)

    def effective_omega(self) -> float:
        """Theorem-D.1 inflated omega for ``Hyper.from_theory``:
        (omega_cohort + 1) / (c/n) - 1."""
        from repro.compress.spec import omega_participation
        return omega_participation(self.cohort_rc.omega,
                                   self.participation_frac)

    def round_view(self, k_round: jax.Array):
        """The engine's per-round window: identity (self) at c == n — the
        bit-identical full path — else a :class:`_CohortView` over the
        cohort drawn from ``fold_in(k_round, COHORT_TAG)``."""
        if self.c >= self.n:
            return self
        return _CohortView(self, cohort_indices(k_round, self.n, self.c))

    def window_view(self, sel: jax.Array, loc: jax.Array) -> _CohortView:
        """The slab-store round window (DESIGN.md §16): ``sel`` is the
        round's global cohort — the SAME values :meth:`round_view` would
        draw, precomputed outside the jit by :meth:`cohort_schedule` —
        and ``loc`` its rows inside the chunk slab, which gather/scatter
        address instead of the (n, d) store."""
        return _CohortView(self, sel, loc)

    def round_cohort(self, state_key: jax.Array) -> jax.Array:
        """Recover the round's cohort from a MethodState key (the engine
        derives k_c = split(key, 4)[2]) — observer-side, for the federated
        simulators."""
        k_c = jax.random.split(state_key, 4)[2]
        return cohort_indices(k_c, self.n, self.c)

    def cohort_schedule(self, state_key: jax.Array,
                        length: int) -> np.ndarray:
        """The next ``length`` rounds' cohorts, (length, c) int32 on host.

        Replays the engine's stateless ``split(key, 4)`` chain from
        ``state_key`` (one jitted scan), then recovers each round's
        ``permutation(fold_in(k_c, COHORT_TAG), n)[:c]`` through the
        selection-based :func:`permutation_head` — bit-identical to what
        :meth:`round_view` draws in-jit, at O(n) instead of O(n log n)
        per round.  This is what lets the slab store gather each chunk's
        touched rows BEFORE the scan (DESIGN.md §16)."""
        keys = jax.device_get(_cohort_key_chain(state_key, int(length)))
        sels = np.empty((int(length), self.c), np.int32)
        for j in range(int(length)):
            sels[j] = permutation_head(keys[j], self.n, self.c)
        return sels

    def cohort_counts(self, state_key):
        """(c,) per-cohort Bernoulli wire counts — the slab-body form of
        :meth:`round_wire_counts` (same plan draw, no (n,) scatter)."""
        k_c = jax.random.split(state_key, 4)[2]
        plan = self.cohort_rc.plan(k_c)
        if plan.mask is None:
            raise ValueError("cohort_counts is only defined for mask "
                             "(Bernoulli) plans")
        return jnp.sum(plan.mask != 0, axis=1).astype(jnp.int32)

    def round_wire_counts(self, state_key):
        if not self.samples_clients:
            return FlatSubstrate.round_wire_counts(self, state_key)
        k_c = jax.random.split(state_key, 4)[2]
        sel = cohort_indices(k_c, self.n, self.c)
        cnt = self.cohort_counts(state_key)
        return jnp.zeros((self.n,), jnp.int32).at[sel].set(cnt)


# ---------------------------------------------------------------------------
# tree oracles
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchLossOracle:
    """Per-node gradients from ``loss_fn(params, node_batch)`` (training).

    ``data`` is a batch pytree with a leading node axis (n, ...); the vmap
    lifts the node axis with ``spmd_axis_name`` so GSPMD keeps the scan
    accumulators sharded, and ``grad_specs`` pins per-param shardings.
    The same data batch evaluates both points of a pair — the "same
    samples" requirement of MVR/PAGE — and the megabatch sync round reuses
    the round's batch (B' = B at this layer).  Each gradient comes back in
    the dtype the backward pass made it in (the parameters'): the fused
    node update reads it so and casts it in VMEM, and every other path
    casts it where it becomes state (``TreeSubstrate.lin`` / ``where``,
    the compressors' non-kernel branches, the engine's init).
    ``state_dtype`` is what :meth:`grad_diff` returns, a difference taken
    in f32.
    """

    loss_fn: Callable[[PyTree, Any], jax.Array]
    spmd_axes: Optional[Tuple[str, ...]] = None
    grad_specs: Optional[PyTree] = None
    state_dtype: Any = jnp.float32

    def per_node_grads(self, params, data):
        def gfun(p, b):
            g_ = jax.grad(lambda pp, bb: self.loss_fn(pp, bb))(p, b)
            if self.grad_specs is not None:
                g_ = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, g_, self.grad_specs)
            return g_
        vkw = {}
        if self.spmd_axes:
            vkw["spmd_axis_name"] = self.spmd_axes
        return jax.vmap(gfun, in_axes=(None, 0), **vkw)(params, data)

    def grad(self, key, x, data, size: int = 1):
        return self.per_node_grads(x, data)

    def grad_pair(self, key, x_new, x_old, size: int, data):
        return (self.per_node_grads(x_new, data),
                self.per_node_grads(x_old, data))

    def grad_diff(self, key, x_new, x_old, size: int, data):
        gn, go = self.grad_pair(key, x_new, x_old, size, data)
        return jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32)
                          - b.astype(jnp.float32)).astype(self.state_dtype),
            gn, go)

    def megabatch(self, key, x, size: int, data):
        return self.per_node_grads(x, data)

    def grad_minibatch(self, key, x, size: int, data):
        return self.per_node_grads(x, data)


@dataclasses.dataclass(frozen=True)
class LeafProblemOracle:
    """Adapt a flat Section-1.2 problem to a single-leaf tree substrate.

    The parity bridge: per-node quantities are the problem's (n, d) arrays
    wrapped back into the x-tree's (single-leaf) structure, so a
    TreeSubstrate over it reproduces FlatSubstrate bit for bit.
    """

    problem: Any
    treedef: Any

    @classmethod
    def wrapping(cls, problem, x0_tree) -> "LeafProblemOracle":
        leaves, treedef = jax.tree_util.tree_flatten(x0_tree)
        assert len(leaves) == 1, "LeafProblemOracle is single-leaf only"
        return cls(problem=problem, treedef=treedef)

    def _leaf(self, tree):
        return jax.tree_util.tree_leaves(tree)[0]

    def _wrap(self, arr):
        return jax.tree_util.tree_unflatten(self.treedef, [arr])

    def grad(self, key, x, data=None, size: int = 1):
        return self._wrap(_problem_grad(self.problem, key, self._leaf(x),
                                        size))

    def grad_pair(self, key, x_new, x_old, size: int, data=None):
        gn, go = _problem_grad_pair(self.problem, key, self._leaf(x_new),
                                    self._leaf(x_old), size)
        return self._wrap(gn), self._wrap(go)

    def grad_diff(self, key, x_new, x_old, size: int, data=None):
        return self._wrap(_problem_grad_diff(
            self.problem, key, self._leaf(x_new), self._leaf(x_old), size))

    def megabatch(self, key, x, size: int, data=None):
        return self._wrap(_problem_megabatch(self.problem, key,
                                             self._leaf(x), size))

    def grad_minibatch(self, key, x, size: int, data=None):
        return self._wrap(_problem_grad_minibatch(self.problem, key,
                                                  self._leaf(x), size))


# ---------------------------------------------------------------------------
# tree compression strategies
# ---------------------------------------------------------------------------

def _leaf_fanout(key, leaves):
    """split(key, n_leaves); a single leaf uses the round key directly so
    the single-leaf tree substrate matches the flat substrate bit for bit."""
    if len(leaves) == 1:
        return [key]
    return list(jax.random.split(key, len(leaves)))


def _leaf_size(leaf) -> float:
    sz = 1.0
    for s in leaf.shape[1:]:
        sz *= s
    return sz


def _mean_nodes(per_node):
    """The f32 mean over the leading node axis of every leaf (line 14's
    aggregate), as the ``dasha.aggregate`` scope."""
    with jax.named_scope("dasha.aggregate"):
        return jax.tree_util.tree_map(
            lambda h: jnp.mean(h.astype(jnp.float32), 0), per_node)


@dataclasses.dataclass(frozen=True)
class TreeCompression:
    """Tree-native compression: the trainer's mode knob over
    :mod:`repro.compress.treelevel` (sharding-spec aware, fused-capable)."""

    mode: str = "independent"     # independent | shared_coords | permk
    p: float = 1.0                # Bernoulli-RandP keep probability
    n: int = 1
    use_kernel: bool = False
    specs: Optional[PyTree] = None

    @property
    def static_frac(self) -> float:
        """Payload / dense, per node (the trainer's payload_frac metric)."""
        return 1.0 / self.n if self.mode == "permk" else self.p

    def payload_per_node(self, per_node_tree) -> float:
        return sum(self.static_frac * _leaf_size(l)
                   for l in jax.tree_util.tree_leaves(per_node_tree))

    def estimator_update(self, key, h_new, h, g_local, a: float, aux=None):
        if self.use_kernel:
            if isinstance(aux, MvrFusion):
                # recompute the momentum h-update INSIDE the kernel pass
                m, h_out, gl = fused_tree_update(
                    key, aux.grads_new, h, g_local, mode=self.mode, a=a,
                    p=self.p, n=self.n, variant="mvr", b=aux.b,
                    grads_old=aux.grads_old, specs=self.specs)
            else:
                m, h_out, gl = fused_tree_update(
                    key, h_new, h, g_local, mode=self.mode, a=a, p=self.p,
                    n=self.n, variant="dasha", specs=self.specs)
            return _mean_nodes(m), h_out, gl, self.payload_per_node(h_new)

        # the oracle's gradient becomes state here, in h's dtype
        h_new = jax.tree_util.tree_map(lambda hn, hh: hn.astype(hh.dtype),
                                       h_new, h)
        delta = jax.tree_util.tree_map(
            lambda hn, hh, gl_: hn - hh - a * (gl_ - hh),
            h_new, h, g_local)
        if self.mode == "permk":
            m, agg = permk_compress(key, delta, self.n, specs=self.specs)
        else:
            m = bernoulli_compress(key, delta, self.p, specs=self.specs,
                                   shared=self.mode == "shared_coords")
            agg = _mean_nodes(m)
        gl_new = jax.tree_util.tree_map(jnp.add, g_local, m)
        return agg, h_new, gl_new, self.payload_per_node(h_new)


@dataclasses.dataclass(frozen=True)
class LeafSpecCompressor:
    """Per-leaf RoundCompressor execution: the flat subsystem's spec/plan/
    backend stack applied leaf-by-leaf (each leaf reshaped to (n, d_leaf),
    the spec re-dimensioned).  This is how registry compressors — RandK,
    PermK, QDither, partial participation — run on a tree substrate."""

    rc: RoundCompressor

    @property
    def static_frac(self) -> float:
        return self.rc.payload_per_node / float(self.rc.spec.d)

    def _leaf_rc(self, d_leaf: int) -> RoundCompressor:
        spec = dataclasses.replace(self.rc.spec, d=d_leaf)
        return RoundCompressor(spec, self.rc.n, self.rc.mode,
                               self.rc.backend)

    def payload_per_node(self, per_node_tree) -> float:
        return sum(self._leaf_rc(int(_leaf_size(l))).payload_per_node
                   for l in jax.tree_util.tree_leaves(per_node_tree))

    def estimator_update(self, key, h_new, h, g_local, a: float, aux=None):
        hn_leaves, treedef = jax.tree_util.tree_flatten(h_new)
        h_leaves = jax.tree_util.tree_leaves(h)
        gl_leaves = jax.tree_util.tree_leaves(g_local)
        keys = _leaf_fanout(key, hn_leaves)
        aggs, h_outs, gls, payload = [], [], [], 0.0
        for k, hn, hh, gl in zip(keys, hn_leaves, h_leaves, gl_leaves):
            hn = hn.astype(hh.dtype)
            n = hn.shape[0]
            shape = hn.shape[1:]
            d_leaf = int(_leaf_size(hn))
            rc = self._leaf_rc(d_leaf)

            def flat(t, n=n, d_leaf=d_leaf):
                return t.reshape(n, d_leaf)

            msgs, h_out, gl_new = rc.estimator_update(
                k, flat(hn), flat(hh), flat(gl), a)
            aggs.append(msgs.mean().reshape(shape))
            h_outs.append(h_out.reshape(hn.shape))
            gls.append(gl_new.reshape(hn.shape))
            payload += rc.payload_per_node

        def unflat(ls):
            return jax.tree_util.tree_unflatten(treedef, ls)

        return unflat(aggs), unflat(h_outs), unflat(gls), payload


# ---------------------------------------------------------------------------
# TreeSubstrate
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TreeSubstrate:
    """Params-shaped pytrees with a leading node axis (sharded trainer)."""

    oracle: Any
    n: int
    server_opt: Any                     # repro.optim.base SGD / Adam
    state_dtype: Any = jnp.float32
    comp: Any = None                    # TreeCompression | LeafSpecCompressor

    def with_compressor(self, comp) -> "TreeSubstrate":
        if isinstance(comp, (TreeCompression, LeafSpecCompressor)):
            bound = comp
        else:                           # RoundCompressor / legacy view
            bound = LeafSpecCompressor(as_round_compressor(comp))
        return dataclasses.replace(self, comp=bound)

    # -- oracle ops (delegated) --------------------------------------------
    def grad(self, key, x, data=None, size: int = 1):
        return self.oracle.grad(key, x, data, size)

    def grad_pair(self, key, x_new, x_old, size: int, data=None):
        return self.oracle.grad_pair(key, x_new, x_old, size, data)

    def grad_diff(self, key, x_new, x_old, size: int, data=None):
        return self.oracle.grad_diff(key, x_new, x_old, size, data)

    def megabatch(self, key, x, size: int, data=None):
        return self.oracle.megabatch(key, x, size, data)

    def grad_minibatch(self, key, x, size: int, data=None):
        return self.oracle.grad_minibatch(key, x, size, data)

    # -- arithmetic --------------------------------------------------------
    def lin(self, fn: Callable, *trees):
        sdt = self.state_dtype
        return jax.tree_util.tree_map(
            lambda *ls: fn(*[l.astype(jnp.float32) for l in ls]).astype(sdt),
            *trees)

    def where(self, coin, a, b):
        """Leaf-wise ``a`` where ``coin`` else ``b``, in ``b``'s dtype (the
        state's, where ``a`` is an oracle gradient)."""
        return jax.tree_util.tree_map(
            lambda a_, b_: jnp.where(coin, a_.astype(b_.dtype), b_), a, b)

    def mean_nodes(self, per_node):
        return _mean_nodes(per_node)

    def add_server(self, g, agg):
        return jax.tree_util.tree_map(jnp.add, g, agg)

    def sub_deficit(self, g, deficit):
        """Leaf-wise g - deficit (async in-flight correction, DESIGN.md
        §14)."""
        return jax.tree_util.tree_map(jnp.subtract, g, deficit)

    def zeros_per_node(self, x0):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((self.n,) + p.shape, self.state_dtype), x0)

    def dense_coords(self, per_node_tree) -> float:
        return sum(_leaf_size(l)
                   for l in jax.tree_util.tree_leaves(per_node_tree))

    # -- server ------------------------------------------------------------
    def init_opt(self, x0):
        return self.server_opt.init(x0)

    def server_update(self, x, g, opt_state, hp):
        from repro.optim.base import apply_updates
        updates, opt_state = self.server_opt.update(g, opt_state, x)
        return apply_updates(x, updates), opt_state

    # -- compression -------------------------------------------------------
    def estimator_update(self, key, h_new, h, g_local, a: float, aux=None):
        return self.comp.estimator_update(key, h_new, h, g_local, a, aux)

    # -- metrics -----------------------------------------------------------
    def default_metric(self):
        # memoized for identity-keyed compile caches (see FlatSubstrate)
        cached = self.__dict__.get("_default_metric")
        if cached is not None:
            return cached

        def metric(s):
            return sum(jnp.sum(jnp.square(x))
                       for x in jax.tree_util.tree_leaves(s.g))

        object.__setattr__(self, "_default_metric", metric)
        return metric
