"""The one method skeleton: ``Method.build(variant, compressor, substrate,
hyper) -> (init, step, run)``.

Algorithm 1 (and Algorithm 2's sync round, and MARINA's) written ONCE:

    x^{t+1}  = server_update(x^t, g^t)                      # line 4
    h^{t+1}  = rule.h_update(...)                           # line 8  (varies)
    m, g_i   = substrate.estimator_update(...)              # lines 9-10
    g^{t+1}  = g^t + (1/n) sum_i m_i                        # line 14
    [coin]   with prob p: dense sync round (where-selected) # Alg. 2 / MARINA

Everything variant-specific lives in :mod:`repro.methods.rules`; everything
representation-specific lives in :mod:`repro.methods.substrates`.  Each line
runs under a ``jax.named_scope`` (``dasha.server`` / ``dasha.oracle`` /
``dasha.node_update`` / ``dasha.aggregate``; the substrates add
``dasha.compress`` and ``dasha.aggregate`` inside the node update), which
names the compiled ops in a device trace and adds no op.  The RNG
contract reproduces the seed's flat loop exactly
(``key, k_h, k_c, k_coin = split(key, 4)``), so the legacy
:mod:`repro.core.dasha` entry points are bit-identical shims over this
engine.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.methods import accounting
from repro.methods.rules import VariantRule, get_rule


class StepInfo(NamedTuple):
    """Per-round internals exposed by ``Method.step_full`` for observers
    that need more than the new state — the federated transport simulator
    (:mod:`repro.fed.sim`) encodes ``messages`` (or ``sync_dense`` on a
    coin round) onto a byte-exact wire and bills real network time.

    * ``messages``  — the per-node compressed messages m_i in the
      substrate's backend format (``DenseMessages`` / ``SparseMessages``),
      or None when the substrate does not expose them;
    * ``coin``      — the sync-round coin (None for no-sync variants);
    * ``sync_dense``— the dense per-node sync upload h_sync (None unless
      the rule has a sync round; on a coin round THIS is what ships);
    * ``present``   — (n,) participation coins of the Appendix-D wrapper
      (None when p_participate == 1): absent nodes sent nothing;
    * ``payload``   — the compressed branch's payload coords per node.
    """

    messages: Any = None
    coin: Optional[jax.Array] = None
    sync_dense: Any = None
    present: Optional[jax.Array] = None
    payload: Any = 0.0


class FaultStep(NamedTuple):
    """Per-round fault gating for ``Method.step_full`` (DESIGN.md §18),
    realized host-side by :mod:`repro.fed.faults` and threaded through the
    simulators' scans as (n,) boolean masks.

    * ``drop``  — client i's round is DISCARDED end to end: its message
      never reaches the server (``g`` loses the ``m_i / n`` term) and the
      client keeps its pre-round ``(h_i, g_i)`` — crashes, lost/corrupted
      uploads, missed broadcasts, and deadline cuts all land here.  The
      gating runs AFTER the estimator, so the round's traced math — and
      its RNG stream — is identical to the fault-free engine; only the
      commit is masked.
    * ``reset`` — client i rebooted with blank state THIS round
      (rejoin="reset"): its ``(h_i, g_i)`` are zeroed BEFORE the
      h-update, and the server subtracts the forgotten ``g_i / n``
      (modeled as a reliable out-of-band reset notice) so the invariant
      ``g = mean_i(g_local_i)`` survives.  None means rejoin="stale" —
      the outage freezes state, nothing else.

    ``faults=None`` (the default) keeps the traced body byte-identical to
    the fault-free engine — the zero-fault bit-identity anchor the
    simulators' parity tests rely on.  ``bits_sent`` intentionally still
    counts dropped uploads: the client DID transmit; the wire lost it.
    Only gracefully-degrading rules accept faults — ``sync_requires_all``
    rules recover every message via simulator-billed retries, so their
    math never sees a fault.
    """

    drop: jax.Array
    reset: Optional[jax.Array] = None


class MethodState(NamedTuple):
    """Unified method state; the substrate decides what each field holds
    ((n, d) arrays + a (d,) iterate, or node-axis pytrees + a params tree).
    """

    x: Any                # server iterate
    g: Any                # server gradient estimator
    g_local: Any          # per-node g_i
    h_local: Any          # per-node h_i
    opt_state: Any        # server optimizer state (() for plain SGD-flat)
    key: jax.Array
    t: jax.Array
    bits_sent: jax.Array  # cumulative coords sent per node (accounting)


@dataclasses.dataclass(frozen=True)
class Hyper:
    """Method hyperparameters, shared by every variant (unused fields keep
    their neutral defaults)."""

    gamma: float                    # stepsize
    a: float                        # compressor momentum, 1/(2 omega + 1)
    variant: str = "dasha"          # dasha | page | mvr | sync_mvr | marina
    b: float = 1.0                  # MVR momentum
    p: float = 1.0                  # PAGE / SYNC-MVR / MARINA coin prob
    batch: int = 1                  # B   (0 = exact full-gradient oracle)
    batch_sync: int = 1             # B'  (sync-round megabatch)

    @classmethod
    def from_theory(cls, variant: str, omega: float, n: int, *, L: float,
                    L_hat: Optional[float] = None,
                    L_max: Optional[float] = None,
                    L_sigma: Optional[float] = None,
                    B: int = 1, m: int = 1, eps: float = 0.01,
                    sigma2: float = 0.0, zeta: float = 1.0, d: int = 1,
                    batch_sync: int = 1, gamma_mult: float = 1.0) -> "Hyper":
        """Assemble the Section-6 constants for ``variant``: gamma from the
        matching theorem, a = 1/(2 omega + 1), and the derived p / b / B —
        so callers stop hand-assembling them.  ``gamma_mult`` is the paper's
        powers-of-two stepsize fine-tune (Appendix A)."""
        from repro.compress.spec import momentum_a
        from repro.core.theory import ProblemConstants
        rule = get_rule(variant)
        if rule.theory_gamma is None:
            raise ValueError(f"variant {rule.name!r} has no theory_gamma")
        consts = ProblemConstants(
            eps=eps, n=n, omega=omega, L=L, L_hat=L_hat or L,
            L_max=L_max or L, L_sigma=L_sigma or L, m=m, B=B,
            sigma2=sigma2, d=d, zeta=zeta)
        gamma, extras = rule.theory_gamma(consts)
        return cls(gamma=gamma_mult * gamma, a=momentum_a(omega),
                   variant=rule.name, batch_sync=batch_sync, **extras)


class Method(NamedTuple):
    """``init(x0, key, ...) -> MethodState``; ``step(state, data=None) ->
    MethodState`` (jit-able); ``run(state, num_rounds, ...)`` scans;
    ``step_full(state, data=None, *, deficit=None) -> (MethodState,
    StepInfo)`` is ``step`` plus the wire-observable round internals (same
    traced body); ``deficit`` feeds the async simulators' in-flight
    correction into the server update (DESIGN.md §14)."""

    init: Callable[..., MethodState]
    step: Callable[..., MethodState]
    run: Callable[..., Any]
    step_full: Optional[Callable[..., Any]] = None

    @classmethod
    def build(cls, variant, compressor, substrate, hyper: Hyper) -> "Method":
        """One entrypoint for every variant x substrate x compressor."""
        rule: VariantRule = get_rule(variant)
        sub = substrate.with_compressor(compressor)
        hp = hyper
        a_eff = rule.force_a if rule.force_a is not None else hp.a
        # the sampled-client substrate (DESIGN.md §13) exposes a per-round
        # window; a C-of-n cohort can never answer an all-client dense
        # synchronization round, so barrier rules are rejected up front
        samples = bool(getattr(sub, "samples_clients", False))
        if samples and not rule.supports_client_sampling:
            raise ValueError(
                f"variant {rule.name!r} has a client-synchronization "
                "barrier (sync_requires_all): it cannot run on a sampled-"
                "client substrate — every client must answer sync rounds")

        def init(x0, key, *, init_mode: str = "exact", batch_init: int = 1,
                 grads0=None, data=None) -> MethodState:
            """Cor. 6.2/6.5: g_i^0 = h_i^0 = grad f_i(x^0); Cor. 6.8/6.10:
            a size-B_init minibatch; zeros also allowed (PL setting)."""
            if rule.init_h is not None:
                h0 = rule.init_h(sub, key, hp, x0, data)
                bits0 = sub.dense_coords(h0)
            elif grads0 is not None:
                h0 = grads0
                bits0 = sub.dense_coords(h0)
            elif init_mode == "zeros" or \
                    (getattr(sub, "problem", True) is None):
                h0 = sub.zeros_per_node(x0)
                bits0 = 0.0
            elif init_mode == "exact":
                h0 = sub.grad(key, x0, data, batch_init)
                bits0 = sub.dense_coords(h0)
            elif init_mode == "stoch":
                key, k_init = jax.random.split(key)
                h0 = sub.grad_minibatch(k_init, x0, batch_init, data)
                bits0 = sub.dense_coords(h0)
            else:
                raise ValueError(init_mode)
            # the oracle's dtype into the state's (an identity on the flat
            # substrates and where the two agree)
            h0 = sub.lin(lambda h_: h_, h0)
            return MethodState(x=x0, g=sub.mean_nodes(h0), g_local=h0,
                               h_local=h0, opt_state=sub.init_opt(x0),
                               key=key, t=jnp.zeros((), jnp.int32),
                               bits_sent=jnp.asarray(bits0, jnp.float32))

        def step_full(state: MethodState, data=None, *, deficit=None,
                      window=None, faults: Optional[FaultStep] = None
                      ) -> Tuple[MethodState, StepInfo]:
            """One round, returning the wire-observable internals too
            (:class:`StepInfo`).  ``step`` is this with the info dropped —
            same traced body, so observers never fork the math.

            ``deficit`` is the asynchronous-pipelining hook (DESIGN.md
            §14): the (1/n)-scaled sum of compressed messages the server
            has BROADCAST-counted in ``state.g`` but not yet received.
            The server update then uses g - deficit — exactly what a real
            async server holds, since g is a sum and every landing just
            adds its term back.  ``deficit=None`` (the default, and the
            staleness-0 case) leaves the traced body identical to the
            synchronous engine — the bit-exactness anchor the federated
            simulators' tau=0 parity tests rely on.  Clients are
            unaffected: h/g recursions depend only on the broadcast
            x-sequence and local state.

            ``window`` is the slab-store hook (DESIGN.md §16): a
            ``(sel, loc)`` pair of traced (C,) index vectors replacing
            the in-jit cohort draw.  ``sel`` must hold the SAME global
            ids ``round_view(k_c)`` would draw (the campaign driver
            precomputes them from the stateless key chain) and ``loc``
            their rows inside the chunk slab that ``state.h_local`` /
            ``state.g_local`` then hold instead of the (n, d) store —
            k_c is still split off, so the RNG chain and every drawn
            plan are unchanged and the round stays bit-identical to
            the scatter store.

            ``faults`` is the fault-injection hook (DESIGN.md §18): a
            :class:`FaultStep` of (n,) masks.  Reset rows are zeroed
            before the h-update (with the matching server correction);
            drop rows are reverted AFTER the estimator — the traced
            math up to the commit is untouched, so a zero-mask
            FaultStep is arithmetically (though not trace-) identical
            to ``faults=None``, and ``faults=None`` is trace-identical
            to the fault-free engine."""
            if faults is not None:
                if rule.sync_requires_all:
                    raise ValueError(
                        f"variant {rule.name!r} synchronizes all clients "
                        "(sync_requires_all): the simulator recovers its "
                        "missing messages via retries, so its math never "
                        "sees a fault — faults= is for gracefully-"
                        "degrading rules")
                if samples or window is not None:
                    raise ValueError(
                        "faults= is not supported on sampled-client "
                        "substrates (cohort sampling already models "
                        "absence; composing both is future work)")
            key, k_h, k_c, k_coin = jax.random.split(state.key, 4)
            # line 4 (server) + broadcast
            g_vis = state.g if deficit is None \
                else sub.sub_deficit(state.g, deficit)
            with jax.named_scope("dasha.server"):
                x_new, opt_state = sub.server_update(state.x, g_vis,
                                                     state.opt_state, hp)
            # sampled-client substrates window the round onto a gathered
            # (C, d) cohort slice: the h-update and estimator run at
            # O(C*d), then scatter back; the full path takes the unsliced
            # branch (round_view returns the substrate itself at C == n),
            # keeping its trace — and its RNG stream — untouched
            if window is not None:
                if not samples:
                    raise ValueError("window= requires a sampled-client "
                                     "substrate (samples_clients)")
                rsub = sub.window_view(*window)
            elif samples:
                rsub = sub.round_view(k_c)
            else:
                rsub = sub
            if rsub is sub:
                h_prev, g_prev = state.h_local, state.g_local
            else:
                h_prev = rsub.gather_nodes(state.h_local)
                g_prev = rsub.gather_nodes(state.g_local)
            reset_corr = None
            if faults is not None and faults.reset is not None:
                # rejoin="reset": the client reboots blank BEFORE this
                # round's h-update, and the server forgets its g_i/n term
                rmask = faults.reset[:, None]
                reset_corr = sub.mean_nodes(
                    jnp.where(rmask, g_prev, jnp.zeros_like(g_prev)))
                h_prev = jnp.where(rmask, jnp.zeros_like(h_prev), h_prev)
                g_prev = jnp.where(rmask, jnp.zeros_like(g_prev), g_prev)
            # line 8: THE variant-specific line
            with jax.named_scope("dasha.oracle"):
                h_new, aux = rule.h_update(rsub, k_h, hp, x_new, state.x,
                                           h_prev, data)
            # lines 9-10: m_i = C_i(drift); g_i <- g_i + m_i
            msgs = present = None
            with jax.named_scope("dasha.node_update"):
                if hasattr(rsub, "estimator_update_full"):
                    agg, h_out, g_local, payload, msgs, present = \
                        rsub.estimator_update_full(
                            k_c, h_new, h_prev, g_prev, a_eff, aux)
                else:
                    agg, h_out, g_local, payload = rsub.estimator_update(
                        k_c, h_new, h_prev, g_prev, a_eff, aux)
            if rsub is not sub:
                # unsampled rows FREEZE: offline clients compute nothing
                h_out = rsub.scatter_nodes(state.h_local, h_out)
                g_local = rsub.scatter_nodes(state.g_local, g_local)
            with jax.named_scope("dasha.aggregate"):
                g = sub.add_server(state.g, agg)               # line 14
            if faults is not None:
                if msgs is None:
                    raise ValueError(
                        "faults= needs a substrate exposing per-node "
                        "messages (estimator_update_full)")
                # drop = discard the round: the server never receives
                # m_i (un-add its mean term) and client i reverts to its
                # pre-round — post-reset — (h_i, g_i).  bits_sent still
                # charges the upload: the client DID transmit.
                dmask = faults.drop[:, None]
                with jax.named_scope("dasha.aggregate"):
                    g = g - sub.mean_nodes(
                        jnp.where(dmask, msgs.dense(), 0.0))
                h_out = jnp.where(dmask, h_prev, h_out)
                g_local = jnp.where(dmask, g_prev, g_local)
                if reset_corr is not None:
                    g = g - reset_corr
            coin = h_sync = None
            if rule.has_sync:
                # Alg. 2 lines 9-11 / MARINA: with prob p ALL nodes upload
                # a fresh dense megabatch gradient instead
                coin = jax.random.bernoulli(k_coin, hp.p)
                with jax.named_scope("dasha.oracle"):
                    h_sync = rule.sync_update(sub, k_h, hp, x_new, data)
                h_out = sub.where(coin, h_sync, h_out)
                g_local = sub.where(coin, h_sync, g_local)
                with jax.named_scope("dasha.aggregate"):
                    g = sub.where(coin, sub.mean_nodes(h_sync), g)
            round_pay = accounting.round_payload(
                payload, sub.dense_coords(h_out), coin)
            new = MethodState(x=x_new, g=g, g_local=g_local,
                              h_local=h_out, opt_state=opt_state, key=key,
                              t=state.t + 1,
                              bits_sent=state.bits_sent + round_pay)
            return new, StepInfo(messages=msgs, coin=coin, sync_dense=h_sync,
                                 present=present, payload=payload)

        def step(state: MethodState, data=None) -> MethodState:
            return step_full(state, data)[0]

        def run(state: MethodState, num_rounds: int, *,
                metric_every: int = 1, metric_fn=None, data=None,
                chunk=None, checkpoint=None, checkpoint_every: int = 1):
            """T rounds through the compiled driver (DESIGN.md §10);
            returns (final, metric trace, cumulative payload trace) —
            the seed's RNG/trace contract.  Results are bit-invariant
            across chunk sizes; vs the retired monolithic scan they can
            differ at the last ulp (XLA fusion depends on the scan-body
            shape — compare across shapes with tolerances, DESIGN.md §10).

            ``metric_fn(state) -> scalar`` defaults to ||grad f(x)||^2
            when the substrate's problem exposes an exact gradient.
            ``metric_every > 1`` evaluates the metric only on every k-th
            round (the trace stays length T, holding the last evaluated
            value in between — metrics like the exact gradient norm can
            dominate step cost).  ``chunk`` / ``checkpoint`` /
            ``checkpoint_every`` pass through to the driver (chunking
            never changes results; the hook enables resumable runs)."""
            from repro.methods.driver import run as drive
            if metric_fn is None:
                metric_fn = sub.default_metric()
            final, traces = drive(
                step, state, num_rounds, data=data,
                metrics={"metric": lambda s, d: metric_fn(s)},
                metric_every=metric_every, chunk=chunk,
                checkpoint=checkpoint, checkpoint_every=checkpoint_every)
            return final, traces["metric"], traces["bits_sent"]

        return cls(init=init, step=step, run=run, step_full=step_full)
