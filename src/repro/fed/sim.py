"""Layer 3 of the federated transport subsystem: the event-driven
client/server simulator (DESIGN.md §12) — the small-n ORACLE.

The method MATH is exactly the engine's: every round executes
``Method.step_full`` (the same traced body as ``Method.step``), so the
simulated run's iterates, RNG stream and ``bits_sent`` are those of the
lockstep driver.  What the simulator adds is TIME and BYTES:

* each client's upload is encoded onto the byte-exact wire
  (:mod:`repro.fed.wire`) and shipped through a :class:`~repro.fed.net.
  LinkModel` (latency + bytes/bandwidth x straggler multiplier);
* the server applies client i's message ``m_i`` the moment it lands — an
  ordered event log, valid because DASHA's server state is the SUM
  ``g^{t+1} = g^t + (1/n) sum_i m_i``: addition commutes, so arrival order
  never changes the math (the paper's "no client synchronization");
* a round completes when the server has everything it NEEDS: for DASHA /
  PAGE / MVR that is the participating clients only (Appendix D absent
  clients send nothing and nobody waits for them); for rules with
  ``sync_requires_all`` (SYNC-MVR, MARINA) a sync-coin round is a
  synchronization BARRIER — all n clients must land their DENSE upload, so
  the slowest straggler gates the round;
* with ``tau`` set, rounds PIPELINE (DESIGN.md §14): per-client
  next-free-time clocks replace the single round barrier, the server
  broadcasts x^{t+1} as soon as every round <= t-1-tau has landed, and
  messages still in flight are carried as a deficit on the server
  estimator through ``Method.step_full(..., deficit=...)``; tau=0
  reproduces the barrier bit-exactly (the parity anchor).

Partial participation is an arrival process whose per-round realization is
the engine's own randomness — Appendix-D coins recovered from the plan, or
the sampled substrate's C-of-n cohort (DESIGN.md §13) — so the bytes the
simulator bills and the math the engine runs always agree about who was
absent.

Straggler draws are common random numbers, pre-drawn per campaign through
:func:`repro.fed.net.campaign_multipliers` (downlink matrix first, then
uplink): every round holds one multiplier per client per link whether or
not the client participates, so two methods simulated with the same
``seed`` face the same network — and the vectorized engine
(:mod:`repro.fed.vecsim`) consumes the SAME matrices, which is what makes
the two simulators comparable draw for draw.

Execution is chunked (DESIGN.md §10 conventions): the engine math runs as
jitted ``lax.scan`` segments whose per-round observables (messages, coins,
participation, metric) stream to the host once per chunk — no per-round
dispatch, no per-round device->host sync — and the byte-exact encoding +
arrival heap replay from the stacked arrays.  This simulator remains the
REFERENCE: per-client codec bytes and an explicit event heap; use
:class:`repro.fed.vecsim.VecFedSim` for large n.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import wire
from repro.fed import faults as faultslib
from repro.fed.net import LinkModel, campaign_multipliers
from repro.kernels import ops
from repro.methods.accounting import downlink_receivers
from repro.methods.engine import FaultStep, Hyper, Method
from repro.methods.rules import get_rule
from repro.methods.substrates import gather_slab_rows, slab_layout
from repro.obs.handle import maybe as _obs_scope
from repro.obs.handle import span
from repro.obs.timeline import SERVER, client_track, record_fed_round

X_BYTES_PER_COORD = 4                  # the server broadcast is dense fp32

DEFAULT_CHUNK = 128                    # scan-segment length (memory knob)

#: extra per-round traces emitted by FAULTED campaigns (DESIGN.md §18) —
#: both simulators fill all of them (graceful rules keep the retry
#: columns at zero; sync rules keep ``dropped`` = the pre-retry missing
#: set, every member of which the retries then recover)
FAULT_TRACES = ("senders", "dropped", "late", "lost", "offline",
                "rejoins", "retries", "retry_bytes_up",
                "retry_bytes_down", "wasted_bytes_up", "retry_capped")


class FedEvent(NamedTuple):
    """One server-side event: ``m_i`` applied the moment it lands."""

    time: float
    kind: str                          # "bcast" | "apply" | "round"
    client: int
    round: int
    nbytes: int


class SimResult(NamedTuple):
    state: Any                         # final MethodState
    traces: Dict[str, np.ndarray]      # driver-style named metric traces
    events: Optional[List[FedEvent]]
    summary: Dict[str, float]


def _obs_fault_metrics(h, tr) -> None:
    """Flush a FAULTED campaign's event totals into the obs metrics
    registry (shared with :class:`repro.fed.vecsim.VecFedSim`): counters
    ``fed.faults.offline`` / ``dropped`` / ``late`` / ``lost`` /
    ``rejoins`` / ``retries`` / ``retry_capped`` (client-round events)
    and ``fed.faults.retry_bytes_up`` / ``wasted_bytes_up``."""
    if h.metrics is None:
        return
    m = h.metrics
    for name in ("offline", "dropped", "late", "lost", "rejoins",
                 "retries", "retry_capped", "retry_bytes_up",
                 "wasted_bytes_up"):
        m.counter(f"fed.faults.{name}").inc(float(tr[name].sum()))


def _record_fault_marks(tl, *, t, bcast, completion, arrivals,
                        crash_start, rejoin, rejoin_mode, drop_down,
                        lost, late, miss=None, retries=None,
                        retry_capped=None) -> None:
    """One faulted round's timeline marks (heap oracle only — the vec
    engine's per-client view is reconstructed post hoc): ``crash`` /
    ``rejoin`` instants at the broadcast, ``drop_down`` at the broadcast
    (the client never heard it), ``drop_up`` at the would-have-landed
    arrival, ``deadline_cut`` at the round close, and — for sync rules —
    one SERVER ``retries`` span over the backoff window."""
    for i in np.nonzero(crash_start)[0]:
        tl.instant(client_track(i), "crash", bcast, round=t)
    for i in np.nonzero(rejoin)[0]:
        tl.instant(client_track(i), "rejoin", bcast, round=t,
                   mode=rejoin_mode)
    for i in np.nonzero(drop_down)[0]:
        tl.instant(client_track(i), "drop_down", bcast, round=t)
    for i in np.nonzero(lost)[0]:
        tl.instant(client_track(i), "drop_up", float(arrivals[i]),
                   round=t)
    for i in np.nonzero(late)[0]:
        tl.instant(client_track(i), "deadline_cut", completion, round=t)
    if retries is not None and miss is not None and miss.any():
        tl.span(SERVER, "retries", bcast, completion, round=t,
                clients=int(miss.sum()),
                attempts=int(retries[miss].sum()),
                capped=int(retry_capped[miss].sum()))


def _obs_fed_metrics(h, tr, summary) -> None:
    """Flush one finished campaign's aggregates into the obs metrics
    registry (no-op on a metrics-less handle).  Shared with
    :class:`repro.fed.vecsim.VecFedSim` so both engines emit the same
    instrument names: ``fed.rounds`` / ``fed.bytes_up`` /
    ``fed.bytes_down`` / ``fed.sync_rounds`` counters, the
    ``fed.round_wall_s`` histogram (per-round barrier span, completion
    minus broadcast), and ``fed.sim_wall_clock_s`` /
    ``fed.mean_participants`` gauges."""
    if h.metrics is None:
        return
    m = h.metrics
    m.counter("fed.rounds").inc(summary["rounds"])
    m.counter("fed.bytes_up").inc(summary["bytes_up"])
    m.counter("fed.bytes_down").inc(summary["bytes_down"])
    m.counter("fed.sync_rounds").inc(summary["sync_rounds"])
    hist = m.histogram("fed.round_wall_s")
    for w in tr["sim_wall_clock"] - tr["bcast_clock"]:
        hist.observe(float(w))
    m.gauge("fed.sim_wall_clock_s").set(summary["wall_clock_s"])
    m.gauge("fed.mean_participants").set(summary["mean_participants"])


def _expand_cohort(arr: np.ndarray, sel: np.ndarray, n: int) -> np.ndarray:
    """Scatter a (C, ...) cohort array onto (n, ...) rows (absent rows 0 —
    they are never encoded)."""
    out = np.zeros((n,) + arr.shape[1:], arr.dtype)
    out[sel] = arr
    return out


@dataclasses.dataclass
class FedSim:
    """Event-driven federated run of one variant x compressor x substrate.

    ``uplink`` / ``downlink`` are :class:`repro.fed.net.LinkModel`;
    ``compute_s`` is the per-client local compute time per round.  Traces
    use the driver's named-metric convention, with ``bytes_up`` /
    ``bytes_down`` / ``sim_wall_clock`` streaming next to ``bits_sent``.
    """

    variant: str
    comp: Any                          # RoundCompressor
    substrate: Any                     # FlatSubstrate / SampledFlatSubstrate
    hyper: Hyper
    uplink: LinkModel = LinkModel()
    downlink: LinkModel = LinkModel()
    compute_s: float = 0.01
    seed: int = 0
    chunk: int = DEFAULT_CHUNK
    #: staleness bound for ASYNCHRONOUS PIPELINED rounds (DESIGN.md §14).
    #: None (default) keeps the classic barrier: broadcast t+1 waits for
    #: every required round-t upload.  An int tau >= 0 retires the
    #: barrier: the server broadcasts x^{t+1} as soon as every message
    #: from rounds <= t-1-tau has landed, carrying the still-in-flight
    #: rounds as a deficit on the server estimator
    #: (``Method.step_full(..., deficit=...)``).  tau=0 reproduces the
    #: barrier BIT-exactly (the gate is round t's own completion and the
    #: deficit is provably empty) — the parity anchor tests pin.
    tau: Optional[int] = None
    #: persistent client-state store for sampled substrates (DESIGN.md
    #: §16).  "slab" hoists the (n, d) ``h_local`` / ``g_local`` arrays
    #: out of the scan carry: the cohort schedule is replayed on the host,
    #: the chunk's touched rows gather into a compact (U, d) slab, and one
    #: writeback per chunk scatters them home.  "scatter" keeps the
    #: legacy carry-resident store.  "auto" (default) picks slab whenever
    #: the substrate samples clients.  Both stores are BIT-identical —
    #: same RNG chain, same traces, same wire bytes.
    store: str = "auto"
    #: fault injection (DESIGN.md §18): a :class:`repro.fed.faults.
    #: FaultModel` realizes seeded client crashes (with stale/reset
    #: rejoin), lossy links, corruption (really flipped bytes, caught by
    #: the wire checksum), a deadline and — for ``sync_requires_all``
    #: rules — bounded-backoff retries.  None (default) leaves every
    #: path untouched.  v1 scope: barrier only (``tau=None``) and dense
    #: substrates (no client sampling).
    faults: Optional[faultslib.FaultModel] = None

    def __post_init__(self):
        self.rule = get_rule(self.variant)
        if self.rule.sync_requires_all and self.comp.spec.p_participate < 1:
            raise ValueError(
                f"{self.rule.name!r} has a client-synchronization barrier "
                "(sync_requires_all): Appendix-D partial participation "
                "does not apply — every client must answer sync rounds")
        if not hasattr(self.substrate, "estimator_update_full"):
            raise ValueError(
                "FedSim needs a substrate exposing estimator_update_full "
                "(per-node wire messages) — currently FlatSubstrate only; "
                f"got {type(self.substrate).__name__}")
        if self.tau is not None and int(self.tau) < 0:
            raise ValueError(f"staleness bound tau={self.tau} must be >= 0")
        self.sampled = bool(getattr(self.substrate, "samples_clients",
                                    False))
        if self.store not in ("auto", "slab", "scatter"):
            raise ValueError(f"store={self.store!r} must be 'auto', "
                             "'slab' or 'scatter'")
        if self.store == "slab" and not self.sampled:
            raise ValueError("store='slab' needs a sampled-client "
                             "substrate — dense substrates (including "
                             "SampledFlatSubstrate at c == n, which IS "
                             "the dense path) touch every row every "
                             "round; use store='auto'")
        self.slab = self.sampled and self.store != "scatter"
        self.n = int(getattr(self.substrate, "n", self.comp.n))
        if self.faults is not None:
            if self.tau is not None:
                raise ValueError(
                    "faults= does not compose with asynchronous "
                    "pipelined rounds (tau) yet — the deadline/retry "
                    "policies are defined against the round barrier "
                    "(ROADMAP)")
            if self.sampled:
                raise ValueError(
                    "faults= does not compose with sampled-client "
                    "substrates yet — cohort sampling already models "
                    "absence (ROADMAP)")
            # Appendix-D participation replay for the fault masks: the
            # bound substrate recomputes each round's coins from the SAME
            # keys the scan consumes (jitted once; keys vary, shapes
            # don't)
            self._present_fn = jax.jit(
                self.substrate.with_compressor(self.comp).round_present)
        self.method: Method = Method.build(self.variant, self.comp,
                                           self.substrate, self.hyper)
        # the engine's round keys: key, k_h, k_c, k_coin = split(key, 4);
        # the plan (and with it the wire support) is drawn from k_c.
        # (Eager, not jitted: Plan.kind is a static string.)  The codec
        # only reads the plan when the support is not already in the
        # message records (PermK slice headers, shared seeds, dense-backend
        # masks) — skip the per-round host recompute otherwise.
        if self.sampled:
            self._enc_rc = self.substrate.with_compressor(
                self.comp).cohort_rc
        else:
            self._enc_rc = self.comp
        self._plan = lambda key: self._enc_rc.plan(
            jax.random.split(key, 4)[2])
        spec = self.comp.spec
        self._need_plan = not (spec.name == "randk"
                               and self.comp.mode == "independent"
                               and self.comp.backend == "sparse")
        self._compiled: Dict[Any, Callable] = {}
        self._default_metric = None

    def init(self, x0, key, **kw):
        return self.method.init(x0, key, **kw)

    def _metric_fn(self, metric_fn):
        """Resolve the metric ONCE per sim: a fresh default lambda per run
        would miss the compile cache and re-trace every chunk."""
        if metric_fn is not None:
            return metric_fn
        if self._default_metric is None:
            self._default_metric = self.substrate.default_metric()
        return self._default_metric

    def _chunk_fn(self, length: int, metric_fn) -> Callable:
        """Jitted scan over ``length`` engine rounds, streaming the round
        observables (key, coin, present/cohort, messages, sync upload,
        metric, bits) to the host ONCE per chunk."""
        fn = self._compiled.get((length, metric_fn))
        if fn is not None:
            return fn
        sub, rule = self.substrate, self.rule

        def body(st, _):
            ys = {"key": st.key}
            if self.sampled:
                ys["sel"] = sub.round_cohort(st.key)
            new, info = self.method.step_full(st, None)
            ys["metric"] = metric_fn(new)
            ys["bits"] = new.bits_sent
            ys["values"] = info.messages.values
            if getattr(info.messages, "indices", None) is not None:
                ys["indices"] = info.messages.indices
            if info.coin is not None:
                ys["coin"] = info.coin
            if info.present is not None:
                ys["present"] = info.present
            if rule.has_sync:
                ys["sync"] = info.sync_dense
            return new, ys

        fn = jax.jit(lambda st: jax.lax.scan(body, st, None, length=length))
        self._compiled[(length, metric_fn)] = fn
        return fn

    def _chunk_fn_faulted(self, length: int, metric_fn,
                          reset_mode: bool) -> Callable:
        """The faulted chunk scan for GRACEFULLY-degrading rules: the
        host-precomputed per-round fault masks arrive as scan inputs and
        gate the commit via ``Method.step_full(..., faults=FaultStep)``
        — the engine math up to the commit (and the whole RNG chain) is
        the fault-free scan's."""
        key = ("faulted", length, metric_fn, reset_mode)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn

        def body(st, xs):
            if reset_mode:
                drop, reset = xs
            else:
                drop, reset = xs, None
            ys = {"key": st.key}
            new, info = self.method.step_full(
                st, None, faults=FaultStep(drop=drop, reset=reset))
            ys["metric"] = metric_fn(new)
            ys["bits"] = new.bits_sent
            ys["values"] = info.messages.values
            if getattr(info.messages, "indices", None) is not None:
                ys["indices"] = info.messages.indices
            if info.present is not None:
                ys["present"] = info.present
            return new, ys

        if reset_mode:
            fn = jax.jit(lambda st, drops, resets:
                         jax.lax.scan(body, st, (drops, resets)))
        else:
            fn = jax.jit(lambda st, drops:
                         jax.lax.scan(body, st, drops))
        self._compiled[key] = fn
        return fn

    def _key_chain(self, key, length: int) -> List[jax.Array]:
        """Host replay of the engine's stateless key chain
        (``k_{t+1} = split(k_t, 4)[0]``) for one chunk: the faulted path
        derives each round's Appendix-D participation from the SAME keys
        the scan is about to consume — the masks it hands the scan and
        the coins the engine draws can never disagree."""
        keys = []
        for _ in range(length):
            keys.append(key)
            key = jax.random.split(key, 4)[0]
        return keys

    def _chunk_fn_slab(self, length: int, metric_fn) -> Callable:
        """The chunk scan on the chunk-resident store (DESIGN.md §16):
        the carry holds the (U, d) SLAB instead of the (n, d) arrays, and
        each round's cohort arrives as scan inputs — ``sel`` (global ids,
        for oracles/wire/present) and ``loc`` (slab rows, for the
        gather/scatter).  ``ys`` keeps the legacy schema (``sel`` now a
        passthrough of the precomputed schedule), so :meth:`_round_wire`
        replays bytes unchanged."""
        fn = self._compiled.get(("slab", length, metric_fn))
        if fn is not None:
            return fn
        rule = self.rule

        def body(st, xs):
            sel, loc = xs
            ys = {"key": st.key, "sel": sel}
            new, info = self.method.step_full(st, None, window=(sel, loc))
            ys["metric"] = metric_fn(new)
            ys["bits"] = new.bits_sent
            ys["values"] = info.messages.values
            if getattr(info.messages, "indices", None) is not None:
                ys["indices"] = info.messages.indices
            if info.coin is not None:
                ys["coin"] = info.coin
            if info.present is not None:
                ys["present"] = info.present
            if rule.has_sync:
                ys["sync"] = info.sync_dense
            return new, ys

        fn = jax.jit(lambda st, sels, locs:
                     jax.lax.scan(body, st, (sels, locs)))
        self._compiled[("slab", length, metric_fn)] = fn
        return fn

    def _slab_enter(self, state, uniq_pad: np.ndarray, h=None):
        """Swap the (n, d) store out of the carry: gather the chunk's
        touched rows into the slab; the full arrays wait on the side for
        :meth:`_slab_exit`'s once-per-chunk writeback.  The gather is the
        ``fed.slab_gather`` span (:func:`repro.obs.span`)."""
        idx = jnp.asarray(uniq_pad)
        with span(h, "fed.slab_gather", rows=int(uniq_pad.size)):
            st = state._replace(
                h_local=gather_slab_rows(state.h_local, idx),
                g_local=gather_slab_rows(state.g_local, idx))
        return st, state.h_local, state.g_local

    def _slab_exit(self, state, uniq_pad: np.ndarray, full_h, full_g,
                   h=None):
        """Per-chunk writeback: one O(U·d) scatter into the store (the
        aliased Pallas kernel on compiled backends, XLA drop-scatter
        under interpret — :func:`repro.kernels.ops.slab_writeback`), as
        the ``fed.slab_writeback`` span."""
        idx = jnp.asarray(uniq_pad)
        with span(h, "fed.slab_writeback", rows=int(uniq_pad.size)):
            return state._replace(
                h_local=ops.slab_writeback(full_h, idx, state.h_local),
                g_local=ops.slab_writeback(full_g, idx, state.g_local))

    def _run_chunk(self, state, length: int, metric_fn, h=None):
        """One engine chunk on the active store: the slab path precomputes
        the cohort schedule from ``state.key`` (the same stateless key
        chain the engine folds in-jit), gathers the touched rows, scans
        with the slab in the carry, and writes back once; the scatter
        path is the legacy carry-resident scan."""
        if self.slab:
            sels = self.substrate.cohort_schedule(state.key, length)
            uniq, loc = slab_layout(sels, self.n)
            st, full_h, full_g = self._slab_enter(state, uniq, h)
            st, ys = self._chunk_fn_slab(length, metric_fn)(
                st, jnp.asarray(sels), jnp.asarray(loc))
            state = self._slab_exit(st, uniq, full_h, full_g, h)
        else:
            state, ys = self._chunk_fn(length, metric_fn)(state)
        return state, ys

    def _expand_plan(self, plan, sel: np.ndarray, n: int):
        """Re-key a cohort plan's per-row support by CLIENT id so
        :func:`repro.fed.wire.encode_round` (which walks client rows) reads
        the right support: shared supports broadcast (every row is the
        same), private supports scatter through the cohort."""
        rep = {}
        shared = (self.comp.mode == "shared_coords"
                  and self.comp.spec.name != "permk")
        for field in ("indices", "mask"):
            arr = getattr(plan, field)
            if arr is None:
                continue
            arr = np.asarray(arr)
            if shared:
                rep[field] = np.broadcast_to(arr[0], (n,) + arr.shape[1:])
            else:
                # PermK rows are per-SLOT even under a shared permutation
                # seed — each cohort slot owns a different block
                rep[field] = _expand_cohort(arr, sel, n)
        return plan._replace(**rep) if rep else plan

    def _round_wire(self, ys, j: int, t: int, sender_mask=None):
        """Decode round ``t``'s engine observables (chunk slot ``j``) into
        its wire realization: (coin, active, RoundBytes, raw buffers,
        dense (n, d) message rows).  Shared by the barrier, async and
        faulted paths, so all bill the byte-exact codec identically.
        ``sender_mask`` (faulted graceful rounds) overrides the encoded
        set: only the clients that actually upload get a record."""
        n = self.n
        coin = bool(ys["coin"][j]) if "coin" in ys else False
        if "present" in ys:
            present = np.asarray(ys["present"][j], bool)
        else:
            present = np.ones(n, bool)
        if sender_mask is not None:
            active = np.asarray(sender_mask, bool)
        elif coin and self.rule.sync_requires_all:
            # the barrier: ALL clients answer the sync round
            active = np.ones(n, bool)
        else:
            active = present
        vals = ys["values"][j]
        idxs = ys.get("indices")
        idxs = None if idxs is None else idxs[j]
        slots = None
        if self.sampled:
            sel = np.asarray(ys["sel"][j])
            vals = _expand_cohort(vals, sel, n)
            if idxs is not None:
                idxs = _expand_cohort(idxs, sel, n)
            # slot-keyed headers: under sampling EVERY record carries the
            # client's slot in THIS round's cohort, not its global id —
            # slots are bounded by C (u16-safe at any n), and for PermK
            # the slot additionally names the client's block in the
            # cohort partition of d.  The global id is recovered from the
            # round's replayable cohort (fold_in(k_c, COHORT_TAG)).
            slots = np.full(n, -1, np.int64)
            slots[sel] = np.arange(sel.size)
        msgs = _HostMessages(vals, idxs)
        plan = self._plan(ys["key"][j]) if self._need_plan else None
        if self.sampled and plan is not None:
            plan = self._expand_plan(plan, sel, n)
        bufs = wire.encode_round(
            self.comp, plan, msgs, t, coin=coin,
            sync_values=ys["sync"][j] if "sync" in ys else None,
            present=active, slots=slots)
        return coin, active, wire.round_bytes(bufs), bufs, (vals, idxs)

    def _dense_rows(self, vals, idxs) -> np.ndarray:
        """The (n, d) dense view of one round's messages (the async in-
        flight ledger): scatter-ADD for sparse backends, mirroring
        ``SparseMessages.dense()``; PAD indices (>= d) drop."""
        d = int(self.comp.spec.d)
        if idxs is None:
            return np.asarray(vals, np.float32)
        out = np.zeros((self.n, d), np.float32)
        keep = idxs < d
        rows = np.broadcast_to(np.arange(self.n)[:, None], idxs.shape)
        np.add.at(out, (rows[keep], idxs[keep].astype(np.int64)),
                  np.asarray(vals, np.float32)[keep])
        return out

    def run(self, state, rounds: int, *,
            metric_fn: Optional[Callable] = None,
            log_events: bool = False, max_events: int = 100_000,
            obs=None, start_round: int = 0, clock0: float = 0.0,
            checkpoint: Optional[Callable] = None) -> SimResult:
        """``obs`` is an optional :class:`repro.obs.Obs` handle: a live
        timeline gets every round's per-client message lifetimes
        (DESIGN.md §17) and a metrics registry gets the campaign
        counters — both recorded by THIS host loop on arrays it already
        holds, so observability changes no traced code.

        ``start_round`` / ``clock0`` RESUME a barrier campaign mid-way:
        rounds ``start_round..rounds-1`` run against the SAME seed-
        derived per-round network and fault streams (they are keyed by
        absolute round, so a killed-and-restored campaign replays the
        exact tail an uninterrupted one would), starting the wall clock
        at ``clock0``; traces cover the resumed segment only.
        ``checkpoint(state, next_round, wall_clock)`` fires after every
        chunk — save the MethodState there
        (:func:`repro.checkpoint.io.save_state`) and a later run
        can restore bit-identically."""
        metric_fn = self._metric_fn(metric_fn)
        if not (0 <= int(start_round) <= rounds):
            raise ValueError(f"start_round={start_round} outside "
                             f"[0, {rounds}]")
        with _obs_scope(obs) as h:
            if self.tau is not None:
                if start_round or clock0 or checkpoint is not None:
                    raise ValueError("checkpoint/resume is barrier-only "
                                     "(tau=None)")
                return self._run_async(state, rounds, metric_fn,
                                       log_events, max_events, h)
            if self.faults is not None:
                return self._run_faulted(state, rounds, metric_fn,
                                         log_events, max_events, h,
                                         start_round, clock0, checkpoint)
            return self._run_barrier(state, rounds, metric_fn,
                                     log_events, max_events, h,
                                     start_round, clock0, checkpoint)

    def _run_barrier(self, state, rounds: int, metric_fn,
                     log_events: bool, max_events: int, h,
                     start_round: int = 0, clock0: float = 0.0,
                     checkpoint: Optional[Callable] = None) -> SimResult:
        rng = np.random.default_rng(self.seed)
        n = self.n
        d = int(self.comp.spec.d)
        x_bytes = X_BYTES_PER_COORD * d
        md_all, mu_all = campaign_multipliers(
            rng, rounds, self.downlink, self.uplink, n)
        # the dense broadcast reaches every client that computes this
        # round: the sampled cohort only (unsampled rows freeze), all n
        # otherwise — Appendix-D absentees still refresh h_i locally
        recv = downlink_receivers(n, self.substrate.c if self.sampled
                                  else None)

        names = ("metric", "bits_sent", "bytes_up", "value_bytes",
                 "bytes_down", "sim_wall_clock", "bcast_clock",
                 "sync_round", "participants")
        n_run = rounds - start_round
        tr = {k: np.zeros(n_run) for k in names}
        events: List[FedEvent] = []
        now = float(clock0)
        bytes_up_total = 0
        sync_rounds = 0

        done = start_round
        while done < rounds:
            length = min(self.chunk, rounds - done)
            state, ys = self._run_chunk(state, length, metric_fn, h)
            ys = jax.device_get(ys)                # ONE transfer per chunk
            for j in range(length):
                t = done + j
                rel = t - start_round
                coin, active, rb, _bufs, _ = self._round_wire(ys, j, t)
                up_bytes = np.asarray(rb.per_node, np.float64)
                down_bytes = np.where(active, x_bytes, 0) \
                    .astype(np.float64)

                # common random numbers: every client holds a draw on both
                # links this round, participant or not
                m_down, m_up = md_all[t], mu_all[t]
                t_down = self.downlink.transfer_s(down_bytes, m_down)
                t_up = self.uplink.transfer_s(up_bytes, m_up)
                delay = t_down + self.compute_s + t_up
                tr["bcast_clock"][rel] = now
                heap = []
                for i in range(n):
                    if not active[i]:
                        continue
                    heapq.heappush(heap, (now + delay[i], i))
                # drain arrivals in time order: the server applies m_i the
                # moment it lands (sum-structured g makes order irrelevant
                # to the math; the LAST required arrival completes the
                # round)
                completion = now + self.downlink.latency_s
                while heap:
                    at, i = heapq.heappop(heap)
                    completion = at
                    if log_events and len(events) < max_events:
                        events.append(FedEvent(at, "apply", i, t,
                                               rb.per_node[i]))
                if log_events and len(events) < max_events:
                    events.append(FedEvent(completion, "round", -1, t,
                                           rb.total_bytes))
                if h.timeline is not None:
                    record_fed_round(
                        h.timeline, round=t, bcast=now,
                        completion=completion, active=active,
                        arrivals=now + delay, t_down=t_down, t_up=t_up,
                        per_node_bytes=np.asarray(rb.per_node),
                        down_bytes=down_bytes, compute_s=self.compute_s,
                        coin=coin, server_down_bytes=recv * x_bytes,
                        cohort=np.asarray(ys["sel"][j])
                        if self.sampled else None)
                now = completion

                bytes_up_total += rb.total_bytes
                sync_rounds += int(coin)
                tr["metric"][rel] = float(ys["metric"][j])
                tr["bits_sent"][rel] = float(ys["bits"][j])
                tr["bytes_up"][rel] = rb.total_bytes
                tr["value_bytes"][rel] = rb.value_bytes
                tr["bytes_down"][rel] = recv * x_bytes
                tr["sim_wall_clock"][rel] = now
                tr["sync_round"][rel] = float(coin)
                tr["participants"][rel] = float(active.sum())
            done += length
            if checkpoint is not None:
                checkpoint(state, done, now)

        summary = {
            "rounds": float(n_run),
            "wall_clock_s": now,
            "bytes_up": float(bytes_up_total),
            "bytes_down": float(tr["bytes_down"].sum()),
            "sync_rounds": float(sync_rounds),
            "mean_participants": float(tr["participants"].mean())
            if n_run else 0.0,
            "mean_bytes_up_per_round":
                float(bytes_up_total) / max(n_run, 1),
        }
        _obs_fed_metrics(h, tr, summary)
        return SimResult(state=state, traces=tr,
                         events=events if log_events else None,
                         summary=summary)

    def _verify_round_buffers(self, bufs, t: int, senders: np.ndarray,
                              fc) -> None:
        """The heap oracle's wire-integrity drill: every upload that
        physically reaches the server is checksum-verified
        (:func:`repro.fed.wire.verify`), and a corrupted one has a byte
        REALLY flipped first (:func:`repro.fed.faults.corrupt_bytes`) —
        proving the crc catches exactly the corrupt set and passes the
        pristine set.  A miss either way is a simulator bug, not a fault:
        RuntimeError."""
        arrive = senders & ~fc.drop_up[t]
        for i in np.nonzero(arrive)[0]:
            buf = bufs[i]
            if buf is None:                # header-only formats never are
                raise RuntimeError(f"round {t}: sender {i} produced no "
                                   "wire record")
            if fc.corrupt[t, i]:
                mangled = faultslib.corrupt_bytes(buf, t, int(i))
                try:
                    wire.verify(mangled)
                except wire.WireDecodeError:
                    continue               # caught — treated as dropped
                raise RuntimeError(
                    f"round {t}: corrupted record from client {i} passed "
                    "wire.verify — the checksum missed a real bit flip")
            wire.verify(buf)               # pristine must pass

    def _run_faulted(self, state, rounds: int, metric_fn,
                     log_events: bool, max_events: int, h,
                     start_round: int = 0, clock0: float = 0.0,
                     checkpoint: Optional[Callable] = None) -> SimResult:
        """The FAULTED barrier replay (DESIGN.md §18).

        The fault realization is host-precomputed for the FULL campaign
        (:meth:`repro.fed.faults.FaultModel.draw_campaign` — keyed by
        absolute round, so chunking and kill/restore cannot move it) and
        split by rule family:

        * gracefully-degrading rules (DASHA / PAGE / MVR): the per-round
          drop mask — crashes, downlink losses, uplink losses, checksum-
          caught corruption, deadline-cut stragglers — gates the engine
          commit in-scan (``Method.step_full(..., faults=FaultStep)``);
          the server proceeds with whatever was delivered.  Only actual
          senders are encoded and billed; a short-handed round costs the
          deadline.
        * ``sync_requires_all`` rules (MARINA / SYNC-MVR): the METHOD
          math never sees a fault — the server re-requests every missing
          client with exponential backoff until its upload lands
          (re-paying the downlink ``x`` and the uplink record per
          attempt), so the state trace is bit-identical to the fault-free
          run and the entire fault cost lands in bytes and wall-clock.
          That asymmetry is the paper's robustness story, measured:
          benchmarks/fed_faults_bench.py.

        Fault masks are pure functions of pre-drawn booleans plus the
        ``m_up > deadline_mult`` comparison (module docstring of
        :mod:`repro.fed.faults`), so :class:`repro.fed.vecsim.VecFedSim`
        realizes the IDENTICAL masks in-scan and the integer byte traces
        match bit for bit."""
        fm = self.faults
        rng = np.random.default_rng(self.seed)
        n = self.n
        d = int(self.comp.spec.d)
        x_bytes = X_BYTES_PER_COORD * d
        md_all, mu_all = campaign_multipliers(
            rng, rounds, self.downlink, self.uplink, n)
        sync = self.rule.sync_requires_all
        reset_mode = fm.rejoin == "reset"
        fc = fm.draw_campaign(rounds, n, retries=sync)
        cap = fm.late_cap()
        deadline = fm.deadline_s(self.downlink, self.uplink,
                                 self.compute_s, d)
        cumbk = fm.backoff_cumsum() if sync else None
        lat_d = self.downlink.latency_s

        names = ("metric", "bits_sent", "bytes_up", "value_bytes",
                 "bytes_down", "sim_wall_clock", "bcast_clock",
                 "sync_round", "participants") + FAULT_TRACES
        n_run = rounds - start_round
        tr = {k: np.zeros(n_run) for k in names}
        events: List[FedEvent] = []
        now = float(clock0)
        bytes_up_total = 0
        bytes_down_total = 0
        sync_rounds = 0

        done = start_round
        while done < rounds:
            length = min(self.chunk, rounds - done)
            sl = slice(done, done + length)
            crash_off = fc.crashed[sl] | fc.drop_down[sl]
            mu32 = mu_all[sl].astype(np.float32)
            if sync:
                # retries recover every message: the engine runs the
                # fault-free scan, states bit-identical to no faults
                state, ys = self._run_chunk(state, length, metric_fn, h)
            else:
                keys = self._key_chain(state.key, length)
                present = np.stack([np.asarray(self._present_fn(k), bool)
                                    for k in keys])
                senders_c = present & ~crash_off
                late_c = senders_c & (mu32 > cap) if cap is not None \
                    else np.zeros_like(senders_c)
                lost_c = senders_c & (fc.drop_up[sl] | fc.corrupt[sl])
                drop_c = crash_off | lost_c | late_c
                fn = self._chunk_fn_faulted(length, metric_fn, reset_mode)
                if reset_mode:
                    state, ys = fn(state, jnp.asarray(drop_c),
                                   jnp.asarray(fc.rejoin[sl]))
                else:
                    state, ys = fn(state, jnp.asarray(drop_c))
            ys = jax.device_get(ys)
            for j in range(length):
                t = done + j
                rel = t - start_round
                if sync:
                    coin, active, rb, bufs, _ = self._round_wire(ys, j, t)
                    present_j = active          # all n answer
                    senders = active & ~crash_off[j]
                    late = senders & (mu32[j] > cap) if cap is not None \
                        else np.zeros(n, bool)
                    lost = senders & (fc.drop_up[t] | fc.corrupt[t])
                else:
                    present_j = present[j]
                    senders = senders_c[j]
                    late, lost = late_c[j], lost_c[j]
                    coin, active, rb, bufs, _ = self._round_wire(
                        ys, j, t, sender_mask=senders)
                delivered = senders & ~lost & ~late
                self._verify_round_buffers(bufs, t, senders, fc)

                up_bytes = np.asarray(rb.per_node, np.float64)
                down_bytes = np.where(senders, x_bytes, 0) \
                    .astype(np.float64)
                m_down, m_up = md_all[t], mu_all[t]
                t_down = self.downlink.transfer_s(down_bytes, m_down)
                t_up = self.uplink.transfer_s(up_bytes, m_up)
                delay = t_down + self.compute_s + t_up
                tr["bcast_clock"][rel] = now

                if sync:
                    miss = ~delivered           # ALL n must land
                else:
                    miss = present_j & ~delivered
                any_miss = bool(miss.any())

                # round close: the normal drain over what was delivered,
                # or the deadline when the server had to cut someone
                if delivered.any():
                    base = max(now + delay[i]
                               for i in np.nonzero(delivered)[0])
                else:
                    base = now + lat_d
                if any_miss and deadline is not None:
                    close = now + float(deadline)
                else:
                    close = base

                retries_n = retry_up_n = capped_n = 0
                retry_up_b = retry_down_b = 0
                if sync and any_miss:
                    # bounded-backoff re-requests: client i's recovered
                    # upload lands at close + backoff(first_success) +
                    # one nominal round trip of its own record
                    land = close
                    for i in np.nonzero(miss)[0]:
                        fs = int(fc.first_success[t, i])
                        ua = int(fc.up_attempts[t, i])
                        nb = len(bufs[i])
                        rt = self.downlink.latency_s \
                            + x_bytes / self.downlink.bandwidth_Bps \
                            + self.compute_s + self.uplink.latency_s \
                            + nb / self.uplink.bandwidth_Bps
                        land = max(land, close + cumbk[fs] + rt)
                        retries_n += fs
                        retry_up_n += ua
                        retry_up_b += ua * nb
                        retry_down_b += fs * x_bytes
                        capped_n += int(fc.capped[t, i])
                    completion = land
                else:
                    completion = close

                sent_b = int(up_bytes[senders].sum())
                wasted_b = int(up_bytes[lost | late].sum())
                round_up = sent_b + retry_up_b
                round_down = n * x_bytes + retry_down_b

                if log_events:
                    for i in np.nonzero(delivered)[0]:
                        if len(events) >= max_events:
                            break
                        events.append(FedEvent(float(now + delay[i]),
                                               "apply", int(i), t,
                                               rb.per_node[i]))
                    if len(events) < max_events:
                        events.append(FedEvent(completion, "round", -1,
                                               t, round_up))
                if h.timeline is not None:
                    record_fed_round(
                        h.timeline, round=t, bcast=now,
                        completion=completion, active=senders,
                        arrivals=now + delay, t_down=t_down, t_up=t_up,
                        per_node_bytes=np.asarray(rb.per_node),
                        down_bytes=down_bytes, compute_s=self.compute_s,
                        coin=coin, server_down_bytes=n * x_bytes)
                    _record_fault_marks(
                        h.timeline, t=t, bcast=now, completion=completion,
                        arrivals=now + delay,
                        crash_start=fc.crash_start[t], rejoin=fc.rejoin[t],
                        rejoin_mode=fm.rejoin, drop_down=fc.drop_down[t],
                        lost=lost, late=late,
                        miss=miss if sync else None,
                        retries=fc.first_success[t] if sync else None,
                        retry_capped=fc.capped[t] if sync else None)
                now = completion

                bytes_up_total += round_up
                bytes_down_total += round_down
                sync_rounds += int(coin)
                tr["metric"][rel] = float(ys["metric"][j])
                tr["bits_sent"][rel] = float(ys["bits"][j])
                tr["bytes_up"][rel] = round_up
                tr["value_bytes"][rel] = rb.value_bytes
                tr["bytes_down"][rel] = round_down
                tr["sim_wall_clock"][rel] = now
                tr["sync_round"][rel] = float(coin)
                tr["participants"][rel] = float(n if sync
                                                else delivered.sum())
                tr["senders"][rel] = float(senders.sum())
                tr["dropped"][rel] = float(miss.sum()) if sync \
                    else float((present_j & ~delivered).sum())
                tr["late"][rel] = float(late.sum())
                tr["lost"][rel] = float(lost.sum())
                tr["offline"][rel] = float((present_j
                                            & crash_off[j]).sum())
                tr["rejoins"][rel] = float(fc.rejoin[t].sum())
                tr["retries"][rel] = float(retries_n)
                tr["retry_bytes_up"][rel] = float(retry_up_b)
                tr["retry_bytes_down"][rel] = float(retry_down_b)
                tr["wasted_bytes_up"][rel] = float(wasted_b)
                tr["retry_capped"][rel] = float(capped_n)
            done += length
            if checkpoint is not None:
                checkpoint(state, done, now)

        summary = {
            "rounds": float(n_run),
            "wall_clock_s": now,
            "bytes_up": float(bytes_up_total),
            "bytes_down": float(bytes_down_total),
            "sync_rounds": float(sync_rounds),
            "mean_participants": float(tr["participants"].mean())
            if n_run else 0.0,
            "mean_bytes_up_per_round":
                float(bytes_up_total) / max(n_run, 1),
            "dropped_rounds": float((tr["dropped"] > 0).sum()),
            "retries": float(tr["retries"].sum()),
            "retry_capped": float(tr["retry_capped"].sum()),
            "wasted_bytes_up": float(tr["wasted_bytes_up"].sum()),
        }
        _obs_fed_metrics(h, tr, summary)
        _obs_fault_metrics(h, tr)
        return SimResult(state=state, traces=tr,
                         events=events if log_events else None,
                         summary=summary)

    def _round_fn(self, metric_fn) -> Callable:
        """Per-round jitted engine step WITH the deficit input — the async
        tau >= 1 dispatch.  The deficit feeds back into the next round's
        math, so rounds cannot fuse into one scan; one dispatch per round
        is the oracle's price (use :class:`repro.fed.vecsim.VecFedSim`
        for scale — its ring buffer lives inside the scan carry).  This
        path keeps the legacy carry-resident store regardless of
        ``store=``: with no scan there is no per-round carry copy to
        amortize, and the host-driven dispatch already pays O(n·d) in
        transfers — the slab store's scan-carry win does not apply."""
        fn = self._compiled.get(("round", metric_fn))
        if fn is not None:
            return fn
        sub, rule = self.substrate, self.rule

        def one(st, deficit):
            ys = {"key": st.key}
            if self.sampled:
                ys["sel"] = sub.round_cohort(st.key)
            new, info = self.method.step_full(st, None, deficit=deficit)
            ys["metric"] = metric_fn(new)
            ys["bits"] = new.bits_sent
            ys["values"] = info.messages.values
            if getattr(info.messages, "indices", None) is not None:
                ys["indices"] = info.messages.indices
            if info.coin is not None:
                ys["coin"] = info.coin
            if info.present is not None:
                ys["present"] = info.present
            if rule.has_sync:
                ys["sync"] = info.sync_dense
            return new, ys

        fn = jax.jit(one)
        self._compiled[("round", metric_fn)] = fn
        return fn

    def _run_async(self, state, rounds: int, metric_fn,
                   log_events: bool, max_events: int, h) -> SimResult:
        """Asynchronous pipelined replay (DESIGN.md §14): per-client
        next-free-time clocks, cross-round in-flight messages, and a
        staleness-bounded broadcast gate.

        Per round t: the server broadcasts x^{t+1} at ``T = max(T,
        completion(t-1-tau), flush)`` — it waits only for rounds older
        than the staleness bound (and for a sync flush) — computing
        x^{t+1} from ``g - deficit`` where the deficit is the (1/n)-scaled
        sum of messages still in flight at T.  Clients stay lockstep:
        client i starts round t's compute at ``max(T + downlink_i,
        free_i)`` and its upload lands at ``start + compute + uplink_i``,
        updating ``free_i``.  Arrivals APPLY on landing (g is a sum;
        landings commute), so a slow client's round-t message can land
        after round t+k was already broadcast.

        At tau = 0 the gate is exactly round t-1's completion, the deficit
        is provably empty (nothing can still be in flight), and the
        busy-client branch never binds — so the engine pass reuses the
        barrier's own chunked scans (bit-identical states) and the clock
        arithmetic reproduces the barrier's f64 chains term for term: the
        parity anchor tests/test_fed_async.py pins bit-exactly.

        ``sync_requires_all`` coin rounds flush the pipeline
        (:attr:`repro.methods.rules.VariantRule.pipeline_coin_flush`):
        pre-coin in-flight messages are discarded (the sync reset
        overwrites g) and the next broadcast waits for all n dense
        uploads — MARINA / SYNC-MVR keep paying their barrier."""
        tau = int(self.tau)
        rng = np.random.default_rng(self.seed)
        n = self.n
        d = int(self.comp.spec.d)
        x_bytes = X_BYTES_PER_COORD * d
        md_all, mu_all = campaign_multipliers(
            rng, rounds, self.downlink, self.uplink, n)
        recv = downlink_receivers(n, self.substrate.c if self.sampled
                                  else None)
        flush_rule = self.rule.pipeline_coin_flush
        lat_d = self.downlink.latency_s

        names = ("metric", "bits_sent", "bytes_up", "value_bytes",
                 "bytes_down", "sim_wall_clock", "bcast_clock",
                 "sync_round", "participants")
        tr = {k: np.zeros(rounds) for k in names}
        events: List[FedEvent] = []

        T = 0.0                         # latest broadcast time
        free = np.zeros(n)              # per-client next-free-time clocks
        flush_T = -np.inf               # pending sync-flush gate
        # staleness ring over the last tau+1 dispatched rounds: slot 0 =
        # round t-1-tau (its completion gates broadcast t), slots 1..tau =
        # rounds allowed to still be in flight (their arrivals/messages
        # feed the deficit)
        ring = collections.deque(
            [{"floor": -np.inf, "arr": None, "msgs": None}
             for _ in range(tau + 1)], maxlen=tau + 1)

        step1 = self._round_fn(metric_fn) if tau >= 1 else None
        buf = None
        buf_off = buf_len = 0
        bytes_up_total = 0
        sync_rounds = 0

        for t in range(rounds):
            gate = max(ring[0]["floor"], flush_T)
            T_new = max(T, gate)

            if tau == 0:
                # deficit provably empty: the engine pass IS the barrier's
                # chunked scan — bit-identical jaxpr, bit-identical states
                if buf_off == buf_len:
                    buf_len = min(self.chunk, rounds - t)
                    state, buf = self._run_chunk(state, buf_len,
                                                 metric_fn, h)
                    buf = jax.device_get(buf)
                    buf_off = 0
                ys, j = buf, buf_off
                buf_off += 1
            else:
                deficit = np.zeros(d, np.float32)
                for e in list(ring)[1:]:
                    if e["arr"] is None:
                        continue
                    in_flight = e["arr"] > T_new
                    if in_flight.any():
                        deficit += e["msgs"][in_flight].sum(0)
                state, ys1 = step1(state, deficit / np.float32(n))
                ys1 = jax.device_get(ys1)
                ys = {k: np.asarray(v)[None] for k, v in ys1.items()}
                j = 0

            coin, active, rb, _bufs, (vals, idxs) = self._round_wire(ys, j,
                                                                     t)
            up_bytes = np.asarray(rb.per_node, np.float64)
            down_bytes = np.where(active, x_bytes, 0).astype(np.float64)
            m_down, m_up = md_all[t], mu_all[t]
            t_down = self.downlink.transfer_s(down_bytes, m_down)
            t_up = self.uplink.transfer_s(up_bytes, m_up)
            # a client starts round t's compute once the broadcast reaches
            # it AND its previous upload is done; the not-busy branch
            # repeats the barrier's exact f64 add chain (tau=0 parity)
            busy = free > T_new + t_down
            arr = np.where(busy, (free + self.compute_s) + t_up,
                           T_new + (t_down + self.compute_s + t_up))
            arr_m = np.where(active, arr, -np.inf)
            floor_t = float(arr_m.max()) if active.any() \
                else T_new + lat_d
            free = np.where(active, arr, free)

            if log_events:
                if len(events) < max_events:
                    events.append(FedEvent(T_new, "bcast", -1, t,
                                           recv * x_bytes))
                act_idx = np.nonzero(active)[0]
                for i in act_idx[np.argsort(arr[act_idx], kind="stable")]:
                    if len(events) >= max_events:
                        break
                    events.append(FedEvent(float(arr[i]), "apply", int(i),
                                           t, rb.per_node[i]))
                if len(events) < max_events:
                    events.append(FedEvent(floor_t, "round", -1, t,
                                           rb.total_bytes))
            if h.timeline is not None:
                # async rounds interleave in wall time; the per-track
                # ROUND ids still advance monotonically, which is the
                # invariant Timeline.validate() checks
                record_fed_round(
                    h.timeline, round=t, bcast=T_new, completion=floor_t,
                    active=active, arrivals=arr, t_down=t_down, t_up=t_up,
                    per_node_bytes=np.asarray(rb.per_node),
                    down_bytes=down_bytes, compute_s=self.compute_s,
                    coin=coin, server_down_bytes=recv * x_bytes,
                    cohort=np.asarray(ys["sel"][j])
                    if self.sampled else None)

            ring.popleft()
            if coin and flush_rule:
                # sync reset: g <- mean(h_sync) discards every pre-coin
                # in-flight message, and the NEXT broadcast waits for all
                # n dense sync uploads — the capped-pipelining mechanism
                flush_T = max(flush_T, floor_t)
                for e in ring:
                    e["floor"], e["arr"], e["msgs"] = -np.inf, None, None
                ring.append({"floor": -np.inf, "arr": None, "msgs": None})
            else:
                ring.append({
                    "floor": floor_t, "arr": arr_m,
                    "msgs": self._dense_rows(vals, idxs)
                    if tau >= 1 else None})
            T = T_new

            bytes_up_total += rb.total_bytes
            sync_rounds += int(coin)
            tr["metric"][t] = float(ys["metric"][j])
            tr["bits_sent"][t] = float(ys["bits"][j])
            tr["bytes_up"][t] = rb.total_bytes
            tr["value_bytes"][t] = rb.value_bytes
            tr["bytes_down"][t] = recv * x_bytes
            tr["sim_wall_clock"][t] = floor_t
            tr["bcast_clock"][t] = T_new
            tr["sync_round"][t] = float(coin)
            tr["participants"][t] = float(active.sum())

        summary = {
            "rounds": float(rounds),
            "wall_clock_s": float(tr["sim_wall_clock"].max())
            if rounds else 0.0,
            "bytes_up": float(bytes_up_total),
            "bytes_down": float(tr["bytes_down"].sum()),
            "sync_rounds": float(sync_rounds),
            "mean_participants": float(tr["participants"].mean()),
            "mean_bytes_up_per_round":
                float(bytes_up_total) / max(rounds, 1),
            "tau": float(tau),
        }
        _obs_fed_metrics(h, tr, summary)
        return SimResult(state=state, traces=tr,
                         events=events if log_events else None,
                         summary=summary)


class _HostMessages(NamedTuple):
    """Host-side stand-in for the backend message containers: the codec
    only reads ``.values`` / ``.indices``."""

    values: np.ndarray
    indices: Optional[np.ndarray]


def simulate(variant: str, comp, substrate, hyper: Hyper, x0, key, *,
             rounds: int, uplink: Optional[LinkModel] = None,
             downlink: Optional[LinkModel] = None, compute_s: float = 0.01,
             seed: int = 0, init_kw: Optional[dict] = None,
             metric_fn=None, log_events: bool = False,
             engine: str = "heap", tau: Optional[int] = None,
             store: str = "auto", obs=None,
             faults: Optional[faultslib.FaultModel] = None) -> SimResult:
    """One-shot convenience: build the sim, init the method, run it.

    ``engine="heap"`` (default) is this module's event-driven reference;
    ``engine="vec"`` runs :class:`repro.fed.vecsim.VecFedSim` — same
    bytes, same network draws, one compiled program (DESIGN.md §12).
    ``tau`` selects asynchronous pipelined rounds with that staleness
    bound (DESIGN.md §14); None keeps the round barrier.  ``store``
    picks the persistent client-state store on sampled substrates
    (DESIGN.md §16): "slab" / "scatter" / "auto".  ``faults`` injects a
    seeded :class:`repro.fed.faults.FaultModel` — crashes, lossy links,
    corruption, deadlines/retries (DESIGN.md §18)."""
    if engine == "vec":
        from repro.fed.vecsim import VecFedSim
        cls = VecFedSim
    elif engine == "heap":
        cls = FedSim
    else:
        raise ValueError(f"unknown sim engine {engine!r}")
    sim = cls(variant=variant, comp=comp, substrate=substrate,
              hyper=hyper, uplink=uplink or LinkModel(),
              downlink=downlink or LinkModel(), compute_s=compute_s,
              seed=seed, tau=tau, store=store, faults=faults)
    state = sim.init(x0, key, **(init_kw or {}))
    kw = {} if engine == "vec" else {"log_events": log_events}
    return sim.run(state, rounds, metric_fn=metric_fn, obs=obs, **kw)
