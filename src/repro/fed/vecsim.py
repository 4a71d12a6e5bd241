"""The vectorized federated simulator (DESIGN.md §12): an entire campaign
— engine math, wire bytes, network time — as chunked compiled scans.

Where :class:`repro.fed.sim.FedSim` (the retained small-n ORACLE) encodes
every client's upload through the byte codec and replays arrivals on an
explicit heap, this engine computes the same quantities in array math:

* **Bytes** are analytic.  :func:`repro.fed.wire.wire_schema` classifies
  the compressor's wire format statically (header bytes, bytes per shipped
  value, static count); data-dependent counts (Bernoulli masks) come from
  the substrate's ``round_wire_counts`` — the same plan the engine draws,
  recomputed in-scan (free under jit: pure + CSE).  Per-round totals are
  then exact integers, spot-checked byte-for-byte against the codec in
  tests/test_fed_scale.py.
* **Time** is a masked max.  Straggler multipliers are the SAME
  common-random-number campaign matrices the heap sim consumes
  (:func:`repro.fed.net.campaign_multipliers`, downlink first then
  uplink), streamed into the scan as per-chunk xs; each client's arrival
  is ``latency_down + bytes_down/bw + compute + latency_up + bytes_up/bw
  * mult`` and a round completes at the max over the REQUIRED clients
  (all n on a ``sync_requires_all`` coin round, the participants
  otherwise; an empty round costs the downlink latency).  Arrival ORDER
  never enters the math — the server state is a sum — which is exactly
  why the event heap can collapse to a max.
* **Everything scans.**  One jitted ``lax.scan`` per chunk carries the
  MethodState and emits per-round scalars only (metric, bits, coin,
  participants, value counts, round time): no per-round dispatch, no
  per-round host sync, O(rounds/chunk) transfers per campaign.

Equivalence contract (tests/test_fed_scale.py): against the heap oracle
under the same seed, byte and participation traces are BIT-exact (they are
integer functions of the same engine randomness), and wall-clock agrees to
float32 resolution (the scan computes delays in f32; the oracle in f64).
Throughput: >= 10x the heap reference at n >= 1024
(benchmarks/fed_scale_bench.py -> BENCH_fed_scale.json).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import wire
from repro.fed import faults as faultslib
from repro.fed.net import LinkModel, campaign_streams, round_multipliers
from repro.fed.sim import (DEFAULT_CHUNK, FAULT_TRACES, X_BYTES_PER_COORD,
                           SimResult, _obs_fault_metrics, _obs_fed_metrics)
from repro.kernels import ops
from repro.methods.accounting import downlink_receivers
from repro.methods.engine import FaultStep, Hyper, Method
from repro.methods.rules import get_rule
from repro.methods.substrates import gather_slab_rows as _gather_rows
from repro.methods.substrates import slab_layout
from repro.obs.handle import maybe as _obs_scope
from repro.obs.handle import span


@dataclasses.dataclass
class VecFedSim:
    """Vectorized federated run of one variant x compressor x substrate.

    Drop-in for :class:`repro.fed.sim.FedSim` (same constructor, same
    trace/summary schema, no event log): built for n = 10^4-10^5 clients x
    10^3 rounds, including the sampled-client substrate whose rounds cost
    O(C*d) inside the same scan."""

    variant: str
    comp: Any                          # RoundCompressor
    substrate: Any                     # FlatSubstrate / SampledFlatSubstrate
    hyper: Hyper
    uplink: LinkModel = LinkModel()
    downlink: LinkModel = LinkModel()
    compute_s: float = 0.01
    seed: int = 0
    chunk: int = DEFAULT_CHUNK
    #: staleness bound for asynchronous pipelined rounds (DESIGN.md §14);
    #: None keeps the round barrier.  Same semantics as
    #: :class:`repro.fed.sim.FedSim` — here the per-client clocks and the
    #: bounded in-flight ring live INSIDE the scan carry (clocks rebased
    #: to the broadcast each round so f32 stays sharp; a (tau, n) arrival
    #: ring + a (tau, n, d) message ring feed the deficit), and the scan
    #: still emits per-round scalars only.
    tau: Optional[int] = None
    #: client-state store for sampled substrates (DESIGN.md §16):
    #: ``"slab"`` precomputes each chunk's cohort schedule outside the jit,
    #: gathers the union of touched rows into a compact (U, d) slab, scans
    #: with ONLY the slab in the carry and writes back once per chunk —
    #: the O(n·d)-free fast path; ``"scatter"`` keeps the per-round (n, d)
    #: carry (the pre-slab reference the bit-identity tests compare
    #: against); ``"auto"`` resolves to slab exactly when the substrate
    #: samples clients (c < n).  Both stores are bit-identical — same RNG
    #: chain, traces and wire bytes (tests/test_slab_store.py).
    store: str = "auto"
    #: fault injection (DESIGN.md §18): the same seeded
    #: :class:`repro.fed.faults.FaultModel` the heap oracle consumes —
    #: the campaign realization is host-precomputed and streamed into
    #: the scan as per-round boolean xs, so both simulators face
    #: bit-identical fault masks (and bit-identical byte traces).  v1
    #: scope: barrier only (``tau=None``), dense substrates.
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
                "VecFedSim needs a substrate exposing estimator_update_full"
                f" — got {type(self.substrate).__name__}")
        if self.tau is not None and int(self.tau) < 0:
            raise ValueError(f"staleness bound tau={self.tau} must be >= 0")
        self.sampled = bool(getattr(self.substrate, "samples_clients",
                                    False))
        if self.store not in ("auto", "scatter", "slab"):
            raise ValueError(f"store={self.store!r} must be 'auto', "
                             "'scatter' or 'slab'")
        if self.store == "slab" and not self.sampled:
            raise ValueError("store='slab' needs a sampled-client "
                             "substrate (c < n); at c == n the scatter "
                             "store IS the degenerate slab")
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
        self._bound = self.substrate.with_compressor(self.comp)
        self.schema = wire.wire_schema(
            self._bound.cohort_rc if self.sampled else self.comp,
            slot_keyed=self.sampled)
        self.method: Method = Method.build(self.variant, self.comp,
                                           self.substrate, self.hyper)
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
        fn = self._compiled.get((length, metric_fn))
        if fn is not None:
            return fn
        n, d = self.n, int(self.comp.spec.d)
        rule, schema = self.rule, self.schema
        x_bytes = X_BYTES_PER_COORD * d
        dense_up = float(wire.HEADER_BYTES + 4 * d)
        lat_d = float(self.downlink.latency_s)

        def body(st, xs):
            m_down, m_up = xs                              # (n,) f32 each
            key = st.key                                   # pre-step key
            new, info = self.method.step_full(st, None)
            coin = info.coin if info.coin is not None \
                else jnp.zeros((), bool)
            present = info.present if info.present is not None \
                else jnp.ones((n,), bool)
            if rule.sync_requires_all and info.coin is not None:
                active = jnp.logical_or(present, coin)     # the barrier
            else:
                active = present
            if schema.static_count is None:
                counts = self._bound.round_wire_counts(key)
            else:
                counts = jnp.full((n,), schema.static_count, jnp.int32)
            counts = counts * active                       # absent: 0

            # per-client wire bytes (f32 is exact below 2^24 per client)
            comp_b = schema.header_bytes \
                + schema.bytes_per_value * counts.astype(jnp.float32)
            up_b = jnp.where(coin, dense_up, comp_b) \
                * active.astype(jnp.float32)
            down_b = x_bytes * active.astype(jnp.float32)
            delay = self.downlink.latency_s \
                + down_b / self.downlink.bandwidth_Bps * m_down \
                + self.compute_s \
                + self.uplink.latency_s \
                + up_b / self.uplink.bandwidth_Bps * m_up
            masked = jnp.where(active, delay, -jnp.inf)
            n_active = jnp.sum(active.astype(jnp.int32))
            round_t = jnp.where(n_active > 0, jnp.max(masked), lat_d)
            ys = {"metric": metric_fn(new), "bits": new.bits_sent,
                  "coin": coin, "participants": n_active,
                  "counts_sum": jnp.sum(counts), "round_t": round_t}
            return new, ys

        def scan_chunk(st, m_down, m_up):
            return jax.lax.scan(body, st, (m_down, m_up))

        fn = jax.jit(scan_chunk)
        self._compiled[(length, metric_fn)] = fn
        return fn

    # ------------------------------------------------------------------
    # chunk-resident slab store (DESIGN.md §16)
    # ------------------------------------------------------------------

    def _chunk_fn_slab(self, length: int, metric_fn) -> Callable:
        """The barrier scan body over the chunk slab: the carry holds the
        (U_pad, d) slab — NOT the (n, d) store — plus the server state;
        each round's cohort arrives as xs (global ids ``sel`` for the
        client-id-keyed oracles, slab rows ``loc`` for gather/scatter,
        and the cohort's OWN straggler multipliers, gathered on host from
        the same CRN campaign matrices the scatter store consumes).  All
        emitted quantities are computed in (C,) space; they are bit-equal
        to the scatter body's (n,)-masked forms because every reduction
        here is order-free (integer sums, max) and every per-client float
        op is elementwise on identical inputs."""
        fn = self._compiled.get(("slab", length, metric_fn))
        if fn is not None:
            return fn
        c, d = int(self.substrate.c), int(self.comp.spec.d)
        schema = self.schema
        x_bytes = X_BYTES_PER_COORD * d
        dense_up = float(wire.HEADER_BYTES + 4 * d)

        def body(st, xs):
            m_down_c, m_up_c, sel, loc = xs     # (C,) f32 f32 i32 i32
            key = st.key                        # pre-step key
            new, info = self.method.step_full(st, None, window=(sel, loc))
            # sampled-capable variants have no sync coin (Method.build
            # rejects sync_requires_all on sampled substrates) — keep the
            # scatter body's where() tokens so the float math is
            # expression-identical anyway
            coin = info.coin if info.coin is not None \
                else jnp.zeros((), bool)
            if schema.static_count is None:
                counts = self._bound.cohort_counts(key)          # (C,)
            else:
                counts = jnp.full((c,), schema.static_count, jnp.int32)
            comp_b = schema.header_bytes \
                + schema.bytes_per_value * counts.astype(jnp.float32)
            up_b = jnp.where(coin, dense_up, comp_b)
            delay = self.downlink.latency_s \
                + x_bytes / self.downlink.bandwidth_Bps * m_down_c \
                + self.compute_s \
                + self.uplink.latency_s \
                + up_b / self.uplink.bandwidth_Bps * m_up_c
            ys = {"metric": metric_fn(new), "bits": new.bits_sent,
                  "coin": coin, "participants": jnp.full((), c, jnp.int32),
                  "counts_sum": jnp.sum(counts),
                  "round_t": jnp.max(delay)}
            return new, ys

        def scan_chunk(st, m_down_c, m_up_c, sels, locs):
            return jax.lax.scan(body, st, (m_down_c, m_up_c, sels, locs))

        fn = jax.jit(scan_chunk)
        self._compiled[("slab", length, metric_fn)] = fn
        return fn

    def _slab_chunk_xs(self, state, length: int, md: np.ndarray,
                       mu: np.ndarray):
        """Precompute one chunk's slab plumbing: the cohort schedule
        (replayed from ``state.key`` via the selection-based permutation
        head), the slab layout, and the cohort-gathered multiplier
        slices."""
        sels = self.substrate.cohort_schedule(state.key, length)
        uniq_pad, loc = slab_layout(sels, self.n)
        md_c = np.take_along_axis(md, sels, axis=1)
        mu_c = np.take_along_axis(mu, sels, axis=1)
        return sels, uniq_pad, loc, md_c, mu_c

    def _slab_enter(self, state, uniq_pad: np.ndarray, h=None):
        """Swap the (n, d) store out of the carry: gather the chunk's
        touched rows into the slab.  Returns (slab_state, full_h, full_g)
        — the full arrays stay on host/device UNTOUCHED until
        :meth:`_slab_exit` scatters the slab back once per chunk.  The
        gather is the ``vec.slab_gather`` span (:func:`repro.obs.span`)."""
        idx = jnp.asarray(uniq_pad)
        with span(h, "vec.slab_gather", rows=int(uniq_pad.size)):
            st = state._replace(h_local=_gather_rows(state.h_local, idx),
                                g_local=_gather_rows(state.g_local, idx))
        return st, state.h_local, state.g_local

    def _slab_exit(self, state, uniq_pad: np.ndarray, full_h, full_g,
                   h=None):
        """Per-chunk writeback: one O(U·d) scatter into the store (the
        aliased Pallas kernel on compiled backends, XLA drop-scatter under
        interpret — :func:`repro.kernels.ops.slab_writeback`), as the
        ``vec.slab_writeback`` span."""
        idx = jnp.asarray(uniq_pad)
        with span(h, "vec.slab_writeback", rows=int(uniq_pad.size)):
            return state._replace(
                h_local=ops.slab_writeback(full_h, idx, state.h_local),
                g_local=ops.slab_writeback(full_g, idx, state.g_local))

    def run(self, state, rounds: int, *,
            metric_fn: Optional[Callable] = None, obs=None,
            start_round: int = 0, clock0: float = 0.0,
            checkpoint: Optional[Callable] = None) -> SimResult:
        """``obs`` is an optional :class:`repro.obs.Obs` handle.  The
        scan emits per-round scalars only, so a live timeline here gets
        the ``vec.chunk`` / ``vec.slab_gather`` / ``vec.slab_writeback``
        HOST-track spans (wall time) plus compile spans; the
        per-client simulated-time view is reconstructed post hoc by
        :func:`repro.obs.reconstruct_vec_timeline` from this run's
        result.  A metrics registry gets the same campaign aggregates
        the heap sim emits.

        ``start_round`` / ``clock0`` / ``checkpoint`` carry the same
        kill-and-restore contract as :meth:`repro.fed.sim.FedSim.run`:
        the per-round network and fault streams are keyed by absolute
        round, the wall clock accumulates sequentially from ``clock0``
        (bitwise the uninterrupted chain — never a rebased cumsum), and
        ``checkpoint(state, next_round, wall_clock)`` fires after each
        chunk."""
        metric_fn = self._metric_fn(metric_fn)
        if not (0 <= int(start_round) <= rounds):
            raise ValueError(f"start_round={start_round} outside "
                             f"[0, {rounds}]")
        with _obs_scope(obs) as h:
            if self.tau is not None and rounds > 0:
                if start_round or clock0 or checkpoint is not None:
                    raise ValueError("checkpoint/resume is barrier-only "
                                     "(tau=None)")
                return self._run_async(state, rounds, metric_fn, h)
            if self.faults is not None and rounds > 0:
                return self._run_faulted(state, rounds, metric_fn, h,
                                         start_round, clock0, checkpoint)
            return self._run_barrier(state, rounds, metric_fn, h,
                                     start_round, clock0, checkpoint)

    @staticmethod
    def _seq_wall(round_t: np.ndarray, clock0: float) -> np.ndarray:
        """Per-round absolute wall clock by SEQUENTIAL f64 accumulation
        from ``clock0`` — the exact fp chain an uninterrupted run (or the
        heap oracle's ``now``) produces, so a campaign resumed from a
        checkpointed ``(state, round, wall)`` continues bit-identically
        (``np.cumsum`` is the clock0 == 0 special case; rebasing a cumsum
        by addition would re-associate the chain)."""
        out = np.empty(round_t.shape, np.float64)
        c = float(clock0)
        for i, r in enumerate(round_t.astype(np.float64)):
            c = c + r
            out[i] = c
        return out

    def _run_barrier(self, state, rounds: int, metric_fn, h,
                     start_round: int = 0, clock0: float = 0.0,
                     checkpoint: Optional[Callable] = None) -> SimResult:
        n = self.n
        rng = np.random.default_rng(self.seed)
        streams = campaign_streams(rng, rounds)
        if rounds <= 0 or start_round >= rounds:
            return SimResult(state=state,
                             traces={}, events=None,
                             summary={"rounds": 0.0,
                                      "wall_clock_s": float(clock0)})

        parts = []
        now = float(clock0)
        done = start_round
        while done < rounds:
            length = min(self.chunk, rounds - done)
            # materialize only this chunk's (length, n) multiplier slices
            # (each round's spawned stream draws downlink then uplink —
            # the same order the heap oracle consumes)
            md = np.empty((length, n), np.float32)
            mu = np.empty((length, n), np.float32)
            for j in range(length):
                md[j], mu[j] = round_multipliers(
                    streams[done + j], self.downlink, self.uplink, n)
            with span(h, "vec.chunk", start_round=done, rounds=length):
                if self.slab:
                    sels, uniq, loc, md_c, mu_c = self._slab_chunk_xs(
                        state, length, md, mu)
                    st, full_h, full_g = self._slab_enter(state, uniq, h)
                    st, ys = self._chunk_fn_slab(length, metric_fn)(
                        st, jnp.asarray(md_c), jnp.asarray(mu_c),
                        jnp.asarray(sels), jnp.asarray(loc))
                    state = self._slab_exit(st, uniq, full_h, full_g, h)
                else:
                    state, ys = self._chunk_fn(length, metric_fn)(
                        state, jnp.asarray(md), jnp.asarray(mu))
                part = jax.device_get(ys)          # ONE transfer per chunk
                parts.append(part)
            done += length
            if checkpoint is not None:
                now = float(self._seq_wall(part["round_t"], now)[-1])
                checkpoint(state, done, now)
        ys = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

        n_run = rounds - start_round
        wall = self._seq_wall(ys["round_t"], clock0)
        bcast = np.concatenate([[clock0], wall[:-1]])
        traces, summary = self._bill_round_bytes(
            ys, n_run, wall, bcast,
            wall_clock_s=float(wall[-1]) if n_run else float(clock0))
        _obs_fed_metrics(h, traces, summary)
        return SimResult(state=state, traces=traces, events=None,
                         summary=summary)

    def _bill_round_bytes(self, ys, rounds: int, wall: np.ndarray,
                          bcast: np.ndarray, wall_clock_s: float):
        """Exact byte billing + trace/summary assembly from one campaign's
        stacked per-round scan outputs — shared by the barrier and async
        paths (the clocks differ; the BYTES are the same integer
        functions of the same engine randomness).  Totals are int64 on
        host, immune to the in-scan int32/f32 ranges."""
        n, d = self.n, int(self.comp.spec.d)
        coin = ys["coin"].astype(bool)
        part = ys["participants"].astype(np.int64)
        csum = ys["counts_sum"].astype(np.int64)
        head, bpv = self.schema.header_bytes, self.schema.bytes_per_value
        dense_total = n * (wire.HEADER_BYTES + 4 * d)
        bytes_up = np.where(coin, dense_total, head * part + bpv * csum)
        value_bytes = np.where(coin, n * 4 * d, 4 * csum)
        # cohort-only downlink: the broadcast reaches the clients that
        # compute this round (the C-cohort under sampling, all n otherwise
        # — Appendix-D absentees still refresh h_i locally)
        recv = downlink_receivers(n, self.substrate.c if self.sampled
                                  else None)
        bytes_down = np.full(rounds, X_BYTES_PER_COORD * d * recv,
                             np.int64)
        traces = {
            "metric": ys["metric"].astype(np.float64),
            "bits_sent": ys["bits"].astype(np.float64),
            "bytes_up": bytes_up.astype(np.float64),
            "value_bytes": value_bytes.astype(np.float64),
            "bytes_down": bytes_down.astype(np.float64),
            "sim_wall_clock": wall,
            "bcast_clock": bcast,
            "sync_round": coin.astype(np.float64),
            "participants": part.astype(np.float64),
        }
        summary = {
            "rounds": float(rounds),
            "wall_clock_s": wall_clock_s,
            "bytes_up": float(bytes_up.sum()),
            "bytes_down": float(bytes_down.sum()),
            "sync_rounds": float(coin.sum()),
            "mean_participants": float(part.mean()),
            "mean_bytes_up_per_round": float(bytes_up.sum()) / rounds,
        }
        return traces, summary

    # ------------------------------------------------------------------
    # fault injection (DESIGN.md §18)
    # ------------------------------------------------------------------

    def _chunk_fn_graceful_faulted(self, length: int, metric_fn,
                                   reset_mode: bool) -> Callable:
        """The faulted barrier scan for gracefully-degrading rules: the
        host-precomputed per-round fault booleans arrive as xs, the full
        drop mask is assembled IN-scan from them plus the one float
        comparison ``m_up > deadline_mult`` (pure functions of the same
        inputs the heap oracle reads — bit-identical masks), and the
        engine commit is gated via ``step_full(..., faults=FaultStep)``.
        Emitted byte quantities are integer sums over the sender set; a
        short-handed round costs the static f32 deadline."""
        key_ = ("gfault", length, metric_fn, reset_mode)
        fn = self._compiled.get(key_)
        if fn is not None:
            return fn
        fm = self.faults
        n, d = self.n, int(self.comp.spec.d)
        schema = self.schema
        x_bytes = X_BYTES_PER_COORD * d
        lat_d = float(self.downlink.latency_s)
        cap = fm.late_cap()
        dl = fm.deadline_s(self.downlink, self.uplink, self.compute_s, d)

        def body(st, xs):
            if reset_mode:
                m_down, m_up, crash_off, lostx, reset = xs
            else:
                m_down, m_up, crash_off, lostx = xs
                reset = None
            key = st.key                               # pre-step key
            # the SAME Appendix-D plan the engine draws (pure + CSE)
            present = self._bound.round_present(key)
            senders = present & ~crash_off
            if cap is not None:
                late = senders & (m_up > cap)
            else:
                late = jnp.zeros((n,), bool)
            lost = senders & lostx
            drop = crash_off | lost | late
            new, info = self.method.step_full(
                st, None, faults=FaultStep(drop=drop, reset=reset))
            delivered = senders & ~lost & ~late
            miss = present & ~delivered

            if schema.static_count is None:
                counts = self._bound.round_wire_counts(key)
            else:
                counts = jnp.full((n,), schema.static_count, jnp.int32)
            counts = counts * senders                  # only senders ship
            comp_b = schema.header_bytes \
                + schema.bytes_per_value * counts.astype(jnp.float32)
            up_b = comp_b * senders.astype(jnp.float32)
            down_b = x_bytes * senders.astype(jnp.float32)
            delay = self.downlink.latency_s \
                + down_b / self.downlink.bandwidth_Bps * m_down \
                + self.compute_s \
                + self.uplink.latency_s \
                + up_b / self.uplink.bandwidth_Bps * m_up
            masked = jnp.where(delivered, delay, -jnp.inf)
            n_del = jnp.sum(delivered.astype(jnp.int32))
            base = jnp.where(n_del > 0, jnp.max(masked),
                             jnp.float32(lat_d))
            any_miss = jnp.any(miss)
            if dl is not None:
                round_t = jnp.where(any_miss, jnp.float32(dl), base)
            else:
                round_t = base
            waste = lost | late
            i32 = jnp.int32
            ys = {"metric": metric_fn(new), "bits": new.bits_sent,
                  "coin": jnp.zeros((), bool),
                  "participants": n_del,
                  "counts_sum": jnp.sum(counts),
                  "round_t": round_t,
                  "senders": jnp.sum(senders.astype(i32)),
                  "dropped": jnp.sum(miss.astype(i32)),
                  "late": jnp.sum(late.astype(i32)),
                  "lost": jnp.sum(lost.astype(i32)),
                  "offline": jnp.sum((present & crash_off).astype(i32)),
                  "wasted_n": jnp.sum(waste.astype(i32)),
                  "wasted_counts": jnp.sum(counts * waste)}
            return new, ys

        fn = jax.jit(lambda st, *xs: jax.lax.scan(body, st, xs))
        self._compiled[key_] = fn
        return fn

    def _chunk_fn_sync_faulted(self, length: int, metric_fn) -> Callable:
        """The faulted barrier scan for ``sync_requires_all`` rules
        (MARINA / SYNC-MVR): the engine step is the FAULT-FREE one — the
        server's bounded-backoff re-requests recover every missing upload,
        so the method math and state trace are bit-identical to a
        fault-free campaign — and the faults land entirely in bytes and
        wall-clock: the round closes at the deadline, then each missing
        client's recovered upload lands after its backoff + one nominal
        round trip, with every attempt billed (downlink ``x`` per
        attempt, the uplink record per attempt reaching a live
        client)."""
        key_ = ("sfault", length, metric_fn)
        fn = self._compiled.get(key_)
        if fn is not None:
            return fn
        fm = self.faults
        n, d = self.n, int(self.comp.spec.d)
        rule, schema = self.rule, self.schema
        x_bytes = X_BYTES_PER_COORD * d
        dense_up = float(wire.HEADER_BYTES + 4 * d)
        lat_d = float(self.downlink.latency_s)
        cap = fm.late_cap()
        dl = fm.deadline_s(self.downlink, self.uplink, self.compute_s, d)
        cumbk = jnp.asarray(fm.backoff_cumsum(), jnp.float32)

        def body(st, xs):
            m_down, m_up, crash_off, lostx, fs, ua, capped = xs
            key = st.key                               # pre-step key
            new, info = self.method.step_full(st, None)
            coin = info.coin if info.coin is not None \
                else jnp.zeros((), bool)
            present = info.present if info.present is not None \
                else jnp.ones((n,), bool)
            if rule.sync_requires_all and info.coin is not None:
                active = jnp.logical_or(present, coin)  # the barrier
            else:
                active = present
            if schema.static_count is None:
                counts = self._bound.round_wire_counts(key)
            else:
                counts = jnp.full((n,), schema.static_count, jnp.int32)
            counts = counts * active

            senders = active & ~crash_off
            if cap is not None:
                late = senders & (m_up > cap)
            else:
                late = jnp.zeros((n,), bool)
            lost = senders & lostx
            delivered = senders & ~lost & ~late
            miss = ~delivered                          # ALL n must land

            comp_b = schema.header_bytes \
                + schema.bytes_per_value * counts.astype(jnp.float32)
            nb = jnp.where(coin, jnp.float32(dense_up), comp_b)
            up_b = nb * senders.astype(jnp.float32)
            down_b = x_bytes * senders.astype(jnp.float32)
            delay = self.downlink.latency_s \
                + down_b / self.downlink.bandwidth_Bps * m_down \
                + self.compute_s \
                + self.uplink.latency_s \
                + up_b / self.uplink.bandwidth_Bps * m_up
            masked = jnp.where(delivered, delay, -jnp.inf)
            n_del = jnp.sum(delivered.astype(jnp.int32))
            base = jnp.where(n_del > 0, jnp.max(masked),
                             jnp.float32(lat_d))
            any_miss = jnp.any(miss)
            if dl is not None:
                close = jnp.where(any_miss, jnp.float32(dl), base)
            else:
                close = base
            # recovered upload of client i: close + backoff(first
            # success) + one NOMINAL round trip of its own record
            rt = jnp.float32(self.downlink.latency_s) \
                + jnp.float32(x_bytes) \
                / jnp.float32(self.downlink.bandwidth_Bps) \
                + jnp.float32(self.compute_s) \
                + jnp.float32(self.uplink.latency_s) \
                + nb / jnp.float32(self.uplink.bandwidth_Bps)
            land = jnp.where(miss, close + cumbk[fs] + rt, -jnp.inf)
            round_t = jnp.where(any_miss,
                                jnp.maximum(close, jnp.max(land)), close)

            i32 = jnp.int32
            mi = miss.astype(i32)
            ys = {"metric": metric_fn(new), "bits": new.bits_sent,
                  "coin": coin,
                  "participants": jnp.sum(active.astype(i32)),
                  "counts_sum": jnp.sum(counts),
                  "round_t": round_t,
                  "senders": jnp.sum(senders.astype(i32)),
                  "counts_send": jnp.sum(counts * senders),
                  "dropped": jnp.sum(mi),
                  "late": jnp.sum(late.astype(i32)),
                  "lost": jnp.sum(lost.astype(i32)),
                  "offline": jnp.sum(crash_off.astype(i32)),
                  "retries": jnp.sum(fs * mi),
                  "retry_up_n": jnp.sum(ua * mi),
                  "retry_counts": jnp.sum(counts * ua * mi),
                  "capped": jnp.sum((capped & miss).astype(i32)),
                  "wasted_n": jnp.sum((lost | late).astype(i32)),
                  "wasted_counts": jnp.sum(counts * (lost | late))}
            return new, ys

        fn = jax.jit(lambda st, *xs: jax.lax.scan(body, st, xs))
        self._compiled[key_] = fn
        return fn

    def _bill_round_bytes_faulted(self, ys, fc, sync: bool, n_run: int,
                                  start_round: int, wall: np.ndarray,
                                  bcast: np.ndarray, wall_clock_s: float):
        """Faulted-campaign billing from the stacked scan outputs: the
        same exact-integer formulas the heap oracle realizes from its raw
        buffers — ``len(buf_i) = header + bytes_per_value * count_i``
        (or the dense record on a coin round) — summed over the SENDER
        set, plus the sync rules' retry re-payments.  Every operand is an
        int64 host array of in-scan integer sums, so heap-vs-vec byte
        traces are bit-exact."""
        n, d = self.n, int(self.comp.spec.d)
        x_bytes = X_BYTES_PER_COORD * d
        head, bpv = self.schema.header_bytes, self.schema.bytes_per_value
        dense_up = wire.HEADER_BYTES + 4 * d
        i64 = np.int64
        coin = ys["coin"].astype(bool)
        part = ys["participants"].astype(i64)
        senders = ys["senders"].astype(i64)
        csum = ys["counts_sum"].astype(i64)
        csend = ys["counts_send"].astype(i64) if sync else csum
        wasted_n = ys["wasted_n"].astype(i64)
        wasted_c = ys["wasted_counts"].astype(i64)
        sl = slice(start_round, start_round + n_run)

        if sync:
            retries = ys["retries"].astype(i64)
            retry_up_n = ys["retry_up_n"].astype(i64)
            retry_c = ys["retry_counts"].astype(i64)
            capped = ys["capped"].astype(i64)
            sent = np.where(coin, dense_up * senders,
                            head * senders + bpv * csend)
            retry_up_b = np.where(coin, dense_up * retry_up_n,
                                  head * retry_up_n + bpv * retry_c)
            retry_down_b = retries * x_bytes
            value_bytes = np.where(coin, n * 4 * d, 4 * csum)
            wasted_b = np.where(coin, dense_up * wasted_n,
                                head * wasted_n + bpv * wasted_c)
        else:
            retries = retry_up_n = capped = np.zeros(n_run, i64)
            retry_up_b = retry_down_b = np.zeros(n_run, i64)
            sent = head * senders + bpv * csend
            value_bytes = 4 * csend
            wasted_b = head * wasted_n + bpv * wasted_c
        bytes_up = sent + retry_up_b
        bytes_down = n * x_bytes + retry_down_b

        traces = {
            "metric": ys["metric"].astype(np.float64),
            "bits_sent": ys["bits"].astype(np.float64),
            "bytes_up": bytes_up.astype(np.float64),
            "value_bytes": value_bytes.astype(np.float64),
            "bytes_down": bytes_down.astype(np.float64),
            "sim_wall_clock": wall,
            "bcast_clock": bcast,
            "sync_round": coin.astype(np.float64),
            "participants": part.astype(np.float64),
            "senders": senders.astype(np.float64),
            "dropped": ys["dropped"].astype(np.float64),
            "late": ys["late"].astype(np.float64),
            "lost": ys["lost"].astype(np.float64),
            "offline": ys["offline"].astype(np.float64),
            "rejoins": fc.rejoin[sl].sum(axis=1).astype(np.float64),
            "retries": retries.astype(np.float64),
            "retry_bytes_up": retry_up_b.astype(np.float64),
            "retry_bytes_down": retry_down_b.astype(np.float64),
            "wasted_bytes_up": wasted_b.astype(np.float64),
            "retry_capped": capped.astype(np.float64),
        }
        summary = {
            "rounds": float(n_run),
            "wall_clock_s": wall_clock_s,
            "bytes_up": float(bytes_up.sum()),
            "bytes_down": float(bytes_down.sum()),
            "sync_rounds": float(coin.sum()),
            "mean_participants": float(part.mean()) if n_run else 0.0,
            "mean_bytes_up_per_round":
                float(bytes_up.sum()) / max(n_run, 1),
            "dropped_rounds": float((traces["dropped"] > 0).sum()),
            "retries": float(retries.sum()),
            "retry_capped": float(capped.sum()),
            "wasted_bytes_up": float(wasted_b.sum()),
        }
        return traces, summary

    def _run_faulted(self, state, rounds: int, metric_fn, h,
                     start_round: int = 0, clock0: float = 0.0,
                     checkpoint: Optional[Callable] = None) -> SimResult:
        """The faulted barrier campaign, vectorized: the fault realization
        is the heap oracle's own host-precomputed
        :class:`repro.fed.faults.FaultCampaign` (absolute-round-keyed, so
        chunking / kill-and-restore cannot move it), streamed into the
        faulted scan bodies as per-round xs."""
        fm = self.faults
        n = self.n
        rng = np.random.default_rng(self.seed)
        streams = campaign_streams(rng, rounds)
        if start_round >= rounds:
            return SimResult(state=state, traces={}, events=None,
                             summary={"rounds": 0.0,
                                      "wall_clock_s": float(clock0)})
        sync = self.rule.sync_requires_all
        reset_mode = fm.rejoin == "reset"
        fc = fm.draw_campaign(rounds, n, retries=sync)

        parts = []
        now = float(clock0)
        done = start_round
        while done < rounds:
            length = min(self.chunk, rounds - done)
            sl = slice(done, done + length)
            md = np.empty((length, n), np.float32)
            mu = np.empty((length, n), np.float32)
            for j in range(length):
                md[j], mu[j] = round_multipliers(
                    streams[done + j], self.downlink, self.uplink, n)
            crash_off = fc.crashed[sl] | fc.drop_down[sl]
            lostx = fc.drop_up[sl] | fc.corrupt[sl]
            with span(h, "vec.chunk", start_round=done, rounds=length):
                if sync:
                    fn = self._chunk_fn_sync_faulted(length, metric_fn)
                    state, ys = fn(state, jnp.asarray(md), jnp.asarray(mu),
                                   jnp.asarray(crash_off), jnp.asarray(lostx),
                                   jnp.asarray(fc.first_success[sl]),
                                   jnp.asarray(fc.up_attempts[sl]),
                                   jnp.asarray(fc.capped[sl]))
                else:
                    fn = self._chunk_fn_graceful_faulted(length, metric_fn,
                                                         reset_mode)
                    args = (jnp.asarray(md), jnp.asarray(mu),
                            jnp.asarray(crash_off), jnp.asarray(lostx))
                    if reset_mode:
                        args += (jnp.asarray(fc.rejoin[sl]),)
                    state, ys = fn(state, *args)
                part = jax.device_get(ys)          # ONE transfer per chunk
                parts.append(part)
            done += length
            if checkpoint is not None:
                now = float(self._seq_wall(part["round_t"], now)[-1])
                checkpoint(state, done, now)
        ys = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

        n_run = rounds - start_round
        wall = self._seq_wall(ys["round_t"], clock0)
        bcast = np.concatenate([[clock0], wall[:-1]])
        traces, summary = self._bill_round_bytes_faulted(
            ys, fc, sync, n_run, start_round, wall, bcast,
            wall_clock_s=float(wall[-1]))
        _obs_fed_metrics(h, traces, summary)
        _obs_fault_metrics(h, traces)
        return SimResult(state=state, traces=traces, events=None,
                         summary=summary)

    # ------------------------------------------------------------------
    # asynchronous pipelined rounds (DESIGN.md §14)
    # ------------------------------------------------------------------

    def _chunk_fn_async(self, length: int, metric_fn) -> Callable:
        """The async scan body: per-client clocks + the bounded in-flight
        ring live in the CARRY, rebased to the broadcast time every round
        so float32 stays sharp no matter how long the campaign runs; the
        scan emits per-round scalars only (``bcast_rel`` = how far the
        broadcast advanced, ``land_rel`` = when the round's own uploads
        finish, both relative — the host f64-cumsums absolute clocks).

        tau=0 parity is arithmetic, not coincidence: the gate is exactly
        the previous round's ``land_rel`` (so the emitted durations are
        the barrier scan's ``round_t`` sequence bit-for-bit), the
        busy-client branch never binds (a client frees before the round
        it gates completes), and the deficit ring does not exist — the
        engine call is the identical no-deficit jaxpr."""
        fn = self._compiled.get(("async", length, metric_fn))
        if fn is not None:
            return fn
        n, d = self.n, int(self.comp.spec.d)
        rule, schema = self.rule, self.schema
        x_bytes = X_BYTES_PER_COORD * d
        dense_up = float(wire.HEADER_BYTES + 4 * d)
        lat_d = float(self.downlink.latency_s)
        tau = int(self.tau)
        flush_rule = rule.pipeline_coin_flush
        neg_inf = jnp.float32(-jnp.inf)

        def body(carry, xs):
            if tau >= 1:
                st, free, ring_a, ring_floor, ring_m, flush = carry
            else:
                st, free, ring_a, ring_floor, flush = carry
            m_down, m_up = xs                          # (n,) f32 each
            key = st.key                               # pre-step key

            # broadcast gate: rounds <= t-1-tau (ring slot 0) + any
            # pending sync flush must have landed; rebase all clocks so
            # "0" is the new broadcast instant
            gate = jnp.maximum(ring_floor[0], flush)
            adv = jnp.maximum(gate, jnp.float32(0.0))
            free = free - adv
            ring_a = ring_a - adv
            ring_floor = ring_floor - adv
            flush = neg_inf

            if tau >= 1:
                in_flight = ring_a[1:] > 0.0           # (tau, n)
                deficit = jnp.sum(
                    jnp.where(in_flight[..., None], ring_m, 0.0),
                    axis=(0, 1)) / jnp.float32(n)
                new, info = self.method.step_full(st, None,
                                                  deficit=deficit)
            else:
                new, info = self.method.step_full(st, None)
            coin = info.coin if info.coin is not None \
                else jnp.zeros((), bool)
            present = info.present if info.present is not None \
                else jnp.ones((n,), bool)
            if rule.sync_requires_all and info.coin is not None:
                active = jnp.logical_or(present, coin)  # the flush round
            else:
                active = present
            if schema.static_count is None:
                counts = self._bound.round_wire_counts(key)
            else:
                counts = jnp.full((n,), schema.static_count, jnp.int32)
            counts = counts * active

            comp_b = schema.header_bytes \
                + schema.bytes_per_value * counts.astype(jnp.float32)
            up_b = jnp.where(coin, dense_up, comp_b) \
                * active.astype(jnp.float32)
            down_b = x_bytes * active.astype(jnp.float32)
            # a client starts once the broadcast reaches it AND it is
            # free; the not-busy branch is the barrier scan's delay
            # expression token for token (tau=0 bit parity)
            dd = self.downlink.latency_s \
                + down_b / self.downlink.bandwidth_Bps * m_down
            a_new = jnp.where(
                free > dd,
                free + self.compute_s + self.uplink.latency_s
                + up_b / self.uplink.bandwidth_Bps * m_up,
                self.downlink.latency_s
                + down_b / self.downlink.bandwidth_Bps * m_down
                + self.compute_s
                + self.uplink.latency_s
                + up_b / self.uplink.bandwidth_Bps * m_up)
            masked = jnp.where(active, a_new, -jnp.inf)
            n_active = jnp.sum(active.astype(jnp.int32))
            land = jnp.where(n_active > 0, jnp.max(masked), lat_d)
            free = jnp.where(active, a_new, free)

            pushed_a = jnp.concatenate([ring_a[1:], masked[None]], 0)
            pushed_f = jnp.concatenate([ring_floor[1:], land[None]], 0)
            if tau >= 1:
                rows = info.messages.dense().astype(jnp.float32)
                if self.sampled:
                    sel = self.substrate.round_cohort(key)
                    rows = jnp.zeros((n, d), jnp.float32).at[sel] \
                        .set(rows)
                pushed_m = jnp.concatenate([ring_m[1:], rows[None]], 0)
            if flush_rule:
                # sync coin: the reset g <- mean(h_sync) discards every
                # pre-coin in-flight message; the next broadcast waits
                # for all n dense uploads via the flush gate
                do_flush = coin
                flush = jnp.where(do_flush, land, neg_inf)
                ring_a = jnp.where(do_flush, neg_inf, pushed_a)
                ring_floor = jnp.where(do_flush, neg_inf, pushed_f)
                if tau >= 1:
                    ring_m = jnp.where(do_flush, jnp.float32(0.0),
                                       pushed_m)
            else:
                ring_a, ring_floor = pushed_a, pushed_f
                if tau >= 1:
                    ring_m = pushed_m

            ys = {"metric": metric_fn(new), "bits": new.bits_sent,
                  "coin": coin, "participants": n_active,
                  "counts_sum": jnp.sum(counts),
                  "bcast_rel": adv, "land_rel": land}
            if tau >= 1:
                out = (new, free, ring_a, ring_floor, ring_m, flush)
            else:
                out = (new, free, ring_a, ring_floor, flush)
            return out, ys

        def scan_chunk(carry, m_down, m_up):
            return jax.lax.scan(body, carry, (m_down, m_up))

        fn = jax.jit(scan_chunk)
        self._compiled[("async", length, metric_fn)] = fn
        return fn

    def _chunk_fn_async_slab(self, length: int, metric_fn) -> Callable:
        """Async scan body over the chunk slab (DESIGN.md §16): the
        MethodState carries the (U_pad, d) slab, and the in-flight message
        ring references SLAB ROWS — a (tau, C, d) ring of raw cohort
        messages plus a (tau, C) ring of their global ids — instead of the
        scatter store's (tau, n, d) dense ring.  The deficit is computed
        by scattering each ring slot back into a transient (n, d) zeros
        buffer (exact placement, no arithmetic) and reusing the scatter
        body's masked-sum expression VERBATIM: summing the gathered
        (tau, C, d) rows directly is NOT bit-safe (XLA CPU's strided
        multi-accumulator reduction makes the result depend on element
        position), so the transient rebuild is the price of bit-identity;
        it is a temp, not a carry, and exists only at tau >= 1.  The
        per-client clocks (``free``, the (tau+1, n) arrival ring) stay
        n-shaped — O(n) floats, not O(n·d) — with cohort updates
        scattered at ``sel``, which is elementwise-identical to the
        scatter body's where(active, ...) forms."""
        fn = self._compiled.get(("slab-async", length, metric_fn))
        if fn is not None:
            return fn
        n, d = self.n, int(self.comp.spec.d)
        c = int(self.substrate.c)
        schema = self.schema
        x_bytes = X_BYTES_PER_COORD * d
        dense_up = float(wire.HEADER_BYTES + 4 * d)
        tau = int(self.tau)
        neg_inf = jnp.float32(-jnp.inf)
        # sampled substrates reject sync_requires_all rules, so the slab
        # body never sees a coin flush (marina's pipeline_coin_flush)
        assert not self.rule.pipeline_coin_flush

        def body(carry, xs):
            if tau >= 1:
                st, free, ring_a, ring_floor, ring_m, ring_sel, flush = \
                    carry
            else:
                st, free, ring_a, ring_floor, flush = carry
            m_down_c, m_up_c, sel, loc = xs     # (C,) f32 f32 i32 i32
            key = st.key                        # pre-step key

            gate = jnp.maximum(ring_floor[0], flush)
            adv = jnp.maximum(gate, jnp.float32(0.0))
            free = free - adv
            ring_a = ring_a - adv
            ring_floor = ring_floor - adv
            flush = neg_inf

            if tau >= 1:
                in_flight = ring_a[1:] > 0.0    # (tau, n)
                ring_full = jax.vmap(
                    lambda s, r: jnp.zeros((n, d), jnp.float32)
                    .at[s].set(r))(ring_sel, ring_m)
                deficit = jnp.sum(
                    jnp.where(in_flight[..., None], ring_full, 0.0),
                    axis=(0, 1)) / jnp.float32(n)
                new, info = self.method.step_full(
                    st, None, deficit=deficit, window=(sel, loc))
            else:
                new, info = self.method.step_full(st, None,
                                                  window=(sel, loc))
            coin = info.coin if info.coin is not None \
                else jnp.zeros((), bool)
            if schema.static_count is None:
                counts = self._bound.cohort_counts(key)          # (C,)
            else:
                counts = jnp.full((c,), schema.static_count, jnp.int32)
            comp_b = schema.header_bytes \
                + schema.bytes_per_value * counts.astype(jnp.float32)
            up_b = jnp.where(coin, dense_up, comp_b)
            free_c = free[sel]
            dd = self.downlink.latency_s \
                + x_bytes / self.downlink.bandwidth_Bps * m_down_c
            a_new = jnp.where(
                free_c > dd,
                free_c + self.compute_s + self.uplink.latency_s
                + up_b / self.uplink.bandwidth_Bps * m_up_c,
                self.downlink.latency_s
                + x_bytes / self.downlink.bandwidth_Bps * m_down_c
                + self.compute_s
                + self.uplink.latency_s
                + up_b / self.uplink.bandwidth_Bps * m_up_c)
            masked = jnp.full((n,), -jnp.inf, jnp.float32).at[sel] \
                .set(a_new)
            land = jnp.max(a_new)               # C >= 1 active clients
            free = free.at[sel].set(a_new)

            ring_a = jnp.concatenate([ring_a[1:], masked[None]], 0)
            ring_floor = jnp.concatenate([ring_floor[1:], land[None]], 0)
            if tau >= 1:
                rows = info.messages.dense().astype(jnp.float32)  # (C, d)
                ring_m = jnp.concatenate([ring_m[1:], rows[None]], 0)
                ring_sel = jnp.concatenate([ring_sel[1:], sel[None]], 0)

            ys = {"metric": metric_fn(new), "bits": new.bits_sent,
                  "coin": coin, "participants": jnp.full((), c, jnp.int32),
                  "counts_sum": jnp.sum(counts),
                  "bcast_rel": adv, "land_rel": land}
            if tau >= 1:
                out = (new, free, ring_a, ring_floor, ring_m, ring_sel,
                       flush)
            else:
                out = (new, free, ring_a, ring_floor, flush)
            return out, ys

        def scan_chunk(carry, m_down_c, m_up_c, sels, locs):
            return jax.lax.scan(body, carry, (m_down_c, m_up_c, sels, locs))

        fn = jax.jit(scan_chunk)
        self._compiled[("slab-async", length, metric_fn)] = fn
        return fn

    def _run_async(self, state, rounds: int, metric_fn, h) -> SimResult:
        n, d = self.n, int(self.comp.spec.d)
        tau = int(self.tau)
        rng = np.random.default_rng(self.seed)
        streams = campaign_streams(rng, rounds)

        free = jnp.zeros((n,), jnp.float32)
        ring_a = jnp.full((tau + 1, n), -jnp.inf, jnp.float32)
        ring_floor = jnp.full((tau + 1,), -jnp.inf, jnp.float32)
        flush = jnp.float32(-jnp.inf)
        if tau >= 1:
            if self.slab:
                # slab-row message ring: raw (C, d) cohort rows + their
                # global ids; zeros scatter to zeros, matching the dense
                # ring's zeros init bit for bit
                c = int(self.substrate.c)
                ring_m = jnp.zeros((tau, c, d), jnp.float32)
                ring_sel = jnp.zeros((tau, c), jnp.int32)
            else:
                ring_m = jnp.zeros((tau, n, d), jnp.float32)

        parts = []
        done = 0
        while done < rounds:
            length = min(self.chunk, rounds - done)
            md = np.empty((length, n), np.float32)
            mu = np.empty((length, n), np.float32)
            for j in range(length):
                md[j], mu[j] = round_multipliers(
                    streams[done + j], self.downlink, self.uplink, n)
            with span(h, "vec.chunk", start_round=done, rounds=length):
                if self.slab:
                    sels, uniq, loc, md_c, mu_c = self._slab_chunk_xs(
                        state, length, md, mu)
                    st, full_h, full_g = self._slab_enter(state, uniq, h)
                    if tau >= 1:
                        carry = (st, free, ring_a, ring_floor, ring_m,
                                 ring_sel, flush)
                    else:
                        carry = (st, free, ring_a, ring_floor, flush)
                    carry, ys = self._chunk_fn_async_slab(length, metric_fn)(
                        carry, jnp.asarray(md_c), jnp.asarray(mu_c),
                        jnp.asarray(sels), jnp.asarray(loc))
                    if tau >= 1:
                        st, free, ring_a, ring_floor, ring_m, ring_sel, \
                            flush = carry
                    else:
                        st, free, ring_a, ring_floor, flush = carry
                    state = self._slab_exit(st, uniq, full_h, full_g, h)
                else:
                    if tau >= 1:
                        carry = (state, free, ring_a, ring_floor, ring_m,
                                 flush)
                    else:
                        carry = (state, free, ring_a, ring_floor, flush)
                    carry, ys = self._chunk_fn_async(length, metric_fn)(
                        carry, jnp.asarray(md), jnp.asarray(mu))
                    if tau >= 1:
                        state, free, ring_a, ring_floor, ring_m, flush = carry
                    else:
                        state, free, ring_a, ring_floor, flush = carry
                parts.append(jax.device_get(ys))   # ONE transfer per chunk
            done += length
        ys = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

        # absolute clocks: broadcast times are the f64 cumsum of the
        # per-round advances; a round's own uploads land land_rel later.
        # (At tau=0 bcast_rel[t] == land_rel[t-1] exactly, so sim_wall_
        # clock reproduces the barrier's cumsum bit for bit.)
        bcast = np.cumsum(ys["bcast_rel"].astype(np.float64))
        wall = bcast + ys["land_rel"].astype(np.float64)
        traces, summary = self._bill_round_bytes(
            ys, rounds, wall, bcast, wall_clock_s=float(wall.max()))
        summary["tau"] = float(tau)
        _obs_fed_metrics(h, traces, summary)
        return SimResult(state=state, traces=traces, events=None,
                         summary=summary)
