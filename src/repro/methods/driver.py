"""The compiled experiment driver (DESIGN.md §10).

Every run loop in the repo is a caller of this module: ``run(method, state,
rounds, ...)`` executes rounds in chunked ``jax.lax.scan`` segments whose
carry is donated back to XLA (``jax.jit(..., donate_argnums=(0,))``), so the
h/g/opt buffers of long runs never double-allocate; data is drawn *inside*
the scan via ``data_fn(key, t)`` (no per-step host round-trip); metrics
stream out as a NAMED dict trace per chunk; and a checkpoint hook fires
between chunks for resumable runs.

Key contracts:

* **Chunking is invisible**: the step sequence of a chunked run is the step
  sequence of one monolithic scan (the method's RNG lives in its state), so
  ``chunk`` is a compile-time/memory knob, never a semantics knob.
* **Data keys are stateless**: the per-round data key is
  ``fold_in(data_key, state.t)`` — no key chain in the carry — so a
  checkpoint-restored run regenerates the SAME data stream as an
  uninterrupted one (resume bit-identity, tested in tests/test_driver.py).
* **Donation is safe**: the caller's input state is defensively copied
  before the first donating call; only driver-internal carries are donated.
  A caller that drops its state (``run(..., donate_input=True)``) skips the
  copy, which would otherwise hold the state twice in device memory.
  On backends without donation support (CPU) donation is auto-disabled.
* ``sweep(method_fn, values, state, rounds, ...)`` vmaps the chunk runner
  over a hyperparameter axis (the Appendix-A powers-of-two stepsize tunes):
  G methods compile ONCE and run as one batched scan.

``method`` may be a :class:`repro.methods.Method` or a bare
``step(state, data) -> state`` callable; any state NamedTuple works —
``bits_sent`` is traced when present, and ``state.t`` (when present) indexes
the data stream, falling back to the driver's own round counter.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.handle import maybe as _obs_scope
from repro.obs.handle import span

PyTree = Any
MetricFn = Callable[[Any, Any], jax.Array]       # (state, data) -> scalar

#: default scan-segment length; a pure compile-time/memory knob
DEFAULT_CHUNK = 128


def default_host_traces() -> bool:
    """Whether chunk traces should leave the device as they stream: on CPU
    a device_get is a free memcpy and moves trace assembly off the XLA
    dispatch path; on accelerators keeping traces device-side preserves
    the asynchronous chunk chain.  ONE policy for Driver and sweep."""
    return jax.default_backend() == "cpu"


def _resolve_step(method) -> Callable:
    return method.step if hasattr(method, "step") else method


def _round_index(state, i):
    """The global round counter: ``state.t`` when the state carries one
    (survives checkpoint-resume), else the driver's own per-run counter."""
    t = getattr(state, "t", None)
    return i if t is None else t


def _scan_chunk(step, data_fn, data, metrics: Dict[str, MetricFn],
                metric_every: int, length: int, carry, data_key):
    """One donated scan segment: carry = (state, i0, last-metric dict)."""

    def body(c, j):
        st, i0, last = c
        # pre-step global round index: drives BOTH the data key and the
        # metric cadence, so a resumed run draws the same batches and
        # evaluates metrics at the same global rounds as an uninterrupted
        # one (the held value between evaluations restarts at 0 per run()
        # call — metric_every=1, the default, holds nothing)
        t = _round_index(st, i0 + j)
        with jax.named_scope("driver.data"):
            d = data if data_fn is None else \
                data_fn(jax.random.fold_in(data_key, t), t)
        new = step(st, d)
        vals = {}
        with jax.named_scope("driver.metrics"):
            for name, fn in metrics.items():
                if metric_every > 1:
                    vals[name] = jax.lax.cond(t % metric_every == 0,
                                              lambda _: fn(new, d),
                                              lambda _: last[name], None)
                else:
                    vals[name] = fn(new, d)
        out = dict(vals)
        bits = getattr(new, "bits_sent", None)
        if bits is not None:
            out["bits_sent"] = bits
        return (new, i0, vals), out

    (state, i0, last), traces = jax.lax.scan(body, carry,
                                             jnp.arange(length, dtype=jnp.int32))
    return (state, i0 + length, last), traces


def _metric_zeros(metrics: Dict[str, MetricFn], state, data_template,
                  batch_shape: Tuple[int, ...] = ()):
    """Initial "last evaluated value" per metric (matches the engine's
    seed-era m0 = zeros contract for metric_every > 1)."""
    out = {}
    for name, fn in metrics.items():
        s = jax.eval_shape(fn, state, data_template)
        out[name] = jnp.zeros(batch_shape + s.shape, s.dtype)
    return out


def _data_template(data_fn, data, data_key):
    if data_fn is None:
        return data
    return jax.eval_shape(data_fn, data_key,
                          jax.ShapeDtypeStruct((), jnp.int32))


def _fetch(h, traces):
    """One chunk's traces to the host, as the ``driver.fetch`` span."""
    with span(h, "driver.fetch"):
        return jax.device_get(traces)


def _empty_traces(metrics, state, data_template, bits: bool):
    tr = {name: jnp.zeros((0,) + s.shape, s.dtype)
          for name, s in ((n, jax.eval_shape(f, state, data_template))
                          for n, f in metrics.items())}
    if bits:
        tr["bits_sent"] = jnp.zeros((0,), jnp.float32)
    return tr


class Driver:
    """Reusable compiled runner for one (method, data, metrics) config.

    ``Driver(method, ...).run(state, rounds)`` keeps the jitted chunk
    functions cached across calls, so repeated runs (resumed runs, repeated
    experiments) recompile nothing.
    """

    def __init__(self, method, *, data_fn=None, data=None,
                 metrics: Optional[Dict[str, MetricFn]] = None,
                 metric_every: int = 1, chunk: Optional[int] = None,
                 donate: Optional[bool] = None,
                 host_traces: Optional[bool] = None):
        if data_fn is not None and data is not None:
            raise ValueError("pass data_fn (in-jit) OR data (static), "
                             "not both")
        self.step = _resolve_step(method)
        self.data_fn = data_fn
        self.data = data
        self.metrics = dict(metrics or {})
        self.metric_every = int(metric_every)
        self.chunk = chunk
        if donate is None:
            # donation is unimplemented on CPU (jax warns and ignores it)
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        if host_traces is None:
            host_traces = default_host_traces()
        self.host_traces = bool(host_traces)
        self._compiled: Dict[int, Callable] = {}

    def _chunk_fn(self, length: int) -> Callable:
        fn = self._compiled.get(length)
        if fn is None:
            def run_chunk(carry, data_key):
                return _scan_chunk(self.step, self.data_fn, self.data,
                                   self.metrics, self.metric_every, length,
                                   carry, data_key)
            fn = jax.jit(run_chunk,
                         donate_argnums=(0,) if self.donate else ())
            self._compiled[length] = fn
        return fn

    def run(self, state, rounds: int, *, data_key: Optional[jax.Array] = None,
            checkpoint: Optional[Callable] = None,
            checkpoint_every: int = 1, obs=None,
            donate_input: bool = False):
        """Drive ``rounds`` rounds; returns ``(final_state, traces)`` with
        ``traces`` a dict of length-``rounds`` arrays (named metrics plus
        ``bits_sent`` when the state carries it).

        ``checkpoint(state, rounds_done, chunk_traces)`` fires after every
        ``checkpoint_every``-th chunk and after the final one.  ``obs`` is
        an optional :class:`repro.obs.Obs` handle: HOST-track wall spans
        of the run's set-up and of each chunk's dispatch, trace fetch and
        hook call, and compile spans — recorded between chunks, never inside traced code.  The
        same spans reach a running profiler as ``repro.driver.*``
        annotations, with or without ``obs``.  ``donate_input=True``
        hands the caller's ``state`` buffers to the first chunk: the caller
        must not read them afterwards.
        """
        if self.data_fn is not None and data_key is None:
            raise ValueError("data_fn requires an explicit data_key")
        if data_key is None:
            data_key = jax.random.PRNGKey(0)        # unused
        with span(obs, "driver.prepare"):
            template = _data_template(self.data_fn, self.data, data_key)
            if rounds <= 0:
                return state, _empty_traces(
                    self.metrics, state, template,
                    bits=hasattr(state, "bits_sent"))
            if self.donate and not donate_input:
                # the first donating call would invalidate the caller's
                # buffers
                state = jax.tree_util.tree_map(jnp.copy, state)
            carry = (state, jnp.zeros((), jnp.int32),
                     _metric_zeros(self.metrics, state, template))
        chunk = self.chunk or min(rounds, DEFAULT_CHUNK)
        done, n_chunk, parts = 0, 0, []
        with _obs_scope(obs) as h:
            while done < rounds:
                length = min(chunk, rounds - done)
                with span(h, "driver.dispatch", start_round=done,
                          rounds=length):
                    carry, tr = self._chunk_fn(length)(carry, data_key)
                done += length
                n_chunk += 1
                # one transfer per chunk (CPU default): the traces leave
                # the device as they stream, so finishing a run never
                # dispatches a many-operand XLA concatenate over live
                # chunk buffers
                parts.append(_fetch(h, tr) if self.host_traces else tr)
                if checkpoint is not None and \
                        (done >= rounds or n_chunk % checkpoint_every == 0):
                    with span(h, "driver.checkpoint", rounds_done=done):
                        checkpoint(carry[0], done, tr)
        cat = np.concatenate if self.host_traces else jnp.concatenate
        traces = {k: cat([p[k] for p in parts]) for k in parts[0]}
        return carry[0], traces


def run(method, state, rounds: int, *, data_fn=None, data=None,
        data_key=None, metrics=None, metric_every: int = 1,
        chunk: Optional[int] = None, checkpoint=None,
        checkpoint_every: int = 1, donate: Optional[bool] = None):
    """One-shot convenience over :class:`Driver` (see its docs)."""
    drv = Driver(method, data_fn=data_fn, data=data, metrics=metrics,
                 metric_every=metric_every, chunk=chunk, donate=donate)
    return drv.run(state, rounds, data_key=data_key, checkpoint=checkpoint,
                   checkpoint_every=checkpoint_every)


# ---------------------------------------------------------------------------
# vmapped hyperparameter sweeps (Appendix A stepsize tunes)
# ---------------------------------------------------------------------------

class Sweeper:
    """Reusable vmapped-sweep runner for one ``method_fn`` config.

    Like :class:`Driver`, the jitted chunk functions are cached on the
    instance, so repeated ``.run()`` calls (re-tunes, timing reps) compile
    nothing after the first.  The one-shot :func:`sweep` used to rebuild
    the jit per invocation — a fresh-closure recompile per call that the
    recompile sentinels (``repro.analysis.recompile``) now flag.
    """

    def __init__(self, method_fn, *, data_fn=None, data=None,
                 metrics: Optional[Dict[str, MetricFn]] = None,
                 metric_every: int = 1, chunk: Optional[int] = None,
                 donate: Optional[bool] = None,
                 host_traces: Optional[bool] = None):
        if data_fn is not None and data is not None:
            raise ValueError("pass data_fn (in-jit) OR data (static), "
                             "not both")
        self.method_fn = method_fn
        self.data_fn = data_fn
        self.data = data
        self.metrics = dict(metrics or {})
        self.metric_every = int(metric_every)
        self.chunk = chunk
        if donate is None:
            # donation is unimplemented on CPU (jax warns and ignores it)
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        if host_traces is None:
            host_traces = default_host_traces()
        self.host_traces = bool(host_traces)
        self._compiled: Dict[int, Callable] = {}

    def _chunk_fn(self, length: int) -> Callable:
        fn = self._compiled.get(length)
        if fn is None:
            def vrun(vals, carry, dk):
                def one(v, c):
                    step = _resolve_step(self.method_fn(v))
                    return _scan_chunk(step, self.data_fn, self.data,
                                       self.metrics, self.metric_every,
                                       length, c, dk)
                return jax.vmap(one)(vals, carry)
            fn = jax.jit(vrun, donate_argnums=(1,) if self.donate else ())
            self._compiled[length] = fn
        return fn

    def run(self, values, state, rounds: int, *,
            data_key: Optional[jax.Array] = None, obs=None):
        """Run ``rounds`` rounds of every lane; returns ``(final_states,
        traces)`` with a leading (G,) axis on every state leaf and
        (G, rounds) traces.  ``obs`` as in :meth:`Driver.run`."""
        values = jax.tree_util.tree_map(jnp.asarray, values)
        leaves = jax.tree_util.tree_leaves(values)
        if not leaves:
            raise ValueError("sweep needs at least one value axis")
        G = leaves[0].shape[0]
        if self.data_fn is not None and data_key is None:
            raise ValueError("data_fn requires an explicit data_key")
        if data_key is None:
            data_key = jax.random.PRNGKey(0)        # unused
        with span(obs, "driver.prepare"):
            template = _data_template(self.data_fn, self.data, data_key)
            stacked = jax.tree_util.tree_map(
                lambda l: jnp.tile(l, (G,) + (1,) * jnp.ndim(l)), state)
            carry = (stacked, jnp.zeros((G,), jnp.int32),
                     _metric_zeros(self.metrics, state, template,
                                   batch_shape=(G,)))
        chunk = self.chunk or min(rounds, DEFAULT_CHUNK)
        done, parts = 0, []
        with _obs_scope(obs) as h:
            while done < rounds:
                length = min(chunk, rounds - done)
                with span(h, "driver.dispatch", start_round=done,
                          rounds=length, lanes=G):
                    carry, tr = self._chunk_fn(length)(values, carry,
                                                       data_key)
                done += length
                parts.append(_fetch(h, tr) if self.host_traces else tr)
        cat = np.concatenate if self.host_traces else jnp.concatenate
        traces = {k: cat([p[k] for p in parts], axis=1)
                  for k in parts[0]} if parts else {}
        return carry[0], traces


def sweep(method_fn, values, state, rounds: int, *, data_fn=None, data=None,
          data_key=None, metrics: Optional[Dict[str, MetricFn]] = None,
          metric_every: int = 1, chunk: Optional[int] = None,
          donate: Optional[bool] = None,
          host_traces: Optional[bool] = None):
    """Vmap the chunked driver over a hyperparameter axis (one-shot
    convenience over :class:`Sweeper` — hold a Sweeper instead when you
    will run the same sweep more than once, so the chunk jits are reused).

    ``method_fn(value) -> Method`` is traced ONCE with a batched tracer for
    ``value`` — the value must only enter arithmetic (a stepsize, a momentum
    b), never Python control flow.  ``values`` is an array or a pytree of
    same-length arrays (e.g. ``{"gamma": ..., "b": ...}``); ``state`` is one
    init state, broadcast across the G lanes (every lane starts from the
    same iterate and RNG key, the paper's tuning protocol — lane j of the
    result is bit-equal to a sequential run at ``values[j]``).

    Returns ``(final_states, traces)`` with a leading (G,) axis on every
    state leaf and (G, rounds) traces.
    """
    sw = Sweeper(method_fn, data_fn=data_fn, data=data, metrics=metrics,
                 metric_every=metric_every, chunk=chunk, donate=donate,
                 host_traces=host_traces)
    return sw.run(values, state, rounds, data_key=data_key)
