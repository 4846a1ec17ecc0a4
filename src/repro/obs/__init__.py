"""Unified observability: metrics registry + pipeline trace + reconcile.

One :class:`Observability` object rides through a run — handed to
``build_pipeline(obs=)`` / ``build_serving(obs=)`` /
``ContinuousBatchingSession(obs=)`` / ``TrainDriver(obs=)`` — and every
execution layer reports into it through two narrow verbs:

  * :meth:`Observability.on_round` — the serving engine calls this once
    per executed schedule round (decode / verify / admit / prefill)
    with the host wall interval; it feeds the ``round_seconds`` /
    ``rounds_total`` / ``bucket_rounds_total`` registry series and,
    when tracing, synthesizes the per-tick Perfetto spans from the
    round's schedule table (:mod:`repro.obs.trace`).  The training
    driver feeds the same series through
    :meth:`Observability.count_round` and leaves its trace to the
    profiler;
  * plain registry access (:meth:`counter` / :meth:`gauge` /
    :meth:`histogram` / :meth:`timer`) for everything that is not a
    table walk — allocator occupancy, batcher goodput, launcher phase
    timing.

``obs=None`` everywhere means "off" with zero overhead: call sites
guard with ``if obs is not None``.  :mod:`repro.obs.reconcile` closes
the loop, turning the collected series back into planner inputs.

The names the program gives its own work in a ``jax.profiler`` trace
live here too, so that code, tests and documents quote one set of
strings:

  * device scopes (``jax.named_scope``; they reach each HLO op's
    ``op_name`` metadata and survive autodiff): the phases of a
    training step, :data:`PHASES`, the stage-to-stage transfers,
    :data:`SEND`, and the sequence mixers' cores, :data:`MIXERS`;
  * host spans (:func:`span`, a ``jax.profiler.TraceAnnotation``, on
    the device trace's clock and below a microsecond when no profiler
    runs): one training round, :data:`TRAIN_SPANS`, and the loader's
    steps, :data:`LOADER_SPANS`.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional

from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.reconcile import ReconcileReport, reconcile, stage_seconds
from repro.obs.trace import RoundRecord, TraceRecorder

__all__ = ["Counter", "Gauge", "Histogram", "Observability",
           "ReconcileReport", "Registry", "RoundRecord", "TraceRecorder",
           "reconcile", "scoped", "span", "stage_seconds"]

# ---- device scopes -----------------------------------------------------
EMBED = "embed"            # token embedding forward, backward and update
FWD = "fwd"                # a stage's forward in one tick (F phase)
HEAD = "head"              # final norm, LM head, loss and their gradients
BWD = "bwd"                # a stage's recompute, backward, accumulation
OPTIMIZER = "optimizer"    # optimizer updates and the gradient mean
SEND = "send"              # stage-to-stage ppermutes (inside fwd / bwd)
ATTENTION = "attention"    # attention core: QK^T, softmax, PV
WKV = "wkv"                # RWKV-6 WKV scan
PHASES = (EMBED, FWD, HEAD, BWD, OPTIMIZER)
MIXERS = (ATTENTION, WKV)

# ---- host spans -------------------------------------------------------
TRAIN_ROUND = "train.round"          # one round of TrainDriver.run
TRAIN_LOAD = "train.load"            # loader.get
TRAIN_DISPATCH = "train.dispatch"    # the jitted step, until it returns
TRAIN_WAIT = "train.wait"            # block_until_ready on the loss
TRAIN_READBACK = "train.readback"    # the metrics to Python floats
TRAIN_CHECKPOINT = "train.checkpoint"
LOADER_DRAW = "loader.draw"          # the host batch
LOADER_PLACE = "loader.place"        # host batch -> device arrays
TRAIN_SPANS = (TRAIN_ROUND, TRAIN_LOAD, TRAIN_DISPATCH, TRAIN_WAIT,
               TRAIN_READBACK, TRAIN_CHECKPOINT)
LOADER_SPANS = (LOADER_DRAW, LOADER_PLACE)


def span(name: str):
    """A host span in the profiler's trace (``with span(TRAIN_LOAD):``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class Observability:
    """Registry + optional trace recorder + the clock that stamps both.

    ``clock`` defaults to ``time.perf_counter``; analytic benchmarks
    pass their modeled clock so spans and histograms carry modeled
    seconds through the identical code path (``scripts/obs_smoke.py``
    leans on this for its exact-ratio assertion).
    """

    def __init__(self, registry: Optional[Registry] = None,
                 trace=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.registry = registry if registry is not None else Registry()
        if trace is True:
            trace = TraceRecorder()
        self.trace: Optional[TraceRecorder] = trace or None
        self.clock = clock

    # ---- registry passthrough --------------------------------------------

    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str) -> Histogram:
        return self.registry.histogram(name)

    def timer(self, name: str, **labels):
        """Phase timer on this object's clock (see ``Registry.timer``)."""
        return self.registry.timer(name, clock=self.clock, **labels)

    # ---- execution-layer verbs -------------------------------------------

    def on_round(self, kind: str, sched, t0: float, t1: float, *,
                 bucket: Optional[int] = None,
                 t_fwd=1.0, t_bwd=1.0) -> None:
        """One executed schedule round: ``[t0, t1)`` seconds over
        ``sched``'s table.  ``bucket`` tags bucketed serving rounds with
        the lattice size actually run."""
        self.count_round(kind, t0, t1, bucket=bucket)
        if self.trace is not None:
            self.trace.record_round(kind, sched, t0, t1, bucket=bucket,
                                    t_fwd=t_fwd, t_bwd=t_bwd)

    def count_round(self, kind: str, t0: float, t1: float, *,
                    bucket: Optional[int] = None) -> None:
        """The registry half of :meth:`on_round`, with no trace: training
        rounds are traced by the profiler (host spans, device scopes)."""
        dt = max(float(t1 - t0), 0.0)
        self.registry.histogram("round_seconds").observe(dt, kind=kind)
        self.registry.counter("rounds_total").inc(kind=kind)
        if bucket is not None:
            self.registry.counter("bucket_rounds_total").inc(
                kind=kind, bucket=bucket)

    def page_gauges(self, alloc, *,
                    queue_depth: Optional[int] = None) -> None:
        """Snapshot a ``PageAllocator``'s occupancy (and, when given,
        the admission queue depth behind it)."""
        self.registry.gauge("pages_in_use").set(alloc.live_pages)
        self.registry.gauge("pages_free").set(alloc.free_pages)
        if queue_depth is not None:
            self.registry.gauge("admit_queue_depth").set(queue_depth)

    # ---- output -----------------------------------------------------------

    def save(self, *, trace_out: Optional[str] = None,
             metrics_out: Optional[str] = None) -> None:
        if trace_out and self.trace is not None:
            self.trace.save(trace_out)
        if metrics_out:
            self.registry.save(metrics_out)
