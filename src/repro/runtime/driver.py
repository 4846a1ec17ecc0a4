"""Fault-tolerant training driver.

Responsibilities at fleet scale (DESIGN.md §10):
  * periodic per-stage checkpointing (paper §4) + restart from the last
    round checkpointed by *all* stages;
  * failure handling — any exception in a round triggers restore + replay
    (data is deterministic in step, so replayed rounds are identical);
  * elastic scaling — on a world-size change, re-run the partitioner for
    the new machine count, re-group the stage-stacked parameters
    (checkpoint.reshard_stages), and continue;
  * straggler mitigation — measured per-stage tick times feed the
    rectangular partitioner, which proposes a rebalanced (pp, tp) plan
    (the paper's answer to skew: better partitioning, not work stealing).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro import obs as obs_lib
from repro.checkpoint.manager import CheckpointManager, reshard_stages
from repro.core import profiler as prof
from repro.core.partitioner import PlanChoice, plan_search


@dataclasses.dataclass
class DriverConfig:
    checkpoint_every: int = 10
    max_restarts: int = 3
    keep_last: int = 3


class TrainDriver:
    def __init__(self, bundle, loader, ckpt_dir: str,
                 cfg: DriverConfig = DriverConfig(),
                 failure_hook: Optional[Callable[[int], None]] = None,
                 obs=None,
                 stage_seconds_fn: Optional[Callable[[int], Any]] = None):
        self.bundle = bundle
        self.loader = loader
        self.cfg = cfg
        self.ckpt = CheckpointManager(ckpt_dir)
        self.failure_hook = failure_hook or (lambda step: None)
        # observability (repro.obs.Observability): each executed round
        # feeds round_seconds / rounds_total{kind=train}.  The SPMD step
        # is one fused device program, so the host cannot time stages
        # individually; stage_seconds_fn (step -> per-stage seconds,
        # e.g. from a profiler hook or a straggler harness) feeds the
        # stage_round_seconds{stage=} histograms that
        # replan_from_registry re-plans from.  Where the round's time
        # goes is named in the profiler's own trace: the host spans
        # below (repro.obs.TRAIN_SPANS) and the step's device scopes.
        self.obs = obs if obs is not None else getattr(bundle, "obs", None)
        self.stage_seconds_fn = stage_seconds_fn
        self._jit_step = jax.jit(
            bundle.train_step,
            in_shardings=(bundle.state_shardings(), bundle.batch_shardings()),
            out_shardings=(bundle.state_shardings(), None),
            donate_argnums=0)
        self.metrics_log: List[Dict[str, float]] = []
        self.stage_times: List[float] = []
        # the fault behind every restore-and-replay this driver has done
        # — a run that must not hide faults checks ``restarts``
        self.faults: List[str] = []

    # ---------------- main loop -------------------------------------------

    def run(self, state, n_rounds: int, start_step: int = 0):
        step = start_step
        restarts = 0
        while step < n_rounds:
            try:
                with obs_lib.span(obs_lib.TRAIN_ROUND):
                    state, step, saved = self._round(state, step)
                if saved:
                    # durable progress: a complete checkpoint resets the
                    # failure budget, so max_restarts bounds *consecutive*
                    # failures, not sporadic ones over a long run
                    restarts = 0
            except Exception as e:
                restarts += 1
                if restarts > self.cfg.max_restarts:
                    raise
                self.faults.append(f"step {step}: {type(e).__name__}: {e}")
                state, step = self.restore_latest(state)
        return state, step

    def _round(self, state, step: int):
        """One round under its host spans; returns (state, step + 1,
        whether a checkpoint was saved)."""
        self.failure_hook(step)          # may raise (simulated fault)
        with obs_lib.span(obs_lib.TRAIN_LOAD):
            batch = self.loader.get(step)
        clk = self.obs.clock if self.obs is not None else time.perf_counter
        t0 = clk()
        with obs_lib.span(obs_lib.TRAIN_DISPATCH):
            state, metrics = self._jit_step(state, batch)
        with obs_lib.span(obs_lib.TRAIN_WAIT):
            jax.block_until_ready(metrics["loss"])
        t1 = clk()
        self.stage_times.append(t1 - t0)
        if self.obs is not None:
            self.obs.count_round("train", t0, t1)
            if self.stage_seconds_fn is not None:
                hist = self.obs.histogram("stage_round_seconds")
                for s, sec in enumerate(self.stage_seconds_fn(step)):
                    hist.observe(float(sec), stage=s)
        with obs_lib.span(obs_lib.TRAIN_READBACK):
            self.metrics_log.append(
                {k: float(v) for k, v in metrics.items()})
        step += 1
        if step % self.cfg.checkpoint_every:
            return state, step, False
        with obs_lib.span(obs_lib.TRAIN_CHECKPOINT):
            self.ckpt.save(step, state, self.bundle.plan.pp)
        return state, step, True

    @property
    def restarts(self) -> int:
        return len(self.faults)

    def restore_latest(self, state_template):
        rnd = self.ckpt.latest_complete_round()
        if rnd is None:
            # no complete checkpoint: restart from scratch (round 0)
            st = jax.jit(self.bundle.init_state,
                         out_shardings=self.bundle.state_shardings())(
                jax.random.key(0))
            return st, 0
        host_template = jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype),
            jax.eval_shape(self.bundle.init_state, jax.random.key(0)))
        restored = self.ckpt.restore(rnd, host_template)
        sh = self.bundle.state_shardings()
        restored = jax.tree.map(jax.device_put, restored, sh)
        return restored, rnd


# --------------------------------------------------------------------------
# Elastic re-planning
# --------------------------------------------------------------------------

def elastic_replan(spec, old_plan, new_model_axis: int, hw=prof.TPU_V5E, *,
                   minibatch_tokens: int, data_replicas: int,
                   measured_stage_seconds=None, schedules=None,
                   hbm_bytes=None) -> Any:
    """Choose (pp, tp, schedule, virtual_stages) for a new model axis.

    Backed by :func:`~repro.core.partitioner.plan_search`: every
    candidate is scored by the simulated time-weighted round_time of its
    schedule tables and rejected when its MemoryModel exceeds the HBM
    budget — so a shrink event can re-pick the schedule too (e.g.
    stash → interleaved to trade the now-unaffordable version ring for
    bubble; the restart is a sync point, so the switch is semantically
    clean and ``reshard_state_for_plan`` regroups the chunks).

    ``measured_stage_seconds`` (per physical stage of ``old_plan``)
    calibrates the analytic profile before the search — see
    :func:`rebalance_from_measurements`.
    """
    choice = plan_choice(spec, old_plan, new_model_axis, hw,
                         minibatch_tokens=minibatch_tokens,
                         data_replicas=data_replicas,
                         measured_stage_seconds=measured_stage_seconds,
                         schedules=schedules, hbm_bytes=hbm_bytes)
    return choice.plan


def plan_choice(spec, old_plan, new_model_axis: int, hw=prof.TPU_V5E, *,
                minibatch_tokens: int, data_replicas: int,
                measured_stage_seconds=None, schedules=None,
                hbm_bytes=None) -> PlanChoice:
    """elastic_replan returning the full scored PlanChoice (round_time,
    bubble, MemoryModel) — what launch/train and launch/dryrun surface."""
    profiles = prof.profile_analytic(spec, hw,
                                     minibatch_tokens=minibatch_tokens)
    if measured_stage_seconds is not None:
        profiles = prof.scale_profiles_to_measurements(
            profiles, measured_stage_seconds, n_stages=old_plan.pp,
            virtual_stages=old_plan.virtual_stages)
    return plan_search(spec, old_plan, new_model_axis, hw,
                       minibatch_tokens=minibatch_tokens,
                       data_replicas=data_replicas, profiles=profiles,
                       schedules=schedules, hbm_bytes=hbm_bytes)


def plan_search_report(spec, base_plan, hw=prof.TPU_V5E, *, seq_len: int,
                       global_batch: int, data_replicas: int,
                       prefix: str = "", workload: str = "train",
                       sp: bool = False, weight_dtype=None,
                       kv_dtype=None) -> PlanChoice:
    """Shared launch-entry-point surface: search, print, return.

    Used by launch/train.py and launch/dryrun.py so the microbatch-token
    derivation and the printed summary stay in sync between them.
    ``workload`` follows :func:`~repro.core.partitioner.plan_search`:
    serving workloads derive per-microbatch tokens from the decode
    microbatch count (one query token per row when decoding) and budget
    the KV/SSM cache against the HBM alongside the weights.
    """
    dp = max(data_replicas, 1)
    if workload == "train":
        mb_tokens = seq_len * max(global_batch // dp
                                  // base_plan.microbatches, 1)
        choice = plan_choice(spec, base_plan, base_plan.pp * base_plan.tp,
                             hw, minibatch_tokens=mb_tokens,
                             data_replicas=data_replicas)
    else:
        from repro.core.schedule import fit_serving_microbatches
        R = fit_serving_microbatches(base_plan.decode_microbatches,
                                     global_batch, dp, sp=sp)
        rows = global_batch if sp else max(global_batch // dp // R, 1)
        mb_tokens = rows * (seq_len if workload == "prefill" else 1)
        choice = plan_search(spec, base_plan, base_plan.pp * base_plan.tp,
                             hw, minibatch_tokens=mb_tokens,
                             data_replicas=data_replicas,
                             workload=workload, cache_len=seq_len,
                             global_batch=global_batch, sp=sp,
                             weight_dtype=weight_dtype, kv_dtype=kv_dtype)
    print(f"{prefix}plan_search[{workload}]: {choice.describe()}")
    print(f"{prefix}  predicted {choice.memory}")
    return choice


def _storage_perms(plan):
    """(to_layer_major, from_layer_major) row-gather indices, or None.

    Interleaved storage row p = s·v + j holds model chunk j·S + s
    (schedule.storage_chunk_order); layer-major order is what
    ``reshard_stages`` regroups over.
    """
    if plan.virtual_stages == 1:
        return None
    order = np.asarray(plan.make_schedule().storage_chunk_order())
    return np.argsort(order), order


def _regroup_chunks(tree, old_plan, new_plan):
    """Stage-stacked leaves [old_chunks, ...] -> [new_chunks, ...].

    Goes through canonical layer-major chunk order: un-permute the
    interleaved storage order if the source is interleaved, regroup the
    stage boundaries, re-permute for an interleaved target.
    """
    old_chunks = old_plan.pp * old_plan.virtual_stages
    new_chunks = new_plan.pp * new_plan.virtual_stages
    src = _storage_perms(old_plan)
    if src is not None:
        tree = jax.tree.map(lambda a: a[src[0]], tree)
    tree = reshard_stages(tree, old_chunks, new_chunks)
    dst = _storage_perms(new_plan)
    if dst is not None:
        tree = jax.tree.map(lambda a: a[dst[1]], tree)
    return tree


def reshard_state_for_plan(state_host, spec, old_plan, new_plan):
    """Move a host-side checkpointed state to a new pipeline layout.

    Handles any (pp, virtual_stages) -> (pp', virtual_stages') move —
    parameters are keyed by global layer, so an interleaved source or
    target is a storage-order permutation around the same layer-major
    regroup.  Ring sizes and whether a stash ring exists at all come
    from the target plan's schedule (core/schedule.py) — a
    flush/interleaved target drops the ring, a 1F1B target rebuilds it
    at the new 2(S−1)+1 size from the current weights, and an
    async-interleaved target rebuilds the chunk-major per-chunk ring
    ([stash_slots, pp'·v', ...] over the regrouped storage rows) the
    same way (the restart is a sync point, so seeding every version
    with the live weights is exact).

    Serving plans ride the same path: the serving engine stores its
    weights (and caches) in the SAME chunk-major storage order as the
    training interleaved family, so a train checkpoint at (pp, v) is
    bit-identical under a serve plan at (pp, v) — the round-trip is the
    identity on parameters — and a serving state (no ``opt_stages`` /
    ``stash`` keys) regroups its parameters without growing them.  The
    per-slot ``pos``/``live`` vectors of a continuous-batching state
    are slot-major, not chunk-major: they pass through untouched while
    the cache rows permute, staying aligned with the (unchanged) slot
    axis — partially-filled states reshard exactly like full ones.
    """
    old_sched = old_plan.make_schedule()
    new_sched = new_plan.make_schedule()
    same_layout = (old_plan.virtual_stages == new_plan.virtual_stages
                   and old_plan.pp == new_plan.pp)
    has_rings = "stash" in state_host
    old_ring = old_sched.uses_stash_ring and has_rings
    new_ring = new_sched.uses_stash_ring and has_rings
    if same_layout and old_ring == new_ring \
            and (not new_ring
                 or old_sched.stash_slots == new_sched.stash_slots):
        return state_host
    # a schedule-only change at the same (pp, v) still falls through: the
    # state tree's stash ring must be dropped/rebuilt to the new schedule
    new_chunks = new_plan.pp * new_plan.virtual_stages
    new_stages = (state_host["params"]["stages"] if same_layout
                  else _regroup_chunks(state_host["params"]["stages"],
                                       old_plan, new_plan))
    import jax.numpy as jnp

    from repro.models.spec import stage_varying_scalars

    out = dict(state_host)
    params = dict(state_host["params"])
    params["stages"] = new_stages
    # windows/thetas re-derive from the spec (rows follow storage order)
    w, t = stage_varying_scalars(spec, new_chunks)
    w = jnp.asarray(w, jnp.int32)
    t = jnp.asarray(t, jnp.float32)
    dst = _storage_perms(new_plan)
    if dst is not None:
        w, t = w[dst[1]], t[dst[1]]
    params["layer_windows"] = w
    params["layer_thetas"] = t
    out["params"] = params
    # optimizer/stash state: re-group the same way (training states only —
    # a serving state carries neither)
    if "opt_stages" in state_host:
        out["opt_stages"] = {
            slot: (sub if same_layout
                   else _regroup_chunks(sub, old_plan, new_plan))
            for slot, sub in state_host["opt_stages"].items()}
    # a serving KV/SSM cache is chunk-stacked like the weights: permute
    # its rows through the same storage orders.  Across chunk *counts*
    # the per-row layer groups change and live recurrent state cannot be
    # re-cut — refuse loudly; the caller re-prefills after replanning.
    if "cache" in state_host:
        old_chunks = old_plan.pp * old_plan.virtual_stages
        if old_chunks != new_chunks:
            raise ValueError(
                "cannot reshard a serving KV/SSM cache across chunk "
                f"counts ({old_chunks} -> {new_chunks} storage rows): "
                "per-row layer groups change; re-prefill after "
                "replanning (params regroup fine — drop 'cache' from "
                "the state to move weights only)")
        src = _storage_perms(old_plan)
        dst = _storage_perms(new_plan)

        def _rows(a):
            if src is not None:
                a = a[src[0]]
            if dst is not None:
                a = a[dst[1]]
            return a

        out["cache"] = jax.tree.map(_rows, state_host["cache"])
        # the paged KV page pool is chunk-stacked exactly like the dense
        # cache: permute its leading rows the same way.  Page tables are
        # slot-major and shared across all paged layers — they pass
        # through untouched, like pos/live.
        if "pages" in state_host:
            out["pages"] = jax.tree.map(_rows, state_host["pages"])
    if has_rings:
        out["stash"] = {"current": new_stages}
        if new_sched.uses_stash_ring:
            out["stash"]["ring"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (new_sched.stash_slots,) + a.shape) + 0,
                new_stages)
    return out


# --------------------------------------------------------------------------
# Straggler mitigation: profile-guided rebalancing
# --------------------------------------------------------------------------

def rebalance_from_measurements(spec, plan, measured_stage_seconds,
                                hw=prof.TPU_V5E, *, minibatch_tokens: int,
                                data_replicas: int, slack: float = 1.25,
                                schedules=None, hbm_bytes=None):
    """If one stage is >slack× the median (straggler), propose a new plan.

    Returns (new_plan, rebalanced: bool).  The measured per-stage times
    are scaled into the analytic profile
    (profiler.scale_profiles_to_measurements) *before* the search — the
    replanner used to call the purely analytic profile and therefore
    proposed the same plan regardless of what was measured; now the DP
    sees the straggler's layers as genuinely slower, so deeper tp (or a
    different schedule) can shrink the straggling stage's work.
    """
    times = np.asarray(measured_stage_seconds, float)
    med = float(np.median(times))
    if med <= 0 or float(times.max()) <= slack * med:
        return plan, False
    new_plan = elastic_replan(spec, plan, plan.pp * plan.tp, hw,
                              minibatch_tokens=minibatch_tokens,
                              data_replicas=data_replicas,
                              measured_stage_seconds=measured_stage_seconds,
                              schedules=schedules, hbm_bytes=hbm_bytes)
    same = ((new_plan.pp, new_plan.tp, new_plan.virtual_stages)
            == (plan.pp, plan.tp, plan.virtual_stages)
            and new_plan.make_schedule().name == plan.make_schedule().name)
    if same and plan.pp > 1:
        # fall back: halve pipeline depth, double tensor parallelism —
        # but only if that plan would survive plan_search's own checks
        fb = plan.with_(pp=plan.pp // 2, tp=plan.tp * 2)
        if _plan_is_buildable(spec, fb, hw,
                              minibatch_tokens=minibatch_tokens,
                              data_replicas=data_replicas,
                              hbm_bytes=hbm_bytes):
            new_plan = fb
    return new_plan, True


def replan_from_registry(spec, plan, registry, hw=prof.TPU_V5E, *,
                         minibatch_tokens: int, data_replicas: int,
                         slack: float = 1.25, schedules=None,
                         hbm_bytes=None):
    """Rebalance off telemetry the run actually collected.

    Reads the per-stage mean wall seconds out of the metrics registry's
    ``stage_round_seconds{stage=}`` histograms (populated by
    :class:`TrainDriver` via its ``stage_seconds_fn`` hook, or by any
    executor timing its stages through ``Registry.timer``) and hands
    them to :func:`rebalance_from_measurements` — the end of the
    paper's profile→plan→measure→replan loop, with no hand-injected
    numbers between the measurement and the search.  Returns
    ``(new_plan, rebalanced)``; raises ``ValueError`` when any of
    ``plan.pp`` stages has no samples.
    """
    from repro.obs.reconcile import stage_seconds
    measured = stage_seconds(registry, plan.pp)
    return rebalance_from_measurements(
        spec, plan, measured, hw, minibatch_tokens=minibatch_tokens,
        data_replicas=data_replicas, slack=slack, schedules=schedules,
        hbm_bytes=hbm_bytes)


def _plan_is_buildable(spec, plan, hw, *, minibatch_tokens: int,
                       data_replicas: int, hbm_bytes=None) -> bool:
    """Structural + HBM feasibility, mirroring plan_search's filters."""
    n_chunks = plan.pp * plan.virtual_stages
    if spec.n_layers % n_chunks:
        return False
    if spec.n_heads and spec.n_heads % plan.tp:
        return False
    if plan.virtual_stages > 1 and plan.microbatches % plan.pp:
        return False
    try:
        spec.stage_program(n_chunks)
    except AssertionError:
        return False
    mm = plan.make_schedule().memory_model(
        spec, plan, hw, microbatch_tokens=minibatch_tokens,
        data_replicas=data_replicas)
    budget = hw.hbm_bytes if hbm_bytes is None else hbm_bytes
    return mm.fits(budget)
