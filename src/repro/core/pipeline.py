"""PipeDream pipelined training as one jit'd SPMD step (paper §3.3–3.5).

One ``train_step`` = one *round* of R microbatches through a pluggable
:class:`~repro.core.schedule.PipelineSchedule`.  The scan body is one
double-tick:

  F shard_map   every stage gathers its row of the schedule's forward
                table — (microbatch, local chunk, input source, stash
                slot, weight-version slot, residual slot) — forwards
                that chunk, records weights/residuals into the slots the
                table names, and ppermutes activations downstream.
  head/loss     (pjit level, vocab-sharded over the whole model axis)
                the microbatch the schedule's exit table names gets its
                loss and d(loss)/d(hidden); the owning stage starts its
                backward in the same tick — Figure 8's F(m),B(m)
                adjacency.
  B shard_map   every stage gathers its backward-table row, re-runs the
                chunk forward under jax.vjp with the *table-named*
                weight version and residual (stage-granular remat),
                psums/reduce-scatters stage grads over the replica axis
                (replicated stages, §3.2), and either applies its update
                immediately (asynchronous per-stage updates) or
                accumulates for a round-end flush, then ppermutes input
                grads upstream.

All microbatch/slot indices come from gathered schedule-table rows —
there is no tick/stage index arithmetic in this module; adding a
schedule means subclassing PipelineSchedule, not editing this file.
The schedule registry (core/schedule.py) maps ``plan.schedule`` /
``plan.stash_mode`` onto:

  1f1b         paper default (policy 'stash': F latest, B stashed; or
               'vertical': uniform delayed version), update per mb.
  gpipe        flush family — 1F1B timing, grads accumulated, one
               synchronous update per round ('flush' = 1 weight
               version, '2bw' = PipeDream-2BW-style double buffer).
  interleaved  Megatron-style virtual stages: each physical stage holds
               ``plan.virtual_stages`` model chunks (stage-stacked
               params carry S·v rows in storage order s·v+j -> chunk
               j·S+s), shrinking the bubble for S >= 3.  Flush
               semantics (accumulate).
  interleaved_async
               the same interleaved timing with per-microbatch updates:
               each chunk keeps its own weight-version ring, stored
               chunk-major ([V, S·v, ...] — slot, then storage row), F
               records the chunk's live weights into (slot, chunk) and
               B re-reads exactly that version, then updates only that
               chunk's weight/optimizer rows.

Weight-stash ring primitives and the ZeRO-1 sharded-optimizer update
live in core/versioning.py.  Boundary ticks run the same program on
masked data — the pipeline bubble costs real slots, exactly as on
hardware.  Embedding updates apply once per round; head/final-norm
update per tick (output-stage semantics).  See DESIGN.md §5/§7.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs as obs_lib
from repro.core import schedule as sched_lib
from repro.core.schedule import (B_CHUNK, B_FROM_HEAD, B_MB, B_RESID_READ,
                                 B_VERSION, F_CHUNK, F_FROM_EMBEDS, F_MB,
                                 F_RESID_WRITE, F_STASH_WRITE, F_VERSION,
                                 PipelineSchedule)
from repro.core.versioning import (replicated_microbatch_update, tree_add,
                                   tree_chunk, tree_chunk_add,
                                   tree_chunk_ring_read,
                                   tree_chunk_ring_write, tree_chunk_write,
                                   tree_ring_read, tree_ring_write,
                                   tree_scale, tree_select, zero1_axes,
                                   zero1_microbatch_update, zero1_opt_pspec)
from repro.models import lm_head
from repro.models import spec as spec_lib
from repro.models.init import init_params
from repro.models.stage import StageStatics, encoder_fwd, make_statics, stage_fwd
from repro.parallel.compat import shard_map
from repro.parallel.mesh import AXIS_STAGE, AXIS_TENSOR, ParallelismPlan, data_axes


def _is_pspec(x):
    return isinstance(x, P)


# --------------------------------------------------------------------------
# Bundle
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PipelineBundle:
    spec: spec_lib.ModelSpec
    plan: ParallelismPlan
    mesh: Mesh
    statics: StageStatics
    sched: PipelineSchedule
    train_step: Callable            # (state, batch) -> (state, metrics)
    init_state: Callable            # (key) -> state
    state_pspecs: Any
    batch_pspecs: Dict[str, P]
    batch_shapes: Dict[str, jax.ShapeDtypeStruct]
    seq_len: int
    microbatch_size: int
    # observability hook (repro.obs.Observability or None = off): the
    # driver counts each executed round into its registry
    obs: Any = None
    # the optimizer train_step applies (an oracle replays the same one)
    optimizer: Any = None

    def state_shardings(self):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.state_pspecs, is_leaf=_is_pspec)

    def batch_shardings(self):
        return {k: NamedSharding(self.mesh, v)
                for k, v in self.batch_pspecs.items()}

    def batch_specs(self):
        sh = self.batch_shardings()
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
                for k, v in self.batch_shapes.items()}


def build_pipeline(spec: spec_lib.ModelSpec, plan: ParallelismPlan,
                   mesh: Mesh, *, seq_len: int, global_batch: int,
                   optimizer, aux_weight: float = 0.01,
                   compute_dtype=jnp.bfloat16, obs=None) -> PipelineBundle:
    """Construct the pipelined train step for one (arch, shape, mesh)."""
    S = plan.pp
    R = plan.microbatches
    daxes = data_axes(mesh)
    dp = int(np.prod([mesh.devices.shape[mesh.axis_names.index(a)]
                      for a in daxes]))
    assert global_batch % (dp * R) == 0, (global_batch, dp, R)
    mb = global_batch // (dp * R)          # per-replica microbatch size
    bmb = global_batch // R                # global rows per microbatch

    sched = sched_lib.make_schedule(plan)
    assert not sched.is_serving, (
        f"schedule {sched.name!r} is forward-only (serving): it has no "
        "backward slots to train with — drive it through "
        "serving/engine.py::build_serving instead")
    sched.validate()
    vs = sched.virtual_stages               # local chunks per stage
    n_chunks = sched.n_chunks
    V = sched.stash_slots                   # weight-version ring size
    Vr = sched.resid_slots                  # residual ring size
    use_ring = sched.uses_stash_ring
    accumulate = sched.accumulate or plan.grad_sync == "per_round"
    # vs > 1 with a ring is the async interleaved schedule: the stash is
    # chunk-major ([V, S·v, ...]) and F/B index it by the table's
    # (version-slot, chunk) column pair.  No schedule forwards *from*
    # the stash at virtual stages (vertical sync is vs == 1 only).
    assert not (sched.fwd_from_stash and vs > 1), sched.name
    # Static schedule tables; gathered per (tick, stage) inside the
    # shard_map bodies — they become tiny jaxpr constants.
    tabs = sched.tables()
    FT, BT = np.asarray(tabs.fwd), np.asarray(tabs.bwd)
    EXIT_T, DEMB_T = np.asarray(tabs.exit_mb), np.asarray(tabs.demb_mb)
    # The model is cut into n_chunks pieces; all model-side construction
    # (init, statics, per-layer scalars) sees the chunk count as "pp".
    mplan = plan.with_(pp=n_chunks, schedule="auto", virtual_stages=1) \
        if vs > 1 else plan

    tp_axis = AXIS_TENSOR if plan.tp > 1 else None
    # ZeRO-1: opt-state sharding over data applies in every mode; the
    # manual reduce-scatter/all-gather update is only needed on the
    # per-microbatch (non-accumulate) path — the round-end pjit update
    # is partitioned by XLA from the pspecs alone.
    zero1_shard = plan.zero1 and dp > 1
    zero1_manual = zero1_shard and not accumulate
    is_vlm = spec.frontend == "vision"
    has_enc = spec.encoder is not None
    n_patch = spec.n_patches if is_vlm else 0
    text_len = seq_len - n_patch

    statics = make_statics(spec, mplan, tokens_per_mb=mb * seq_len)
    dnames = daxes if len(daxes) > 1 else daxes[0]

    enc_len = spec.encoder.source_len if has_enc else 1
    d_enc = spec.encoder.d_model if has_enc else 1

    def run_stage(w_stage, x, windows, thetas, cross_x=None):
        pos = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32),
                               (x.shape[0], seq_len))
        h, _, aux = stage_fwd(w_stage, x, statics, positions=pos,
                              windows=windows, thetas=thetas,
                              tp_axis=tp_axis, cross_x=cross_x)
        return h, aux

    if vs > 1:
        # chunk transitions wrap from the last stage back to stage 0
        fwd_perm = [(i, (i + 1) % S) for i in range(S)] if S > 1 else []
        bwd_perm = [((i + 1) % S, i) for i in range(S)] if S > 1 else []
    else:
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i + 1, i) for i in range(S - 1)]

    def gather_row(table, tick):
        """Row of a [T, S, C] schedule table for (tick, this stage)."""
        s = jax.lax.axis_index(AXIS_STAGE)
        rows = jax.lax.dynamic_index_in_dim(jnp.asarray(table), tick, 0,
                                            keepdims=False)
        return jax.lax.dynamic_index_in_dim(rows, s, 0, keepdims=False)

    def local_chunk(weights, windows, thetas, chunk):
        """This tick's chunk view of the stage-local stacked params."""
        if vs == 1:
            return weights, windows[0], thetas[0]
        return (tree_chunk(weights, chunk),
                jax.lax.dynamic_index_in_dim(windows, chunk, 0,
                                             keepdims=False),
                jax.lax.dynamic_index_in_dim(thetas, chunk, 0,
                                             keepdims=False))

    # ======================= F phase (shard_map body) ===================
    @obs_lib.scoped(obs_lib.FWD)
    def f_phase(tick, weights, stash, resid, recv_f, embeds, windows,
                thetas, enc_ring):
        row = gather_row(FT, tick)
        f = row[F_MB]
        valid = f >= 0
        fsafe = jnp.clip(f, 0, R - 1)

        w_loc, win_loc, th_loc = local_chunk(weights, windows, thetas,
                                             row[F_CHUNK])
        x0 = jax.lax.dynamic_index_in_dim(embeds, fsafe, 0, keepdims=False)
        x_in = jnp.where(row[F_FROM_EMBEDS] > 0, x0, recv_f[0])
        if use_ring:
            stash = (tree_ring_write(stash, row[F_STASH_WRITE], w_loc,
                                     valid)
                     if vs == 1 else
                     tree_chunk_ring_write(stash, row[F_STASH_WRITE],
                                           row[F_CHUNK], w_loc, valid))
        if sched.fwd_from_stash:
            w_f = tree_ring_read(stash, row[F_VERSION])
        else:
            w_f = w_loc
        cross = None
        if has_enc:
            cross = jax.lax.dynamic_index_in_dim(enc_ring, fsafe, 0,
                                                 keepdims=False)
        h, aux = run_stage(w_f, x_in, win_loc, th_loc, cross)
        slot = row[F_RESID_WRITE]
        old = jax.lax.dynamic_index_in_dim(resid, slot, 0, keepdims=False)
        resid = jax.lax.dynamic_update_index_in_dim(
            resid, jnp.where(valid, x_in[None].astype(resid.dtype), old),
            slot, 0)
        with jax.named_scope(obs_lib.SEND):
            h_send = (jax.lax.ppermute(h, AXIS_STAGE, fwd_perm) if S > 1
                      else h)
        aux = aux * valid.astype(aux.dtype)
        return stash, resid, h_send[None], h[None], aux[None]

    # ======================= B phase (shard_map body) ===================
    # the stage's recompute, backward and accumulation are ``bwd``; a
    # per-microbatch update is ``optimizer``, outside it
    @obs_lib.scoped(obs_lib.BWD)
    def b_grads(tick, weights, stash, resid, recv_b, g_exit, grad_acc,
                windows, thetas, enc_ring, denc_ring):
        row = gather_row(BT, tick)
        b = row[B_MB]
        valid = b >= 0
        bsafe = jnp.clip(b, 0, R - 1)

        w_loc, win_loc, th_loc = local_chunk(weights, windows, thetas,
                                             row[B_CHUNK])
        g_in = jnp.where(row[B_FROM_HEAD] > 0, g_exit, recv_b[0])
        if use_ring:
            w_used = (tree_ring_read(stash, row[B_VERSION]) if vs == 1
                      else tree_chunk_ring_read(stash, row[B_VERSION],
                                                row[B_CHUNK]))
        else:
            w_used = w_loc
        x_saved = jax.lax.dynamic_index_in_dim(
            resid, row[B_RESID_READ], 0, keepdims=False)[0]
        # g_exit carries global-batch normalization (head loss is a mean
        # over all Bmb rows), so psum of per-replica partial dW is already
        # the exact global gradient; aux is averaged over replicas.
        aux_ct = jnp.float32(aux_weight / dp) * valid.astype(jnp.float32)

        if has_enc:
            cross = jax.lax.dynamic_index_in_dim(enc_ring, bsafe, 0,
                                                 keepdims=False)

            def f_full(w, x, cx):
                return run_stage(w, x, win_loc, th_loc, cx)

            _, vjp = jax.vjp(f_full, w_used, x_saved, cross)
            dW, dx, dcx = vjp((g_in.astype(x_saved.dtype), aux_ct))
            if tp_axis is not None:
                dcx = jax.lax.pmean(dcx, tp_axis)
            old = jax.lax.dynamic_index_in_dim(denc_ring[0], bsafe, 0,
                                               keepdims=False)
            dcx = jnp.where(valid, dcx.astype(denc_ring.dtype), old)
            denc_ring = jax.lax.dynamic_update_index_in_dim(
                denc_ring[0], dcx, bsafe, 0)[None]
        else:
            def f_txt(w, x):
                return run_stage(w, x, win_loc, th_loc)

            _, vjp = jax.vjp(f_txt, w_used, x_saved)
            dW, dx = vjp((g_in.astype(x_saved.dtype), aux_ct))

        if tp_axis is not None:
            dW = jax.tree.map(tp_weight_grad, dW, tp_sharded)
        dW = tree_scale(dW, valid.astype(jnp.float32))
        dx = dx * valid.astype(dx.dtype)
        dx_embeds = dx if tp_axis is None else jax.lax.pmean(dx, tp_axis)

        if accumulate:
            if vs == 1:
                grad_acc = tree_add(grad_acc, dW)
            else:
                grad_acc = tree_chunk_add(grad_acc, dW, row[B_CHUNK])
        return row, valid, w_loc, dW, dx, dx_embeds, grad_acc, denc_ring

    @obs_lib.scoped(obs_lib.OPTIMIZER)
    def b_update(row, step, valid, weights, w_loc, opt_state, dW):
        """Per-microbatch update of exactly the chunk this B row names:
        vs == 1 updates the whole stage block in place; vs > 1 (async
        interleaved) reads the chunk's weight and optimizer rows,
        updates them, and writes them back — the stage's other chunks
        are untouched this tick."""
        upd_o = (tree_chunk(opt_state, row[B_CHUNK]) if vs > 1
                 else opt_state)
        upd_w = w_loc if vs > 1 else weights
        if zero1_manual:
            upd_w, upd_o = zero1_microbatch_update(
                optimizer, dW, upd_o, upd_w, step, valid,
                z1_axes=z1_axes, daxes=daxes, dnames=dnames, dp=dp)
        else:
            upd_w, upd_o = replicated_microbatch_update(
                optimizer, dW, upd_o, upd_w, step, valid,
                dnames=dnames)
        if vs > 1:
            return (tree_chunk_write(weights, row[B_CHUNK], upd_w),
                    tree_chunk_write(opt_state, row[B_CHUNK], upd_o))
        return upd_w, upd_o

    def b_phase(tick, step, weights, stash, opt_state, resid, recv_b,
                g_exit, grad_acc, windows, thetas, enc_ring, denc_ring):
        row, valid, w_loc, dW, dx, dx_embeds, grad_acc, denc_ring = b_grads(
            tick, weights, stash, resid, recv_b, g_exit, grad_acc,
            windows, thetas, enc_ring, denc_ring)
        if accumulate:
            new_w, new_opt = weights, opt_state
        else:
            new_w, new_opt = b_update(row, step, valid, weights, w_loc,
                                      opt_state, dW)
        with jax.named_scope(obs_lib.BWD), jax.named_scope(obs_lib.SEND):
            g_send = (jax.lax.ppermute(dx, AXIS_STAGE, bwd_perm) if S > 1
                      else dx)
        return (new_w, new_opt, g_send[None], grad_acc, dx_embeds[None],
                denc_ring)

    # ======================= pspecs =====================================
    _box = {}

    def _init_for_shapes():
        p, s = init_params(spec, mplan, jax.random.key(0), compute_dtype)
        _box["pspecs"] = s  # pspecs are static; capture via side channel
        return p

    params_shape = jax.eval_shape(_init_for_shapes)
    pspecs = _box["pspecs"]

    stage_pspec = pspecs["stages"]
    # With check_vma=False, a vjp inside the shard_map differentiates the
    # sum of the tp ranks' copies of the loss, and counts each rank's copy
    # of a tensor-replicated input as a variable of its own.  So a
    # tensor-sharded weight's cotangent is tp times its gradient, and a
    # tensor-replicated weight's or activation's gradient is the mean of
    # the ranks' cotangents.  The dx sent to the previous stage stays per
    # rank: it is the cotangent of that stage's per-rank output copies.
    tp_sharded = jax.tree.map(
        lambda p: AXIS_TENSOR in jax.tree.leaves(tuple(p)), stage_pspec,
        is_leaf=_is_pspec)

    def tp_weight_grad(ct, sharded):
        return ct / plan.tp if sharded else jax.lax.pmean(ct, tp_axis)

    stash_pspec = (jax.tree.map(lambda p: P(None, *p), stage_pspec,
                                is_leaf=_is_pspec)
                   if use_ring else {"_": P()})
    act_pspec = P(AXIS_STAGE, dnames, None, None)         # (pp,Bmb,S,d)
    resid_pspec = P(None, AXIS_STAGE, dnames, None, None)  # (Vr,pp,Bmb,S,d)
    emb_pspec = P(None, dnames, None, None)               # (R,Bmb,S,d)
    gexit_pspec = P(dnames, None, None)
    win_pspec = P(AXIS_STAGE, None)
    scalar_pspec = P()

    enc_pspec = P(None, dnames, None, None)
    denc_pspec = (P(AXIS_STAGE, None, dnames, None, None) if has_enc
                  else P(AXIS_STAGE, None, None, None, None))

    z1_axes = (zero1_axes(params_shape["stages"], stage_pspec, mesh, dp)
               if zero1_shard else
               jax.tree.map(lambda _: -1, params_shape["stages"]))
    opt_leaf_pspec = (zero1_opt_pspec(stage_pspec, z1_axes, daxes)
                      if zero1_shard else stage_pspec)
    opt_st_shape = jax.eval_shape(
        lambda: optimizer.init(params_shape["stages"]))
    opt_stage_pspec = {slot: opt_leaf_pspec for slot in opt_st_shape}

    if accumulate:
        gacc_pspec = jax.tree.map(lambda p: P(dnames, *p), stage_pspec,
                                  is_leaf=_is_pspec)
    else:
        gacc_pspec = {"_": P(dnames, None)}

    f_sharded = shard_map(
        f_phase, mesh=mesh,
        in_specs=(scalar_pspec, stage_pspec, stash_pspec, resid_pspec,
                  act_pspec, emb_pspec, win_pspec, win_pspec, enc_pspec),
        out_specs=(stash_pspec, resid_pspec, act_pspec, act_pspec,
                   P(AXIS_STAGE)),
        check_vma=False)

    b_sharded = shard_map(
        b_phase, mesh=mesh,
        in_specs=(scalar_pspec, scalar_pspec, stage_pspec, stash_pspec,
                  opt_stage_pspec, resid_pspec, act_pspec, gexit_pspec,
                  gacc_pspec, win_pspec, win_pspec, enc_pspec, denc_pspec),
        out_specs=(stage_pspec, opt_stage_pspec, act_pspec, gacc_pspec,
                   act_pspec, denc_pspec),
        check_vma=False)

    # ======================= the train step =============================
    def train_step(state, batch):
        params = state["params"]
        tokens, labels = batch["tokens"], batch["labels"]  # (R,Bmb,text)
        step = state["step"]

        with jax.named_scope(obs_lib.EMBED):
            text_embeds = lm_head.embed_tokens(params["embed"], tokens)
            if is_vlm:
                embeds = jnp.concatenate(
                    [batch["patches"].astype(text_embeds.dtype),
                     text_embeds], axis=2)
                lab_full = jnp.concatenate(
                    [jnp.full((R, bmb, n_patch), -1, labels.dtype),
                     labels], axis=2)
            else:
                embeds, lab_full = text_embeds, labels
            embeds = jax.lax.with_sharding_constraint(
                embeds.astype(compute_dtype), NamedSharding(mesh, emb_pspec))

        enc_vjp = None
        if has_enc:
            fr = batch["frames"].reshape(R * bmb, enc_len, d_enc)
            with jax.named_scope(obs_lib.FWD):
                enc_out_flat, enc_vjp = jax.vjp(
                    lambda ep, fx: encoder_fwd(ep, fx, spec),
                    params["encoder"], fr.astype(compute_dtype))
            enc_ring = jax.lax.with_sharding_constraint(
                enc_out_flat.reshape(R, bmb, enc_len, d_enc),
                NamedSharding(mesh, enc_pspec))
        else:
            enc_ring = jnp.zeros((1, bmb, 1, 1), compute_dtype)

        zeros_act = jnp.zeros((S, bmb, seq_len, spec.d_model), compute_dtype)
        carry = {
            "w": state["stash"]["current"],
            "stash": (state["stash"]["ring"] if use_ring
                      else {"_": jnp.zeros((1,), jnp.float32)}),
            "opt": state["opt_stages"],
            "head": params["head"],
            "fnorm": params["final_norm"],
            "head_opt": state["opt_head"],
            "recv_f": zeros_act,
            "recv_b": zeros_act,
            "resid": jnp.zeros((Vr, S, bmb, seq_len, spec.d_model),
                               compute_dtype),
            "gacc": (jax.tree.map(
                lambda a: jnp.zeros((dp,) + a.shape, jnp.float32),
                params["stages"]) if accumulate
                else {"_": jnp.zeros((dp, 1), jnp.float32)}),
            "dhead_acc": (jnp.zeros(params["head"].shape, jnp.float32)
                          if accumulate else jnp.zeros((1,), jnp.float32)),
            "dfnorm_acc": (jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32),
                params["final_norm"]) if accumulate
                else jnp.zeros((1,), jnp.float32)),
            "d_embeds": jnp.zeros((R, bmb, seq_len, spec.d_model),
                                  compute_dtype),
            "denc": (jnp.zeros((S, R, bmb, enc_len, d_enc), compute_dtype)
                     if has_enc
                     else jnp.zeros((S, 1, 1, 1, 1), compute_dtype)),
            "loss_sum": jnp.zeros((), jnp.float32),
            "aux_sum": jnp.zeros((), jnp.float32),
        }

        win, th = params["layer_windows"], params["layer_thetas"]

        # ---- head + loss for the exiting microbatch ----------------------
        @obs_lib.scoped(obs_lib.HEAD)
        def head_tick(carry, tick, h_all):
            """The exit microbatch's loss and d(loss)/d(hidden); updates
            ``carry``'s head, final norm and sums in place."""
            m_exit = jax.lax.dynamic_index_in_dim(
                jnp.asarray(EXIT_T), tick, 0, keepdims=False)
            valid_e = m_exit >= 0
            msafe = jnp.clip(m_exit, 0, R - 1)
            with jax.named_scope(obs_lib.SEND):    # last stage -> head
                h_exit = h_all[S - 1]
            lab = jax.lax.dynamic_index_in_dim(lab_full, msafe, 0,
                                               keepdims=False)
            vmask = (lab >= 0).astype(jnp.float32)
            lab_safe = jnp.maximum(lab, 0)

            def loss_fn(head, fnorm, h):
                loss, _ = lm_head.head_loss(
                    head, fnorm["scale"], h, lab_safe, norm_kind=spec.norm,
                    norm_bias=fnorm.get("bias"), valid_mask=vmask,
                    vocab=spec.vocab)
                return loss

            loss, (dhead, dfnorm, dh) = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2))(
                carry["head"], carry["fnorm"], h_exit)
            ve = valid_e.astype(jnp.float32)
            carry["loss_sum"] = carry["loss_sum"] + loss * ve
            g_exit = (dh.astype(jnp.float32) * ve).astype(compute_dtype)

            if accumulate:
                carry["dhead_acc"] = carry["dhead_acc"] + \
                    dhead.astype(jnp.float32) * ve
                carry["dfnorm_acc"] = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * ve,
                    carry["dfnorm_acc"], dfnorm)
            else:
                hf_new, hf_opt = optimizer.update(
                    {"h": dhead, "f": dfnorm}, carry["head_opt"],
                    {"h": carry["head"], "f": carry["fnorm"]}, step)
                carry["head"] = tree_select(valid_e, hf_new["h"],
                                            carry["head"])
                carry["fnorm"] = tree_select(valid_e, hf_new["f"],
                                             carry["fnorm"])
                carry["head_opt"] = tree_select(valid_e, hf_opt,
                                                carry["head_opt"])
            return g_exit

        def tick_body(carry, tick):
            stash, resid, recv_f, h_all, aux = f_sharded(
                tick, carry["w"], carry["stash"], carry["resid"],
                carry["recv_f"], embeds, win, th, enc_ring)
            carry["stash"], carry["resid"], carry["recv_f"] = \
                stash, resid, recv_f
            carry["aux_sum"] = carry["aux_sum"] + aux.sum()
            g_exit = head_tick(carry, tick, h_all)

            # ---- backward phase -----------------------------------------
            new_w, new_opt, recv_b, gacc, dx_all, denc = b_sharded(
                tick, step, carry["w"], carry["stash"], carry["opt"],
                carry["resid"], carry["recv_b"], g_exit, carry["gacc"],
                win, th, enc_ring, carry["denc"])
            carry["w"], carry["opt"], carry["recv_b"] = new_w, new_opt, recv_b
            carry["gacc"], carry["denc"] = gacc, denc

            # stage 0's dx is d(embeddings) when its backward finishes a
            # microbatch's first chunk (schedule demb table)
            with jax.named_scope(obs_lib.EMBED):
                b0 = jax.lax.dynamic_index_in_dim(
                    jnp.asarray(DEMB_T), tick, 0, keepdims=False)
                valid_b0 = b0 >= 0
                b0safe = jnp.clip(b0, 0, R - 1)
                prev = jax.lax.dynamic_index_in_dim(
                    carry["d_embeds"], b0safe, 0, keepdims=False)
                upd = jnp.where(valid_b0, dx_all[0], prev)
                carry["d_embeds"] = jax.lax.dynamic_update_index_in_dim(
                    carry["d_embeds"], upd, b0safe, 0)
            return carry, None

        carry, _ = jax.lax.scan(tick_body, carry,
                                jnp.arange(sched.n_ticks, dtype=jnp.int32))

        # ---- round-end updates -------------------------------------------
        new_params = dict(params)
        new_state = dict(state)
        step = state["step"]

        if accumulate:
            with jax.named_scope(obs_lib.OPTIMIZER):
                g_st = jax.tree.map(lambda a: jnp.sum(a, axis=0) / R,
                                    carry["gacc"])
                carry["w"], carry["opt"] = optimizer.update(
                    g_st, carry["opt"], carry["w"], step)
                hf_new, hf_opt = optimizer.update(
                    {"h": carry["dhead_acc"] / R,
                     "f": jax.tree.map(lambda a: a / R,
                                       carry["dfnorm_acc"])},
                    carry["head_opt"],
                    {"h": carry["head"], "f": carry["fnorm"]}, step)
                carry["head"], carry["fnorm"] = hf_new["h"], hf_new["f"]
                carry["head_opt"] = hf_opt

        # embedding update, once per round (DESIGN.md §7)
        with jax.named_scope(obs_lib.EMBED):
            demb = carry["d_embeds"][:, :, n_patch:, :] if is_vlm \
                else carry["d_embeds"]
            d_table = lm_head.embed_bwd(params["embed"], tokens,
                                        demb.astype(jnp.float32)) / R
            emb2, eopt2 = optimizer.update(d_table, state["opt_embed"],
                                           params["embed"], step)
        new_params["embed"] = emb2
        new_state["opt_embed"] = eopt2

        if has_enc:
            with jax.named_scope(obs_lib.BWD):
                denc_sum = jnp.sum(carry["denc"].astype(jnp.float32),
                                   axis=0)
                (denc_params, _) = enc_vjp(
                    denc_sum.reshape(R * bmb, enc_len, d_enc).astype(
                        compute_dtype))
            with jax.named_scope(obs_lib.OPTIMIZER):
                encp2, encopt2 = optimizer.update(
                    jax.tree.map(lambda a: a.astype(jnp.float32) / R,
                                 denc_params),
                    state["opt_encoder"], params["encoder"], step)
            new_params["encoder"] = encp2
            new_state["opt_encoder"] = encopt2

        new_params["head"] = carry["head"]
        new_params["final_norm"] = carry["fnorm"]
        new_params["stages"] = carry["w"]
        new_state["params"] = new_params
        new_state["stash"] = ({"current": carry["w"], "ring": carry["stash"]}
                              if use_ring else {"current": carry["w"]})
        new_state["opt_stages"] = carry["opt"]
        new_state["opt_head"] = carry["head_opt"]
        new_state["step"] = step + 1

        metrics = {"loss": carry["loss_sum"] / R,
                   "aux": carry["aux_sum"] / R}
        return new_state, metrics

    # ======================= state init + pspecs ========================
    def init_state(key):
        params, _ = init_params(spec, mplan, key, compute_dtype)
        if vs > 1:
            # storage order: row s*v + j holds model chunk j*S + s, so
            # the contiguous stage shard owns its interleaved chunks
            perm = jnp.asarray(sched.storage_chunk_order())
            params = dict(params)
            params["stages"] = jax.tree.map(lambda a: a[perm],
                                            params["stages"])
            params["layer_windows"] = params["layer_windows"][perm]
            params["layer_thetas"] = params["layer_thetas"][perm]
        stages = params["stages"]
        stash = {"current": stages}
        if use_ring:
            stash["ring"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (V,) + a.shape) + 0,
                stages)
        state = {
            "params": params,
            "stash": stash,
            "opt_stages": optimizer.init(stages),
            "opt_head": optimizer.init({"h": params["head"],
                                        "f": params["final_norm"]}),
            "opt_embed": optimizer.init(params["embed"]),
            "step": jnp.zeros((), jnp.int32),
        }
        if has_enc:
            state["opt_encoder"] = optimizer.init(params["encoder"])
        return state

    opt_hf_shape = jax.eval_shape(lambda: optimizer.init(
        {"h": params_shape["head"], "f": params_shape["final_norm"]}))
    opt_head_pspec = {slot: {"h": pspecs["head"], "f": pspecs["final_norm"]}
                      for slot in opt_hf_shape}
    opt_emb_shape = jax.eval_shape(
        lambda: optimizer.init(params_shape["embed"]))
    opt_emb_pspec = {slot: pspecs["embed"] for slot in opt_emb_shape}

    state_pspecs = {
        "params": pspecs,
        "stash": ({"current": stage_pspec, "ring": stash_pspec}
                  if use_ring else {"current": stage_pspec}),
        "opt_stages": opt_stage_pspec,
        "opt_head": opt_head_pspec,
        "opt_embed": opt_emb_pspec,
        "step": P(),
    }
    if has_enc:
        opt_enc_shape = jax.eval_shape(
            lambda: optimizer.init(params_shape["encoder"]))
        state_pspecs["opt_encoder"] = {slot: pspecs["encoder"]
                                       for slot in opt_enc_shape}

    batch_shapes = {
        "tokens": jax.ShapeDtypeStruct((R, bmb, text_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((R, bmb, text_len), jnp.int32),
    }
    batch_pspecs = {
        "tokens": P(None, dnames, None),
        "labels": P(None, dnames, None),
    }
    if is_vlm:
        batch_shapes["patches"] = jax.ShapeDtypeStruct(
            (R, bmb, n_patch, spec.d_model), compute_dtype)
        batch_pspecs["patches"] = P(None, dnames, None, None)
    if has_enc:
        batch_shapes["frames"] = jax.ShapeDtypeStruct(
            (R, bmb, enc_len, d_enc), compute_dtype)
        batch_pspecs["frames"] = P(None, dnames, None, None)

    return PipelineBundle(
        spec=spec, plan=plan, mesh=mesh, statics=statics, sched=sched,
        train_step=train_step, init_state=init_state,
        state_pspecs=state_pspecs, batch_pspecs=batch_pspecs,
        batch_shapes=batch_shapes, seq_len=seq_len, microbatch_size=mb,
        obs=obs, optimizer=optimizer)
