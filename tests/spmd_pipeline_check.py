"""SPMD pipeline vs sequential reference — run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=<N>.

Usage: python tests/spmd_pipeline_check.py <data> <pp> <tp> <mode> [arch]
           [zero1] [schedule] [virtual_stages] [steps]
Exits nonzero (assertion) on mismatch; prints MATCH lines on success.

For ``schedule=interleaved`` the pipeline runs S physical stages with v
chunks each; the reference runs the SAME model as a sequential pp = S*v
flush pipeline (flush semantics are schedule-timing-independent), with
the pipeline's storage-order (s*v + j -> chunk j*S + s) parameters
permuted back to chunk order before comparison.

For ``schedule=interleaved_async`` (per-microbatch updates, per-chunk
weight-version rings) the update order is timing-dependent, so the
sequential oracle walks the SAME async-interleaved schedule tables
natively — state stays in storage order on both sides and is compared
directly.
"""
import os
import sys

if __name__ == "__main__":
    data, pp, tp = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "stash"
    arch = sys.argv[5] if len(sys.argv) > 5 else "dense"
    zero1 = bool(int(sys.argv[6])) if len(sys.argv) > 6 else False
    schedule = sys.argv[7] if len(sys.argv) > 7 else "auto"
    vstages = int(sys.argv[8]) if len(sys.argv) > 8 else 1
    steps = int(sys.argv[9]) if len(sys.argv) > 9 else 1
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={data * pp * tp}")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


# relative L2 gap allowed between each weight's update and the
# reference's; fp32 reduction-order differences give ~1e-5
UPDATE_RTOL = 1e-3


def build_tiny_spec(arch: str):
    from repro.models import spec as S
    if arch == "dense":
        blocks = tuple(S.BlockSpec(window=(-1 if i % 2 else 8),
                                   rope_theta=1e4 * (1 + i % 2))
                       for i in range(4))
        return S.ModelSpec(name="tiny", d_model=32, n_layers=4, n_heads=4,
                           n_kv=2, d_head=8, d_ff=64, vocab=64,
                           blocks=blocks, qk_norm=True)
    if arch == "dense8":
        blocks = tuple(S.BlockSpec(window=(-1 if i % 2 else 8),
                                   rope_theta=1e4 * (1 + i % 2))
                       for i in range(8))
        return S.ModelSpec(name="tiny8", d_model=32, n_layers=8, n_heads=4,
                           n_kv=2, d_head=8, d_ff=64, vocab=64,
                           blocks=blocks, qk_norm=True)
    if arch == "moe":
        blocks = tuple(S.BlockSpec(ffn="moe") for _ in range(4))
        return S.ModelSpec(name="tmoe", d_model=32, n_layers=4, n_heads=4,
                           n_kv=4, d_head=8, d_ff=64, vocab=64,
                           blocks=blocks,
                           moe=S.MoESpec(n_experts=4, top_k=2, d_expert=16))
    if arch == "rwkv":
        blocks = tuple(S.BlockSpec(mixer="rwkv", ffn="rwkv_cmix")
                       for _ in range(4))
        return S.ModelSpec(name="trwkv", d_model=32, n_layers=4, n_heads=0,
                           n_kv=0, d_head=0, d_ff=96, vocab=64,
                           blocks=blocks,
                           rwkv=S.RWKVSpec(head_dim=8, decay_lora=4,
                                           tmix_lora=4),
                           family="ssm", subquadratic=True)
    if arch == "hybrid":
        def blk(i):
            return S.BlockSpec(mixer=("attn" if i % 4 == 0 else "mamba"),
                               ffn=("moe" if i % 2 == 1 else "dense"))
        return S.ModelSpec(name="tjam", d_model=32, n_layers=8, n_heads=4,
                           n_kv=2, d_head=8, d_ff=64, vocab=64,
                           blocks=tuple(blk(i) for i in range(8)),
                           moe=S.MoESpec(n_experts=4, top_k=2, d_expert=16),
                           mamba=S.MambaSpec(d_state=4, expand=2),
                           family="hybrid", subquadratic=True)
    raise ValueError(arch)


def _unpermute(state, perm):
    """Storage-order pipeline state -> chunk-order (reference) state."""
    inv = np.argsort(perm)
    out = dict(state)
    params = dict(state["params"])
    params["stages"] = jax.tree.map(lambda a: a[inv], params["stages"])
    params["layer_windows"] = params["layer_windows"][inv]
    params["layer_thetas"] = params["layer_thetas"][inv]
    out["params"] = params
    out["opt_stages"] = {k: jax.tree.map(lambda a: a[inv], sub)
                         for k, sub in state["opt_stages"].items()}
    out["stash"] = {"current": params["stages"]}
    return out


def main(data, pp, tp, mode, arch, zero1=False, schedule="auto", vstages=1,
         steps=1):
    from repro.core.pipeline import build_pipeline
    from repro.core.reference import reference_train_step
    from repro.optim import SGDM
    from repro.parallel.mesh import ParallelismPlan, split_model_axis
    from repro.launch.mesh import make_host_mesh

    spec = build_tiny_spec(arch)
    R = 4
    plan = ParallelismPlan(pp=pp, tp=tp, microbatches=R, stash_mode=mode,
                           remat=True, zero1=zero1, schedule=schedule,
                           virtual_stages=vstages)
    mesh = make_host_mesh(data=data, model=pp * tp)
    dmesh = split_model_axis(mesh, pp, tp)

    seq, gbatch = 16, data * R * 2
    opt = SGDM(lr=0.05, momentum=0.9)
    bundle = build_pipeline(spec, plan, dmesh, seq_len=seq,
                            global_batch=gbatch, optimizer=opt,
                            compute_dtype=jnp.float32)

    key = jax.random.key(0)
    state = jax.jit(bundle.init_state,
                    out_shardings=bundle.state_shardings())(key)
    bmb = gbatch // R
    tokens = jax.random.randint(jax.random.key(1), (R, bmb, seq), 0,
                                spec.vocab, jnp.int32)
    labels = jax.random.randint(jax.random.key(2), (R, bmb, seq), 0,
                                spec.vocab, jnp.int32)
    batch = {"tokens": tokens, "labels": labels}
    bsh = bundle.batch_shardings()
    batch_dev = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}

    step = jax.jit(bundle.train_step,
                   in_shardings=(bundle.state_shardings(), bsh),
                   out_shardings=(bundle.state_shardings(), None))

    # reference: flush-interleaved runs against a chunk-level sequential
    # flush pipeline (chunk order); async-interleaved runs the oracle on
    # the same schedule tables natively (storage order, no permutation)
    if vstages > 1 and schedule != "interleaved_async":
        ref_plan = plan.with_(pp=pp * vstages, schedule="auto",
                              virtual_stages=1)
        perm = bundle.sched.storage_chunk_order()
    else:
        ref_plan = plan
        perm = None
    ref_state = jax.device_get(state)
    ref_state = jax.tree.map(jnp.asarray, ref_state)
    if perm is not None:
        ref_state = _unpermute(ref_state, perm)
    init_params = ref_state["params"]

    for i in range(steps):
        new_state, metrics = step(state, batch_dev)
        ref_state, ref_metrics = reference_train_step(
            spec, ref_plan, ref_state, batch, opt, aux_weight=0.01 / 1.0)
        print(f"step {i}: pipeline loss {float(metrics['loss']):.6f} "
              f"aux {float(metrics['aux']):.6f} | reference loss "
              f"{float(ref_metrics['loss']):.6f} "
              f"aux {float(ref_metrics['aux']):.6f}")

        # tp>1 changes fp32 reduction order (psum of partial products);
        # tp=1 configs match near-bitwise.
        atol = 2e-4 if arch in ("rwkv", "hybrid") else 5e-5
        if tp > 1:
            atol = max(atol, 5e-4)
        np.testing.assert_allclose(float(metrics["loss"]),
                                   float(ref_metrics["loss"]), atol=atol,
                                   rtol=1e-4)
        state = new_state

    got_state = jax.device_get(new_state)
    got_state = jax.tree.map(jnp.asarray, got_state)
    if perm is not None:
        got_state = _unpermute(got_state, perm)
    got = got_state["params"]
    want = jax.device_get(ref_state["params"])
    flat_w, _ = jax.tree.flatten(want)
    paths = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, g), w in zip(paths, flat_w):
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=atol, rtol=2e-3, err_msg=f"param mismatch at {name}")
    # Updates are small next to the weights, so the bound above cannot
    # see a gradient off by a constant factor: each weight's total update
    # (final - initial) must also match the reference's in relative L2.
    # Not for MoE over data replicas: each replica routes, drops past
    # capacity and balances load over its own tokens, the reference over
    # the whole batch.
    per_replica_routing = data > 1 and spec.moe is not None
    for (path, g), w, w0 in zip(paths, flat_w,
                                jax.tree.leaves(init_params)):
        g, w, w0 = (np.asarray(a, np.float64) for a in (g, w, w0))
        moved = np.linalg.norm(w - w0)
        if moved and not per_replica_routing:
            gap = np.linalg.norm(g - w) / moved
            assert gap < UPDATE_RTOL, (
                f"update mismatch at {jax.tree_util.keystr(path)}: "
                f"relative gap {gap}")
    print(f"MATCH data={data} pp={pp} tp={tp} mode={mode} arch={arch} "
          f"zero1={zero1} schedule={schedule} v={vstages} steps={steps}")


if __name__ == "__main__":
    main(data, pp, tp, mode, arch, zero1, schedule, vstages, steps)
