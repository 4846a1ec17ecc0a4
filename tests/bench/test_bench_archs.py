"""An architecture is added to the benchmark by files alone
(bench/archs/<mixer>.py, found by the configuration's "mixer"), and the
reference trains a block's auxiliary loss with the cross-entropy."""
import os
import statistics
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "archs")
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from bench import archs, flops, harness, phases, reference  # noqa: E402


def test_fixture_architecture_runs_through_the_harness(monkeypatch):
    """A mixer whose module lives outside bench/archs, named by a tiny
    configuration: find_cell -> Program -> reference -> judge."""
    cfg = dict(tiny.load("tiny-danube"), mixer="attn_alias")
    attn_flops = flops.train_flops_per_token(dict(cfg, mixer="attn"), 32)
    monkeypatch.setattr(archs, "DIR", FIXTURES)
    tiny.use_tiny_cell(monkeypatch, "tiny-danube").cfg = cfg
    cell = harness.find_cell("tiny.train")
    assert cell.cfg["mixer"] == "attn_alias"
    program = harness.Program(cell, require_chip=False)
    try:
        prog = program.start(2 ** 31 + 11)
        # the traced run's HLO is that of the step that ran: no compile
        compiled = len(program.compiles)
        names = phases.hlo_op_names(program.step_hlo()).values()
        assert len(program.compiles) == compiled
        scopes = archs.load(cfg).SCOPES
        assert any(phases.scopes_of(n, scopes) for n in names)
        program.state = None
        ref = harness.reference_readings(program)
    finally:
        program.close()
    correct, checks, _ = harness.judge(cell.cfg, prog, ref)
    assert correct, checks
    assert flops.train_flops_per_token(cfg, 32) == attn_flops


def test_mixer_without_a_module_fails_as_cell_failed(monkeypatch):
    cfg = dict(tiny.load("tiny-danube"), mixer="nonesuch")
    path = os.path.join(archs.DIR, "nonesuch.py")
    for call in (lambda: flops.train_flops_per_token(cfg, 32),
                 lambda: reference.Reference(cfg)):
        with pytest.raises(harness.CellFailed) as e:
            call()
        assert path in str(e.value)
    tiny.use_tiny_cell(monkeypatch, "tiny-danube").cfg = cfg
    with pytest.raises(harness.CellFailed, match="nonesuch"):
        harness.Program(harness.find_cell("tiny.train"), require_chip=False)


# ---------------------------------------------------------------- aux loss

D, F, E, V, S, ROWS, LAYERS, W = 16, 32, 4, 32, 8, 2, 3, 0.3
TOY = {"mixer": "toymoe", "hidden_size": D, "intermediate_size": F,
       "n_routed_experts": E, "vocab_size": V, "num_hidden_layers": LAYERS,
       "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
       "aux_loss_weight": W, "param_dtype": "float32",
       "optimizer": {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
       "init": [{"match": "^embed$", "mean": 0.0, "std": 1.0},
                {"match": "^head$|mlp/w[12]$", "mean": 0.0, "std": 0.3},
                {"match": "scale$", "mean": 1.0, "std": 0.0},
                {"match": "router$", "mean": 0.0, "std": 0.5},
                {"match": "gain$", "mean": 1.0, "std": 0.5}]}


def toy_shapes():
    """The program's stage-stacked layout of the toy (pp = 1)."""
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(i):
        t = {"norm1": {"scale": s(1, D)},
             "mlp": {"w1": s(1, D, F), "w2": s(1, F, D)}}
        if i >= TOY["first_k_dense_replace"]:
            t.update(router=s(1, D, E), gain=s(1, E))
        return t
    return {"embed": s(V, D), "head": s(D, V), "final_norm": {"scale": s(D)},
            "stages": {f"layer_{i}": layer(i) for i in range(LAYERS)}}


def toy_batches(microbatches):
    rng = np.random.default_rng(3)
    toks = rng.integers(0, V, (microbatches, ROWS, S + 1)).astype(np.int32)
    return [{"tokens": toks[..., :-1], "labels": toks[..., 1:]}]


def whole_model_grad(cfg, p, batch):
    """jax.grad of the mean over microbatches of CE + w * sum of aux, the
    whole model at once, at HIGHEST."""
    mod = archs.load(cfg)
    dot = lambda eq, a, b: jnp.einsum(eq, a, b,
                                      precision=jax.lax.Precision.HIGHEST)

    def objective(p, tokens, labels):
        x, aux = p["embed"][tokens], 0.0
        for i, lp in enumerate(p["layers"]):
            x, a = jax.vmap(lambda r: mod.block(cfg, dot, lp, r, i))(x)
            aux = aux if a is None else aux + jnp.mean(a)
        logits = dot("rsd,dv->rsv", mod.final_norm(cfg, p["final_norm"], x),
                     p["head"])
        ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0])
        return ce + cfg["aux_loss_weight"] * aux

    grads = [jax.grad(objective)(p, t, l)
             for t, l in zip(batch["tokens"], batch["labels"])]
    return jax.tree.map(lambda *g: sum(g) / len(g), *grads)


def test_flops_sum_each_layers_own_terms(monkeypatch):
    """A dense first layer counts its own weights, a routed layer its
    router beside them (the toy's matmul_params)."""
    monkeypatch.setattr(archs, "DIR", FIXTURES)
    dense, routed = 2 * D * F, 2 * D * F + D * E
    assert flops.train_flops_per_token(TOY, S) == \
        3 * 2.0 * (D * V + dense + (LAYERS - 1) * routed)


@pytest.mark.parametrize("mode,microbatches", [("flush", 2), ("stash", 1)])
def test_aux_loss_gradient_matches_the_whole_model(monkeypatch, mode,
                                                   microbatches):
    """Adam's first moment after one flush round, or one stash
    microbatch, is (1 - b1) times the whole model's gradient: each leaf's
    norm within 1e-6 of the larger of its own and the median leaf's (the
    benchmark's measure of a leaf, reference.gaps), since a gradient that
    sums terms of both signs, as the last layer's gains do, keeps only
    the float32 rounding of the terms."""
    monkeypatch.setattr(archs, "DIR", FIXTURES)
    cfg = dict(TOY, plan={"pp": 1, "stash_mode": mode})
    key, shapes = jax.random.key(7), toy_shapes()
    ref = reference.Reference(cfg)
    batches = toy_batches(microbatches)
    got = ref.train(key, shapes, batches)["grad"]
    g = whole_model_grad(cfg, ref.initial(key, shapes), batches[0])
    b1 = cfg["optimizer"]["b1"]
    want = {k: float(v) for k, v in reference.leaf_norms(
        jax.tree.map(lambda a: (1 - b1) * a, g)).items()}
    assert set(got) == set(want)
    assert any(k.endswith("router") for k in want)
    med = statistics.median(want.values())
    for k in want:
        assert abs(got[k] - want[k]) <= 1e-6 * max(want[k], med), k
    # the auxiliary term moves the router: without it the gradient differs
    no_aux = whole_model_grad(dict(cfg, aux_loss_weight=0.0),
                              ref.initial(key, shapes), batches[0])
    k = "layers/1/router"
    moved = float(reference.leaf_norms(
        {"layers": [None, {"router": (1 - b1) * no_aux["layers"][1][
            "router"]}]})[k])
    assert abs(moved - got[k]) > 1e-3 * got[k]


def test_aux_loss_without_its_weight_fails(monkeypatch):
    monkeypatch.setattr(archs, "DIR", FIXTURES)
    cfg = {k: v for k, v in TOY.items() if k != "aux_loss_weight"}
    cfg["plan"] = {"pp": 1, "stash_mode": "flush"}
    with pytest.raises(harness.CellFailed, match="aux_loss_weight"):
        reference.Reference(cfg).train(jax.random.key(7), toy_shapes(),
                                       toy_batches(1))
