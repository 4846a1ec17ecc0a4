"""Plain float32 reference of a cell's first training rounds.

It imports nothing of the program.  It starts from the weights that
``bench/weights.py`` draws from the seed (the program's initial weights,
bit for bit, upcast to float32), trains on the same token rows, and
follows the semantics the configuration states on one pipeline stage:

* 1F1B with weight stashing (``stash_mode`` "stash"): every microbatch
  runs forward and backward with the stage weights of the moment, and
  each stage layer, the head and the final norm take one Adam update per
  microbatch, in microbatch order;
* a flush schedule (``stash_mode`` "flush", GPipe's): every microbatch
  runs with the round's weights, and each takes one update per round,
  with the mean over microbatches of its gradient;
* the embedding table is read as it stood at the start of the round and
  takes one update per round, with the mean over microbatches of its
  gradient;
* Adam's bias correction counts rounds, not updates (the configuration's
  ``bias_correction_step``), and its moments are float32;
* the weights are kept in the dtype the configuration states
  (``param_dtype``, and float32 for ``float32_params``), as the program
  keeps them: each update's step, and the updated weight, are rounded to
  it; everything else, gradients included, is float32;
* each layer is the configuration's architecture module's ``block``
  (bench/archs/<mixer>.py), on one row at a time;
* where a block returns an auxiliary loss (a router's balance loss), a
  microbatch's objective is its mean token cross-entropy plus
  ``aux_loss_weight`` times the sum over layers of the block's ``aux``,
  each the mean over the microbatch's rows; its gradient reaches the
  layer's weights and the layers below under both semantics above;
* the reported loss is the mean over microbatches of each microbatch's
  mean token cross-entropy, without the auxiliary term (the program's
  ``loss`` reports it apart, as ``aux``).

Matrix products run at ``Precision.HIGHEST``.  ``fp8=True`` is the
control, the reference computed in float8 e4m3 where the configuration
computes in bfloat16: every matrix product's operands, the embedding's
rows and every layer's output are rounded to e4m3 (a power-of-two scale
per tensor), gradients passed straight through.  The head's loss is
computed in row blocks, and an architecture module keeps its own largest
intermediate small (attention one key-value head group at a time), so
that the reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import statistics
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import CellFailed, archs, weights

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
HEAD_BLOCKS = 4


def fake_fp8(x):
    """x rounded to e4m3 at a power-of-two scale; identity gradient."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / E4M3_MAX)))
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_dot(fp8: bool):
    def dot(eq, a, b):
        if fp8:
            a, b = fake_fp8(a), fake_fp8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    return dot


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


# ------------------------------------------------------------------ layout

def to_reference(tree, n_layers: int, pp: int):
    """The program's stage-stacked layout -> {embed, head, final_norm,
    layers: [per-layer dict]}, dtypes kept (layer l = stage l // lps,
    position l % lps)."""
    lps = n_layers // pp
    layers = [jax.tree.map(lambda a: a[l // lps],
                           tree["stages"][f"layer_{l % lps}"])
              for l in range(n_layers)]
    return {"embed": tree["embed"], "head": tree["head"],
            "final_norm": tree["final_norm"], "layers": layers}


def f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def leaf_norms(ref_tree) -> Dict[str, jnp.ndarray]:
    """{name: L2 norm}, named by path in the reference's layout."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(ref_tree)[0]:
        out[weights.leaf_name(path)] = jnp.sqrt(jnp.sum(a.astype(F32) ** 2))
    return out


def moment_norms(m) -> dict:
    """Adam's first moment read back as gradients, in the reference's
    layout: {"grad": {leaf: ||m||}}."""
    return {"grad": {k: float(x) for k, x in jax.jit(leaf_norms)(m).items()}}


def change_norms(cfg, key, like, params):
    """{name: norm of the leaf's change from the seed's weights};
    ``like`` has the program layout's shapes, ``params`` the reference's
    layout."""
    p0 = to_reference(weights.generate(cfg, key, like),
                      cfg["num_hidden_layers"], cfg["plan"]["pp"])
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(F32) - b.astype(F32), params, p0))


# ------------------------------------------------------------------ training

def adam_update(opt, p, g, m, v, t):
    """Adam with float32 moments and step; the weight stays in its own
    dtype, so the step and the result are rounded to it."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    step = lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
    return p - step.astype(p.dtype), m, v


def tree_adam(opt, p, g, m, v, t):
    out = jax.tree.map(lambda *a: adam_update(opt, *a, t), p, g, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


class Reference:
    """Jitted pieces of one configuration's reference; ``train`` runs
    whole rounds and returns the readings ``gaps`` takes."""

    def __init__(self, cfg: dict, *, fp8: bool = False):
        if cfg["plan"]["pp"] != 1:
            raise NotImplementedError(
                "the reference follows one pipeline stage; a plan with "
                "pp > 1 delays weight versions between stages")
        self.cfg = cfg
        dot = make_dot(fp8)
        arch = archs.load(cfg)
        self.kinds = [arch.layer_kind(cfg, i)
                      for i in range(cfg["num_hidden_layers"])]
        weight = cfg.get("aux_loss_weight")
        opt = cfg["optimizer"]
        # weights stay in the dtype they are kept in; every computation,
        # and every gradient, is float32
        act = fake_fp8 if fp8 else (lambda x: x)

        def rows(i):
            """Layer i, and every layer of its kind, over a microbatch's
            rows: (x, aux per row or None)."""
            def one(p, x):
                y, aux = arch.block(cfg, dot, p, x, i)
                return act(y), aux
            return jax.vmap(one, (None, 0))

        def back(layer, p, x, g):
            """(dp, dx) of the microbatch's objective, g = d/dy."""
            (_, aux), vjp = jax.vjp(layer, f32(p), x)
            if aux is None:
                return vjp((g, None))
            if weight is None:
                raise CellFailed(
                    f"a {cfg['mixer']} block returns an auxiliary loss and "
                    f"the configuration states no aux_loss_weight")
            return vjp((g, jnp.full(aux.shape, weight / aux.shape[0], F32)))

        def kind(layer):
            def layer_fwd(p, x):
                return layer(f32(p), x)[0]

            def layer_bwd(p, m, v, x, g, t):
                dp, dx = back(layer, p, x, g)
                p, m, v = tree_adam(opt, p, dp, m, v, t)
                return p, m, v, dx

            # flush schedules: gradients summed over the round, one update
            def layer_grad(p, x, g):
                return back(layer, p, x, g)

            return (jax.jit(layer_fwd),
                    jax.jit(layer_bwd, donate_argnums=(0, 1, 2)),
                    jax.jit(layer_grad))

        self.layer_fwd, self.layer_bwd, self.layer_grad = {}, {}, {}
        for k in dict.fromkeys(self.kinds):
            (self.layer_fwd[k], self.layer_bwd[k],
             self.layer_grad[k]) = kind(rows(self.kinds.index(k)))

        def head_loss(hp, x, labels):
            d = x.shape[-1]
            xb = x.reshape(HEAD_BLOCKS, -1, d)
            lb = labels.reshape(HEAD_BLOCKS, -1)

            def blk(args):
                xx, ll = args
                logits = dot("nd,dv->nv", arch.final_norm(
                    cfg, hp["final_norm"], xx), hp["head"])
                lse = jax.nn.logsumexp(logits, -1)
                return jnp.sum(lse - jnp.take_along_axis(
                    logits, ll[:, None], -1)[:, 0])

            return jnp.sum(jax.lax.map(jax.checkpoint(blk), (xb, lb))) \
                / labels.size

        def head_step(hp, m, v, x, labels, t):
            loss, (dhp, dx) = jax.value_and_grad(head_loss, (0, 1))(
                f32(hp), x, labels)
            hp, m, v = tree_adam(opt, hp, dhp, m, v, t)
            return loss, dx, hp, m, v

        def embed_acc(acc, tokens, g):
            return acc.at[tokens.reshape(-1)].add(g.reshape(-1, g.shape[-1]))

        def head_grad(hp, x, labels):
            loss, (dhp, dx) = jax.value_and_grad(head_loss, (0, 1))(
                f32(hp), x, labels)
            return loss, dhp, dx

        def update(p, m, v, gsum, n, t):
            return tree_adam(opt, p, jax.tree.map(lambda a: a / n, gsum), m,
                             v, t)

        def embed_step(e, m, v, acc, n, t):
            return adam_update(opt, e, acc / n, m, v, t)

        self.head_step = jax.jit(head_step, donate_argnums=(0, 1, 2))
        self.embed_acc = jax.jit(embed_acc, donate_argnums=0)
        self.embed_step = jax.jit(embed_step, donate_argnums=(0, 1, 2))
        self.gather = jax.jit(lambda e, tok: act(e[tok].astype(F32)))
        self.flush = cfg["plan"]["stash_mode"] == "flush"
        self.head_grad = jax.jit(head_grad)
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                           donate_argnums=0)
        self.update = jax.jit(update, donate_argnums=(0, 1, 2))

    def initial(self, key, shapes):
        """The initial weights in the reference's layout and dtypes."""
        pp = self.cfg["plan"]["pp"]
        n = self.cfg["num_hidden_layers"]
        return jax.jit(lambda k: to_reference(
            weights.generate(self.cfg, k, shapes), n, pp))(key)

    def train(self, key, shapes, batches: List[dict], *,
              microbatches_used: int | None = None,
              drop_state: bool = False) -> dict:
        """Rounds over ``batches`` ({tokens, labels}: (R, rows, S)) from the
        seed's initial weights.  Two planted faults: ``microbatches_used``
        < R leaves the rest of every round out (the mean is over the
        rest), and ``drop_state`` starts every round from zero moments at
        t = 1 (the optimizer's state dropped between steps).

        Returns {"loss": [per round], "grad": {leaf: ||m|| after round 1},
        "change": {leaf: ||W_end - W_0||}}."""
        p = self.initial(key, shapes)
        zeros = lambda t: jax.tree.map(lambda a: jnp.zeros(a.shape, F32), t)
        head = {"head": p.pop("head"), "final_norm": p.pop("final_norm")}
        fresh = lambda: {
            "layers": [(zeros(lp), zeros(lp)) for lp in p["layers"]],
            "head": (zeros(head), zeros(head)),
            "embed": (zeros(p["embed"]), zeros(p["embed"]))}
        opt = fresh()
        losses, grads = [], None
        for rnd, batch in enumerate(batches):
            t = jnp.float32(1 if drop_state else rnd + 1)
            if drop_state and rnd:
                # the old moments go before the new are made: both at
                # once do not fit beside the weights on one chip
                opt = hm = hv = m = v = em = ev = None
                opt = fresh()
            tokens, labels = batch["tokens"], batch["labels"]
            used = microbatches_used or tokens.shape[0]
            acc = jnp.zeros(p["embed"].shape, F32)
            if self.flush:
                hsum = zeros(head)
                lsum = [zeros(lp) for lp in p["layers"]]
            round_loss = []
            for r in range(used):
                xs = [self.gather(p["embed"], tokens[r])]
                for k, lp in zip(self.kinds, p["layers"]):
                    xs.append(self.layer_fwd[k](lp, xs[-1]))
                x_top = xs.pop()
                if self.flush:
                    loss, dh, g = self.head_grad(head, x_top, labels[r])
                    hsum = self.add(hsum, dh)
                else:
                    loss, g, head, hm, hv = self.head_step(
                        head, *opt["head"], x_top, labels[r], t)
                    opt["head"] = (hm, hv)
                round_loss.append(loss)
                for i in reversed(range(len(p["layers"]))):
                    if self.flush:
                        dp, g = self.layer_grad[self.kinds[i]](
                            p["layers"][i], xs[i], g)
                        lsum[i] = self.add(lsum[i], dp)
                        continue
                    lp, m, v, g = self.layer_bwd[self.kinds[i]](
                        p["layers"][i], *opt["layers"][i], xs[i], g, t)
                    p["layers"][i], opt["layers"][i] = lp, (m, v)
                acc = self.embed_acc(acc, tokens[r], g)
            if self.flush:
                n = jnp.float32(used)
                head, hm, hv = self.update(head, *opt["head"], hsum, n, t)
                opt["head"] = (hm, hv)
                for i, lp in enumerate(p["layers"]):
                    lp, m, v = self.update(lp, *opt["layers"][i], lsum[i],
                                           n, t)
                    p["layers"][i], opt["layers"][i] = lp, (m, v)
            p["embed"], em, ev = self.embed_step(
                p["embed"], *opt["embed"], acc, jnp.float32(used), t)
            opt["embed"] = (em, ev)
            losses.append(float(np.mean([float(x) for x in round_loss])))
            if rnd == 0:
                moment = lambda i: {
                    "embed": opt["embed"][i], "head": opt["head"][i]["head"],
                    "final_norm": opt["head"][i]["final_norm"],
                    "layers": [mv[i] for mv in opt["layers"]]}
                grads = moment_norms(moment(0))
        p.update(head)
        change = jax.jit(lambda k, q: change_norms(self.cfg, k, shapes, q))(
            key, p)
        change = {k: float(v) for k, v in change.items()}
        return {"loss": losses, **grads, "change": change}


def gaps(prog: dict, ref: dict, *, still: float = 1e-3) -> dict:
    """Every number the check can compare, and where each is set.

    A leaf's gap is |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf.
      loss_gap: the worst of the first two steps' |program loss -
        reference loss| / reference loss, taken at the weights of at most
        one update;
      last_loss_gap: the same for the last checked step, read but not
        compared: after two near-sign Adam steps that loss jumps on some
        seeds, and its gap with the jump, in any precision;
      grad_gap, grad_median_gap: the worst and the median leaf's gap of
        Adam's first moment after the first step, ||m||;
      change_gap, change_median_gap: the same for each leaf's change over
        the checked steps, over the leaves whose reference gradient is at
        least ``still`` times the median leaf's (the others move by
        round-off alone).
    """
    def leaf_gaps(key, names=None):
        names = list(ref[key]) if names is None else names
        med = statistics.median(ref[key][k] for k in names)
        return {k: abs(prog[key][k] - ref[key][k]) / max(ref[key][k], med)
                for k in names}

    loss = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    med_g = statistics.median(ref["grad"].values())
    moved = [k for k, r in ref["grad"].items() if r >= still * med_g]
    out = {"loss_gap": max(loss[:2]), "last_loss_gap": loss[-1],
           "at": {"loss_gap": f"step {int(np.argmax(loss[:2]))}"},
           "left_out": sorted(set(ref["grad"]) - set(moved))}
    for name, key, names in (("grad", "grad", None),
                             ("change", "change", moved)):
        g = leaf_gaps(key, names)
        worst = max(g, key=g.get)
        out[f"{name}_gap"] = g[worst]
        out["at"][f"{name}_gap"] = worst
        out[f"{name}_median_gap"] = statistics.median(g.values())
    return out
