"""The harness's check: a run whose timed path is broken underneath is not
correct, for each fault a one-chip training cell can have (the exchange
between chips does not exist on one chip)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


def unchanged(step):
    """A step that returns its state unchanged."""
    return lambda state, batch: (state, step(state, batch)[1])


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest: the
    second half of the microbatches is replaced by the first."""
    import jax.numpy as jnp

    def broken(state, batch):
        half = batch["tokens"].shape[0] // 2
        dup = {k: jnp.concatenate([v[:half], v[:half]]) for k, v in
               batch.items()}
        return step(state, dup)
    return broken


def state_dropped(step):
    """The optimizer's state dropped between steps: every step starts
    from zero moments and step count."""
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        fresh = {k: (jax.tree.map(jnp.zeros_like, v)
                     if k.startswith("opt_") or k == "step" else v)
                 for k, v in state.items()}
        return step(fresh, batch)
    return broken


def loss_altered(step):
    """The answer altered where it is produced: the step's loss."""
    def broken(state, batch):
        state, metrics = step(state, batch)
        return state, dict(metrics, loss=metrics["loss"] * 1.01)
    return broken


@pytest.mark.parametrize("fault", [unchanged, half_batch, state_dropped,
                                   loss_altered],
                         ids=lambda f: f.__name__)
def test_broken_step_is_not_correct(monkeypatch, fault):
    tiny.use_tiny_cell(monkeypatch, "tiny-danube")
    result = tiny.run(seed=2 ** 31 + 6, break_step=fault)
    assert result["correct"] is False, result["checks"]
