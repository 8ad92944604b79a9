"""A train run with the step broken underneath comes out not correct.

On the CPU at a tiny size, with the cell's committed limits: a sound run is
correct; a step that returns its parameters unchanged, a loss over half of
the batch (the mean taken over the rest), and a loss whose label tokens are
altered where they are produced (shifted by one position) are not.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import train_cell
from bench.tests.conftest import tiny_cell


def _loss_with(orig, change):
    def loss(params, cfg, batch, **kw):
        return orig(params, cfg, change(batch), **kw)
    return loss


FAULTS = {
    "sound": None,
    "state_unchanged": ("repro.launch.train", "apply_updates",
                        lambda orig: (lambda params, updates: params)),
    "half_batch": ("repro.models.transformer", "lm_loss", lambda orig: _loss_with(
        orig, lambda b: {k: v[: v.shape[0] // 2] for k, v in b.items()})),
    "token_altered": ("repro.models.transformer", "lm_loss", lambda orig: _loss_with(
        orig, lambda b: {**b, "labels": jnp.roll(b["labels"], 1, axis=1)})),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_caught(fault, monkeypatch, peak):
    import importlib

    if FAULTS[fault] is not None:
        mod, name, wrap = FAULTS[fault]
        mod = importlib.import_module(mod)
        monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    jax.clear_caches()
    out = train_cell.run(tiny_cell("stlt-base.train-4k", seconds=1.0), peak)
    assert out["correct"] is (fault == "sound"), (fault, out["checks"])
