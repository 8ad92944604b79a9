"""A serve run with the timed path broken underneath comes out not correct.

Each case drives the rest of a run on the CPU at a tiny size (the look for a
chip is skipped), with the cell's committed limit: a sound run is correct;
a decode step that returns its state unchanged, a decode step that leaves
half of the batch out (each odd row takes the even row's result), and a
sampler that alters the tokens it produces are not. (The cells run on one
chip, so there is no exchange between chips to leave out.)
"""
import jax
import pytest

from bench import serve_cell
from bench.tests.conftest import tiny_cell


def state_unchanged(orig):
    def step(params, cfg, token_t, state, node_cap=None):
        logits, _ = orig(params, cfg, token_t, state, node_cap)
        return logits, state
    return step


def half_batch(orig):
    def step(params, cfg, token_t, state, node_cap=None):
        logits, new = orig(params, cfg, token_t, state, node_cap)
        even = logits[0::2]
        return logits.at[1::2].set(even[: logits[1::2].shape[0]]), new
    return step


def token_altered(orig):
    def sample(logits, rngs, temperatures, top_k=0):
        toks = orig(logits, rngs, temperatures, top_k)
        return (toks + 1) % logits.shape[-1]
    return sample


FAULTS = {
    "sound": None,
    "state_unchanged": ("repro.models.transformer", "decode_step", state_unchanged),
    "half_batch": ("repro.models.transformer", "decode_step", half_batch),
    "token_altered": ("repro.serving.engine", "sample_slot_tokens", token_altered),
}


@pytest.mark.parametrize("workload", ["stlt-base.chat-saturated",
                                      "qwen2-1.5b-stlt.mixed-poisson"])
@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_caught(workload, fault, monkeypatch, peak):
    import importlib

    if FAULTS[fault] is not None:
        mod, name, wrap = FAULTS[fault]
        mod = importlib.import_module(mod)
        monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    jax.clear_caches()
    monkeypatch.setattr(serve_cell, "CAPTURE_EVERY", 1)
    out = serve_cell.run(tiny_cell(workload, seconds=1.5), peak)
    checks = out["checks"]
    assert out["attempted"] > 0 and checks
    assert all(c["value"] is not None for c in checks.values())
    assert out["correct"] is (fault == "sound"), (fault, checks)
