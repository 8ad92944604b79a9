"""The control comes out not correct, at a size a test run holds.

The control is the plain reference one precision step below what the
configuration states (``stlt_lm.control_for``), put in the program's place:
its first token and logits are read by the check as the program's would
be, against the cell's committed limit. The reference's own float32 reading
at the same positions stays inside the limit. The model keeps its published
widths with two layers; the train cell's runs two steps at a small batch.
"""
import numpy as np
import pytest

from bench import run as harness
from bench import train_cell, weights
from bench.references import stlt_lm
from bench.serve_cell import build_config
from bench.tests.conftest import TINY_MODEL


@pytest.mark.parametrize("workload", ["stlt-base.chat-saturated",
                                      "qwen2-1.5b-stlt.mixed-poisson"])
def test_serve_control_fails_the_check(workload):
    import jax.numpy as jnp

    cell = harness.load_cell(workload)
    conf = cell.config
    conf["model"]["num_layers"] = 2
    cfg = build_config(conf)
    m = dict(conf["model"], prefill_chunk=cell.traffic["engine"]["prefill_chunk"])
    W = weights.make(cfg, 5)
    Wx = {"embed": W["embed"], "final_norm": W["final_norm"],
          "layers": stlt_lm.stack_layers(W, m["num_layers"])}
    rng = np.random.default_rng(0)
    low = stlt_lm.control_for(m["dtype"], conf["matmul_precision"])
    exact, control = [], []
    for _ in range(2):
        prompt = rng.integers(3, cfg.vocab, 200).astype(np.int32)
        n = stlt_lm.bucket(200 - 1 + 128)
        toks = np.zeros(n, np.int32)
        toks[:200] = prompt
        kw = dict(m=tuple(sorted(m.items())), n=n, n_read=128, rows=128)
        args = (jnp.asarray(toks), stlt_lm.sinusoid(n, m["d_model"]), 200, 199)
        for prec, out in ((stlt_lm.EXACT, exact), (low, control)):
            row = np.asarray(stlt_lm.served_logits(Wx, *args, prec=prec, **kw)[0])
            out.append((prompt, np.array([row.argmax()], np.int32), [(0, row)]))
    limit = cell.limits["served_logit_error"]
    assert stlt_lm.serve_readings(W, m, exact, 128)["served_logit_error"] <= limit
    got = stlt_lm.serve_readings(W, m, control, 128)["served_logit_error"]
    assert got > limit, (low.name, got, limit)


def test_train_control_fails_the_check():
    cell = harness.load_cell("stlt-base.train-4k")
    conf, tr = cell.config, cell.traffic
    conf["model"].update(TINY_MODEL)
    cfg = build_config(conf)
    m = conf["model"]
    feed = train_cell.make_batches(cfg.vocab, 2, 128, tr["zipf_exponent"], 5)
    batches = [feed(s) for s in range(2)]
    W = weights.make(cfg, 5, served=False, embed_std=tr["embed_std"])
    exact = stlt_lm.train_steps(W, m, tr["optimizer"], batches)
    low = stlt_lm.control_for(m["dtype"], conf["matmul_precision"])
    control = stlt_lm.train_steps(W, m, tr["optimizer"], batches, prec=low)
    got = train_cell.compare(control, exact)
    assert any(got[k] > lim for k, lim in cell.limits.items()), (got, cell.limits)
    same = train_cell.compare(exact, exact)
    assert all(same[k] <= lim for k, lim in cell.limits.items())
