"""Shared pieces of the benchmark's CPU tests: tiny versions of the cells."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = dict(vocab=512, num_layers=2, d_model=64, num_heads=4,
                  num_kv_heads=4, d_ff=128, stlt_nodes=8, stlt_chunk=16)


def tiny_cell(workload: str, seed: int = 7, seconds: float = 2.0):
    """The workload's cell with the model and traffic cut to CPU size;
    its limits are the committed ones."""
    import time

    import jax

    from bench import run as harness

    cell = harness.load_cell(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL)
    tr = cell.traffic = copy.deepcopy(cell.traffic)
    if tr["kind"] == "serve":
        tr["engine"].update(slots=4, prefill_chunk=64, max_len=4096)
        for c in tr["classes"]:
            for k in ("prompt", "output"):
                c[k]["median"] = max(2, c[k]["median"] // 32)
                c[k]["min"] = max(1, c[k]["min"] // 32)
                c[k]["max"] = max(2, c[k]["max"] // 32)
        if "clients" in tr:
            tr["clients"] = 8
        tr["check"]["requests"] = 8
        if "rate_per_s" in tr:
            tr["rate_per_s"] = 400.0   # every slot busy
    else:
        tr.update(batch=2, seq_len=128)
    cell.seed, cell.seconds, cell.trace = seed, seconds, False
    cell.t_start = time.perf_counter()
    cell.compiles = harness.CompileCounter(jax)
    return cell


@pytest.fixture
def peak():
    return {"bf16_flops_per_s": 197e12}
