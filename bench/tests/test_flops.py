"""bench/flops.py against counts made by hand from the published widths."""
import json
import os

import pytest

from bench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def model(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name,block,token,head", [
    # stlt-base: gelu FFN 2*512*2048 + STLT w_v, w_o 2*512^2; mixer 8*64*512
    ("stlt-base", 2_621_440, 6 * (2 * 2_621_440 + 262_144), 2 * 512 * 32_000),
    # Qwen2-1.5B with STLT: swiglu 3*1536*8960 + 2*1536^2; mixer 8*32*1536
    ("qwen2-1.5b-stlt", 46_006_272, 28 * (2 * 46_006_272 + 393_216),
     2 * 1536 * 151_936),
])
def test_model_flops_by_hand(name, block, token, head):
    m = model(name)
    assert flops.block_matmul_params(m) == block
    assert flops.token_flops(m) == token
    assert flops.head_flops(m) == head
    assert flops.train_flops_per_token(m) == 3 * (token + head)
    # serving: prompt tokens without a head, one head per finished prompt,
    # every decoded token with its head
    assert flops.serve_flops(m, 100, 2, 5) == 100 * token + 2 * head + 5 * (token + head)


def test_stlt_base_train_is_about_198_mflop_per_token():
    assert flops.train_flops_per_token(model("stlt-base")) == 197_394_432


def test_kernel_costs():
    f, b = flops.stlt_scan_cost(bh=16, n=4096, dh=64, s=64)
    assert f == 8 * 16 * 4096 * 64 * 64
    assert b == 2 * 16 * 4096 * 64 * 4 + 4 * 16 * 64 * 64 * 4
    f, b = flops.relevance_flash_cost(bh=1, n=4, dh=2, s=3, causal=True)
    assert f == 8 * 4 * 3 * 2 + 10 * (4 * 3 * 2 + 2 * 2)
    assert b == 3 * 4 * 2 * 4
