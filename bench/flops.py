"""Model and kernel operation counts, from shapes alone.

Model FLOPs per token (the numerator of every ``*mfu*`` metric):

* 2 x the matmul parameters of the blocks: the FFN and the STLT mixer's
  value and output projections. The embedding lookup is not a matmul and
  is left out, as is the adaptive-mask projection ``w_alpha``, which is
  applied once per pooled summary and not per token of a prompt.
* the STLT mixer's algorithmic work, 8 * S * d_model per token per layer:
  the complex recurrence h <- lambda h + v and the readout Re(sum u h),
  counted the same whatever engine runs it, so a faster engine shows as a
  higher share.
* the output head, 2 * d_model * vocab, only at positions whose logits
  are read (one per admitted prompt, one per decoded token, every
  position in training).
* training counts 3 x the forward (forward and backward); work that remat
  recomputes is not counted.

This is ``benchmarks/roofline.py``'s 6N/2N arithmetic with the mixer's own
work and the head's restriction to read positions added.
"""
from __future__ import annotations


def _ffn_params(m: dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    return (3 if m["act"] == "swiglu" else 2) * d * f


def block_matmul_params(m: dict) -> int:
    """Matmul parameters of one block (FFN + STLT w_v, w_o)."""
    return _ffn_params(m) + 2 * m["d_model"] ** 2


def mixer_flops_per_token(m: dict) -> int:
    """STLT recurrence + readout per token, one layer."""
    return 8 * m["stlt_nodes"] * m["d_model"]


def token_flops(m: dict) -> int:
    """Forward FLOPs of one token through every block (head excluded)."""
    return m["num_layers"] * (2 * block_matmul_params(m)
                              + mixer_flops_per_token(m))


def head_flops(m: dict) -> int:
    """Forward FLOPs of the output head at one read position."""
    return 2 * m["d_model"] * m["vocab"]


def train_flops_per_token(m: dict) -> int:
    return 3 * (token_flops(m) + head_flops(m))


def serve_flops(m: dict, prompt_tokens: int, prompts_finished: int,
                decoded_tokens: int) -> int:
    """Forward FLOPs of serving: every prompt token, the head once per
    finished prompt, and every decoded token with its head."""
    return (prompt_tokens * token_flops(m) + prompts_finished * head_flops(m)
            + decoded_tokens * (token_flops(m) + head_flops(m)))


def stlt_scan_cost(bh: int, n: int, dh: int, s: int, itemsize: int = 4):
    """(FLOPs, HBM bytes) of one ``kernels.stlt_scan`` call over [bh, n, dh]
    with s nodes: 8 * s * dh per token per row of work; values read once,
    outputs written once, carries read and written in float32."""
    flops = 8 * bh * n * dh * s
    nbytes = 2 * bh * n * dh * itemsize + 4 * bh * s * dh * 4
    return flops, nbytes


def relevance_flash_cost(bh: int, n: int, dh: int, s: int, causal: bool = True,
                         itemsize: int = 4):
    """(FLOPs, HBM bytes) of one ``kernels.relevance_flash`` call: the
    Laplace coefficients (8 * s * dh per token), the relevance scores
    Re(sum_k L_q conj(L_k)) (4 * s * dh per query-key pair), and the
    softmax-weighted values (2 * dh per pair); x and v read, z written."""
    pairs = n * (n + 1) // 2 if causal else n * n
    flops = bh * (8 * n * s * dh + pairs * (4 * s * dh + 2 * dh))
    nbytes = 3 * bh * n * dh * itemsize
    return flops, nbytes
