"""Model FLOPs of every valid prompt token and generated token served in the
traced window (bench/flops.py), over the window times the chip's bf16
peak, in percent."""
from bench import trace_reduce


def read(obs):
    w = trace_reduce.length(obs["window"])
    done = obs["traced"]
    f = obs["flops"].serve_flops(obs["model"], done["prefill_tokens"],
                                 done["prompts_done"], done["decoded"])
    if w <= 0 or f <= 0:
        return None
    return 100.0 * f / (w * obs["peak"]["bf16_flops_per_s"])
