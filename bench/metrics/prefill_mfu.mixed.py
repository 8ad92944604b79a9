"""Model FLOPs of the valid prompt tokens prefilled in the traced window
(bench/flops.py: blocks and mixer per token, the head once per finished
prompt) over the device time of the ``prefill_chunk`` programs times the
chip's bf16 peak, in percent. Padding of a chunk or of a co-pending bucket
counts as waste."""
from bench import trace_reduce


def read(obs):
    runs = trace_reduce.programs(obs["trace"], "prefill_chunk", obs["window"])
    t = sum(t1 - t0 for t0, t1 in runs)
    done = obs["traced"]
    if t <= 0 or done["prefill_tokens"] <= 0:
        return None
    f = obs["flops"].serve_flops(obs["model"], done["prefill_tokens"],
                                 done["prompts_done"], 0)
    return 100.0 * f / (t * obs["peak"]["bf16_flops_per_s"])
