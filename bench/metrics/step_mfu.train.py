"""Model FLOPs per token (bench/flops.py, 3 x forward) times the tokens per
second of the run's window, over the chip's bf16 peak, in percent."""


def read(obs):
    rate = obs.get("train_tokens_per_s")
    if not rate:
        return None
    f = obs["flops"].train_flops_per_token(obs["model"])
    return 100.0 * f * rate / obs["peak"]["bf16_flops_per_s"]
