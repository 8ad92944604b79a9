"""1 - busy / window over the traced window, where busy is the union of the
device's operation intervals, in percent."""
from bench import trace_reduce


def read(obs):
    w = trace_reduce.length(obs["window"])
    if w <= 0 or not obs["trace"].n_devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(obs["trace"], obs["window"]) / w)
