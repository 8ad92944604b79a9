"""1 - busy / ticks, where ticks is the union of the benchmark's tick spans
inside the traced window, so that waiting for arrivals does not count, and
busy the device's operation intervals inside them, in percent."""
from bench import trace_reduce


def read(obs):
    w = trace_reduce.length(obs["ticks"])
    if w <= 0 or not obs["trace"].n_devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(obs["trace"], obs["ticks"]) / w)
