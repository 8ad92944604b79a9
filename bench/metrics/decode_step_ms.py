"""Mean device time of one run of the compiled decode step
(``transformer.decode_step``), from the trace's program events, in ms."""
from bench import trace_reduce


def read(obs):
    runs = trace_reduce.programs(obs["trace"], "decode_step", obs["window"])
    if not runs:
        return None
    return sum(t1 - t0 for t0, t1 in runs) / len(runs) * 1e3
