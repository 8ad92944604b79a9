"""Record the small trace that bench/tests/test_trace_reduce.py reads.

    python3 bench/testdata/record_trace.py [--out DIR]   # on the chip

Two jitted programs named like the engine's (``prefill_chunk`` and
``decode_step``) run inside ``bench.window`` and ``bench.tick`` spans, with
host sleeps between them so the device idles; the trace is written to
``small.xplane.pb`` and its counts to ``small.json`` in DIR (by default
``bench/testdata``).
"""
import argparse
import glob
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def prefill_chunk(x):
    return jnp.tanh(x @ x) @ x


def decode_step(x):
    return jnp.sin(x @ x).sum(0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=HERE)
    out = ap.parse_args().out
    os.makedirs(out, exist_ok=True)
    pre, dec = jax.jit(prefill_chunk), jax.jit(decode_step)
    x = jnp.ones((1024, 1024), jnp.float32)
    pre(x).block_until_ready()
    dec(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(5):
            with jax.profiler.TraceAnnotation("bench.tick"):
                if i % 2 == 0:
                    pre(x).block_until_ready()
                dec(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    with open(os.path.join(out, "small.json"), "w") as f:
        json.dump({"decode_step": 5, "prefill_chunk": 3, "ticks": 5,
                   "device_kind": jax.devices()[0].device_kind}, f)
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
