"""Record the small serve trace that bench/tests/test_tick_phases.py reads.

    python3 bench/testdata/record_serve_trace.py [--out DIR]   # on the chip

A one-layer stlt-base at the CPU tests' widths serves two requests over
two ticks through ``bench/engine_driver.py``, each tick inside a
``bench.tick`` span and the whole inside ``bench.window``, as a serve cell's
traced window is; the engine adds its own ``serve.*`` phase spans. The
profiler records no Python calls, and the file keeps every event but drops
what no reader uses (:func:`slim`), to stay small. The trace is written to
``serve.xplane.pb`` and the engine's counters for it to ``serve.json`` in
DIR (by default ``bench/testdata``).
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import weights  # noqa: E402
from bench.engine_driver import EngineDriver  # noqa: E402
from bench.run import load_cell  # noqa: E402
from bench.serve_cell import build_config  # noqa: E402

MODEL = dict(vocab=512, num_layers=1, d_model=64, num_heads=4,
             num_kv_heads=4, d_ff=128, stlt_nodes=8, stlt_chunk=16)
SLOTS, CHUNK = 4, 64
# two ticks: both prompts in one [SLOTS, CHUNK] prefill, the short one goes
# live and ends; the long one's second chunk alone at [1, CHUNK]
PROMPTS = (100, 20)
OUTPUT = 2


def _fields(buf: bytes):
    """(field number, wire type, raw bytes of the whole field, payload) of
    each field of a protobuf message; payload is the bytes of a
    length-delimited field, else None."""
    i = 0
    while i < len(buf):
        start, key, shift = i, 0, 0
        while True:
            c = buf[i]
            i += 1
            key |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                break
        wt, payload = key & 7, None
        if wt == 0:
            while buf[i] & 0x80:
                i += 1
            i += 1
        elif wt in (1, 5):
            i += 8 if wt == 1 else 4
        else:                       # 2: length-delimited
            n, shift = 0, 0
            while True:
                c = buf[i]
                i += 1
                n |= (c & 0x7F) << shift
                shift += 7
                if c < 0x80:
                    break
            payload = buf[i:i + n]
            i += n
        yield key >> 3, wt, buf[start:i], payload


def _delimited(number: int, payload: bytes) -> bytes:
    out, n = [(number << 3) | 2], len(payload)
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            break
    return bytes(out) + payload


def _without_stats(line: bytes) -> bytes:
    """An ``XLine`` whose events (field 4) carry no stats (their field 4)."""
    return b"".join(
        _delimited(4, b"".join(r for m, _, r, _ in _fields(ev) if m != 4))
        if n == 4 else raw for n, _, raw, ev in _fields(line))


def slim(xspace: bytes) -> bytes:
    """The trace without what no reader uses: the ``/host:metadata`` plane
    (the programs' metadata), the device's per-op stats, and in every plane
    each event kind's metadata but its id and name. An ``XSpace`` protobuf:
    planes are field 1; a plane's name is field 2, its lines field 3 and
    its event metadata map field 4."""
    out = []
    for num, _, raw, plane in _fields(xspace):
        if num != 1:
            out.append(raw)
            continue
        fields = list(_fields(plane))
        names = [p for n, _, _, p in fields if n == 2]
        if names == [b"/host:metadata"]:
            continue
        device = bool(names) and names[0].startswith(b"/device:")
        kept = []
        for n, _, raw_f, entry in fields:
            if n == 3 and device:
                raw_f = _delimited(3, _without_stats(entry))
            elif n == 4:        # map entry: key 1, value 2 (XEventMetadata)
                parts = []
                for en, _, raw_e, meta in _fields(entry):
                    if en == 2:  # keep the metadata's id (1) and name (2)
                        raw_e = _delimited(2, b"".join(
                            r for m, _, r, _ in _fields(meta) if m in (1, 2)))
                    parts.append(raw_e)
                raw_f = _delimited(4, b"".join(parts))
            kept.append(raw_f)
        out.append(_delimited(1, b"".join(kept)))
    return b"".join(out)


def serve(engine, vocab: int) -> int:
    """Serve the requests, one ``bench.tick`` span a tick; ticks run."""
    rng = np.random.default_rng(0)
    d = EngineDriver(engine, SLOTS, CHUNK)
    for i, n in enumerate(PROMPTS):
        d.submit(i, rng.integers(3, vocab, n), OUTPUT)
    ticks = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while d.active():
            with jax.profiler.TraceAnnotation("bench.tick"):
                d.tick()
            ticks += 1
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(0.001)
    d.finish()
    return ticks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=HERE)
    out = ap.parse_args().out
    os.makedirs(out, exist_ok=True)
    from repro.serving import ServeEngine

    cell = load_cell("stlt-base.chat-saturated")
    cell.config["model"].update(MODEL)
    cfg = build_config(cell.config)
    engine = ServeEngine(weights.make(cfg, 1), cfg, max_len=512,
                         prefill_chunk=CHUNK)
    serve(engine, cfg.vocab)                 # compile every program first
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0             # spans only, no Python calls
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    ticks = serve(engine, cfg.vocab)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    with open(src, "rb") as f, open(os.path.join(out, "serve.xplane.pb"),
                                    "wb") as g:
        g.write(slim(f.read()))
    st = engine.tick_stats
    with open(os.path.join(out, "serve.json"), "w") as f:
        json.dump({"ticks": ticks, "host_syncs": st["host_syncs"],
                   "prefill_dispatches": st["prefill_dispatches"],
                   "prefill_valid_tokens": st["prefill_valid_tokens"],
                   "prefill_slot_tokens": st["prefill_slot_tokens"],
                   "decode_dispatches": st["decode_dispatches"],
                   "device_kind": jax.devices()[0].device_kind}, f)
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
