"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the workload names its configuration (``bench/configs/<config>.json``)
and its traffic mix (``bench/traffic/<traffic>.json``); the mix's ``kind``
picks the module that runs it (``bench/<kind>_cell.py``); each per-layer metric is read
by ``bench/metrics/<metric>.py`` (or the shared ``<base>.py`` of a metric
``<base>.<cell suffix>``); the limits of the correctness check are in
``bench/limits/<workload>.json``.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), and last ``checks``: each number the
correctness check compared, with its limit. Those numbers are also the last
lines of standard error.

With no accelerator, or fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<workload>.json
    end_to_end: list      # BENCHMARK.json metrics that this cell reports
    per_layer: list
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    t_start: float = 0.0  # process start on the host clock (set-up origin)
    compiles: "CompileCounter" = None


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Find a workload's files by name; raises KeyError/FileNotFoundError."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload, chips=wl["chips"],
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(root, "bench", "traffic",
                                   wl["traffic"] + ".json")),
        limits=_json(os.path.join(root, "bench", "limits", workload + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(obs)`` function of ``bench/metrics/<name>.py``, or, where a
    metric named ``<base>.<cell suffix>`` has no file of its own, of
    ``bench/metrics/<base>.py``, the reader that its cells share."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(root, "bench", "metrics",
                            name.rsplit(".", 1)[0] + ".py")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(jax, chips: int) -> dict:
    """The accelerator as JAX reports it; exits 1 where there is none or
    too few chips."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{d.platform} device(s)")
        raise SystemExit(1)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_of(kind: str, root: str = ROOT) -> dict:
    """The device's row of ``bench/peaks.json``; an unknown kind is an error."""
    peaks = _json(os.path.join(root, "bench", "peaks.json"))
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, as
    ``jax.monitoring`` reports them."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.compiled = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == self.COMPILE:
            self.compiled += 1

    def _on_event(self, event, **kwargs):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def total(self) -> int:
        return self.compiled + self.cache_hits


def enable_cache(jax, root: str = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` in the checkout (a fixed path: the path
    is part of the key). Every program is cached, however short."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def matmul_precision(jax, cell: Cell):
    """The matmul precision the configuration states, for the program."""
    import contextlib

    p = cell.config.get("matmul_precision", "default")
    return (contextlib.nullcontext() if p == "default"
            else jax.default_matmul_precision(p))


def memory_peak_bytes(jax) -> int:
    """The chip's peak memory: the allocator's peak of buffers in use plus
    its peak reserved for programs' temporaries. On the TPU the two are
    apart: a compiled step's scratch is reserved, not in use."""
    mem = jax.devices()[0].memory_stats() or {}
    return int(mem.get("peak_bytes_in_use", 0)) + int(
        mem.get("peak_bytes_reserved", 0))


def finish(cell: Cell, out: dict, device: dict) -> dict:
    """The result line: the cell's declared metrics, its checks last."""
    names = cell.per_layer if cell.trace else cell.end_to_end
    metrics = {}
    for m in names:
        v = out["metrics"].get(m["name"])
        if v is None:
            if not cell.trace:
                raise KeyError(f"{cell.name} measured no {m['name']}")
            continue          # a reader with nothing to read
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": {**device, **out.get("device", {})}}
    if cell.trace and "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    cell = load_cell(args.workload)
    cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, bool(args.trace)
    cell.t_start = t_start

    import jax

    device = check_device(jax, cell.chips)
    cache = enable_cache(jax)
    cell.compiles = CompileCounter(jax)
    log(f"{cell.name}: seed {cell.seed}, {cell.seconds:g} s, trace "
        f"{int(cell.trace)}; {device['kind']} x{device['count']}, jax "
        f"{jax.__version__}; compile cache {cache}")
    kind = importlib.import_module(f"bench.{cell.traffic['kind']}_cell")
    with matmul_precision(jax, cell):
        out = kind.run(cell, peak_of(device["kind"]))
    result = finish(cell, out, device)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
