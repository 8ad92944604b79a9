"""The harness finds a cell's files by name and refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench import run as harness

ROOT = harness.ROOT


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in spec()["workloads"]])
def test_every_cell_finds_its_files(workload):
    cell = harness.load_cell(workload)
    wl = {w["name"]: w for w in spec()["workloads"]}[workload]
    assert cell.config["name"] == wl["config"]
    assert cell.traffic["kind"] in ("serve", "train")
    assert os.path.exists(os.path.join(ROOT, "bench", cell.traffic["kind"] + "_cell.py"))
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
    assert all(v is not None for v in cell.limits.values())


@pytest.mark.parametrize("name,file", [
    ("decode_step_ms.saturated", "decode_step_ms.py"),
    ("device_idle_share.train", "device_idle_share.py"),
    ("device_idle_share.mixed", "device_idle_share.mixed.py"),
])
def test_metric_reader_falls_back_to_the_shared_file(name, file):
    read = harness.metric_reader(name)
    assert read.__code__.co_filename == os.path.join(ROOT, "bench", "metrics", file)
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric.train")


@pytest.mark.parametrize("stats,peak", [
    # a train step on the v5e: 0.85 GB of buffers, 10.8 GB of program scratch
    ({"peak_bytes_in_use": 850739712, "peak_bytes_reserved": 10819272704},
     11670012416),
    ({}, 0),
    (None, 0),
])
def test_memory_peak_counts_reserved_program_scratch(stats, peak):
    class Dev:
        def memory_stats(self):
            return stats

    class Jax:
        @staticmethod
        def devices():
            return [Dev()]

    assert harness.memory_peak_bytes(Jax) == peak


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
    with pytest.raises(KeyError):
        harness.peak_of("TPU v0")
    assert harness.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "stlt-base.chat-saturated", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "cpu" in r.stderr
