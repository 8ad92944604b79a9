"""Data pipeline: determinism, structure, pipeline prefetch, corpus."""
import os
import subprocess
import sys

import numpy as np

from repro.data import ByteCorpus, DataPipeline, copy_task_batch, lm_batch_stream, needle_batch


def test_lm_stream_learnable_structure():
    b = lm_batch_stream(0, 0, 4, 256, 97)
    # labels are inputs shifted by one
    np.testing.assert_array_equal(b["inputs"][:, 1:], b["labels"][:, :-1])
    # sparse Markov structure: each token has at most 4 distinct successors
    x = np.concatenate([b["inputs"], b["labels"][:, -1:]], axis=1)
    succ = {}
    for row in x:
        for a, c in zip(row[:-1], row[1:]):
            succ.setdefault(int(a), set()).add(int(c))
    assert max(len(v) for v in succ.values()) <= 4


def test_copy_task_structure():
    b = copy_task_batch(0, 0, 3, 10, 50, reverse=True)
    np.testing.assert_array_equal(b["labels"], b["enc_inputs"][:, ::-1])
    assert b["dec_inputs"][0, 0] == 1  # BOS
    np.testing.assert_array_equal(b["dec_inputs"][:, 1:], b["labels"][:, :-1])


def test_needle_batch_plants_answer():
    b = needle_batch(0, 0, 4, 128, 200)
    assert b["mask"].sum() == 4  # one graded position per row
    for i in range(4):
        assert b["inputs"][i, -1] == b["answer"][i]
        assert b["labels"][i, -2] == b["answer"][i]


def test_byte_corpus_split_and_determinism():
    c = ByteCorpus(b"hello world, this is a tiny corpus for testing packing." * 100)
    b1 = c.batch(3, 2, 16)
    b2 = c.batch(3, 2, 16)
    np.testing.assert_array_equal(b1["inputs"], b2["inputs"])
    np.testing.assert_array_equal(b1["inputs"][:, 1:], b1["labels"][:, :-1])
    v = c.batch(3, 2, 16, split="val")
    assert not np.array_equal(v["inputs"], b1["inputs"])


def test_byte_corpus_batches_match_across_processes():
    """Batches depend on (seed, step, split) only: Python salts ``hash`` per
    process, so a split seed taken from it would differ from run to run."""
    code = ("import sys; from repro.data import ByteCorpus; "
            "c = ByteCorpus(bytes(range(256)) * 300); "
            "sys.stdout.write(c.batch(3, 2, 16, split='val')['inputs'].tobytes().hex())")
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    outs = []
    for salt in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": salt, "PYTHONPATH": src}
        outs.append(subprocess.run([sys.executable, "-c", code], env=env,
                                   capture_output=True, text=True,
                                   check=True, timeout=120).stdout)
    assert outs[0] and outs[0] == outs[1]


def test_repo_corpus_reads_tracked_trees_only(tmp_path):
    from repro.data.text import repo_corpus

    (tmp_path / "src" / "pkg").mkdir(parents=True)
    (tmp_path / "src" / "pkg" / "a.py").write_bytes(b"SOURCE\n")
    (tmp_path / "README.md").write_bytes(b"DOCS\n")
    (tmp_path / "smoke.log.txt").write_bytes(b"STRAY-TOP\n")
    (tmp_path / "logs").mkdir()
    (tmp_path / "logs" / "out.txt").write_bytes(b"STRAY-OUT\n")
    (tmp_path / "src" / ".jax_cache").mkdir()
    (tmp_path / "src" / ".jax_cache" / "x.txt").write_bytes(b"STRAY-CACHE\n")
    assert repo_corpus(str(tmp_path)) == b"DOCS\nSOURCE\n"


def test_pipeline_prefetch_order_and_resume():
    seen = []

    def batch_fn(step):
        seen.append(step)
        return {"x": np.full((2,), step)}

    p = DataPipeline(batch_fn, prefetch=2, start_step=5)
    steps = [next(p)[0] for _ in range(4)]
    p.close()
    assert steps == [5, 6, 7, 8]
