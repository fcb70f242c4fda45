"""Without a TPU the benchmark fails and reports no number; the traffic
generator gives the same inputs for the same seed and the same work for
every seed."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np

from chipbench.harness import traffic
from chipbench.tests.conftest import ROOT

SEED = 3_000_000_017  # wider than 32 bits, as the driver's seeds are


def _run(cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "roshambo.ring4",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p) -> bool:
    lines = p.stdout.strip().splitlines()
    return p.returncode != 0 and not any(l.startswith("{") for l in lines)


def test_cpu_only_machine_gets_no_result():
    p = _run(ROOT)
    assert _no_result(p), p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_get_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _no_result(_run(tmp_path))


def _mix(name):
    return json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json")
                      .read_text())


def test_requests_repeat_for_a_seed_and_keep_their_sizes_across_seeds():
    mixes = [_mix(p.stem) for p in
             sorted((ROOT / "chipbench" / "traffic").glob("*.json"))]
    mixes = [tr for tr in mixes if tr["kind"] == "requests"]
    assert mixes
    for tr in mixes:
        a = traffic.requests(tr, 10.0, SEED, 32000)
        b = traffic.requests(tr, 10.0, SEED, 32000)
        c = traffic.requests(tr, 10.0, SEED + 1, 32000)
        assert [(r.due_s, r.max_new_tokens) for r in a] == \
            [(r.due_s, r.max_new_tokens) for r in b]
        assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
        assert sorted(len(r.prompt) for r in a) == \
            sorted(len(r.prompt) for r in c)
        assert sorted(r.max_new_tokens for r in a) == \
            sorted(r.max_new_tokens for r in c)
        ga = set(np.round(np.diff([r.due_s for r in a]), 9))
        gc = set(np.round(np.diff([r.due_s for r in c]), 9))
        assert len(ga & gc) >= len(a) - 2  # one gap is past the last due
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
        assert all(0 <= r.due_s < 10.0 for r in a)
        assert all(len(r.prompt) % 128 == 0 for r in a)


def test_frames_repeat_for_a_seed():
    cfg = json.loads((ROOT / "chipbench" / "configs" / "roshambo.json")
                     .read_text())
    small = dict(cfg["frames"], pool=4)
    a = traffic.dvs_frames(small, 64, 1, SEED)
    b = traffic.dvs_frames(small, 64, 1, SEED)
    c = traffic.dvs_frames(small, 64, 1, SEED + 1)
    assert a.shape == (4, 1, 64, 64, 1) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.max() == 1.0 and a.min() == 0.0
    assert np.array_equal(traffic.frame_order(64, SEED),
                          traffic.frame_order(64, SEED))
