"""Each driver in-process at tiny widths on the CPU, through the same
``run_cell`` the command uses; and that a cell, a configuration and a mix
are added by files and entries alone."""

import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.run import run_cell
from tests.benchmark._tiny import make_root

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return Manifest(make_root(tmp_path_factory.mktemp("bench_root")))


def tracked_files():
    out = subprocess.run(["git", "status", "--porcelain", "benchmark",
                          "BENCHMARK.json"], cwd=REPO_ROOT,
                         capture_output=True, text=True).stdout
    return out


@pytest.mark.parametrize("cell,trace", [
    ("tiny-train", 0), ("tiny-train", 1), ("tiny-train-dp2", 0),
    ("tiny-chat", 0), ("tiny-chat", 1), ("tiny-batch", 0),
    ("tiny-batch", 1)])
def test_driver_ends_in_one_valid_last_line(man, cell, trace):
    before = tracked_files()
    chips = man.cell(cell)["chips"]
    last = run_cell(man, cell, 2**31 + 11, 0.6, trace,
                    jax.devices()[:chips])
    last = json.loads(json.dumps(last))          # it is JSON as it stands
    assert set(last) - {"breakdown"} == KEYS
    assert last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["device"]["count"] == chips
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in man.metrics_for(cell, group)}
    assert last["metrics"], cell
    for name, m in last["metrics"].items():
        assert m["unit"] == declared[name]
        assert m["value"] > 0 and m["value"] == m["value"]
    if not trace:
        assert set(last["metrics"]) == set(declared)
    # the throw-away root was registered without touching a file that is here
    assert tracked_files() == before


def test_command_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO_ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert "{" not in out.stdout            # no result line
    assert "TPU" in out.stderr
