"""bench.py smoke test: on an explicitly requested CPU run every
CPU-runnable phase lands in the JSON line and the script exits 0 (a phase
that raises makes it exit non-zero)."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_emits_json_line():
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "KERAS_BACKEND": "jax",
        "BENCH_SAMPLES": "4096",
        "BENCH_EPOCHS": "1",
        "BENCH_REPS": "1",
        # tiny serving geometry: the phase must still land in the JSON
        "BENCH_SERVE_DMODEL": "64",
        "BENCH_SERVE_LAYERS": "2",
        "BENCH_SERVE_VOCAB": "128",
        "BENCH_SERVE_SLOTS": "4",
        "BENCH_SERVE_PROMPT": "8",
        "BENCH_SERVE_NEW": "8",
        # the fast-path phase shares BENCH_SERVE_PROMPT and builds a paged
        # engine: prompt + new must be whole 16-token pages (8 + 24 = 32)
        "BENCH_SERVE_FAST_NEW": "24",
        # tiny recovery geometry: checkpoint + crash-resume must land too
        "BENCH_REC_SAMPLES": "1024",
        "BENCH_REC_EPOCHS": "2",
        "BENCH_REC_WORKERS": "2",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["metric"] == "mnist_mlp_sync_samples_per_sec_per_chip"
    assert result["unit"] == "samples/sec/chip"
    assert result["value"] > 0
    assert result["vs_baseline"] > 0
    # the serving phase is CPU-runnable, so its entry must be present
    serving = result["serving"]
    assert serving["agg_tokens_per_sec"] > 0
    assert serving["sequential_tokens_per_sec"] > 0
    assert serving["vs_sequential"] > 0
    assert serving["ttft_p95_ms"] >= serving["ttft_p50_ms"] >= 0
    assert 0 < serving["batch_occupancy"] <= 1
    assert serving["concurrency"] == 4
    # so is the recovery phase: checkpointing tax + one crash-resume cycle
    recovery = result["recovery"]
    assert recovery["plain_fit_s"] > 0
    assert recovery["checkpointed_fit_s"] > 0
    assert recovery["crash_resume_fit_s"] > 0
    assert recovery["epochs"] == 2
    assert recovery["checkpoint_frequency"] == 1
