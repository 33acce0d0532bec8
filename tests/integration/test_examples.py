"""Example-script smoke tests.

The reference's examples double as its integration surface (SURVEY.md §4 —
CI runs them nowhere, and they rot). Here each example runs as a subprocess
on the CPU mesh with tiny ``EX_SAMPLES``/``EX_EPOCHS`` overrides, asserting
it exits cleanly — the same scripts scale back up to real sizes unchanged.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EXAMPLES = [
    "mnist_mlp_spark.py",
    "mnist_cnn_async.py",
    "mllib_mlp.py",
    "ml_mlp.py",
    "ml_pipeline_otto.py",
    "ml_pipeline_imdb_lstm.py",
    "hyperparam_optimization.py",
    "transformer_lm.py",
    "parallelism_tour.py",
    "lm_inference_tour.py",
    "hf_import_tour.py",
    "sharded_generate.py",
    "resnet50_spark.py",
    "ml_pipeline_notebook.ipynb",  # executed via nbconvert
]


@pytest.mark.slow
@pytest.mark.timeout(900)  # resnet50 measures ~134s locally; 900 covers CI
@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script):
    if script == "hf_import_tour.py":
        # torch/transformers are the import tour's conversion oracle, not
        # project dependencies (test_hf_import.py importorskips the same way)
        pytest.importorskip("torch")
        pytest.importorskip("transformers")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "KERAS_BACKEND": "jax",
        # > batch_size(128) per each of the 8 workers, or the reference's
        # skip-small-partitions quirk empties the fit
        "EX_SAMPLES": "2048",
        "EX_EPOCHS": "1",
        "EX_STEPS": "12",
        # resnet50: 8 workers x 20 samples > batch_size(16); one epoch of
        # the conv stack compiles+runs in ~100s on the CPU mesh
        "RESNET_SAMPLES": "160",
        "RESNET_EPOCHS": "1",
    })
    if script.endswith(".ipynb"):
        cmd = [sys.executable, "-m", "nbconvert", "--to", "notebook",
               "--execute", "--stdout", script]
    else:
        cmd = [sys.executable, os.path.join(_REPO, "examples", script)]
    proc = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.join(_REPO, "examples"),
    )
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    # (that resnet50's remat flag actually changes the compiled program is
    # pinned by test_adapters.py::test_remat_flag_reaches_the_compiled_program)
