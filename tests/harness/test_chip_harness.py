"""The harness around the chip: where the compile cache goes, and that
nothing quietly stands in for the device — no CPU re-run, no "not a TPU"
from a broken backend, no default peak, no quiet reference path for a page
the kernels cannot read. Fast, pure logic: nothing here compiles."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import numpy as np

import harness_env

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def cache_config():
    """Restore ``jax_compilation_cache_dir`` so later tests compile as
    before (the cache is only opened at the first compile after this)."""
    before = jax.config.jax_compilation_cache_dir
    in_key = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      in_key)


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch,
                                                         cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/the/driver")
    before = jax.config.jax_compilation_cache_dir
    assert harness_env.place_compile_cache() == "/placed/by/the/driver"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("placed", [None, "/placed/by/the/driver"])
def test_compile_cache_key_keeps_the_programs_names(monkeypatch,
                                                    cache_config, placed):
    """Scope and kernel names are HLO metadata: left out of the key (JAX's
    default), a cache filled by a build without them would hand back
    executables whose profile shows none."""
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    harness_env.place_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch,
                                                            cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert harness_env.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert harness_env.place_compile_cache() == want      # and stays there
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "platform=cpu" in proc.stderr


def test_is_tpu_backend_propagates_a_backend_error(monkeypatch):
    from elephas_tpu.ops import pallas_ops

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pallas_ops.is_tpu_backend()


def test_peak_flops_has_no_default_for_an_unknown_accelerator():
    from benchmark.peaks import peaks_for

    assert peaks_for("TPU v5 lite")[0] == 197e12
    for kind in ("cpu", "TPU v99"):
        with pytest.raises(ValueError, match=kind):
            peaks_for(kind)


def test_dryrun_multichip_raises_instead_of_rerunning(monkeypatch):
    import __graft_entry__ as graft

    def no_children(*args, **kwargs):
        raise AssertionError("dryrun_multichip started a child process")

    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"only {n - 1} cpu device"):
        graft.dryrun_multichip(n)


def test_paged_kernel_gate_refuses_instead_of_falling_back(monkeypatch):
    """Pure shape logic. On the v5e Mosaic compiled the paged kernels for
    pages of 8 and 16 rows in bf16 and f32 pools alike (PR 21's chip run),
    so both take the kernel there whatever the pool's dtype; a page the
    kernels cannot read raises on the TPU instead of taking the gathered
    reference. Off the TPU every page takes the reference, the CPU
    contract."""
    from elephas_tpu.ops import paged_attention as pa

    for page in (4, 8, 16):
        assert pa._use_pallas(page) is False

    monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    assert pa._use_pallas(16) is True
    assert pa._use_pallas(8) is True
    for page in (4, 12):
        with pytest.raises(ValueError, match=f"page_size {page}"):
            pa._use_pallas(page)

    # the dispatchers carry the refusal: a bf16 pool with page 4 on the TPU
    q = jnp.zeros((1, 1, 1, 128), jnp.bfloat16)
    pool = jnp.zeros((3, 1, 4, 128), jnp.bfloat16)
    table = jnp.ones((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="page_size 4"):
        pa.paged_decode_attention(q, pool, pool, table, jnp.zeros(1, jnp.int32),
                                  4)


def test_greedy_streams_agree_only_up_to_a_tie():
    """Two greedy rollouts count as one answer when equal, or when they
    part where the two candidates' logits tie; a real disagreement fails."""

    class Stub:
        """``apply`` scores token 3 and token 5 a hair apart, token 7 far
        below, whatever the context."""

        def apply(self, params, tokens, positions, attn):
            row = jnp.zeros((9,), jnp.float32).at[3].set(1.000)
            row = row.at[5].set(0.998).at[7].set(0.5)
            return jnp.broadcast_to(row, tokens.shape + (9,))

    prompt = np.array([1, 2], np.int32)
    agree = lambda a, b: harness_env.greedy_streams_agree(
        Stub(), None, prompt, np.array(a), np.array(b), tol=0.05)

    assert agree([3, 3, 3], [3, 3, 3]) == (True, "equal")
    ok, note = agree([3, 3, 4], [3, 5, 8])       # parts at a tie: 3 vs 5
    assert ok and note.startswith("split at token 1")
    ok, note = agree([3, 3, 3], [3, 7, 3])       # 7 is no tie
    assert not ok and "0.5000" in note
    ok, note = agree([3, 3], [3, 3, 3])
    assert not ok and "lengths" in note
