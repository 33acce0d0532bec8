"""What the harness scripts share: where JAX's persistent compile cache
goes, and when two greedy token streams count as the same answer.

``chip_smoke.py``, ``__graft_entry__.py`` and ``benchmark/run.py`` call
:func:`place_compile_cache` before their first compile, so a second run on
the same machine reuses the first run's compiled programs instead of paying
for every train and serving program again; ``chip_smoke.py`` judges its
engine streams with :func:`greedy_streams_agree`.
"""

import os

# The path is part of the cache key's lookup, so it is one fixed directory
# inside the checkout (listed in .gitignore) — never a tempfile, pid or time.
REPO_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              ".jax_cache")


def place_compile_cache() -> str:
    """Return the compile-cache directory in force, setting it if needed.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment this does nothing:
    JAX reads that variable itself, and no other path is set in code.
    Otherwise ``jax_compilation_cache_dir`` becomes :data:`REPO_CACHE_DIR`.
    """
    import jax

    # The program's scope and kernel names are HLO metadata. JAX leaves
    # metadata out of the cache key by default, so a cache filled by a
    # build with other names (or none) would hand back executables whose
    # profile reads as that build's: keep the names in the key.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


# Greedy rollouts of one prompt by two numerically different programs can
# part where two logits tie: the serving engine prefills through
# ``decode_chunk`` and ``generate`` through the flash kernel, and on the TPU
# a default-precision matmul multiplies in bf16. On the v5e the splits seen
# were between logits 0.001-0.004 apart and vanished under
# ``jax.default_matmul_precision("highest")``; on the CPU, where both sides
# run the same jax.numpy references, the streams are equal.
TIE_TOL = 0.05


def greedy_streams_agree(model, params, prompt, a, b, tol=TIE_TOL):
    """Do greedy continuations ``a`` and ``b`` of ``prompt`` agree?

    They do when they are equal, or when at the first place they differ the
    teacher-forced logits of both candidates lie within ``tol`` of the
    maximum. After a split the two are different texts, so nothing further
    is compared. Returns ``(agree, note)``; ``note`` is ``"equal"`` or says
    where the streams parted and by how much.
    """
    import jax
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return False, f"lengths {a.shape} and {b.shape}"
    if (a == b).all():
        return True, "equal"
    i = int(np.argmax(a != b))
    ctx = np.concatenate([prompt, a[:i]]).astype(np.int32)[None]
    pos = np.arange(ctx.shape[1], dtype=np.int32)[None]
    logits = np.asarray(jax.jit(
        lambda p, t, ps: model.apply(p, t, ps, "dense"))(params, ctx, pos)
    )[0, -1].astype(np.float32)
    la, lb, top = float(logits[a[i]]), float(logits[b[i]]), float(logits.max())
    note = (f"split at token {i}: logits {la:.4f} and {lb:.4f}, "
            f"maximum {top:.4f}")
    return top - min(la, lb) <= tol, note
