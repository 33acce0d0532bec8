"""Long-context transformer LM with dp×sp sequence parallelism.

EXTENSION BEYOND THE REFERENCE (no analog in ``b13n3rd/elephas`` — its
longest-sequence workload is a whole-sequence-per-worker IMDB LSTM). A
GPT-style decoder-only LM trains with the batch sharded over the ``"data"``
mesh axis and the SEQUENCE sharded over a ``"seq"`` axis, attention computed
exactly via ring attention (``ppermute`` KV rotation over ICI) or
DeepSpeed-Ulysses all-to-alls — context length scales linearly with the
seq-axis size.

Task: character-level language modelling of synthetic text with long-range
structure (each line ends by repeating its opening word, so the model must
carry information across the sequence).

Run (TPU): ``KERAS_BACKEND=jax python examples/transformer_lm.py``
Run (CPU mesh): prefix with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
"""

import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SEQ_LEN = 128
VOCAB = 32


def synthetic_corpus(n_rows: int, seed: int = 0) -> np.ndarray:
    """``[n, SEQ_LEN+1]`` int rows: random prefix, then the prefix repeated —
    forcing attention across half the context window."""
    rng = np.random.default_rng(seed)
    half = SEQ_LEN // 2 + 1
    prefix = rng.integers(2, VOCAB, size=(n_rows, half))
    rows = np.concatenate([prefix, prefix], axis=1)[:, : SEQ_LEN + 1]
    assert rows.shape[1] == SEQ_LEN + 1
    return rows


def main():
    import jax
    import optax

    from elephas_tpu.models import (
        TransformerLM,
        build_lm_train_step,
        build_mesh_sp,
        make_lm_batches,
        shard_lm_batch,
    )

    n_dev = len(jax.devices())
    sp = max(d for d in (1, 2, 4, 8) if n_dev % d == 0 and SEQ_LEN % d == 0)
    dp = n_dev // sp
    mesh = build_mesh_sp(data=dp, seq=sp)
    print(f"devices={n_dev} mesh=data:{dp} x seq:{sp} "
          f"(context/chip = {SEQ_LEN // sp} of {SEQ_LEN} tokens)")

    model = TransformerLM(vocab=VOCAB, d_model=64, n_heads=8, n_layers=2,
                          d_ff=128, max_len=SEQ_LEN)
    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    params = model.shard_params(mesh, model.init(seed=0))
    state = opt_init(params)

    tokens, positions, targets = make_lm_batches(synthetic_corpus(8 * dp))
    td, pd, gd = shard_lm_batch(mesh, tokens, positions, targets)

    for i in range(60):
        params, state, loss = step(params, state, td, pd, gd)
        if i % 10 == 0 or i == 59:
            print(f"step {i:3d}  loss/token {float(loss):.4f}")

    final = float(loss)
    # random-guess CE is ln(30) ≈ 3.4; the copy structure is learnable far
    # below that
    assert final < 2.0, f"LM failed to learn long-range copy task: {final}"

    # -- inference epilogue: KV-cached greedy generation ------------------
    # Prompt with a training row's prefix + a few repeated tokens; greedy
    # generation (flash-decode kernel path on TPU) must continue the
    # repetition the model learned. (A 60-step d64 model memorizes its 8
    # training rows rather than learning the general copy algorithm —
    # held-out copying needs longer training; this exercises the decode
    # machinery end-to-end on what the model actually knows.)
    import jax.numpy as jnp

    host_params = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
    row = synthetic_corpus(8 * dp)[0]  # a training row
    half = SEQ_LEN // 2 + 1
    cut = half + 4
    out = np.asarray(model.generate(
        host_params, row[None, :cut], n_new=SEQ_LEN - cut,
    ))[0]
    acc = float((out[cut:SEQ_LEN] == row[cut:SEQ_LEN]).mean())
    print(f"greedy continuation accuracy on the copy tail: {acc:.2f}")
    assert acc > 0.8, f"decode diverged from the learned sequence: {acc}"
    print("ok")


if __name__ == "__main__":
    main()
