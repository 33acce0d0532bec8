"""Sequence-sharded generation: decode without gathering to one device.

EXTENSION BEYOND THE REFERENCE (no analog in ``b13n3rd/elephas`` — its
inference surface is driver-local ``model.predict``). A ``TransformerLM``
trained dp×sp keeps training state resident across the mesh; this example
shows the matching inference path: ``build_lm_generate`` compiles
generation as ONE ``shard_map`` program where the batch shards over
``"data"`` and the KV cache shards over ``"seq"`` along time — per-chip
cache memory drops by the seq-axis size, and the decode horizon scales
with the mesh instead of one chip's HBM
(``elephas_tpu/models/sharded_generate.py`` for the logsumexp merge).

The script trains briefly on a copy task, generates with the sharded
program, and checks the rollout token-for-token against the gathered
single-device ``generate`` — the exactness contract the tests pin.

Run (TPU): ``KERAS_BACKEND=jax python examples/sharded_generate.py``
Run (CPU mesh): prefix with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
"""

import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SEQ = 32
VOCAB = 24
STEPS = int(os.environ.get("EX_STEPS", 30))


def corpus(n, seed=0):
    """Rows whose second half repeats the first — learnable in seconds."""
    rng = np.random.default_rng(seed)
    half = SEQ // 2 + 1
    first = rng.integers(0, VOCAB, size=(n, half))
    return np.concatenate([first, first[:, : SEQ + 1 - half]], axis=1)


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from elephas_tpu.models import (
        TransformerLM,
        build_lm_generate,
        build_lm_train_step,
        build_mesh_sp,
        make_lm_batches,
        shard_lm_batch,
    )

    n_dev = jax.local_device_count()
    seq_axis = 4 if n_dev % 4 == 0 else 1
    data_axis = n_dev // seq_axis
    mesh = build_mesh_sp(data=data_axis, seq=seq_axis)
    print(f"mesh: data={data_axis} x seq={seq_axis}")

    model = TransformerLM(vocab=VOCAB, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_len=SEQ, pos_encoding="rotary")
    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    params = model.shard_params(mesh, model.init(seed=0))
    state = opt_init(params)
    for i in range(STEPS):
        rows = corpus(4 * data_axis, seed=i)
        batch = shard_lm_batch(mesh, *make_lm_batches(rows))
        params, state, loss = step(params, state, *batch)
    print(f"trained {STEPS} steps, final loss {float(loss):.3f}")

    # generate with the seq-sharded cache; prompt = first half of fresh rows
    prompt = corpus(2 * data_axis, seed=999)[:, : SEQ // 2].astype(np.int32)
    n_new = SEQ - SEQ // 2
    gen = build_lm_generate(model, mesh)
    sharded = np.asarray(gen(params, prompt, n_new))

    gathered_params = {k: jnp.asarray(np.asarray(v)) for k, v in
                       params.items()}
    gathered = np.asarray(model.generate(gathered_params, prompt, n_new))
    assert (sharded == gathered).all(), "sharded rollout diverged"

    # the trained model should mostly copy the prompt forward
    want = corpus(2 * data_axis, seed=999)[:, SEQ // 2: SEQ]
    acc = float((sharded[:, SEQ // 2:] == want).mean())
    print(f"sharded == gathered rollout; copy-task accuracy {acc:.2f}")


if __name__ == "__main__":
    main()
