"""HF checkpoint import tour: torch GPT-2/Llama → TPU-native LM →
verify → quantize → (sharded) generate.

EXTENSION BEYOND THE REFERENCE (``b13n3rd/elephas`` consumes Keras models
only — SURVEY.md §2.5; it has no foreign-checkpoint interop). This script
demonstrates the migration path from the HuggingFace ecosystem:

1. build a small ``transformers`` GPT-2 and a Llama-style GQA model in
   torch (stand-ins for real checkpoints — pass ``HF_MODEL=<path>`` to
   import a downloaded one instead);
2. ``lm_from_hf`` converts each into the functional ``TransformerLM``
   layout (architecture — gelu/swiglu, rmsnorm, biases, rope_theta, GQA —
   resolved from the HF config);
3. verify logits parity against the torch forward pass;
4. run the framework's own machinery on the imported weights: KV-cached
   greedy generation, int8 quantized generation, and dp×sp sequence-
   sharded generation on the device mesh — all without touching torch
   again.

Run (TPU): ``KERAS_BACKEND=jax python examples/hf_import_tour.py``
Run (CPU mesh): prefix with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
"""

import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def tiny_hf_models():
    import torch
    import transformers

    torch.manual_seed(0)
    gpt2 = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0))
    llama = transformers.LlamaForCausalLM(transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, attention_dropout=0.0))
    mixtral = transformers.MixtralForCausalLM(transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, attention_dropout=0.0, sliding_window=None,
        attn_implementation="eager"))
    gpt2.eval(), llama.eval(), mixtral.eval()
    return {"gpt2": gpt2, "llama-gqa": llama, "mixtral-moe": mixtral}


def main():
    import jax
    import jax.numpy as jnp
    import torch

    from elephas_tpu.models import build_lm_generate, build_mesh_sp, lm_from_hf
    from elephas_tpu.models.quantize import quantize_lm_params, quantized_nbytes

    if os.environ.get("HF_MODEL"):
        from elephas_tpu.models import load_hf_lm

        model, params = load_hf_lm(os.environ["HF_MODEL"])
        todo = [(os.environ["HF_MODEL"], model, params, None)]
    else:
        todo = []
        for name, hf in tiny_hf_models().items():
            model, params = lm_from_hf(hf)
            todo.append((name, model, params, hf))

    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 120, size=(4, 10)).astype(np.int32)

    for name, model, params, hf in todo:
        print(f"\n=== {name}: {model.n_layers}L d{model.d_model} "
              f"{model.activation}/{model.norm} "
              f"H{model.n_heads}/KV{model.n_kv_heads} ===")
        p = jax.tree.map(jnp.asarray, params)

        if hf is not None:
            pos = np.broadcast_to(np.arange(prompt.shape[1]), prompt.shape)
            with jax.default_matmul_precision("float32"):
                ours = np.asarray(model.apply(p, prompt, pos))
            with torch.no_grad():
                theirs = hf(input_ids=torch.tensor(
                    prompt, dtype=torch.long)).logits.numpy()
            print(f"logits parity vs torch: max|Δ| = "
                  f"{np.abs(ours - theirs).max():.2e}")

        out = np.asarray(model.generate(p, prompt, 12))
        print("greedy generate:", out[0, -12:].tolist())

        qp = quantize_lm_params(p)
        qout = np.asarray(model.generate(qp, prompt, 12))
        agree = float((qout == out).mean())
        print(f"int8 generate ({quantized_nbytes(qp)/2**20:.1f} MiB "
              f"resident): {agree:.0%} token agreement")

        n_dev = len(jax.devices())
        if n_dev >= 2:
            mesh = build_mesh_sp(data=2 if n_dev >= 8 else 1,
                                 seq=4 if n_dev >= 8 else n_dev)
            gen = build_lm_generate(model, mesh)
            sout = np.asarray(gen(model.shard_params(mesh, p), prompt, 12))
            print(f"sharded generate over {dict(mesh.shape)}: "
                  f"{'token-for-token equal' if (sout == out).all() else 'MISMATCH'}")


if __name__ == "__main__":
    main()
