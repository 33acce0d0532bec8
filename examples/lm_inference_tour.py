"""Inference & fine-tuning tour: LoRA → merge → quantize → generate →
speculative decoding, end to end on one small LM.

EXTENSION BEYOND THE REFERENCE (no analog in ``b13n3rd/elephas`` — its
inference surface is ``model.predict`` and it has no fine-tuning or
quantization machinery). The pipeline here is the modern deployment story,
each stage verified against the previous one:

1. pretrain a small ``TransformerLM`` briefly (dp×sp mesh);
2. LoRA-fine-tune on a shifted task — only the rank-r adapters train, the
   base stays bit-frozen;
3. ``merge_lora`` bakes the adapters in; ``quantize_lm_params`` compresses
   the merged weights to int8 (bit-identical inference vs dequantized);
4. KV-cached ``generate`` (flash-decode kernel on TPU) and
   ``generate_speculative`` (the pretrained model drafts for the
   fine-tuned one) produce the same greedy output;
5. the deployed artifact goes behind a continuous-batching
   ``ServingEngine``: interleaved requests share one slot-batched KV
   cache, each streams out with its own TTFT/throughput, and every greedy
   continuation equals the per-request ``generate``.

Run (TPU): ``KERAS_BACKEND=jax python examples/lm_inference_tour.py``
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
STEPS = int(os.environ.get("EX_STEPS", 40))


def corpus(n, stride, seed=0):
    """Rows whose second half repeats the first shifted by ``stride`` mod
    vocab — pretraining uses stride 0 (plain copy), fine-tuning stride 3."""
    rng = np.random.default_rng(seed)
    half = SEQ // 2 + 1
    prefix = rng.integers(0, VOCAB, size=(n, half))
    rows = np.concatenate([prefix, (prefix + stride) % VOCAB], axis=1)
    return rows[:, : SEQ + 1]


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from elephas_tpu.models import (
        TransformerLM,
        apply_lora,
        build_lm_train_step,
        build_lora_lm_train_step,
        build_mesh_sp,
        lora_trainable_count,
        make_lm_batches,
        merge_lora,
        quantize_lm_params,
        quantized_nbytes,
        shard_lm_batch,
    )

    n_dev = len(jax.devices())
    sp = max(d for d in (1, 2, 4) if n_dev % d == 0 and SEQ % d == 0)
    dp = n_dev // sp
    mesh = build_mesh_sp(data=dp, seq=sp)
    model = TransformerLM(vocab=VOCAB, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_len=SEQ, pos_encoding="rotary")

    # 1. pretrain on the copy task
    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    params = model.shard_params(mesh, model.init(seed=0))
    state = opt_init(params)
    batch = shard_lm_batch(mesh, *make_lm_batches(corpus(8 * dp, stride=0)))
    for i in range(STEPS):
        params, state, loss = step(params, state, *batch)
    print(f"pretrain loss {float(loss):.3f}")

    # 2. LoRA fine-tune on the shifted task: base frozen, adapters learn
    host_base = {k: np.asarray(v) for k, v in params.items()}
    # independent buffers: the LoRA step donates its params, so the copy
    # handed to apply_lora must not be the one we keep for the draft
    base = {k: jnp.asarray(v) for k, v in host_base.items()}
    lparams = apply_lora({k: jnp.asarray(v) for k, v in host_base.items()},
                         rank=4)
    trainable, total = lora_trainable_count(lparams)
    lstep, lopt_init = build_lora_lm_train_step(model, mesh,
                                                optax.adam(1e-2), attn="ring")
    lstate = lopt_init(lparams)
    fbatch = shard_lm_batch(mesh,
                            *make_lm_batches(corpus(8 * dp, stride=3, seed=7)))
    first = last = None
    for i in range(2 * STEPS):
        lparams, lstate, loss = lstep(lparams, lstate, *fbatch)
        first = float(loss) if first is None else first
        last = float(loss)
    print(f"lora fine-tune ({trainable:,}/{total:,} trainable): "
          f"loss {first:.3f} -> {last:.3f}")
    assert last < first

    # 3. merge + quantize for deployment
    merged = merge_lora(lparams)
    qparams = quantize_lm_params(merged)
    orig_bytes = sum(np.asarray(v).nbytes for v in merged.values())
    print(f"merged+quantized: {orig_bytes:,} -> {quantized_nbytes(qparams):,} "
          "bytes")

    # 4. generate with the quantized fine-tuned model; then speculative
    # decoding with the PRETRAINED model as draft — same greedy output
    row = corpus(1, stride=3, seed=7)[0]
    cut = SEQ // 2 + 3
    prompt = row[None, :cut]
    plain = np.asarray(model.generate(qparams, prompt, n_new=SEQ - cut))
    spec = np.asarray(model.generate_speculative(
        qparams, prompt, n_new=SEQ - cut, draft=model, draft_params=base,
        spec_k=3,
    ))
    np.testing.assert_array_equal(plain, spec)
    acc = float((plain[0, cut:SEQ] == row[cut:SEQ]).mean())
    print(f"greedy == speculative; fine-tuned continuation accuracy {acc:.2f}")

    # 5. serve the deployed artifact: interleaved requests, one shared
    # slot-batched KV cache, per-request TTFT/throughput from the engine's
    # own metrics
    from elephas_tpu.serving import ServingEngine

    reqs = []
    for i in range(6):
        r = corpus(1, stride=3, seed=20 + i)[0]
        c = SEQ // 2 + 1 + i % 3        # mixed prompt lengths
        reqs.append((r[:c].astype(np.int32), SEQ - c))
    eng = ServingEngine(model, qparams, n_slots=4)
    ids = []
    for p, n_new in reqs:
        ids.append(eng.submit(p, n_new))
        eng.step()                      # interleave submission with decode
    fin = eng.drain(max_steps=1000)
    snap = eng.snapshot()
    print(f"served {snap['counters']['completed']} requests through "
          f"{snap['engine']['n_slots']} slots "
          f"(occupancy {snap['engine']['batch_occupancy']:.2f})")
    print("  request  prompt  new  ttft_ms   tok/s")
    for rid in ids:
        t = fin[rid].timing
        print(f"  {rid:>7}  {t.prompt_tokens:>6}  {t.generated_tokens:>3}"
              f"  {t.ttft * 1e3:7.1f}  {t.decode_tokens_per_sec:6.1f}")
    for rid, (p, n_new) in zip(ids, reqs):
        ref = np.asarray(model.generate(qparams, p[None], n_new))[0, len(p):]
        np.testing.assert_array_equal(fin[rid].tokens, ref)
    print("serving == per-request generate")
    print("ok")


if __name__ == "__main__":
    main()
