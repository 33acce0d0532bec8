#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path runs on the TPU.

One process, public entry points only, data and weights made from a seed (no
``keras.datasets``, no network, no native library). Four stages, all at the
full width of the dense ``TransformerLM`` the repo has a chip history for
(vocab 8192, d_model 2048, 8 heads of 256, d_ff 8192, max_len 2048, bf16
compute, rotary, tied embeddings), plus the paper's own entry point:

  A  ``SparkModel(mode="synchronous").fit`` / ``.predict`` on the
     MNIST-shaped MLP through ``SparkContext`` / ``to_simple_rdd``;
  B  ``build_lm_train_step`` for a few steps on a fixed batch, once with the
     library defaults and once with ``overlap_grads=True, fused_apply=True``
     (and, on an even number of chips, once sequence-parallel with ring
     attention);
  C  ``ServingEngine`` dense and paged answering eight requests, checked
     against ``model.generate``, and paged against dense on logits;
  D  every Pallas dispatcher those stages go through: the lowered call holds
     a Mosaic custom call, runs compiled, and agrees with its reference.

Nothing is caught and carried on: the first failed check raises and the
exit code is non-zero. Without a TPU the default invocation exits 2 before
doing any work. On success the last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}``, with the device as JAX reports it.

``--rehearsal`` runs the same control flow at tiny shapes on whatever JAX
finds (the CPU here), for debugging before chip time is spent. Off the TPU
the dispatchers take their jax.numpy references, so stage D cannot show a
Mosaic call there; its output says ``rehearsal`` and it is never the default.
"""

import argparse
import functools
import gc
import json
import os
import sys
import time

# The package never sets the Keras backend itself (only the examples do).
os.environ.setdefault("KERAS_BACKEND", "jax")

HERE = os.path.dirname(os.path.abspath(__file__))

FULL = dict(
    vocab=8192, d_model=2048, n_heads=8, n_layers=8, d_ff=8192, max_len=2048,
    seq=2048, batch=4, steps=6,
    mlp_samples=16384, mlp_epochs=4, mlp_batch=128,
    n_slots=8, page=16, max_new=32,
    prompt_lens=(32, 100, 257, 400, 512, 100, 257, 32), probe_len=100,
)
TINY = dict(
    vocab=256, d_model=64, n_heads=2, n_layers=2, d_ff=128, max_len=128,
    seq=128, batch=4, steps=6,
    mlp_samples=2048, mlp_epochs=3, mlp_batch=64,
    n_slots=8, page=16, max_new=8,
    prompt_lens=(8, 25, 33, 50, 64, 25, 33, 8), probe_len=25,
)

# Paged against dense logits, as a share of the largest dense logit: bf16
# activations round at 2^-8 and the two paths accumulate at different
# granularity (ops/paged_attention.py says why it is not bitwise).
PAGED_LOGIT_RTOL = 0.02


def say(*args):
    print(*args, flush=True)


class CompileMeter:
    """Sums JAX's own compile-phase durations (tracing, lowering, backend
    compile or persistent-cache retrieval) and counts persistent-cache hits
    and writes, so a stage's wall time splits into compile and run."""

    _PHASES = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self._PHASES:
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def run_stage(name, meter, report, fn, *args):
    """Run one stage and record its wall time split into compile and run.
    A stage that fails raises: nothing is caught here."""
    say(f"--- stage {name}")
    c0, h0, w0 = meter.compile_s, meter.hits, meter.writes
    t0 = time.perf_counter()
    detail = fn(*args)
    wall = time.perf_counter() - t0
    compile_s = meter.compile_s - c0
    row = {
        "stage": name, "passed": True,
        "wall_s": round(wall, 2), "compile_s": round(compile_s, 2),
        "run_s": round(wall - compile_s, 2),
        "cache_hits": meter.hits - h0, "cache_writes": meter.writes - w0,
    }
    row.update(detail or {})
    report.append(row)
    say(f"stage {name}: PASSED wall {row['wall_s']}s = compile "
        f"{row['compile_s']}s + run {row['run_s']}s "
        f"(compile cache: {row['cache_hits']} hits, "
        f"{row['cache_writes']} written)")
    gc.collect()


def require(cond, message):
    if not cond:
        raise AssertionError(message)


def spread_over_devices(tree, what):
    """On several chips: every array of ``tree`` lives on all of them."""
    import jax

    n = len(jax.devices())
    if n == 1:
        return
    for leaf in jax.tree_util.tree_leaves(tree):
        require(len(leaf.sharding.device_set) == n,
                f"{what}: an array of shape {leaf.shape} lives on "
                f"{len(leaf.sharding.device_set)} of {n} devices")


def memory_not_all_on_first(what):
    """On several chips: device 0 does not hold everything."""
    import jax

    devs = jax.devices()
    if len(devs) == 1 or devs[0].memory_stats() is None:
        return None
    used = [d.memory_stats()["bytes_in_use"] for d in devs]
    say(f"{what}: bytes_in_use per device {used}")
    # device 0 may also hold a single-device reference copy of the weights
    require(min(used[1:]) > 0.25 * used[0],
            f"{what}: memory is piled on device 0: {used}")
    return used


# -- stage A: the paper's trainer ---------------------------------------------


def stage_a(cfg):
    import jax
    import keras
    import numpy as np

    from elephas_tpu import SparkModel
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import to_simple_rdd

    n_dev = len(jax.devices())
    rng = np.random.default_rng(0)
    n, d, c = cfg["mlp_samples"], 784, 10
    x = rng.normal(size=(n, d)).astype("float32")
    y = np.eye(c, dtype="float32")[(x @ rng.normal(size=(d, c))).argmax(1)]

    model = keras.Sequential([
        keras.layers.Dense(128, activation="relu"),
        keras.layers.Dense(128, activation="relu"),
        keras.layers.Dense(c, activation="softmax"),
    ])
    model.build((None, d))
    model.compile(optimizer="adam", loss="categorical_crossentropy",
                  metrics=["accuracy"])

    sc = SparkContext(master=f"local[{n_dev}]", appName="chip_smoke")
    rdd = to_simple_rdd(sc, x, y, num_slices=n_dev)
    spark_model = SparkModel(model, mode="synchronous", num_workers=n_dev)
    spark_model.fit(rdd, epochs=cfg["mlp_epochs"],
                    batch_size=cfg["mlp_batch"], verbose=0,
                    validation_split=0.0)
    losses = [float(v) for v in spark_model.training_histories[-1]["loss"]]
    say(f"A: SparkModel.fit over {n_dev} worker(s), loss per epoch {losses}")
    require(len(losses) == cfg["mlp_epochs"], f"A: {len(losses)} epochs ran")
    require(all(np.isfinite(losses)), f"A: non-finite loss {losses}")
    require(losses[-1] < losses[0], f"A: loss did not fall: {losses}")

    pred = np.asarray(spark_model.predict(x[:64]))
    require(pred.shape == (64, c), f"A: predict shape {pred.shape}")
    require(np.isfinite(pred).all(), "A: non-finite predictions")
    require(np.allclose(pred.sum(1), 1.0, atol=1e-3),
            "A: softmax rows do not sum to 1")
    return {"workers": n_dev, "loss_first": losses[0], "loss_last": losses[-1]}


# -- stage B: the LM trainer --------------------------------------------------


def lm_model(cfg):
    from elephas_tpu.models import TransformerLM

    return TransformerLM(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], d_ff=cfg["d_ff"], max_len=cfg["max_len"],
        compute_dtype="bfloat16", pos_encoding="rotary", tie_embeddings=True)


def stage_b(cfg):
    import jax
    import numpy as np

    from elephas_tpu.models import (adam_compact, build_lm_train_step,
                                    build_mesh_sp, make_lm_batches,
                                    shard_lm_batch)

    n_dev = len(jax.devices())
    model = lm_model(cfg)
    host_params = model.init(seed=0)      # once: every run starts from it
    batch = max(cfg["batch"], n_dev)
    rows = np.random.default_rng(0).integers(
        0, cfg["vocab"], size=(batch, cfg["seq"] + 1))

    runs = [("defaults", dict(data=n_dev, seq=1), "flash", {}),
            ("overlap_grads+fused_apply", dict(data=n_dev, seq=1), "flash",
             dict(overlap_grads=True, fused_apply=True))]
    if n_dev % 2 == 0:
        # sequence parallelism: the ring kernels and their collectives
        runs.append(("ring", dict(data=n_dev // 2, seq=2), "ring", {}))

    out = {}
    for label, axes, attn, knobs in runs:
        mesh = build_mesh_sp(**axes)
        step, opt_init = build_lm_train_step(
            model, mesh, adam_compact(1e-3), attn=attn, **knobs)
        params = model.shard_params(mesh, host_params)
        state = opt_init(params)
        tokens, positions, targets = shard_lm_batch(
            mesh, *make_lm_batches(rows))
        spread_over_devices((params, state), f"B[{label}] params and "
                                             "optimizer state")
        losses, times = [], []
        for _ in range(cfg["steps"]):
            t0 = time.perf_counter()
            params, state, loss = step(params, state, tokens, positions,
                                       targets)
            losses.append(float(loss))     # host sync: the step is done
            times.append(time.perf_counter() - t0)
        spread_over_devices((params, state), f"B[{label}] updated state")
        used = memory_not_all_on_first(f"B[{label}]")
        say(f"B[{label}]: mesh data={axes['data']} seq={axes['seq']} "
            f"attn={attn} B={batch} T={cfg['seq']}; loss per step "
            f"{[round(v, 4) for v in losses]}; first step "
            f"{times[0]:.2f}s (compile + run), later steps "
            f"{min(times[1:]) * 1e3:.1f} ms")
        require(all(np.isfinite(losses)), f"B[{label}]: loss {losses}")
        require(losses[-1] < losses[0],
                f"B[{label}]: loss did not fall: {losses}")
        out[label] = {"loss_first": losses[0], "loss_last": losses[-1],
                      "first_step_s": round(times[0], 2),
                      "later_step_ms": round(min(times[1:]) * 1e3, 1)}
        if used is not None:
            out[label]["bytes_in_use"] = used
        del params, state, step, opt_init, tokens, positions, targets
        gc.collect()
    return {"train": out}


# -- stage C: the server ------------------------------------------------------


def stage_c(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.models import build_mesh_sp
    from elephas_tpu.serving import ServingEngine
    from harness_env import TIE_TOL, greedy_streams_agree

    n_dev = len(jax.devices())
    model = lm_model(cfg)
    host_params = model.init(seed=0)
    params = {k: jnp.asarray(v) for k, v in host_params.items()}
    mesh = None
    if n_dev > 1:
        sp = 2 if n_dev % 2 == 0 else 1
        mesh = build_mesh_sp(data=n_dev // sp, seq=sp)
    engine_params = (params if mesh is None
                     else model.shard_params(mesh, host_params))

    rng = np.random.default_rng(1)
    max_new = cfg["max_new"]
    prompts = [rng.integers(0, cfg["vocab"], size=n).astype(np.int32)
               for n in cfg["prompt_lens"]]
    require(any(len(p) % cfg["page"] for p in prompts),
            "C: no prompt length off the page grid")

    # the reference: per-request model.generate, batched by prompt length
    want = [None] * len(prompts)
    for n in sorted(set(cfg["prompt_lens"])):
        rows = [i for i, p in enumerate(prompts) if len(p) == n]
        got = np.asarray(model.generate(
            params, np.stack([prompts[i] for i in rows]), max_new))
        for j, i in enumerate(rows):
            want[i] = got[j, n:]

    streams, detail = {}, {}
    for paged in (False, True):
        label = "paged" if paged else "dense"
        engine = ServingEngine(model, engine_params, n_slots=cfg["n_slots"],
                               max_len=cfg["max_len"], paged=paged,
                               page_size=cfg["page"], mesh=mesh)
        spread_over_devices(engine.kv.cache, f"C[{label}] KV arrays")
        ids = [engine.submit(p, max_new) for p in prompts]
        finished = engine.drain(max_steps=100 * max_new)
        used = memory_not_all_on_first(f"C[{label}]")
        require(len(finished) == len(prompts),
                f"C[{label}]: {len(finished)} of {len(prompts)} finished")
        streams[label] = []
        for rid in ids:
            fin = finished[rid]
            toks = np.asarray(fin.tokens)
            require(fin.finish_reason == "length",
                    f"C[{label}] {rid}: finished by {fin.finish_reason}")
            require(toks.shape == (max_new,),
                    f"C[{label}] {rid}: {toks.shape[0]} tokens")
            require(((toks >= 0) & (toks < cfg["vocab"])).all(),
                    f"C[{label}] {rid}: token out of range")
            streams[label].append(toks)
        detail[label] = {"requests": len(ids),
                         "tokens": int(sum(len(t) for t in streams[label]))}
        if used is not None:
            detail[label]["bytes_in_use"] = used
        say(f"C[{label}]: {len(ids)} requests finished, "
            f"{detail[label]['tokens']} tokens")
        del engine, finished
        gc.collect()

    for label, other, name, key in (
            ("dense", want, "model.generate", "equal_to_generate"),
            ("paged", streams["dense"], "dense engine", "equal_to_dense")):
        equal = 0
        for i, p in enumerate(prompts):
            agree, note = greedy_streams_agree(model, params, p,
                                               streams[label][i], other[i])
            equal += note == "equal"
            require(agree, f"C[{label}] request {i} (prompt {len(p)}) "
                           f"against {name}: {note}")
            if note != "equal":
                say(f"C[{label}] request {i} against {name}: {note} "
                    f"(within the tie tolerance {TIE_TOL})")
        detail[label][key] = equal
        say(f"C[{label}]: {equal} of {len(prompts)} greedy streams equal to "
            f"{name}" + ("" if equal == len(prompts)
                         else "; the others part at a tie"))

    detail["paged_vs_dense_logits"] = paged_against_dense_logits(
        cfg, model, params, prompts)
    return {"serve": detail}


def paged_against_dense_logits(cfg, model, params, prompts):
    """Prefill one prompt whose length is off the page grid, then take one
    decode step, through the dense cache and through a page pool: the two
    sets of logits agree within ``PAGED_LOGIT_RTOL`` of the largest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, page = cfg["probe_len"], cfg["page"]
    prompt = next(p for p in prompts if len(p) == n)
    require(n % page, "C: the logits probe must end inside a page")
    tokens = jnp.asarray(prompt[None])
    cache = model.init_cache(1, cfg["max_len"])
    m = cache["k"].shape[3] // page                  # pages per slot
    shape = (model.n_layers, m + 1, model.n_kv_heads, page,
             model.d_model // model.n_heads)
    pool = {"k": jnp.zeros(shape, model.compute_dtype),
            "v": jnp.zeros(shape, model.compute_dtype)}
    # page 0 is the trash page; map the slot's pages in a shuffled order
    table = jnp.asarray(
        np.random.default_rng(2).permutation(m)[None] + 1, jnp.int32)

    chunk_d, cache = jax.jit(model.decode_chunk)(params, tokens, 0, cache)
    chunk_p, pool = jax.jit(functools.partial(
        model.decode_chunk_paged, page=page))(params, tokens, 0, pool, table)
    nxt = jnp.argmax(chunk_d[:, -1], axis=-1).astype(jnp.int32)
    step_d, _ = jax.jit(model.decode_step)(params, nxt, n, cache)
    step_p, _ = jax.jit(functools.partial(
        model.decode_step_paged, page=page))(params, nxt, n, pool, table)
    errs = {}
    for name, a, b in (("prefill", chunk_d, chunk_p),
                       ("decode", step_d, step_p)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        require(np.isfinite(a).all() and np.isfinite(b).all(),
                f"C: non-finite {name} logits")
        errs[name] = float(np.abs(a - b).max() / np.abs(a).max())
        require(errs[name] <= PAGED_LOGIT_RTOL,
                f"C: paged against dense {name} logits differ by "
                f"{errs[name]:.3g} of the largest logit "
                f"{np.abs(a).max():.3g} (tolerance {PAGED_LOGIT_RTOL})")
    say(f"C: paged against dense logits, prompt {n}, page {page}: largest "
        f"difference as a share of the largest logit "
        f"{ {k: float(f'{v:.3g}') for k, v in errs.items()} } "
        f"(tolerance {PAGED_LOGIT_RTOL})")
    return errs


# -- stage D: the kernels really are the kernels ------------------------------


def kernel_cases(cfg, on_tpu):
    """``(name, dispatcher call, reference call, arguments, tolerance)`` for
    every Pallas dispatcher stages A-C go through, at those stages' shapes
    and dtypes. The tolerance is relative to the largest reference value."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.ops import (attention_reference, decode_attention,
                                 decode_attention_reference, flash_attention,
                                 layer_norm, layer_norm_reference)
    from elephas_tpu.ops.flash_decode import (cache_write_row,
                                              cache_write_row_reference,
                                              decode_attention_lse,
                                              decode_attention_reference_lse)
    from elephas_tpu.ops.paged_attention import (
        paged_chunk_attention, paged_chunk_reference, paged_decode_attention,
        paged_decode_attention_lse, paged_decode_reference,
        paged_decode_reference_lse)
    from elephas_tpu.ops.pallas_flash import (flash_attention_rope,
                                              make_rope_tables)

    rng = np.random.default_rng(3)
    bf16, f32 = jnp.bfloat16, jnp.float32
    B, T, H = cfg["batch"], cfg["seq"], cfg["n_heads"]
    D, Dh = cfg["d_model"], cfg["d_model"] // cfg["n_heads"]
    S, page, Tc = cfg["n_slots"], cfg["page"], cfg["max_len"]

    def normal(shape, dtype, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def weighted(fn):
        """Scalar whose gradient is the VJP of ``fn`` against ``g``."""
        return lambda g, *xs: jnp.sum(fn(*xs).astype(f32) * g.astype(f32))

    cases = []

    # training attention: generate's prefill calls flash_attention on
    # rotated q/k; the train step calls the rope-fused variant
    q, k, v, g = (normal((B, T, H, Dh), bf16) for _ in range(4))
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
    dense = lambda q, k, v: attention_reference(q, k, v, causal=True)
    cases.append(("flash_attention fwd", flash, dense, (q, k, v), 2e-2))
    cases.append(("flash_attention grad",
                  jax.grad(weighted(flash), argnums=(1, 2, 3)),
                  jax.grad(weighted(dense), argnums=(1, 2, 3)),
                  (g, q, k, v), 3e-2))
    n_odd = cfg["probe_len"]        # a prompt length off every tile grid
    cases.append((f"flash_attention fwd T={n_odd}", flash, dense,
                  (q[:1, :n_odd], k[:1, :n_odd], v[:1, :n_odd]), 2e-2))

    half = Dh // 2
    ang = (np.arange(T)[:, None]
           * 10000.0 ** (-np.arange(half) / half)[None, :])
    cos = jnp.asarray(np.broadcast_to(np.cos(ang), (B, T, half)), f32)
    sin = jnp.asarray(np.broadcast_to(np.sin(ang), (B, T, half)), f32)

    def rotate(x):                  # half-split rotary, in f32
        x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
        c, s = cos[:, :, None, :], sin[:, :, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                               -1).astype(x.dtype)

    # The rope-fused kernel is chosen by the model (``TransformerLM`` with
    # attn="flash" on a TPU), not by a dispatcher of its own, so a rehearsal
    # has to ask for interpret mode itself.
    c2, s2 = make_rope_tables(cos, sin)
    fused = lambda q, k, v: flash_attention_rope(q, k, v, c2, s2, True,
                                                 interpret=not on_tpu)
    rotated = lambda q, k, v: attention_reference(rotate(q), rotate(k), v,
                                                  causal=True)
    cases.append(("flash_attention_rope fwd", fused, rotated, (q, k, v),
                  2e-2))
    cases.append(("flash_attention_rope grad",
                  jax.grad(weighted(fused), argnums=(1, 2, 3)),
                  jax.grad(weighted(rotated), argnums=(1, 2, 3)),
                  (g, q, k, v), 3e-2))

    # layer norm on the f32 residual stream
    x, gx = normal((B * T, D), f32), normal((B * T, D), f32)
    scale, bias = normal((D,), f32) + 1.0, normal((D,), f32)
    cases.append(("layer_norm fwd", layer_norm, layer_norm_reference,
                  (x, scale, bias), 1e-3))
    cases.append(("layer_norm grad",
                  jax.grad(weighted(layer_norm), argnums=(1, 2, 3)),
                  jax.grad(weighted(layer_norm_reference),
                           argnums=(1, 2, 3)),
                  (gx, x, scale, bias), 1e-3))

    # dense decode: one query per slot against the whole slot cache
    Hkv, G = H, 1
    qd = normal((S, Hkv, G, Dh), bf16)
    kc, vc = (normal((S, Hkv, Tc, Dh), bf16) for _ in range(2))
    pos = jnp.asarray(np.linspace(0, Tc - 1, S).astype(np.int32))
    cases.append(("decode_attention", decode_attention,
                  decode_attention_reference, (qd, kc, vc, pos), 2e-3))
    cases.append(("decode_attention_lse", decode_attention_lse,
                  decode_attention_reference_lse, (qd, kc, vc, pos), 2e-3))

    # the decode step's own forms: a layer of the stacked cache read in
    # place, and the one-row write whose outputs alias the cache (exact)
    ks, vs = (normal((2, S, Hkv, Tc, Dh), bf16) for _ in range(2))
    cases.append(("decode_attention stacked",
                  functools.partial(decode_attention, layer=1),
                  functools.partial(decode_attention_reference, layer=1),
                  (qd, ks, vs, pos), 2e-3))
    cases.append(("cache_write_row", cache_write_row,
                  cache_write_row_reference,
                  (ks, vs, qd[:, :, 0], qd[:, :, 0], 1, pos), 0.0))

    # paged decode and paged chunk, through a shuffled block table
    M = Tc // page
    kp, vp = (normal((S * M + 1, Hkv, page, Dh), bf16) for _ in range(2))
    table = jnp.asarray(rng.permutation(S * M).reshape(S, M) + 1, jnp.int32)
    paged = dict(page=page)
    cases.append(("paged_decode_attention",
                  functools.partial(paged_decode_attention, **paged),
                  functools.partial(paged_decode_reference, **paged),
                  (qd, kp, vp, table, pos), 2e-3))
    cases.append(("paged_decode_attention_lse",
                  functools.partial(paged_decode_attention_lse, **paged),
                  functools.partial(paged_decode_reference_lse, **paged),
                  (qd, kp, vp, table, pos), 2e-3))
    C = min(128, Tc // 4)           # one prefill chunk continuing a slot
    qc = normal((1, Hkv, G, C, Dh), bf16)
    pos0 = jnp.asarray([n_odd], jnp.int32)
    cases.append(("paged_chunk_attention",
                  functools.partial(paged_chunk_attention, **paged),
                  functools.partial(paged_chunk_reference, **paged),
                  (qc, kp, vp, table[:1], pos0), 2e-3))
    return cases


def stage_d(cfg, on_tpu):
    import jax
    import numpy as np

    rows = []
    for name, call, reference, args, tol in kernel_cases(cfg, on_tpu):
        lowered = jax.jit(call).lower(*args)
        mosaic = "tpu_custom_call" in lowered.as_text()
        if on_tpu:
            require(mosaic, f"D[{name}]: no Mosaic custom call in the "
                            "lowered program — the dispatcher took a "
                            "reference path on the TPU")
        got = jax.tree_util.tree_leaves(lowered.compile()(*args))
        ref = jax.tree_util.tree_leaves(jax.jit(reference)(*args))
        require(len(got) == len(ref), f"D[{name}]: output count")
        worst = 0.0
        for a, b in zip(got, ref):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            require(a.shape == b.shape, f"D[{name}]: {a.shape} vs {b.shape}")
            require(np.isfinite(a).all(), f"D[{name}]: non-finite output")
            worst = max(worst, float(np.abs(a - b).max()
                                     / max(1.0, np.abs(b).max())))
        require(worst <= tol, f"D[{name}]: differs from its reference by "
                              f"{worst:.3g} (tolerance {tol})")
        rows.append({"kernel": name, "mosaic": mosaic,
                     "error": float(f"{worst:.3g}"), "tolerance": tol})
        say(f"D[{name}]: "
            + ("Mosaic custom call, compiled; " if mosaic
               else "reference path (not a TPU); ")
            + f"error {worst:.3g} against its reference (tolerance {tol})")
    return {"kernels": rows}


# -- entry --------------------------------------------------------------------


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny shapes on whatever JAX finds; debugging only")
    args = parser.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearsal:
        print(f"chip_smoke: needs a TPU, JAX found platform="
              f"{device['platform']} ({device['kind']} x{device['count']})",
              file=sys.stderr)
        return 2

    import elephas_tpu

    pkg = os.path.dirname(os.path.abspath(elephas_tpu.__file__))
    if os.path.dirname(pkg) != HERE:
        print(f"chip_smoke: elephas_tpu was imported from {pkg}, not from "
              f"this checkout ({HERE})", file=sys.stderr)
        return 2

    import jaxlib
    from importlib import metadata

    from harness_env import place_compile_cache

    cache_dir = place_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    mode = "rehearsal" if args.rehearsal else "chip"
    say(f"chip_smoke [{mode}]: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries before this run)")

    cfg = TINY if args.rehearsal else FULL
    meter, report = CompileMeter(), []
    t0 = time.perf_counter()
    run_stage("A SparkModel.fit/predict", meter, report, stage_a, cfg)
    run_stage("B build_lm_train_step", meter, report, stage_b, cfg)
    run_stage("C ServingEngine", meter, report, stage_c, cfg)
    run_stage("D Pallas dispatchers", meter, report, stage_d, cfg, on_tpu)
    total = time.perf_counter() - t0

    say(f"all stages passed in {total:.1f}s: compile {meter.compile_s:.1f}s, "
        f"compile cache {meter.hits} hits / {meter.writes} written")
    say("stages " + json.dumps(report))
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
