"""Training steps: ``build_lm_train_step`` on a data mesh over the cell's
chips, a fresh batch through ``make_lm_batches`` + ``shard_lm_batch`` each
step, every step's loss fetched (one step later, so that the device always
has its next step queued).

Timeline: set-up (weights on the device, loss-and-gradient check against
the plain reference, optimizer state, two warm-up steps) | the window:
whole steps until ``--seconds`` have passed | (traced run only)
``profile_steps`` more steps with the profiler on.
"""

import contextlib
import math
import time

import numpy as np

from benchmark import trace
from benchmark.peaks import lm_train_flops_per_token, peaks_for
from benchmark.weights import make_weights

# Loss and gradients, program (bfloat16 compute, flash kernels) against the
# reference (float32, highest precision), at the cell's widths and depth on
# 512 tokens. The loss is a mean over 512 positions, so rounding averages
# out: 2e-3 relative. A gradient leaf is compared by the norm of the
# difference over the norm of the reference: bfloat16 products through a
# few layers leave one to two percent; float32 computed at a lower
# precision than stated, or a dropped term, leaves tens of percent.
LOSS_RTOL = 2e-3
GRAD_RTOL = 0.04


def check_gradients(ctx, model, weights):
    import jax
    import jax.numpy as jnp

    ref = ctx.manifest.module("reference", ctx.cfg["family"])
    chk = ctx.cfg["check"]
    n, leaves = chk["tokens"], list(chk["grad_leaves"])
    # On several chips the weights are replicated: the check runs on the
    # first chip's copy (a Mosaic kernel outside shard_map cannot be
    # partitioned, and one sequence needs one chip).
    weights = {k: v.addressable_shards[0].data for k, v in weights.items()}
    rng = np.random.default_rng([ctx.seed, 5])
    row = rng.integers(0, ctx.cfg["vocab_size"], size=n + 1)
    tokens = jnp.asarray(row[None, :-1], jnp.int32)
    targets = jnp.asarray(row[None, 1:], jnp.int32)
    positions = jnp.arange(n, dtype=jnp.int32)[None]

    # tokens are arguments, not closed over: a closed-over array is a
    # constant of the program, and every seed would compile it anew
    def program(part, rest, tokens, positions, targets):
        return model.loss({**rest, **part}, tokens, positions, targets,
                          attn=ctx.cfg["train"]["attn"]) / n

    part = {k: weights[k] for k in leaves}
    rest = {k: v for k, v in weights.items() if k not in leaves}
    loss, grads = jax.jit(jax.value_and_grad(program))(
        part, rest, tokens, positions, targets)
    want_loss, want = ref.loss_and_grads(ctx.cfg, weights, tokens[0],
                                         targets[0], leaves)
    loss, want_loss = float(loss), float(want_loss)
    ok = abs(loss - want_loss) <= LOSS_RTOL * abs(want_loss)
    notes = [f"loss {loss:.5f} against {want_loss:.5f}"]
    for k in leaves:
        g = np.asarray(grads[k], np.float32)
        w = np.asarray(want[k], np.float32)
        rel = float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
        ok = ok and np.isfinite(g).all() and rel <= GRAD_RTOL
        notes.append(f"grad {k} off by {rel:.4f} of its norm")
    ctx.log("check: " + "; ".join(notes) + f" (limits {LOSS_RTOL}, "
            f"{GRAD_RTOL}) -> {'ok' if ok else 'WRONG'}")
    return bool(ok)


def run(ctx):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu import models as M

    cfg, mix, tr = ctx.cfg, ctx.mix, ctx.cfg["train"]
    chips = len(ctx.devices)
    model = ctx.manifest.module("families", cfg["family"]).build_model(cfg)
    mesh = M.build_mesh_sp(data=chips, seq=1, devices=ctx.devices)
    weights = make_weights(model, ctx.seed, cfg["weights"]["dtype"],
                           cfg["weights"]["float32_leaves"],
                           sharding=NamedSharding(mesh, P()))
    correct = check_gradients(ctx, model, weights)

    optimizer = getattr(M, tr["optimizer"])(tr["learning_rate"])
    step, opt_init = M.build_lm_train_step(
        model, mesh, optimizer, attn=tr["attn"], **tr["step_kwargs"])
    params = model.shard_params(mesh, weights)
    del weights
    state = opt_init(params)

    seq, rows = tr["sequence_length"], tr["rows_per_chip"] * chips
    rng = np.random.default_rng([ctx.seed, 6])
    pool = [rng.integers(0, cfg["vocab_size"], size=(rows, seq + 1))
            for _ in range(int(mix["pool_batches"]))]
    tokens_per_step = rows * seq
    annotate = False

    def span(name):
        if not annotate:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name)

    clock = time.perf_counter
    input_ms, step_ms, losses = [], [], []
    in_flight = []                     # losses still on the device, oldest first

    def dispatch(i):
        nonlocal params, state
        t_a = clock()
        with span("input"):
            batch = M.shard_lm_batch(mesh, *M.make_lm_batches(
                pool[i % len(pool)]))
        input_ms.append((clock() - t_a) * 1e3)
        params, state, loss = step(params, state, *batch)
        in_flight.append(loss)

    last_done = [clock()]

    def collect():
        """Wait for the oldest step in flight; a step's time is the time
        since the step before it was done."""
        losses.append(float(in_flight.pop(0)))
        now = clock()
        step_ms.append((now - last_done[0]) * 1e3)
        last_done[0] = now

    def one_step(i):
        # One step stays queued behind the one that runs, as a training
        # loop runs it: the host prepares and enqueues step i while the
        # device works on step i-1, then fetches the loss of step i-1. A
        # stall of the host (other tenants share its cores) then costs
        # nothing until it outlasts a whole step.
        with span("train_step"):
            dispatch(i)
            if len(in_flight) > 1:
                collect()

    for i in range(2):                      # compile, then one warm step
        one_step(i)
    collect()
    for samples in (input_ms, step_ms, losses):
        samples.clear()

    programs0 = ctx.meter.programs
    t0 = last_done[0] = clock()
    setup_s = t0 - ctx.t_start
    n = 0
    while clock() - t0 < ctx.seconds:
        one_step(n)
        n += 1
    collect()                               # the window ends with its last step done
    elapsed = clock() - t0
    compiled = ctx.meter.programs - programs0
    window_losses, window_steps = list(losses), list(step_ms)
    window_input = list(input_ms)

    summary = None
    if ctx.trace:
        prof = trace.Profiler(ctx.out_dir)
        prof.start()
        annotate = True
        for j in range(int(mix.get("profile_steps", 4))):
            one_step(n + j)
        collect()
        annotate = False
        summary = prof.stop()

    bad = sum(1 for v in window_losses if not math.isfinite(v))
    k = len(pool)
    falls = (len(window_losses) >= 2 * k
             and np.mean(window_losses[-k:]) < np.mean(window_losses[:k]))
    ctx.log(f"window: {n} steps of {tokens_per_step} tokens in "
            f"{elapsed:.3f} s; loss first cycle "
            f"{np.mean(window_losses[:k]):.4f}, last "
            f"{np.mean(window_losses[-k:]):.4f}; {bad} non-finite; "
            f"{compiled} programs compiled inside")
    correct = correct and bad == 0 and falls and compiled == 0
    tokens_per_s = n * tokens_per_step / elapsed
    # off the TPU (the tests) there is no peak, and no utilisation
    flops = (peaks_for(ctx.devices[0].device_kind)[0]
             if ctx.devices[0].platform == "tpu" else None)
    facts = {
        "step_ms": window_steps, "input_ms": window_input,
        "tokens_per_s": tokens_per_s, "chips": chips,
        "flops_per_token": lm_train_flops_per_token(cfg, seq),
        "peak_flops": flops, "trace": summary,
    }
    return {
        "correct": bool(correct), "attempted": n, "failed": bad,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "facts": facts,
    }
