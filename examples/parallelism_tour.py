"""A runnable tour of every parallelism schedule the framework ships.

EXTENSION SHOWCASE (the reference is data-parallel only — SURVEY.md §2.3).
On whatever devices are visible this script builds each trainer on a small
model, runs a few steps, and prints the loss trajectory: tensor (tp),
pipeline (pp), expert (ep, both routings), ZeRO-3 (fsdp), the dp×sp(×ep)
transformer LMs, and the 3-D dp×pp×tp composite. Every schedule here is
verified against a single-device oracle in `tests/` — this file is the
user-facing "how do I hold it" companion.

Run (CPU mesh): prefix with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
Run (TPU): ``KERAS_BACKEND=jax python examples/parallelism_tour.py``
"""

import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def second_axis(n_devices: int) -> int:
    return max(d for d in (1, 2, 4, 8) if n_devices % d == 0)


def run_steps(step, params, state, batch, n=6):
    losses = []
    for _ in range(n):
        params, state, loss = step(params, state, *batch)
        losses.append(float(loss))
    return losses


def main():
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import elephas_tpu.parallel as par
    from elephas_tpu.models import (
        MoETransformerLM,
        build_lm_train_step,
        build_mesh_sp,
        make_lm_batches,
        shard_lm_batch,
    )

    rng = np.random.default_rng(0)
    n_dev = len(jax.devices())
    second = second_axis(n_dev)
    dp = n_dev // second
    print(f"{n_dev} device(s); second-axis size {second}")

    def xent(y, yp):
        return -jnp.sum(y * jax.nn.log_softmax(yp, -1), -1)

    x = rng.normal(size=(32 * dp, 16)).astype("float32")
    y = np.eye(4, dtype="float32")[rng.integers(0, 4, size=32 * dp)]

    def data_batch(mesh, spec=P("data")):
        return (jax.device_put(x, NamedSharding(mesh, spec)),
                jax.device_put(y, NamedSharding(mesh, spec)))

    # -- tensor parallelism: Megatron column/row pairs
    mesh = par.build_mesh2d(data=dp, model=second)
    tpm = par.TensorParallelMLP([16, 8 * second, 8 * second, 8 * second, 4],
                                tp=second)
    step, oi = par.build_tp_train_step(tpm, mesh, optax.adam(1e-2), xent)
    p = tpm.shard_params(mesh, tpm.init())
    print("tp   ", run_steps(step, p, oi(p), data_batch(mesh)))

    # -- pipeline parallelism: GPipe microbatching
    mesh = par.build_mesh_pp(data=dp, pipe=second)
    ppm = par.PipelineDenseStack(d_in=16, hidden=16, d_out=4,
                                 n_stages=second)
    step, oi = par.build_pp_train_step(ppm, mesh, optax.adam(1e-2), xent,
                                       n_micro=4)
    p = ppm.shard_params(mesh, ppm.init())
    print("pp   ", run_steps(step, p, oi(p), data_batch(mesh)))

    # -- expert parallelism: token-choice and dropless expert-choice
    for routing in ("token_choice", "expert_choice"):
        mesh = par.build_mesh_ep(data=dp, expert=second)
        moe = par.MoEFeedForward(d_model=16, d_ff=32,
                                 n_experts=2 * second, k=2, routing=routing)
        step, oi = par.build_ep_train_step(
            moe, mesh, optax.adam(1e-2),
            lambda a, b: jnp.sum((a - b) ** 2, -1))
        p = moe.shard_params(mesh, moe.init())
        xt = rng.normal(size=(16 * n_dev, 16)).astype("float32")
        spec = P(("data", "expert"))
        batch = (jax.device_put(xt, NamedSharding(mesh, spec)),) * 2
        print(f"ep({routing[:5]})", run_steps(step, p, oi(p), batch))

    # -- ZeRO-3 / fsdp: params+grads+opt state chunked over the data axis
    mesh = par.build_mesh(n_dev)
    shapes = {"w0": (16, 32), "b0": (32,), "w1": (32, 4), "b1": (4,)}

    def apply_fn(pr, xb):
        h = jax.nn.relu(jnp.dot(xb, pr["w0"]) + pr["b0"])
        return jnp.dot(h, pr["w1"]) + pr["b1"]

    step, oi, fsdp = par.build_fsdp_train_step(
        apply_fn, shapes, mesh, optax.adam(1e-2), xent)
    p = fsdp.shard(mesh, fsdp.chunk_host(
        {k: (rng.normal(size=s) * 0.1).astype("float32")
         for k, s in shapes.items()}))
    xf = rng.normal(size=(8 * n_dev, 16)).astype("float32")
    yf = np.eye(4, dtype="float32")[rng.integers(0, 4, size=8 * n_dev)]
    batch = (jax.device_put(xf, NamedSharding(mesh, P("data"))),
             jax.device_put(yf, NamedSharding(mesh, P("data"))))
    print("fsdp ", run_steps(step, p, oi(p), batch))

    # -- dp×sp×ep: MoE transformer LM, sequence + experts on one axis
    mesh = build_mesh_sp(data=dp, seq=second)
    lm = MoETransformerLM(vocab=13, d_model=16, n_heads=second, n_layers=1,
                          d_ff=32, max_len=16 * second,
                          n_experts=2 * second, k=1, ep_groups=second)
    step, oi = build_lm_train_step(lm, mesh, optax.adam(3e-3), attn="ring")
    rows = rng.integers(0, 13, size=(4 * dp, 16 * second + 1))
    batch = shard_lm_batch(mesh, *make_lm_batches(rows))
    p = lm.shard_params(mesh, lm.init())
    print("lm   ", run_steps(step, p, oi(p), batch))

    # -- 3-D composite: dp × pipeline × tensor (needs >= 4 devices)
    if n_dev >= 4:
        tp3 = 2
        pp3 = second // tp3 if second > tp3 else 2
        dp3 = n_dev // (pp3 * tp3)
        mesh = par.build_mesh_3d(data=dp3, pipe=pp3, model=tp3)
        m3 = par.TensorPipelineStack(d_in=16, hidden=16, d_out=4,
                                     n_stages=pp3)
        step, oi = par.build_3d_train_step(m3, mesh, optax.adam(1e-2), xent,
                                           n_micro=4)
        x3 = rng.normal(size=(16 * dp3, 16)).astype("float32")
        y3 = np.eye(4, dtype="float32")[rng.integers(0, 4, size=16 * dp3)]
        batch = (jax.device_put(x3, NamedSharding(mesh, P("data"))),
                 jax.device_put(y3, NamedSharding(mesh, P("data"))))
        p = m3.shard_params(mesh, m3.init())
        print("3d   ", run_steps(step, p, oi(p), batch))

    print("ok")


if __name__ == "__main__":
    main()
