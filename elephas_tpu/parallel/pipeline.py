"""Pipeline parallelism over a ``("data", "pipe")`` mesh.

EXTENSION BEYOND THE REFERENCE. The reference is data-parallel only — every
executor holds a complete replica and pipeline parallelism is "explicitly
ABSENT" (SURVEY.md §2.3) — so model *depth* is capped by one worker's memory
exactly as width is. This module removes the depth cap the TPU-native way:
layers are grouped into P stages, each stage's parameters live on one
position along a ``"pipe"`` mesh axis, and microbatches stream through the
stage ring via ``jax.lax.ppermute`` (nearest-neighbor ICI hops — the same
topology ring attention rides). The whole pipelined step is ONE ``shard_map``
program; the backward pass is the *reverse* pipeline for free, because XLA
transposes ``ppermute`` to the inverted permutation and ``lax.scan`` to the
reversed scan — no hand-written 1F1B state machine, no Python scheduler.

Schedule: GPipe (Huang et al. 2019). With M microbatches and P stages the
program runs ``M + P - 1`` ticks; every device applies its stage every tick,
so the bubble fraction is ``(P-1)/(M+P-1)`` — choose ``n_micro >> pipe`` to
amortize. Ramp-up/drain ticks compute on don't-care data whose outputs carry
zero cotangent (they never reach the loss), so results are exact, not
approximate: forward and gradients match the unpipelined oracle
bit-closely (``tests/parallel/test_pipeline.py``).

Stages must be shape-homogeneous (``stage_fn: [mb, h] -> [mb, h]``) so one
rotating activation buffer serves every hop; the in/out projections that
change width run replicated outside the ring (their gradients are restored
to the replicated invariant with one pipe-axis ``psum`` — see
``build_pp_train_step``). Composes with the ``"data"`` axis: dp×pp in one
executable, batch sharded over ``"data"``, stages over ``"pipe"``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS, build_mesh_2axis
from .param_utils import (
    gather_host,
    glorot,
    make_opt_init,
    opt_state_specs,
    shard_by_specs,
)

PIPE_AXIS = "pipe"


def build_mesh_pp(data: Optional[int] = None, pipe: int = 1,
                  devices: Optional[Sequence] = None) -> Mesh:
    """A 2-D ``("data", "pipe")`` mesh; ``pipe`` = pipeline depth (stage
    count). Adjacent devices form the stage ring (innermost axis) so the
    per-tick activation hop is a nearest-neighbor ICI transfer."""
    return build_mesh_2axis(PIPE_AXIS, data=data, second=pipe,
                            devices=devices)


def pipeline_apply(stage_fn: Callable, stage_params, x, n_micro: int,
                   axis_name: str = PIPE_AXIS):
    """Run ``x`` through the stage ring; call INSIDE ``shard_map``.

    ``stage_params`` are THIS rank's stage parameters (the local shard of the
    ``[P, ...]`` stacked stage params, leading axis squeezed). ``x`` is the
    local batch ``[B, h]``, replicated over the pipe axis and (typically)
    sharded over ``"data"``; ``B`` must divide by ``n_micro``. Returns the
    pipelined output ``[B, h]``, replicated over the pipe axis again (one
    masked ``psum`` broadcasts the last stage's emissions).

    The GPipe tick loop is a ``lax.scan`` so the reverse-mode transpose is
    the reverse pipeline; don't-care ramp/drain outputs receive zero
    cotangent through the output mask.
    """
    p = axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro={n_micro}")
    mb = b // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])
    ticks = n_micro + p - 1
    perm = [(i, (i + 1) % p) for i in range(p)]

    def tick(carry, t):
        # carry = activation computed here last tick, now hopping one stage on
        recv = jax.lax.ppermute(carry, axis_name, perm)
        feed = jax.lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False
        )
        inp = jnp.where(rank == 0, feed, recv)
        out = stage_fn(stage_params, inp)
        return out, out

    zero = jnp.zeros_like(x_micro[0])
    _, ys = jax.lax.scan(tick, zero, jnp.arange(ticks))
    # Rank P-1 emits microbatch m at tick m + P - 1; broadcast its valid
    # window back to every pipe rank (the data-axis shard stays put).
    valid = jax.lax.dynamic_slice_in_dim(ys, p - 1, n_micro, axis=0)
    mask = (rank == p - 1).astype(valid.dtype)
    out = jax.lax.psum(valid * mask, axis_name)
    return out.reshape((b,) + out.shape[2:])


# -- a functional pipelined dense stack ---------------------------------------


class PipelineDenseStack:
    """Dense residual blocks split into homogeneous pipeline stages.

    ``n_stages × layers_per_stage`` layers of ``h → h`` (activation applied
    after each), bracketed by replicated in/out projections
    ``d_in → h`` / ``h → d_out``. Stage params are stacked on a leading
    ``[P, ...]`` axis sharded over ``"pipe"``; projections replicate.
    :meth:`init` returns FULL host params (the dense view for tests and
    checkpoints); :meth:`shard_params` places them on the mesh.
    """

    def __init__(self, d_in: int, hidden: int, d_out: int, n_stages: int,
                 layers_per_stage: int = 1, activation=jax.nn.relu,
                 final_activation=None):
        if n_stages < 1 or layers_per_stage < 1:
            raise ValueError("n_stages and layers_per_stage must be >= 1")
        self.d_in = d_in
        self.hidden = hidden
        self.d_out = d_out
        self.n_stages = n_stages
        self.layers_per_stage = layers_per_stage
        self.activation = activation
        self.final_activation = final_activation

    def param_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Full (unsharded) shape/dtype per param — the shape-only source for
        :meth:`init` and the train-step builder's optimizer-state specs."""
        S, G, h = self.n_stages, self.layers_per_stage, self.hidden
        return {
            "win": jax.ShapeDtypeStruct((self.d_in, h), jnp.float32),
            "bin": jax.ShapeDtypeStruct((h,), jnp.float32),
            "w": jax.ShapeDtypeStruct((S, G, h, h), jnp.float32),
            "b": jax.ShapeDtypeStruct((S, G, h), jnp.float32),
            "wout": jax.ShapeDtypeStruct((h, self.d_out), jnp.float32),
            "bout": jax.ShapeDtypeStruct((self.d_out,), jnp.float32),
        }

    def init(self, seed: int = 0) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(seed)
        return {
            name: glorot(rng, *sds.shape, dtype=sds.dtype)
            if name.startswith("w") else np.zeros(sds.shape, sds.dtype)
            for name, sds in self.param_shapes().items()
        }

    def specs(self) -> Dict[str, P]:
        """Stage stacks shard their leading axis over ``"pipe"``; the in/out
        projections replicate (every rank computes them, gradients are
        pipe-psummed back to agreement)."""
        return {
            "win": P(), "bin": P(),
            "w": P(PIPE_AXIS), "b": P(PIPE_AXIS),
            "wout": P(), "bout": P(),
        }

    def shard_params(self, mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
        return shard_by_specs(mesh, self.specs(), params)

    def gather_params(self, params: Dict[str, Any]) -> Dict[str, np.ndarray]:
        return gather_host(params)

    def _stage_fn(self, stage_params, x):
        """One stage's layers; runs every tick. ``stage_params`` =
        ``(w [G, h, h], b [G, h])`` for THIS rank's stage."""
        w, b = stage_params
        h = x
        for g in range(self.layers_per_stage):
            h = self.activation(jnp.dot(h, w[g]) + b[g])
        return h

    def apply(self, params: Dict[str, Any], x, n_micro: int):
        """Forward INSIDE shard_map: ``params["w"]/["b"]`` are local
        ``[1, G, ...]`` pipe shards."""
        h = self.activation(jnp.dot(x, params["win"]) + params["bin"])
        h = pipeline_apply(
            self._stage_fn, (params["w"][0], params["b"][0]), h, n_micro
        )
        y = jnp.dot(h, params["wout"]) + params["bout"]
        return self.final_activation(y) if self.final_activation else y

    def apply_reference(self, params: Dict[str, Any], x):
        """Single-device oracle on FULL params (no mesh, no microbatching)."""
        h = self.activation(jnp.dot(x, params["win"]) + params["bin"])
        for s in range(self.n_stages):
            for g in range(self.layers_per_stage):
                h = self.activation(jnp.dot(h, params["w"][s, g]) + params["b"][s, g])
        y = jnp.dot(h, params["wout"]) + params["bout"]
        return self.final_activation(y) if self.final_activation else y


def build_pp_train_step(model: PipelineDenseStack, mesh: Mesh, optimizer,
                        per_sample_loss, n_micro: int):
    """Compile one dp×pp gradient-synchronous training step.

    Returns ``(step, opt_init)`` with the same contract as
    ``tensor.build_tp_train_step``: batch sharded over ``"data"``, stage
    params sharded over ``"pipe"``, optimizer state sharded like the params.

    Gradient collectives, and why each is (not) needed:

    - stage params (``w``/``b``): NONE over ``"pipe"`` — each rank owns its
      stage outright, and the reverse pipeline delivers its cotangles
      locally; ``psum`` over ``"data"`` like any dp gradient.
    - replicated projections (``win``/``wout``...): ``psum`` over ``"pipe"``.
      The loss is masked to the last pipe rank (so it is counted once, not P
      times); under that masking each rank holds only its *partial* of the
      projection gradients — rank 0 the whole ``win`` gradient, rank P-1 the
      whole ``wout`` gradient, zeros elsewhere — and the pipe-psum restores
      the identical-across-ranks invariant replication requires.
    """
    if mesh.shape[PIPE_AXIS] != model.n_stages:
        raise ValueError(
            f"pipe axis size {mesh.shape[PIPE_AXIS]} != n_stages "
            f"{model.n_stages} (one stage per pipe rank)"
        )
    return build_staged_train_step(
        model, mesh, optimizer, per_sample_loss, n_micro,
        stage_keys=("w", "b"),
    )


def build_staged_train_step(model, mesh: Mesh, optimizer, per_sample_loss,
                            n_micro: int, stage_keys):
    """Shared step builder for pipelined models (``build_pp_train_step`` and
    ``composite.build_3d_train_step``): ``model`` needs ``apply(params, x,
    n_micro)``, ``specs()``, ``param_shapes()``. ``stage_keys`` are the
    pipe-owned params whose gradients skip the pipe-axis psum; all other
    params are pipe-replicated and get one. Additional mesh axes inside the
    stage (e.g. ``"model"``) manage their own collectives via the stage's
    primitives."""
    pspecs = model.specs()
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    data_spec = P(DATA_AXIS)

    def step_impl(params, opt_state, x, y):
        prank = jax.lax.axis_index(PIPE_AXIS)
        psize = axis_size(PIPE_AXIS)

        def loss_fn(p):
            y_pred = model.apply(p, x, n_micro)
            local = jnp.sum(per_sample_loss(y, y_pred))
            # Count the (pipe-replicated) loss once: mask to the last rank.
            return jnp.where(prank == psize - 1, local, 0.0)

        local_loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = {
            k: (g if k in stage_keys else jax.lax.psum(g, PIPE_AXIS))
            for k, g in grads.items()
        }
        n = jax.lax.psum(jnp.asarray(x.shape[0], jnp.float32), DATA_AXIS)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, DATA_AXIS) / n, grads
        )
        loss = jax.lax.psum(
            jax.lax.psum(local_loss, PIPE_AXIS), DATA_AXIS
        ) / n
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(pspecs, sspecs, data_spec, data_spec),
            out_specs=(pspecs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )

    return step, make_opt_init(optimizer, mesh, sspecs)
