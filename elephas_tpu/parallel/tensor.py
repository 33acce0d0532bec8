"""Tensor parallelism over a 2-D ``("data", "model")`` mesh.

EXTENSION BEYOND THE REFERENCE. The reference is data-parallel only — every
executor holds a complete model replica (SURVEY.md §2.3: tensor parallelism
"explicitly ABSENT") — so model width is capped by one worker's memory. This
module removes that cap the TPU-native way: weight matrices are sharded over
a second mesh axis (``"model"``) and the partial products are combined with
one ``psum`` riding ICI, Megatron-style, while the ``"data"`` axis keeps the
engine's data parallelism. Both axes live in ONE ``shard_map`` program, so a
dp×tp step is still a single XLA executable.

Layer primitives (run INSIDE ``shard_map``; shards are the local blocks):

- :func:`column_parallel_dense` — ``W`` split along its OUTPUT dim. Each
  shard computes its slice of the activations; no communication. The natural
  first half of a Megatron pair (the nonlinearity applies elementwise to the
  sharded activations).
- :func:`row_parallel_dense` — ``W`` split along its INPUT dim, consuming
  activations that are already feature-sharded. Partial products are summed
  with ``psum`` over the model axis; the bias is added once after the sum.

A column→row pair therefore costs exactly one collective, the classic
Megatron-LM schedule (Shoeybi et al. 2019) — and XLA overlaps that psum with
the next layer's matmul when it can.

:class:`TensorParallelMLP` builds a functional MLP from these pairs with
deterministically-sharded initialization, and :func:`build_tp_train_step`
compiles the full dp×tp training step: batch sharded over ``"data"``, params
sharded over ``"model"``, per-batch gradient ``psum`` over ``"data"`` (the
gradient-synchronous schedule of ``engine.py``), optimizer state sharded
exactly like the params (so optimizer memory also scales down with tp —
ZeRO-flavored for free). Gradients of model-sharded params need NO collective
over the model axis: the ``psum`` in the forward differentiates to the
identity on each shard's partial product (shard_map's transpose rule), which
the equivalence test verifies against a single-device dense oracle.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS, build_mesh_2axis
from .param_utils import (  # noqa: F401 — opt_state_specs re-exported
    gather_host,
    glorot,
    make_opt_init,
    opt_state_specs,
    shard_by_specs,
)

MODEL_AXIS = "model"


def build_mesh2d(data: Optional[int] = None, model: int = 1,
                 devices: Optional[Sequence] = None) -> Mesh:
    """A 2-D ``("data", "model")`` mesh; ``model`` = tensor-parallel degree."""
    return build_mesh_2axis(MODEL_AXIS, data=data, second=model,
                            devices=devices)


# -- layer primitives (inside shard_map) --------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_identity_grad(x, axis_name):
    """``psum`` whose VJP is the identity.

    Inside ``shard_map(check_vma=False)`` the default transpose of ``psum``
    is another ``psum`` (replication is untracked, so JAX assumes the
    cotangent needs summing), which would scale every upstream gradient by
    the axis size. For a row-parallel sum the correct cotangent IS the
    unsummed one — ``d(Σ_m part_m)/d(part_m) = 1`` and the incoming cotangent
    is already identical on every shard — so the identity transpose restores
    the dense-model gradients exactly (verified leaf-by-leaf in
    ``tests/parallel/test_tensor.py``).
    """
    return jax.lax.psum(x, axis_name)


def _psum_ig_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_ig_bwd(axis_name, _, ct):
    return (ct,)


psum_identity_grad.defvjp(_psum_ig_fwd, _psum_ig_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def identity_psum_grad(x, axis_name):
    """Identity forward, ``psum`` backward — Megatron's ``f`` operator.

    A column-parallel layer reads a REPLICATED input; each model shard's
    backward pass produces only its own partial of the input cotangent
    (``ct_y_m @ w_m^T``), so the true cotangent is their model-axis sum.
    Together with :func:`psum_identity_grad` (the conjugate ``g``), forward
    and backward each carry exactly one all-reduce per column→row pair.
    """
    return x


def _id_pg_fwd(x, axis_name):
    return x, None


def _id_pg_bwd(axis_name, _, ct):
    return (jax.lax.psum(ct, axis_name),)


identity_psum_grad.defvjp(_id_pg_fwd, _id_pg_bwd)


def column_parallel_dense(x, w_shard, b_shard, activation=None,
                          axis_name=MODEL_AXIS):
    """``[B, F] @ [F, H/P] + [H/P]`` → feature-sharded ``[B, H/P]``.

    No forward communication; the input's cotangent is all-reduced in the
    backward pass (see :func:`identity_psum_grad`).
    """
    x = identity_psum_grad(x, axis_name)
    y = jnp.dot(x, w_shard, preferred_element_type=jnp.float32)
    y = (y + b_shard).astype(x.dtype)
    return activation(y) if activation is not None else y


def row_parallel_dense(x_shard, w_shard, b, axis_name=MODEL_AXIS,
                       activation=None):
    """Feature-sharded ``[B, H/P] @ [H/P, O]`` → ``psum`` → full ``[B, O]``.

    ``b`` is replicated over the model axis and added once, after the sum.
    """
    part = jnp.dot(x_shard, w_shard, preferred_element_type=jnp.float32)
    y = (psum_identity_grad(part, axis_name) + b).astype(x_shard.dtype)
    return activation(y) if activation is not None else y


# -- a functional tensor-parallel MLP ----------------------------------------


class TensorParallelMLP:
    """Functional MLP of Megatron column→row pairs.

    ``dims = [in, h1, h2, ..., out]`` with hidden activations; every even
    layer is column-parallel (hidden dim sharded over ``"model"``), every odd
    layer row-parallel. Hidden dims must divide by the tp degree. Params are a
    flat dict of named arrays; :meth:`init` returns FULL (unsharded) host
    params so tests and checkpoints see the dense view, :meth:`shard_params`
    places them on the mesh with the right :class:`PartitionSpec` per leaf.
    """

    def __init__(self, dims: Sequence[int], tp: int,
                 activation=jax.nn.relu, final_activation=None):
        if len(dims) < 3 or len(dims) % 2 == 0:
            raise ValueError(
                "dims must be [in, h1, ..., out] with an even layer count "
                "(column/row pairs); pad with an extra hidden layer"
            )
        for h in dims[1:-1:2]:
            if h % tp:
                raise ValueError(f"hidden dim {h} not divisible by tp={tp}")
        self.dims = list(dims)
        self.tp = tp
        self.activation = activation
        self.final_activation = final_activation
        self.n_layers = len(dims) - 1

    # param name helpers
    @staticmethod
    def _wname(i: int) -> str:
        return f"w{i}"

    @staticmethod
    def _bname(i: int) -> str:
        return f"b{i}"

    def param_shapes(self) -> Dict[str, Any]:
        """Full (unsharded) shape/dtype per param — the single layout source
        for :meth:`init` and :func:`opt_state_specs`."""
        shapes: Dict[str, Any] = {}
        for i in range(self.n_layers):
            fan_in, fan_out = self.dims[i], self.dims[i + 1]
            shapes[self._wname(i)] = jax.ShapeDtypeStruct(
                (fan_in, fan_out), jnp.float32
            )
            shapes[self._bname(i)] = jax.ShapeDtypeStruct(
                (fan_out,), jnp.float32
            )
        return shapes

    def init(self, seed: int = 0) -> Dict[str, np.ndarray]:
        """Full (unsharded) Glorot-uniform params on the host."""
        rng = np.random.default_rng(seed)
        params: Dict[str, np.ndarray] = {}
        for name, sds in self.param_shapes().items():
            if len(sds.shape) == 2:
                params[name] = glorot(rng, *sds.shape, dtype=sds.dtype)
            else:
                params[name] = np.zeros(sds.shape, sds.dtype)
        return params

    def specs(self) -> Dict[str, P]:
        """PartitionSpec per param: column layers shard the output dim, row
        layers the input dim; row biases are replicated."""
        specs: Dict[str, P] = {}
        for i in range(self.n_layers):
            if i % 2 == 0:  # column-parallel: shard fan_out
                specs[self._wname(i)] = P(None, MODEL_AXIS)
                specs[self._bname(i)] = P(MODEL_AXIS)
            else:  # row-parallel: shard fan_in
                specs[self._wname(i)] = P(MODEL_AXIS, None)
                specs[self._bname(i)] = P()
        return specs

    def shard_params(self, mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
        return shard_by_specs(mesh, self.specs(), params)

    def gather_params(self, params: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Device (possibly sharded) params → full host arrays."""
        return gather_host(params)

    def _layer_activation(self, i: int):
        """Hidden layers get ``activation`` (elementwise, so it applies to
        sharded and full features alike); the last layer gets
        ``final_activation``."""
        if i == self.n_layers - 1:
            return self.final_activation
        return self.activation

    def apply(self, params: Dict[str, Any], x):
        """Forward pass INSIDE shard_map: params are local shards."""
        h = x
        for i in range(self.n_layers):
            w, b = params[self._wname(i)], params[self._bname(i)]
            act = self._layer_activation(i)
            if i % 2 == 0:
                h = column_parallel_dense(h, w, b, activation=act)
            else:
                h = row_parallel_dense(h, w, b, activation=act)
        return h

    def apply_reference(self, params: Dict[str, Any], x):
        """Single-device oracle on FULL params (no mesh, no collectives)."""
        h = x
        for i in range(self.n_layers):
            h = jnp.dot(h, params[self._wname(i)]) + params[self._bname(i)]
            act = self._layer_activation(i)
            if act is not None:
                h = act(h)
        return h


def build_tp_train_step(model: TensorParallelMLP, mesh: Mesh, optimizer,
                        per_sample_loss):
    """Compile one dp×tp gradient-synchronous training step.

    Returns ``(step, opt_init)``:

    - ``opt_init(sharded_params) -> opt_state`` — state sharded like params.
    - ``step(params, opt_state, x, y) -> (params, opt_state, loss)`` — ``x``
      ``[B, F]`` / ``y`` ``[B, C]`` sharded over ``"data"``; params/state
      sharded over ``"model"``; one grad ``psum`` over ``"data"`` per step.

    Sharding invariants ride in/out via the PartitionSpecs, so the returned
    params feed the next call without reshard.
    """
    pspecs = model.specs()
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    data_spec = P(DATA_AXIS)

    def step_impl(params, opt_state, x, y):
        def loss_fn(p):
            y_pred = model.apply(p, x)
            return jnp.sum(per_sample_loss(y, y_pred))

        local_loss, grads = jax.value_and_grad(loss_fn)(params)
        # Explicit data-axis reduction: shard_map's psum transposes to a
        # broadcast, so a forward-side psum would NOT sum the gradients —
        # without this line each data group would apply only its own grads
        # and the "replicated over data" invariant on params would break.
        n = jax.lax.psum(jnp.asarray(x.shape[0], jnp.float32), DATA_AXIS)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, DATA_AXIS) / n, grads
        )
        loss = jax.lax.psum(local_loss, DATA_AXIS) / n
        # Model-axis grads need no collective: the forward psum's cotangent
        # reaches each shard's partial product directly, and replicated
        # leaves (row biases) see identical cotangents on every shard.
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
