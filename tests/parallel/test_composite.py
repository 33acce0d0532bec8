"""3-D dp×pp×tp composite vs the single-device dense oracle.

The 8 virtual CPU devices fold into a (2, 2, 2) ("data", "pipe", "model")
mesh: GPipe microbatching over "pipe" with Megatron column→row pairs over
"model" inside each stage must reproduce the unsharded math exactly.
"""

import numpy as np
import optax
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elephas_tpu.parallel.composite import (
    TensorPipelineStack,
    build_3d_train_step,
    build_mesh_3d,
)
from tests._helpers import softmax_xent as _softmax_xent


@pytest.mark.parametrize("dp,pp,tp", [(2, 2, 2), (1, 4, 2), (1, 2, 4)])
def test_forward_matches_dense(dp, pp, tp):
    mesh = build_mesh_3d(data=dp, pipe=pp, model=tp)
    model = TensorPipelineStack(d_in=12, hidden=16, d_out=6, n_stages=pp,
                                pairs_per_stage=2)
    params = model.init(seed=3)
    x = np.random.default_rng(0).normal(size=(16, 12)).astype(np.float32)

    want = np.asarray(model.apply_reference(params, x))

    fwd = jax.jit(
        shard_map(
            lambda p, xb: model.apply(p, xb, n_micro=4),
            mesh=mesh, in_specs=(model.specs(), P("data")),
            out_specs=P("data"), check_vma=False,
        )
    )
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    got = np.asarray(fwd(model.shard_params(mesh, params), xd))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_train_step_matches_dense():
    dp, pp, tp = 2, 2, 2
    mesh = build_mesh_3d(data=dp, pipe=pp, model=tp)
    model = TensorPipelineStack(d_in=10, hidden=16, d_out=4, n_stages=pp)
    optimizer = optax.adam(1e-2)
    params = model.init(seed=1)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(32, 10)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=32)]

    def oracle_loss(p):
        return jnp.mean(_softmax_xent(y, model.apply_reference(p, x)))

    o_state = optimizer.init(params)
    o_params = params
    o_losses = []
    for _ in range(3):
        loss, grads = jax.value_and_grad(oracle_loss)(o_params)
        updates, o_state = optimizer.update(grads, o_state, o_params)
        o_params = jax.tree_util.tree_map(jnp.add, o_params, updates)
        o_losses.append(float(loss))

    step, opt_init = build_3d_train_step(
        model, mesh, optimizer, _softmax_xent, n_micro=4
    )
    sharded = model.shard_params(mesh, params)
    state = opt_init(sharded)
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    yd = jax.device_put(y, NamedSharding(mesh, P("data")))
    losses = []
    for _ in range(3):
        sharded, state, loss = step(sharded, state, xd, yd)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, o_losses, rtol=1e-4, atol=1e-5)
    got = model.gather_params(sharded)
    for k, v in o_params.items():
        np.testing.assert_allclose(
            got[k], np.asarray(v), rtol=3e-4, atol=3e-5, err_msg=k
        )


def test_validation():
    mesh = build_mesh_3d(data=2, pipe=2, model=2)
    with pytest.raises(ValueError, match="pipe axis"):
        build_3d_train_step(
            TensorPipelineStack(4, 8, 2, n_stages=4),
            mesh, optax.sgd(0.1), _softmax_xent, 2,
        )
    with pytest.raises(ValueError, match="not divisible"):
        build_3d_train_step(
            TensorPipelineStack(4, 9, 2, n_stages=2),
            mesh, optax.sgd(0.1), _softmax_xent, 2,
        )
    with pytest.raises(ValueError, match="needs"):
        build_mesh_3d(data=4, pipe=4, model=4)
