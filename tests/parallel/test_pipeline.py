"""Pipelined stack vs the single-device dense oracle.

GPipe microbatching + ppermute hops are a pure re-scheduling of the same
math: forward outputs and training trajectories must match the unpipelined
reference bit-closely on the 8 virtual CPU devices (conftest).
"""

import numpy as np
import optax
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elephas_tpu.parallel.pipeline import (
    PipelineDenseStack,
    build_mesh_pp,
    build_pp_train_step,
)


from tests._helpers import softmax_xent as _softmax_xent  # noqa: E402


@pytest.mark.parametrize("dp,pp,n_micro", [(1, 8, 4), (2, 4, 4), (4, 2, 2)])
def test_forward_matches_dense(dp, pp, n_micro):
    mesh = build_mesh_pp(data=dp, pipe=pp)
    model = PipelineDenseStack(
        d_in=12, hidden=16, d_out=6, n_stages=pp, layers_per_stage=2
    )
    params = model.init(seed=3)
    x = np.random.default_rng(0).normal(size=(16, 12)).astype(np.float32)

    want = np.asarray(model.apply_reference(params, x))

    sharded = model.shard_params(mesh, params)
    fwd = jax.jit(
        shard_map(
            lambda p, xb: model.apply(p, xb, n_micro),
            mesh=mesh, in_specs=(model.specs(), P("data")),
            out_specs=P("data"), check_vma=False,
        )
    )
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    got = np.asarray(fwd(sharded, xd))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dp,pp,opt_name", [(2, 4, "adam"), (4, 2, "sgd")])
def test_train_step_matches_dense(dp, pp, opt_name):
    mesh = build_mesh_pp(data=dp, pipe=pp)
    model = PipelineDenseStack(
        d_in=10, hidden=16, d_out=4, n_stages=pp, layers_per_stage=1
    )
    optimizer = optax.adam(1e-2) if opt_name == "adam" else optax.sgd(0.1)
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

    step, opt_init = build_pp_train_step(
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
            got[k], np.asarray(v), rtol=2e-4, atol=2e-5, err_msg=k
        )


def test_validation():
    with pytest.raises(ValueError):
        PipelineDenseStack(4, 8, 2, n_stages=0)
    mesh = build_mesh_pp(data=2, pipe=4)
    model = PipelineDenseStack(4, 8, 2, n_stages=2)
    with pytest.raises(ValueError):
        build_pp_train_step(model, mesh, optax.sgd(0.1), _softmax_xent, 2)
