"""The names a profile shows: every scope a reader of
``benchmark/program_trace.py`` looks for occurs in the compiled train step
and decode step (dense and MoE), backward operations read ``transpose(``,
and every ``pallas_call`` under ``ops/`` carries a name of its own."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import models as M
from elephas_tpu.serving.engine import _decode_kernel

DENSE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
             max_len=32, pos_encoding="rotary", activation="swiglu",
             norm="rmsnorm", ffn_bias=False, n_kv_heads=2)
MOE_SCOPES = {"moe", "moe_route", "moe_dispatch", "moe_experts",
              "moe_combine"}
LAYER = {"layers", "attn", "attn_core", "ffn"}


def _model(moe: bool):
    if moe:
        return M.MoETransformerLM(**DENSE, n_experts=4, k=2,
                                  capacity_factor=2.0)
    return M.TransformerLM(**DENSE)


def _op_names(compiled) -> list:
    return re.findall(r'op_name="([^"]+)"', compiled.as_text())


def _scopes(names) -> set:
    """Every path component, with transform wrappers taken off."""
    return {w for n in names for w in re.findall(r"[A-Za-z_][A-Za-z0-9_]*",
                                                 n)}


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_train_step_carries_every_scope_forward_and_backward(moe):
    model = _model(moe)
    mesh = M.build_mesh_sp(data=1, seq=1, devices=jax.devices()[:1])
    step, opt_init = M.build_lm_train_step(model, mesh, M.adam_compact(1e-3),
                                           attn="flash")
    params = model.shard_params(mesh, model.init(0))
    rows = np.random.default_rng(0).integers(0, 64, size=(2, 17))
    batch = M.shard_lm_batch(mesh, *M.make_lm_batches(rows))
    names = _op_names(step.lower(params, opt_init(params), *batch).compile())
    want = {"embed", "head", "loss", "optimizer"} | LAYER
    assert want | (MOE_SCOPES if moe else set()) <= _scopes(names)
    backward = [n for n in names if "transpose(" in n]
    assert {"layers", "attn", "attn_core", "ffn", "head", "loss"} <= _scopes(
        backward)
    # the optimizer is no transpose of anything, and the forward is there
    assert not any("optimizer" in n for n in backward)
    assert any("jvp(layers)" in n and "transpose(" not in n for n in names)


def test_gradient_reduction_is_scoped_on_a_data_mesh():
    model = _model(False)
    mesh = M.build_mesh_sp(data=2, seq=1, devices=jax.devices()[:2])
    for overlap in (False, True):
        step, opt_init = M.build_lm_train_step(
            model, mesh, M.adam_compact(1e-3), attn="flash",
            overlap_grads=overlap)
        params = model.shard_params(mesh, model.init(0))
        rows = np.random.default_rng(0).integers(0, 64, size=(4, 17))
        batch = M.shard_lm_batch(mesh, *M.make_lm_batches(rows))
        text = step.lower(params, opt_init(params), *batch).as_text(
            debug_info=True)
        assert "grad_reduce" in text, overlap


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_decode_step_carries_every_scope(moe):
    model = _model(moe)
    params = {k: jnp.asarray(v) for k, v in model.init(0).items()}
    cache = model.init_cache(4, 32)
    state = (jnp.zeros(4, jnp.int32), jnp.arange(4, dtype=jnp.int32),
             jnp.zeros(4, jnp.float32), jnp.zeros((4, 2), jnp.uint32),
             jnp.ones(4, bool))
    names = _op_names(_decode_kernel.lower(model, params, cache,
                                           *state).compile())
    want = {"embed", "kv_write", "head", "sample"} | LAYER
    assert want | (MOE_SCOPES if moe else set()) <= _scopes(names)
    assert not any("transpose(" in n for n in names)
    # the scan's own slices of the stacked cache carry `layers` alone
    own = [n for n in names if "layers" in n
           and not _scopes([n]) & (want | MOE_SCOPES) - {"layers"}]
    assert any("dynamic" in n for n in own)


def test_prefill_slot_carries_the_cache_scopes():
    model = _model(False)
    params = {k: jnp.asarray(v) for k, v in model.init(0).items()}
    cache = model.init_cache(4, 32)
    fn = jax.jit(lambda p, c, t: model.prefill_slot(p, t, 1, c))
    names = _op_names(fn.lower(params, cache,
                               jnp.zeros((1, 8), jnp.int32)).compile())
    assert {"embed", "kv_write", "head"} | LAYER <= _scopes(names)


# -- kernel names ---------------------------------------------------------------

def _pallas_names(fn, *args) -> list:
    """The ``name`` of every ``pallas_call`` in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(str(eqn.params["name"]))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(sub, "jaxpr", sub)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _kernel_cases():
    from elephas_tpu.ops import grouped_matmul as G
    from elephas_tpu.ops.flash_decode import (flash_cache_write_row,
                                              flash_decode)
    from elephas_tpu.ops.layer_norm import fused_layer_norm
    from elephas_tpu.ops.paged_attention import (paged_flash_chunk,
                                                 paged_flash_decode_lse)
    from elephas_tpu.ops.pallas_flash import flash_attention_tpu
    from elephas_tpu.ops.pallas_ops import fused_xent_from_logits

    f32 = jnp.float32
    q = jnp.ones((1, 16, 2, 8), f32)
    qd, kd = jnp.ones((2, 2, 2, 8), f32), jnp.ones((2, 2, 16, 8), f32)
    pool = jnp.ones((5, 2, 8, 8), f32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 9], jnp.int32)
    lhs, rhs = jnp.ones((256, 256), f32), jnp.ones((2, 256, 128), f32)
    gmap = jnp.asarray([0, 1], jnp.int32)
    logits = jnp.ones((8, 128), f32)
    labels = jax.nn.one_hot(jnp.arange(8), 128)
    x, s = jnp.ones((8, 128), f32), jnp.ones((128,), f32)
    # past the one-pass backward's VMEM budget dq has a kernel of its own
    long_q = jnp.ones((1, 6144, 1, 128), f32)
    return [
        ({"flash_fwd", "flash_bwd_dkv"},
         jax.grad(lambda q: flash_attention_tpu(
             q, q, q, True, interpret=True).sum()), (q,)),
        ({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"},
         jax.grad(lambda q: flash_attention_tpu(
             q, q, q, True, interpret=True).sum()), (long_q,)),
        ({"flash_decode"},
         lambda q, k: flash_decode(q, k, k, 3, interpret=True), (qd, kd)),
        ({"kv_write_row"},
         lambda k, new: flash_cache_write_row(k, k, new, new, 1, pos,
                                              interpret=True),
         (jnp.ones((2, 2, 2, 16, 8), f32), jnp.ones((2, 2, 8), f32))),
        ({"paged_decode"},
         lambda q, p: paged_flash_decode_lse(q, p, p, table, pos, 8,
                                             interpret=True), (qd, pool)),
        ({"paged_chunk"},
         lambda q, p: paged_flash_chunk(q, p, p, table, pos, 8,
                                        interpret=True),
         (jnp.ones((2, 2, 2, 4, 8), f32), pool)),
        ({"grouped_matmul_fwd", "grouped_matmul_bwd_dx",
          "grouped_matmul_bwd_dw"},
         jax.grad(lambda a, b: G.gmm(a, b, gmap, True).sum(),
                  argnums=(0, 1)), (lhs, rhs)),
        ({"fused_xent_fwd", "fused_xent_bwd"},
         jax.grad(lambda z: fused_xent_from_logits(z, labels, True).sum()),
         (logits,)),
        ({"layer_norm_fwd", "layer_norm_bwd"},
         jax.grad(lambda x: fused_layer_norm(x, s, s, 1e-5, True).sum()),
         (x,)),
    ]


@pytest.mark.parametrize("case", range(9), ids=[
    "flash", "flash_long", "flash_decode", "kv_write_row", "paged_decode", "paged_chunk",
    "grouped_matmul", "fused_xent", "layer_norm"])
def test_each_pallas_call_carries_its_name(case):
    want, fn, args = _kernel_cases()[case]
    assert set(_pallas_names(fn, *args)) == want


def test_pallas_kernel_names_are_distinct_and_cover_every_call():
    import glob
    import os

    import elephas_tpu.ops as ops

    # the flash cases share their kernels; every other name is one kernel's
    names = set().union(*(want for want, _, _ in _kernel_cases()))
    assert len(names) == 14
    # every pallas_call in the sources passes name=
    for path in glob.glob(os.path.join(os.path.dirname(ops.__file__),
                                       "*.py")):
        src = open(path).read()
        for call in re.finditer(r"pl\.pallas_call\(", src):
            depth, i = 1, call.end()
            while depth:
                depth += {"(": 1, ")": -1}.get(src[i], 0)
                i += 1
            assert "name=" in src[call.end():i], (path, call.start())
