"""Pallas flash-attention training kernels vs the dense oracle (interpret
mode on CPU), forward and backward, across MHA/GQA, causal/full, padded and
uneven tile shapes. The jnp scan implementation (``flash_attention.py``) is
itself oracle-tested in ``test_flash_attention.py``; here the hand-written
TPU kernels must match the same dense reference, gradients included."""

import functools

import numpy as np
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp

from elephas_tpu.ops import attention_reference, pallas_flash
from elephas_tpu.ops.pallas_flash import flash_attention_tpu


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


@pytest.fixture(params=["one_pass", "two_kernels"])
def bwd_form(request, monkeypatch):
    """Each backward form in turn: the one-pass kernel that accumulates dq
    beside dk/dv, and the separate dq and dk/dv kernels it stands down to
    where a whole sweep's dq does not fit in VMEM."""
    one_pass = request.param == "one_pass"
    monkeypatch.setattr(pallas_flash, "_one_pass_bwd", lambda *a: one_pass)
    return request.param


CASES = [
    # B, T, H, Hkv, Dh, causal, bq, bk
    (2, 256, 4, 4, 64, True, 128, 128),
    (2, 256, 4, 2, 64, True, 128, 128),     # grouped-query
    (1, 200, 4, 4, 64, True, 128, 128),     # T padded up to the tile
    (2, 256, 4, 4, 64, False, 128, 128),    # non-causal
    (1, 384, 8, 2, 32, True, 256, 128),     # uneven q/k tiles + GQA
    (1, 160, 2, 1, 16, False, 128, 128),    # padded + non-causal + MQA
]


@pytest.mark.parametrize("b,t,h,hkv,dh,causal,bq,bk", CASES)
@pytest.mark.usefixtures("bwd_form")
def test_forward_and_grads_match_dense(b, t, h, hkv, dh, causal, bq, bk):
    rng = np.random.default_rng(0)
    q = _rand(rng, b, t, h, dh)
    k = _rand(rng, b, t, hkv, dh)
    v = _rand(rng, b, t, hkv, dh)
    g = _rand(rng, b, t, h, dh)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=causal)

    def ker(q, k, v):
        return flash_attention_tpu(q, k, v, causal, bq, bk, True)

    np.testing.assert_allclose(
        np.asarray(ker(q, k, v)), np.asarray(ref(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )
    want = jax.vjp(ref, q, k, v)[1](g)
    got = jax.vjp(ker, q, k, v)[1](g)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-5, rtol=2e-5,
            err_msg=name,
        )


def test_bf16_inputs_roundtrip():
    """bf16 in → bf16 out, f32 accumulation inside (tolerance is bf16's)."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.bfloat16)
    out = flash_attention_tpu(q, q, q, True, 128, 128, True)
    assert out.dtype == jnp.bfloat16
    want = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_kernel_under_shard_map_matches_oracle():
    """On real multi-chip hardware the ulysses/LM paths invoke the Pallas
    kernels INSIDE shard_map (per-shard local attention after the
    all_to_all). Pin that composition: kernel under shard_map over a
    dp mesh == dense oracle, forward and backward."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.parallel import build_mesh

    rng = np.random.default_rng(3)
    B, T, H, Dh = 8, 128, 2, 32
    q = _rand(rng, B, T, H, Dh)
    g = _rand(rng, B, T, H, Dh)
    mesh = build_mesh(4)

    def local(q):
        return flash_attention_tpu(q, q, q, True, 128, 128, True)

    fwd = jax.jit(shard_map(
        local, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False,
    ))
    qd = jax.device_put(q, NamedSharding(mesh, P("data")))
    want = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(fwd(qd)), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    def loss(q):
        return jnp.sum(fwd(q) * g)

    def oracle_loss(q):
        return jnp.sum(attention_reference(q, q, q, causal=True) * g)

    got = jax.grad(loss)(qd)
    ref = jax.grad(oracle_loss)(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_with_pallas_kernel_matches_oracle(monkeypatch):
    """The REAL multi-chip long-context composition: ulysses all_to_alls
    around the Pallas flash kernel, under shard_map, gradients included.
    On CPU the dispatcher picks the jnp scan, so force the kernel (interpret
    mode) through the same ``flash_attention`` seam the TPU path uses."""
    import sys

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.ops import attention_reference
    from elephas_tpu.ops.ulysses import ulysses_attention_local
    from elephas_tpu.parallel import build_mesh

    ul = sys.modules["elephas_tpu.ops.ulysses"]
    monkeypatch.setattr(
        ul, "flash_attention",
        lambda q, k, v, causal=False, window=None: flash_attention_tpu(
            q, k, v, causal, 128, 128, True, window=window),
    )

    rng = np.random.default_rng(5)
    B, T, H, Dh = 2, 256, 4, 32
    q = _rand(rng, B, T, H, Dh)
    g = _rand(rng, B, T, H, Dh)
    mesh = build_mesh(4)

    fwd = jax.jit(shard_map(
        lambda q: ulysses_attention_local(q, q, q, True, "data"),
        mesh=mesh, in_specs=P(None, "data"), out_specs=P(None, "data"),
        check_vma=False,
    ))
    qd = jax.device_put(q, NamedSharding(mesh, P(None, "data")))
    want = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(fwd(qd)), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    got = jax.grad(lambda q: jnp.sum(fwd(q) * g))(qd)
    ref = jax.grad(
        lambda q: jnp.sum(attention_reference(q, q, q, causal=True) * g)
    )(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,hkv", [(True, 4), (True, 2), (False, 4)])
@pytest.mark.usefixtures("bwd_form")
def test_ring_with_pallas_kernel_matches_oracle(causal, hkv):
    """The TPU ring body (_ring_flash_local): per-visit Pallas flash merged
    by logsumexp, KV blocks rotating via ppermute — vs the dense oracle,
    gradients included (kernel VJP + lse cotangent + jnp merge)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.ops import attention_reference
    from elephas_tpu.ops.ring_attention import _ring_flash_local
    from elephas_tpu.parallel import build_mesh

    rng = np.random.default_rng(7)
    B, T, H, Dh = 2, 256, 4, 32
    q = _rand(rng, B, T, H, Dh)
    k = _rand(rng, B, T, hkv, Dh)
    v = _rand(rng, B, T, hkv, Dh)
    g = _rand(rng, B, T, H, Dh)
    mesh = build_mesh(4)

    fwd = jax.jit(shard_map(
        lambda q, k, v: _ring_flash_local(q, k, v, causal, "data",
                                          interpret=True),
        mesh=mesh, in_specs=P(None, "data"), out_specs=P(None, "data"),
        check_vma=False,
    ))
    spec = NamedSharding(mesh, P(None, "data"))
    qd, kd, vd = (jax.device_put(a, spec) for a in (q, k, v))
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(fwd(qd, kd, vd)),
                               np.asarray(want), atol=2e-5, rtol=2e-5)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v) * g)

    def oracle_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) * g)

    got = jax.grad(loss, argnums=(0, 1, 2))(qd, kd, vd)
    ref = jax.grad(oracle_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5, err_msg=name)


@pytest.mark.parametrize("hkv,dh,t", [(4, 32, 256), (2, 64, 256), (2, 32, 200)])
@pytest.mark.usefixtures("bwd_form")
def test_rope_fused_matches_prerotated_oracle(hkv, dh, t):
    """flash_attention_rope (in-kernel rotation, derotated gradients) must
    equal rotate-then-attend exactly — forward and all three gradients."""
    import jax

    from elephas_tpu.models.transformer import _rope_angles, _rope_rotate
    from elephas_tpu.ops import attention_reference
    from elephas_tpu.ops.pallas_flash import (flash_attention_rope,
                                              make_rope_tables)

    rng = np.random.default_rng(11)
    B, H = 2, 4
    q = _rand(rng, B, t, H, dh)
    k = _rand(rng, B, t, hkv, dh)
    v = _rand(rng, B, t, hkv, dh)
    g = _rand(rng, B, t, H, dh)
    positions = jnp.broadcast_to(jnp.arange(t), (B, t))
    cos, sin = _rope_angles(positions, dh)
    cos4, sin4 = cos[:, :, None, :], sin[:, :, None, :]
    c2, s2 = make_rope_tables(cos, sin)

    def ref(q, k, v):
        return attention_reference(_rope_rotate(q, cos4, sin4),
                                   _rope_rotate(k, cos4, sin4), v,
                                   causal=True)

    def ker(q, k, v):
        return flash_attention_rope(q, k, v, c2, s2, True, 128, 128, True)

    np.testing.assert_allclose(np.asarray(ker(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    want = jax.vjp(ref, q, k, v)[1](g)
    got = jax.vjp(ker, q, k, v)[1](g)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("window", [24, 64, 130])
@pytest.mark.usefixtures("bwd_form")
def test_windowed_ring_with_pallas_kernel_matches_oracle(window):
    """Round 5: the TPU ring body's 4-way windowed switch (skip/diag/full/
    banded-partial) in interpret mode vs the dense windowed oracle,
    gradients included — windows below / at / past the 64-token shard
    exercise every branch, including the banded partial fold's autodiff."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu.ops import attention_reference
    from elephas_tpu.ops.ring_attention import _ring_flash_local
    from elephas_tpu.parallel import build_mesh

    rng = np.random.default_rng(8)
    B, T, H, Dh = 1, 256, 2, 32
    q = _rand(rng, B, T, H, Dh)
    k = _rand(rng, B, T, H, Dh)
    v = _rand(rng, B, T, H, Dh)
    g = _rand(rng, B, T, H, Dh)
    mesh = build_mesh(4)

    fwd = jax.jit(shard_map(
        lambda q, k, v: _ring_flash_local(q, k, v, True, "data",
                                          interpret=True, window=window),
        mesh=mesh, in_specs=P(None, "data"), out_specs=P(None, "data"),
        check_vma=False,
    ))
    spec = NamedSharding(mesh, P(None, "data"))
    qd, kd, vd = (jax.device_put(a, spec) for a in (q, k, v))
    want = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(fwd(qd, kd, vd)),
                               np.asarray(want), atol=2e-5, rtol=2e-5)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v) * g)

    def oracle_loss(q, k, v):
        return jnp.sum(
            attention_reference(q, k, v, causal=True, window=window) * g)

    got = jax.grad(loss, argnums=(0, 1, 2))(qd, kd, vd)
    ref = jax.grad(oracle_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5, err_msg=name)


BWD_CASES = [c + (jnp.float32, False, None) for c in CASES] + [
    # B, T, H, Hkv, Dh, causal, bq, bk, dtype, rope, window
    (2, 256, 4, 2, 64, True, 128, 128, jnp.float32, True, None),
    (1, 200, 4, 4, 32, True, 128, 128, jnp.float32, True, None),
    (1, 256, 4, 2, 32, True, 128, 128, jnp.float32, False, 64),
    (1, 384, 4, 4, 32, True, 256, 128, jnp.float32, False, 130),
    (1, 256, 4, 2, 64, True, 128, 128, jnp.bfloat16, True, None),
]


@pytest.mark.parametrize("b,t,h,hkv,dh,causal,bq,bk,dtype,rope,window",
                         BWD_CASES)
def test_one_pass_backward_equals_two_kernels(monkeypatch, b, t, h, hkv, dh,
                                              causal, bq, bk, dtype, rope,
                                              window):
    """The one-pass backward makes the products the two kernels make and
    sums dq's in the same order: in float32 dq, dk and dv agree bit for
    bit, the lse cotangent, rope, windows and padding included. With bf16
    inputs the CPU's interpret mode leaves a few in ten thousand of dq's
    elements apart, by less than one bf16 step of dq's largest element;
    dk and dv stay equal."""
    from elephas_tpu.models.transformer import _rope_angles

    rng = np.random.default_rng(13)
    q, do = (_rand(rng, b, h, t, dh).astype(dtype) for _ in range(2))
    k, v = (_rand(rng, b, hkv, t, dh).astype(dtype) for _ in range(2))
    tables = None
    if rope:
        cos, sin = _rope_angles(jnp.broadcast_to(jnp.arange(t), (b, t)), dh)
        tables = pallas_flash.make_rope_tables(cos, sin)
    o, lse = pallas_flash._flash_fwd_tpu(q, k, v, causal, bq, bk, True,
                                         rope=tables, window=window)
    g_lse = jnp.broadcast_to(_rand(rng, b, h, 1, t), lse.shape)
    grads = {}
    for one_pass in (True, False):
        monkeypatch.setattr(pallas_flash, "_one_pass_bwd",
                            lambda *a, one_pass=one_pass: one_pass)
        grads[one_pass] = pallas_flash._flash_bwd_tpu(
            q, k, v, o, lse, do, causal, bq, bk, True, delta_minus=g_lse,
            rope=tables, window=window)
    for name, a, b_ in zip(("dq", "dk", "dv"), grads[True], grads[False]):
        assert a.dtype == b_.dtype == dtype, name
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        if dtype == jnp.bfloat16 and name == "dq":
            assert np.mean(a != b_) < 1e-3
            np.testing.assert_allclose(a, b_, rtol=0,
                                       atol=2.0 ** -8 * np.abs(b_).max())
        else:
            np.testing.assert_array_equal(a, b_, err_msg=name)


@pytest.mark.parametrize("dtype,t,one_pass", [
    (jnp.bfloat16, 8192, True), (jnp.bfloat16, 8193, False),
    (jnp.float32, 5120, True), (jnp.float32, 5121, False)])
def test_one_pass_backward_stands_down_past_its_vmem_budget(dtype, t,
                                                            one_pass):
    """At head size 128 and the default 512-row tiles, dq of a whole sweep
    fits beside dk/dv up to 8,192 bf16 or 5,120 f32 positions; past them
    the backward is the separate dq and dk/dv kernels."""
    B, H, Hkv, Dh = 1, 2, 1, 128
    s = lambda *shape, d=dtype: jax.ShapeDtypeStruct(shape, d)
    jaxpr = jax.make_jaxpr(functools.partial(
        pallas_flash._flash_bwd_tpu, causal=True, bq=512, bk=512,
        interpret=True))(s(B, H, t, Dh), s(B, Hkv, t, Dh), s(B, Hkv, t, Dh),
                         s(B, H, t, Dh), s(B, H, 8, t, d=jnp.float32),
                         s(B, H, t, Dh))
    text = str(jaxpr)
    assert "flash_bwd_dkv" in text
    assert ("flash_bwd_dq" not in text) == one_pass
