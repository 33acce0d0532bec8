"""The decode step's KV cache rides the layer scan's CARRY and is written
one row at a time — structure read from the jaxpr, no chip needed.

As scanned input / stacked output of the layer scan (the form before PR
25) every layer sliced its whole ``[B, Hkv, T, Dh]`` cache out of the
stack, restacked it, and XLA copied the result into the donated buffer:
41% of a decode program on the chip (PERF.md §6, PR 25). The carry form
leaves nothing of cache size to move; these tests hold it there for the
dense, MoE, mixed-window (period 2) and all-windowed (rolling) models, and
pin the per-row mixed-window step (layer index ``i·p + g``) against the
teacher-forced forward.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import MoETransformerLM, TransformerLM

_BASE = dict(vocab=61, d_model=32, n_heads=4, n_kv_heads=2, n_layers=4,
             d_ff=64, max_len=64, pos_encoding="rotary", norm="rmsnorm",
             activation="swiglu", ffn_bias=False)


def _model(kind):
    if kind == "dense":
        return TransformerLM(**_BASE)
    if kind == "moe":
        return MoETransformerLM(n_experts=4, k=2, capacity_factor=2.0,
                                **_BASE)
    if kind == "mixed":        # period 2, a full-attention layer: linear cache
        return TransformerLM(attn_window=[None, 6, None, 6], **_BASE)
    if kind == "ring":         # every layer windowed: rolling cache
        return TransformerLM(attn_window=[4, 8, 4, 8], **_BASE)
    raise ValueError(kind)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _decode_jaxpr(model, per_row, batch=3):
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    cache = model.init_cache(batch, length=40)
    pos = jnp.arange(batch) + 2 if per_row else jnp.asarray(5)
    jaxpr = jax.make_jaxpr(model.decode_step)(
        params, jnp.zeros((batch,), jnp.int32), pos, cache)
    return jaxpr.jaxpr, tuple(cache["k"].shape)


@pytest.mark.parametrize("per_row", [False, True])
@pytest.mark.parametrize("kind", ["dense", "moe", "mixed", "ring"])
def test_layer_scan_carries_the_cache(kind, per_row):
    model = _model(kind)
    jaxpr, full = _decode_jaxpr(model, per_row)
    layer = full[1:]                       # [B, Hkv, T, Dh]
    steps = model.n_layers // model._window_period()
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == steps
             and any(v.aval.shape == full for v in e.invars)]
    assert len(scans) == 1, "one layer scan sees the cache"
    scan = scans[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carry = scan.invars[n_consts:n_consts + n_carry]
    xs = scan.invars[n_consts + n_carry:]
    ys = scan.outvars[n_carry:]
    assert [v.aval.shape for v in carry].count(full) == 2
    assert [v.aval.shape for v in scan.outvars[:n_carry]].count(full) == 2
    for v in list(xs) + list(ys):          # no per-layer slice in or out
        assert v.aval.shape[-4:] != layer, v.aval
    assert not any(v.aval.shape == full for v in scan.invars[:n_consts])


@pytest.mark.parametrize("kind", ["dense", "moe", "mixed", "ring"])
def test_tpu_path_makes_nothing_of_layer_size(kind, monkeypatch):
    """On the TPU path (the dispatchers steered here, in the test) both
    kernels take the whole stack with a layer index, so no equation of the
    program outside them yields one layer of the cache: no dynamic_slice,
    no dynamic_update_slice, no stack."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr, full = _decode_jaxpr(_model(kind), per_row=True)
    names = set()
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
        for v in eqn.outvars:
            assert v.aval.shape != full[1:], (eqn.primitive.name, v.aval)
            if v.aval.shape == full:       # only the loop and the writer
                assert eqn.primitive.name in ("scan", "pallas_call"), eqn
    assert {"flash_decode", "kv_write_row"} <= names


@pytest.mark.parametrize("kind", ["mixed", "ring"])
def test_per_row_mixed_window_step_matches_teacher_forced(kind):
    """Rows at different positions through the period-2 scan: each row's
    logits equal the teacher-forced forward's at its own position, and the
    new K/V rows land in layer ``i·p + g`` of the stack, at that row's
    position only."""
    model = _model(kind)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=4).items()}
    rng = np.random.default_rng(8)
    B, T0 = 3, 14
    tokens = rng.integers(0, 61, size=(B, T0)).astype(np.int32)
    positions = np.broadcast_to(np.arange(T0), (B, T0))
    want = np.asarray(model.apply(params, jnp.asarray(tokens),
                                  jnp.asarray(positions), attn="dense"))
    # feed every row its own prefix one token at a time, rows staggered:
    # row b runs `lag[b]` steps behind, so positions differ at every step
    lag = np.array([0, 2, 5])
    cache = model.init_cache(B, length=T0 + int(lag.max()))
    step = jax.jit(model.decode_step)
    got = np.zeros_like(want)
    for t in range(T0 + int(lag.max())):
        pos = np.clip(t - lag, 0, T0 - 1)
        live = (t - lag >= 0) & (t - lag < T0)
        before = {n: np.asarray(c) for n, c in cache.items()}
        logits, new = step(params, jnp.asarray(tokens[np.arange(B), pos]),
                           jnp.asarray(pos, jnp.int32), cache)
        for b in np.flatnonzero(live):
            got[b, pos[b]] = np.asarray(logits[b])
        Tc = before["k"].shape[3]
        for n in ("k", "v"):               # one row per (layer, batch row)
            diff = np.asarray(new[n]) != before[n]
            rows = np.argwhere(diff.any(axis=(2, 4)))      # (l, b, t)
            slot = pos % Tc if model._ring_cache else pos
            assert {(int(b), int(s)) for _, b, s in rows} <= {
                (b, int(slot[b])) for b in range(B)}
        # rows past their prompt re-feed their last token: harmless
        cache = new
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
