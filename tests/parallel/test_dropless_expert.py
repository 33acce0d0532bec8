"""The dropless expert layer (``MoEFeedForward.apply_dropless``): sigmoid
scores with a selection bias, normalised and scaled weights, a shared
expert, and a HELD share of the experts that routes over all and computes
its own part. CPU, seeded weights, tiny widths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.parallel.expert import MoEFeedForward

D, F, E, K = 16, 8, 16, 4


def _layer(n_experts=E, **kw):
    base = dict(activation="swiglu", bias=False, scoring="sigmoid",
                select_bias=True, routed_scale=2.5, n_shared=1)
    return MoEFeedForward(D, F, n_experts, k=K, **{**base, **kw})


def _params(moe, seed=0):
    p = {k: jnp.asarray(v) for k, v in moe.init(seed).items()}
    rng = np.random.default_rng(seed + 1)
    if "wg_b" in p:
        p["wg_b"] = jnp.asarray(0.3 * rng.standard_normal(E), jnp.float32)
    return p


def _x(n, seed=5):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, D)),
                       jnp.float32)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _by_hand(moe, p, x, held=None):
    """The layer token by token and expert by expert, no dispatch."""
    x = np.asarray(x, np.float64)
    wg = np.asarray(p["wg"], np.float64)
    s = 1.0 / (1.0 + np.exp(-(x @ wg)))
    chosen = s + (np.asarray(p["wg_b"], np.float64)
                  if moe.select_bias else 0.0)
    e0, nh = held or (0, E)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-chosen[t])[:moe.k]
        w = s[t, top]
        if moe.norm_topk:
            w = w / w.sum()
        w = w * moe.routed_scale
        for e, we in zip(top, w):
            if e0 <= e < e0 + nh:
                y[t] += we * np.asarray(_swiglu(
                    x[t], *(np.asarray(p[k][e - e0], np.float64)
                            for k in ("w1", "w3", "w2"))))
    if moe.n_shared:
        y += np.asarray(_swiglu(x, *(np.asarray(p[k], np.float64)
                                     for k in ("ws1", "ws3", "ws2"))))
    return y


def test_routing_weights_by_hand():
    """sigmoid + bias (selection only) + normalise + scale."""
    moe = _layer()
    p = _params(moe)
    x = _x(7)
    eidx, w = moe.route(p, x)
    s = jax.nn.sigmoid(x @ p["wg"])
    want_idx = np.argsort(-np.asarray(s + p["wg_b"]), axis=1)[:, :K]
    assert np.array_equal(np.sort(np.asarray(eidx), 1), np.sort(want_idx, 1))
    picked = np.take_along_axis(np.asarray(s), np.asarray(eidx), 1)
    want_w = 2.5 * picked / picked.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-6)
    # the bias moves the choice, never the weight: without it other experts
    no_bias = np.argsort(-np.asarray(s), axis=1)[:, :K]
    assert not np.array_equal(np.sort(no_bias, 1), np.sort(want_idx, 1))
    # not normalised, not scaled: the chosen scores themselves
    plain = _layer(norm_topk=False, routed_scale=1.0)
    _, w1 = plain.route(p, x)
    np.testing.assert_allclose(np.asarray(w1), picked, rtol=1e-6)


@pytest.mark.parametrize("n", [1, 5, 64])
def test_uncut_layer_against_the_layer_by_hand(n):
    moe = _layer()
    p = _params(moe)
    x = _x(n)
    y, aux = moe.apply_dropless(p, x)
    assert float(aux) == 0.0
    np.testing.assert_allclose(np.asarray(y), _by_hand(moe, p, x),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_experts,per,kw", [
    (16, 4, {}),
    # the A.X-K1 shape of router: no selection bias, 3 of 12 experts a share
    (12, 3, {"select_bias": False}),
], ids=["4-of-16-with-bias", "3-of-12-no-bias"])
def test_shares_add_up_to_the_uncut_layer(n_experts, per, kw):
    """k=4: the parts of 4 shares of ``per`` experts, the shared expert
    counted once, add up to the uncut layer."""
    full = _layer(n_experts, **kw)
    p = {k: jnp.asarray(v) for k, v in full.init(0).items()}
    if "wg_b" in p:
        p["wg_b"] = jnp.asarray(0.3 * np.random.default_rng(1)
                                .standard_normal(n_experts), jnp.float32)
    assert ("wg_b" in p) == full.select_bias
    x = _x(24)
    whole, _ = full.apply_dropless(p, x)
    shared = full._shared_ffn(p, x)
    parts = []
    for r in range(4):
        share = _layer(n_experts, held=(per * r, per), **kw)
        ps = {**p, **{k: p[k][per * r:per * r + per]
                      for k in ("w1", "w3", "w2")}}
        assert share.param_shapes()["w1"].shape == (per, D, F)
        assert share.param_shapes()["wg"].shape == (D, n_experts)  # uncut
        part, _ = share.apply_dropless(ps, x)
        if n_experts == E:
            np.testing.assert_allclose(
                np.asarray(part), _by_hand(share, ps, x,
                                           held=(per * r, per)),
                rtol=2e-5, atol=2e-5)
        parts.append(part - shared)       # every share computed it alike
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=2e-5, atol=2e-5)


def test_no_token_is_dropped_under_total_imbalance():
    """Every token picks the same held expert (and only it of the held):
    the capacity executors would keep ``capacity`` of them."""
    share = _layer(held=(4, 4), select_bias=False)
    p = _params(share)
    n = 40
    x = jnp.abs(_x(n)) + 0.5                     # all-positive tokens
    wg = np.full((D, E), -1.0, np.float32)       # score ~0 everywhere...
    wg[:, 6] = 1.0                               # ...but expert 6 (held)
    wg[:, [0, 1, 2]] = 0.5                       # and three absent ones
    p["wg"] = jnp.asarray(wg)
    eidx, _ = share.route(p, x)
    assert (np.sort(np.asarray(eidx), 1) == [0, 1, 2, 6]).all()
    stats = []
    y, _ = share.apply_dropless(p, x, stats=stats)
    np.testing.assert_allclose(np.asarray(y),
                               _by_hand(share, p, x, held=(4, 4)),
                               rtol=2e-5, atol=2e-5)
    pairs, rows, most, touched = (int(v) for v in stats[0])
    assert (pairs, most, touched) == (n, n, 1)   # all 40 at one expert
    tm, cap = share.dropless_plan(n)
    assert rows == -(-n // tm) * tm and rows <= cap


def test_the_buffer_holds_the_worst_routing_and_marks_dead_tiles():
    share = _layer(held=(0, 4))
    n = 6
    tm, rows = share.dropless_plan(n)
    assert rows >= n * min(K, 4) and rows % tm == 0
    eidx = jnp.asarray(np.random.default_rng(0).permuted(
        np.tile(np.arange(E), (n, 1)), axis=1)[:, :K], jnp.int32)
    row, tok, gmap, sizes = share._held_layout(eidx, tm, rows)
    held = np.asarray(eidx) < 4
    assert int(sizes.sum()) == held.sum()
    r = np.asarray(row)
    assert (r[~held] == rows).all() and len(set(r[held])) == held.sum()
    # each held pair's row lies in a tile of its own expert; the rest dead
    g = np.asarray(gmap)
    assert (g[r[held] // tm] == np.asarray(eidx)[held]).all()
    live = -(-np.asarray(sizes) // tm).sum()
    assert (g[:live] < 4).all() and (g[live:] == 4).all()
    assert (np.diff(g) >= 0).all()
    t = np.asarray(tok)
    assert (t[r[held]] == np.nonzero(held)[0]).all()
    assert (np.delete(t, r[held]) == n).all()


@pytest.mark.parametrize("layer", [None, 1])
def test_kernel_in_interpret_mode_agrees_with_its_reference(layer):
    """Tileable widths, the Pallas kernels interpreted on the CPU, dead
    tiles skipped; one layer's weights, or a layer of a stack in place."""
    moe = MoEFeedForward(128, 128, 8, k=2, activation="swiglu", bias=False,
                         scoring="sigmoid", n_shared=1, held=(2, 4))
    p = {k: jnp.asarray(v) for k, v in moe.init(3).items()}
    x = jnp.asarray(np.random.default_rng(2).standard_normal((12, 128)),
                    jnp.float32)
    want, _ = moe.apply_dropless(p, x)
    if layer is not None:
        rng = np.random.default_rng(9)
        p = {**p, **{k: jnp.stack([jnp.asarray(
            rng.standard_normal(p[k].shape), jnp.float32), p[k]])
            for k in ("w1", "w3", "w2")}}
    got, _ = moe.apply_dropless(p, x, interpret=True, layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_the_capacity_executors_refuse_the_new_layer():
    moe = _layer(held=(0, 4))
    p = _params(moe)
    for run in (moe.apply_slots, moe.apply_gmm, moe.apply_reference):
        with pytest.raises(ValueError, match="apply_dropless"):
            run(p, _x(8))
    with pytest.raises(ValueError, match="held="):
        MoEFeedForward(D, F, E, k=K, held=(14, 4))
    with pytest.raises(ValueError, match="bias-free SwiGLU"):
        MoEFeedForward(D, F, E, k=K, scoring="sigmoid")      # relu, biases
    plain = MoEFeedForward(D, F, E, k=K)
    assert not plain.dropless and plain.expert_keys() == (
        "w1", "b1", "w2", "b2")
    assert plain.shared_keys() == ("wg",)
