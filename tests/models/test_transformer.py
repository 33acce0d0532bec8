"""Sequence-parallel transformer LM vs the dense single-device oracle.

Batch over "data", sequence over "seq", ring or Ulysses attention inside one
shard_map program — forward logits and training trajectories must match the
unsharded dense-attention model on the 8 virtual CPU devices (conftest).
"""

import numpy as np
import optax
import pytest

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elephas_tpu.models.transformer import (
    TransformerLM,
    build_lm_train_step,
    build_mesh_sp,
    make_lm_batches,
    shard_lm_batch,
)


def _model():
    return TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                         d_ff=32, max_len=32)


def _data(b=4, t=32, vocab=17, seed=0):
    rng = np.random.default_rng(seed)
    # learnable structure: next token = (token + 1) % vocab with noise-free
    # deterministic rows → the LM can drive loss toward zero
    start = rng.integers(0, vocab, size=(b, 1))
    rows = (start + np.arange(t + 1)) % vocab
    return make_lm_batches(rows)


@pytest.mark.parametrize("attn,dp,sp", [("ring", 2, 4), ("ulysses", 2, 4),
                                        ("ring", 1, 8), ("flash", 4, 1)])
def test_forward_matches_dense(attn, dp, sp):
    model = _model()
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    tokens, positions, targets = _data()

    want = np.asarray(model.apply(params, tokens, positions, attn="dense"))

    mesh = build_mesh_sp(data=dp, seq=sp)
    fwd = jax.jit(
        shard_map(
            lambda p, tk, ps: model.apply(p, tk, ps, attn=attn),
            mesh=mesh,
            in_specs=(model.specs(), P("data", "seq"), P("data", "seq")),
            out_specs=P("data", "seq"),
            check_vma=False,
        )
    )
    sharding = NamedSharding(mesh, P("data", "seq"))
    got = np.asarray(fwd(model.shard_params(mesh, model.init(seed=1)),
                         jax.device_put(tokens, sharding),
                         jax.device_put(positions, sharding)))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_train_step_matches_dense(attn):
    model = _model()
    optimizer = optax.adam(1e-2)
    tokens, positions, targets = _data()
    params0 = model.init(seed=2)

    # dense oracle
    o_params = {k: jnp.asarray(v) for k, v in params0.items()}
    o_state = optimizer.init(o_params)
    ntok = float(tokens.size)
    o_losses = []
    for _ in range(3):
        def loss_fn(p):
            return model.loss(p, tokens, positions, targets, attn="dense") / ntok
        loss, grads = jax.value_and_grad(loss_fn)(o_params)
        updates, o_state = optimizer.update(grads, o_state, o_params)
        o_params = jax.tree_util.tree_map(jnp.add, o_params, updates)
        o_losses.append(float(loss))

    mesh = build_mesh_sp(data=2, seq=4)
    step, opt_init = build_lm_train_step(model, mesh, optimizer, attn=attn)
    params = model.shard_params(mesh, params0)
    state = opt_init(params)
    td, pd, gd = shard_lm_batch(mesh, tokens, positions, targets)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, td, pd, gd)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, o_losses, rtol=2e-4, atol=2e-5)
    for k, v in o_params.items():
        np.testing.assert_allclose(
            np.asarray(params[k]), np.asarray(v), rtol=5e-4, atol=5e-5,
            err_msg=k,
        )


def test_flash_train_step_matches_dense():
    """attn='flash' (blockwise custom-VJP kernel, dp-only mesh) takes the
    same optimization trajectory as the dense oracle — gradients included."""
    model = _model()
    optimizer = optax.adam(1e-2)
    tokens, positions, targets = _data()
    params0 = model.init(seed=2)

    o_params = {k: jnp.asarray(v) for k, v in params0.items()}
    o_state = optimizer.init(o_params)
    ntok = float(tokens.size)
    o_losses = []
    for _ in range(3):
        def loss_fn(p):
            return model.loss(p, tokens, positions, targets, attn="dense") / ntok
        loss, grads = jax.value_and_grad(loss_fn)(o_params)
        updates, o_state = optimizer.update(grads, o_state, o_params)
        o_params = jax.tree_util.tree_map(jnp.add, o_params, updates)
        o_losses.append(float(loss))

    mesh = build_mesh_sp(data=4, seq=1)
    step, opt_init = build_lm_train_step(model, mesh, optimizer, attn="flash")
    params = model.shard_params(mesh, params0)
    state = opt_init(params)
    td, pd, gd = shard_lm_batch(mesh, tokens, positions, targets)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, td, pd, gd)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, o_losses, rtol=2e-4, atol=2e-5)
    for k, v in o_params.items():
        np.testing.assert_allclose(
            np.asarray(params[k]), np.asarray(v), rtol=5e-4, atol=5e-5,
            err_msg=k,
        )


def test_flash_rejected_under_seq_axis():
    mesh = build_mesh_sp(data=1, seq=8)
    model = TransformerLM(vocab=10, d_model=16, n_heads=4, n_layers=1,
                          d_ff=16, max_len=32)
    with pytest.raises(ValueError, match="whole-sequence-per-shard"):
        build_lm_train_step(model, mesh, optax.sgd(0.1), attn="flash")


def test_learns_synthetic_task():
    """Loss must fall substantially on the deterministic +1 sequence task."""
    model = _model()
    mesh = build_mesh_sp(data=2, seq=4)
    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    params = model.shard_params(mesh, model.init(seed=0))
    state = opt_init(params)
    tokens, positions, targets = _data(b=8)
    td, pd, gd = shard_lm_batch(mesh, tokens, positions, targets)
    first = last = None
    for i in range(30):
        params, state, loss = step(params, state, td, pd, gd)
        if i == 0:
            first = float(loss)
        last = float(loss)
    assert last < first * 0.5, (first, last)


def test_rotary_forward_matches_dense_and_learns():
    """RoPE: sharded ring forward equals the dense oracle (absolute
    positions make rotation shard-invariant), params have no pos table,
    and the LM still learns the synthetic task."""
    model = TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=32, pos_encoding="rotary")
    assert "pos" not in model.param_shapes()
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    tokens, positions, targets = _data()

    want = np.asarray(model.apply(params, tokens, positions, attn="dense"))
    mesh = build_mesh_sp(data=2, seq=4)
    fwd = jax.jit(
        shard_map(
            lambda p, tk, ps: model.apply(p, tk, ps, attn="ring"),
            mesh=mesh,
            in_specs=(model.specs(), P("data", "seq"), P("data", "seq")),
            out_specs=P("data", "seq"),
            check_vma=False,
        )
    )
    sharding = NamedSharding(mesh, P("data", "seq"))
    got = np.asarray(fwd(model.shard_params(mesh, model.init(seed=1)),
                         jax.device_put(tokens, sharding),
                         jax.device_put(positions, sharding)))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)

    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    p = model.shard_params(mesh, model.init(seed=0))
    s = opt_init(p)
    td, pd, gd = shard_lm_batch(mesh, *_data(b=8))
    first = last = None
    for i in range(30):
        p, s, loss = step(p, s, td, pd, gd)
        first = float(loss) if i == 0 else first
        last = float(loss)
    assert last < first * 0.5, (first, last)


def test_tied_embeddings_and_eval_step():
    """tie_embeddings drops the head param and still trains/generates;
    build_lm_eval_step's sharded mean CE equals the dense computation."""
    from elephas_tpu.models.transformer import build_lm_eval_step

    model = TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=32, tie_embeddings=True)
    assert "head" not in model.param_shapes()
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    tokens, positions, targets = _data()

    # dense mean CE oracle
    dense = float(model.loss(params, tokens, positions, targets,
                             attn="dense")) / tokens.size

    mesh = build_mesh_sp(data=2, seq=4)
    eval_fn = build_lm_eval_step(model, mesh, attn="ring")
    td, pd, gd = shard_lm_batch(mesh, tokens, positions, targets)
    got = float(eval_fn(model.shard_params(mesh, model.init(seed=1)),
                        td, pd, gd))
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-6)

    # tied model trains and its loss falls
    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    p = model.shard_params(mesh, model.init(seed=0))
    s = opt_init(p)
    td, pd, gd = shard_lm_batch(mesh, *_data(b=8))
    first = last = None
    for i in range(50):
        p, s, loss = step(p, s, td, pd, gd)
        first = float(loss) if i == 0 else first
        last = float(loss)
    assert last < first * 0.6, (first, last)

    # cached generation still equals the uncached rollout when tied
    hp = {k: jnp.asarray(np.asarray(v)) for k, v in p.items()}
    prompt = np.asarray(tokens[:2, :4])
    out = np.asarray(model.generate(hp, prompt, n_new=4))
    seq = prompt.copy()
    for _ in range(4):
        ps = np.broadcast_to(np.arange(seq.shape[1]), seq.shape)
        logits = model.apply(hp, jnp.asarray(seq), jnp.asarray(ps),
                             attn="dense")
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, seq)


@pytest.mark.parametrize("n_kv,pos_enc", [(2, "learned"), (1, "rotary")])
def test_gqa_matches_dense_and_shrinks_cache(n_kv, pos_enc):
    """Grouped-query attention: sharded ring forward equals the dense
    oracle, the KV cache carries only the KV heads, decode stays exact,
    and training still learns."""
    model = TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=32, n_kv_heads=n_kv,
                          pos_encoding=pos_enc)
    assert model.param_shapes()["wk"].shape == (2, 16, 4 * n_kv)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    tokens, positions, targets = _data()

    want = np.asarray(model.apply(params, tokens, positions, attn="dense"))
    mesh = build_mesh_sp(data=2, seq=4)
    fwd = jax.jit(
        shard_map(
            lambda p, tk, ps: model.apply(p, tk, ps, attn="ring"),
            mesh=mesh,
            in_specs=(model.specs(), P("data", "seq"), P("data", "seq")),
            out_specs=P("data", "seq"),
            check_vma=False,
        )
    )
    sharding = NamedSharding(mesh, P("data", "seq"))
    got = np.asarray(fwd(model.shard_params(mesh, model.init(seed=1)),
                         jax.device_put(tokens, sharding),
                         jax.device_put(positions, sharding)))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)

    # cache holds only the KV heads; cached decode still equals the full
    # forward's logits position-by-position
    cache = model.init_cache(batch=tokens.shape[0], length=12)
    # length rounds up to the flash-decode T-block (12 → 16); extra
    # positions are masked by pos
    assert cache["k"].shape == (2, tokens.shape[0], n_kv, 16, 4)
    toks12 = jnp.asarray(tokens[:, :12])
    full = np.asarray(model.apply(params, toks12, positions[:, :12],
                                  attn="dense"))
    step_logits = []
    for t in range(12):
        logits, cache = model.decode_step(params, toks12[:, t], t, cache)
        step_logits.append(np.asarray(logits))
    np.testing.assert_allclose(np.stack(step_logits, 1), full,
                               atol=3e-5, rtol=3e-5)

    step, opt_init = build_lm_train_step(model, mesh, optax.adam(3e-3),
                                         attn="ring")
    p = model.shard_params(mesh, model.init(seed=0))
    s = opt_init(p)
    td, pd, gd = shard_lm_batch(mesh, *_data(b=8))
    first = last = None
    for i in range(30):
        p, s, loss = step(p, s, td, pd, gd)
        first = float(loss) if i == 0 else first
        last = float(loss)
    assert last < first * 0.6, (first, last)


def test_gqa_validation():
    with pytest.raises(ValueError, match="n_kv_heads"):
        TransformerLM(vocab=10, d_model=16, n_heads=4, n_layers=1,
                      d_ff=16, max_len=8, n_kv_heads=3)


def test_pos_encoding_validation():
    with pytest.raises(ValueError, match="pos_encoding"):
        TransformerLM(vocab=10, d_model=16, n_heads=4, n_layers=1,
                      d_ff=16, max_len=8, pos_encoding="alibi")
    with pytest.raises(ValueError, match="even head dim"):
        TransformerLM(vocab=10, d_model=12, n_heads=4, n_layers=1,
                      d_ff=16, max_len=8, pos_encoding="rotary")


def test_bfloat16_compute():
    """bf16 activations: forward stays close to f32, training still learns,
    params/optimizer remain f32."""
    f32 = _model()
    bf16 = TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                         d_ff=32, max_len=32, compute_dtype="bfloat16")
    params = {k: jnp.asarray(v) for k, v in f32.init(seed=1).items()}
    tokens, positions, targets = _data()
    a = np.asarray(f32.apply(params, tokens, positions, attn="dense"))
    b_raw = bf16.apply(params, tokens, positions, attn="dense")
    assert b_raw.dtype == jnp.float32  # logits come back f32, pre-cast
    np.testing.assert_allclose(a, np.asarray(b_raw), atol=0.15, rtol=0.1)

    mesh = build_mesh_sp(data=2, seq=4)
    step, opt_init = build_lm_train_step(bf16, mesh, optax.adam(3e-3),
                                         attn="ring")
    p = bf16.shard_params(mesh, bf16.init(seed=0))
    s = opt_init(p)
    td, pd, gd = shard_lm_batch(mesh, *_data(b=8))
    first = last = None
    for i in range(20):
        p, s, loss = step(p, s, td, pd, gd)
        first = float(loss) if i == 0 else first
        last = float(loss)
    assert p["wq"].dtype == jnp.float32  # master params stay f32
    assert last < first * 0.7, (first, last)


def test_head_divisibility_validation():
    with pytest.raises(ValueError, match="not divisible"):
        TransformerLM(vocab=10, d_model=15, n_heads=4, n_layers=1,
                      d_ff=16, max_len=8)


def test_build_and_call_validation():
    mesh = build_mesh_sp(data=1, seq=8)
    model = TransformerLM(vocab=10, d_model=16, n_heads=4, n_layers=1,
                          d_ff=16, max_len=32)
    # ulysses needs H % seq == 0 (4 % 8 != 0) — caught at build time
    with pytest.raises(ValueError, match="ulysses"):
        build_lm_train_step(model, mesh, optax.sgd(0.1), attn="ulysses")
    # over-long sequences must be rejected, not silently position-clamped
    step, opt_init = build_lm_train_step(model, mesh, optax.sgd(0.1),
                                         attn="ring")
    params = model.shard_params(mesh, model.init())
    state = opt_init(params)
    rows = np.tile(np.arange(41, dtype=np.int64) % 10, (2, 1))
    tokens, positions, targets = make_lm_batches(rows)  # T=40 > max_len=32
    with pytest.raises(ValueError, match="exceeds max_len"):
        step(params, state, *shard_lm_batch(mesh, tokens, positions, targets))
