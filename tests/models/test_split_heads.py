"""``TransformerLM._split_heads``: a projection's result is split into heads
behind a barrier, so the compiler cannot fold the split into the matmul and
then transpose the layer's whole weight matrix to suit the folded form.

(a) The barrier is an identity. One small model of each family the
benchmark's serving cells run, in bfloat16 as they run: ``decode_step`` and
``decode_chunk`` give the same logits and the same cache, bit for bit, as
the same model with the split written the way it was, ``(x @
w).reshape(...)`` (``_folded``, kept here and not in the library).

(b) The barrier does what it is for. ``decode_step`` of a two-layer model
at K-EXAONE's published attention widths is compiled for a described TPU
v5e (no chip; ``benchmark/rehearse_compile.py``'s way) and the compiled
program holds no ``copy`` and no slice fusion whose result is as large as
one layer's ``wq``; with ``_folded`` it holds them, so the check cannot
pass by looking at nothing."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import MoETransformerLM, TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASE = dict(vocab=97, d_model=48, n_heads=4, n_layers=4, d_ff=64,
            max_len=128, pos_encoding="rotary", activation="swiglu",
            norm="rmsnorm", ffn_bias=False, norm_eps=1e-6,
            compute_dtype="bfloat16")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}


def _gqa_windows():
    # K-EXAONE's form: a leading dense layer, window layers with a ring of
    # their own, per-head q/k norms, rotary on the window layers only
    return MoETransformerLM(
        n_experts=8, k=2, dense_layers=1, d_ff_dense=40, scoring="sigmoid",
        select_bias=True, routed_scale=2.5, n_shared=1, held=(2, 4),
        **{**BASE, "n_layers": 5, "n_kv_heads": 2, "d_ff": 16},
        head_dim=12, qk_norm=True, rope_layers="windowed",
        window_cache="ring", attn_window=[6, 6, 6, None, 6])


def _moe_plain():
    # Mixtral's form: every layer full, grouped heads, no q/k norm
    return MoETransformerLM(n_experts=4, k=2, capacity_factor=2.0,
                            aux_weight=0.0, **{**BASE, "n_kv_heads": 2})


def _latent():
    # A.X-K1's form: low-rank q, latent rows, the absorbed decode step
    return MoETransformerLM(
        n_experts=12, k=4, dense_layers=1, d_ff_dense=80, scoring="sigmoid",
        routed_scale=2.5, n_shared=1, held=(3, 3), aux_weight=0.0,
        **{**BASE, "d_ff": 16}, q_lora_rank=24, kv_lora_rank=128,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        rope_scaling=YARN)


def _hybrid():
    # Olmo-Hybrid's form: three linear layers to a full one, one norm over
    # all heads' q and k, reordered norms, sublayer results in float32
    return TransformerLM(
        **{**BASE, "n_layers": 8}, qk_norm="whole", rope_layers="none",
        norm_order="post", act_dtype="float32", layer_types=PERIOD * 2,
        linear_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        linear_allow_neg_eigval=True)


FAMILIES = {"gqa_qknorm_windows": _gqa_windows, "moe_plain": _moe_plain,
            "latent": _latent, "hybrid_whole_norm": _hybrid}


def _folded(self, y, n):
    """The split as it was: a reshape straight after the dot."""
    return y.reshape(*y.shape[:-1], n, y.shape[-1] // n)


def _params(model, seed=0):
    rng = np.random.default_rng(seed + 1)
    out = {}
    for k, v in model.init(seed).items():
        if k.endswith(("_s", "_norm")):     # norm scales not all ones
            v = v + 0.2 * rng.standard_normal(v.shape).astype(np.float32)
        out[k] = jnp.asarray(v)
    return out


def _exact(fn, *args):
    """``fn(*args)`` compiled with every rounding the source writes: left
    free to keep excess precision, the CPU compiler skips the bfloat16
    rounding of q between its dot and the whole-projection norm in the
    FOLDED form (the two reshapes cancel and the two converts meet), and
    the two programs then differ by that rounding, which is the source's."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})(*args)


def _run(model, params, forward):
    """Prefill 9 positions of 3 rows, then the cached ``forward``."""
    toks = np.random.default_rng(2).integers(0, 97, (3, 14)).astype(np.int32)
    cache = model.init_cache(3, 64)
    _, cache = _exact(model.decode_chunk, params, toks[:, :9], 0, cache)
    if forward == "decode_step":
        pos = jnp.asarray([9, 9, 9], jnp.int32)
        return _exact(model.decode_step, params, toks[:, 9], pos, cache)
    return _exact(model.decode_chunk, params, toks[:, 9:], 9, cache)


@pytest.mark.parametrize("forward", ["decode_step", "decode_chunk"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_split_behind_the_barrier_is_the_reshape_bit_for_bit(
        family, forward, monkeypatch):
    model = FAMILIES[family]()
    params = _params(model)
    logits, cache = _run(model, params, forward)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.abs(np.asarray(logits, np.float32)).max() > 1e-3

    monkeypatch.setattr(TransformerLM, "_split_heads", _folded)
    logits0, cache0 = _run(FAMILIES[family](), params, forward)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits0))
    assert set(cache) == set(cache0)
    for k in cache:
        np.testing.assert_array_equal(
            np.asarray(cache[k].astype(jnp.float32)),
            np.asarray(cache0[k].astype(jnp.float32)), err_msg=k)


def test_every_cached_split_goes_through_the_helper(monkeypatch):
    """The helper is on the path of each family's step (a family whose
    projections bypass it would pass the comparison above by not using
    it): q, k, v a full layer; q of a latent layer; z of a linear one."""
    calls = []
    real = TransformerLM._split_heads

    def counting(self, y, n):
        calls.append((y.shape[-1], n))
        return real(self, y, n)

    monkeypatch.setattr(TransformerLM, "_split_heads", counting)
    want = {"gqa_qknorm_windows": {(48, 4), (24, 2)},   # head_dim 12
            "moe_plain": {(48, 4), (24, 2)},
            "latent": {(4 * 24, 4)},                    # nope 16 + rope 8
            "hybrid_whole_norm": {(48, 4), (4 * 16, 4)}}
    for family, shapes in want.items():
        del calls[:]
        model = FAMILIES[family]()
        jax.eval_shape(model.decode_step, model.param_shapes(),
                       jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
                       jax.eval_shape(lambda: model.init_cache(3, 64)))
        assert set(calls) == shapes, family


@pytest.fixture(scope="module")
def found():
    """``scripts/decode_weight_copies.py``, whose reading of a compiled
    program this test shares (importing it also sends the TPU compiler's
    logs nowhere, ``TPU_LOG_DIR``)."""
    spec = importlib.util.spec_from_file_location(
        "_decode_weight_copies",
        os.path.join(ROOT, "scripts", "decode_weight_copies.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo(found):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to read
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")


def test_compiled_decode_step_reads_wq_in_place(found, topo, monkeypatch):
    """K-EXAONE's attention widths (hidden 6144, 64 query and 8 KV heads of
    128, q/k norms), two layers, a small dense FFN: compiled for the
    described v5e, the folded form transposes ``wq`` (8192 x 6144 bf16,
    100.7 MB a layer) before it multiplies it; the helper's form writes
    nothing of that size."""
    from jax.sharding import SingleDeviceSharding

    # the Pallas dispatchers ask for the backend; compiled for the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    kw = dict(vocab=512, d_model=6144, n_heads=64, n_kv_heads=8, head_dim=128,
              n_layers=2, d_ff=256, max_len=256, pos_encoding="rotary",
              activation="swiglu", norm="rmsnorm", ffn_bias=False,
              qk_norm=True, compute_dtype="bfloat16")
    wq_bytes = 6144 * 64 * 128 * 2

    def relayouts():
        model = TransformerLM(**kw)      # a new trace for each form
        f32 = ("ln1_s", "ln2_s", "lnf_s", "qn_s", "kn_s")
        params = {k: jax.ShapeDtypeStruct(
            v.shape, jnp.float32 if k in f32 else jnp.bfloat16, sharding=one)
            for k, v in model.param_shapes().items()}
        assert params["wq"].shape == (2, 6144, 8192)
        cache = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                 for k, v in jax.eval_shape(
                     lambda: model.init_cache(8, 256)).items()}
        row = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)
        text = jax.jit(model.decode_step, donate_argnums=(3,)).lower(
            params, row, row, cache).compile().as_text()
        assert "tpu_custom_call" in text     # compiled for the TPU indeed
        return [r for r in found.written_results(text, wq_bytes)
                if found.is_relayout(r[1], r[2])]

    assert relayouts() == []
    monkeypatch.setattr(TransformerLM, "_split_heads", _folded)
    was = relayouts()
    assert was and all(r[0] == wq_bytes for r in was), was
