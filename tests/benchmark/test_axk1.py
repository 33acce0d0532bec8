"""The ``axk1`` family (A.X-K1): the plain reference (published form of
latent attention) against the program at tiny widths on the CPU, seeded
weights drawn as the benchmark draws them; the harness's own check through
the latent cache and the absorbed decode step; each ``assumed`` item moves
the reference; a control in lower precision that the check refuses; the
latent decode work by hand; and the cell's configuration as written."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import checks, mla_work
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.run import run_cell
from benchmark.weights import make_weights

CELL, CONFIG = "axk1-reason-long-closed", "a.x-k1-serve"
NORMS = ("q_a_norm", "kv_a_norm", "dense_q_a_norm", "dense_kv_a_norm")
TINY = {
    "family": "axk1", "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 48, "intermediate_size": 96,
    "kv_lora_rank": 128, "max_position_embeddings": 4096,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 4,
    "n_routed_experts": 3, "held_experts": [6, 3], "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 2, "topk_method": "none",
    "v_head_dim": 12, "vocab_size": 128,
    "reduced": {"n_routed_experts": {"published": 12, "here": 3,
                                     "why": "share"}},
    "compute_dtype": "float32",
    "weights": {"dtype": "float32",
                "float32_leaves": ["ln1_s", "ln2_s", "lnf_s", "wg"],
                "init": {k: "ones" for k in NORMS}},
    "engine": {"n_slots": 4, "max_len": 400, "max_queue": 64},
    "check": {"prompt_lengths": [5, 16, 150, 290], "decode_steps": 3,
              "stream_max_tokens": 64},
}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO_ROOT)


@pytest.fixture(scope="module")
def tiny(man):
    fam = man.module("families", "axk1")
    ref = man.module("reference", "axk1")
    model = fam.build_model(TINY)
    weights = make_weights(model, 2**31 + 5, "float32",
                           init=TINY["weights"]["init"])
    # norm scales off one, so a dropped norm shows
    rng = np.random.default_rng(0)
    weights = {k: (v + 0.2 * rng.standard_normal(v.shape).astype(np.float32)
                   if k.endswith(("_s", "_norm")) else v)
               for k, v in weights.items()}
    return fam, ref, model, weights


def _tokens(n, seed=3):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def test_family_builds_the_latent_form_and_the_share(tiny):
    _, _, model, weights = tiny
    assert model.latent and model.moe.n_experts == 12
    assert model.moe.held == (6, 3) and not model.moe.select_bias
    assert weights["wg"].shape == (2, 48, 12)
    assert weights["w1"].shape == (2, 3, 48, 32)
    assert weights["wkv_a"].shape == (2, 48, 128 + 8)
    assert weights["dense_wkv_b"].shape == (1, 128, 4 * (16 + 12))
    assert weights["wq_b"].shape == (2, 24, 4 * 24)
    assert weights["wo"].shape == (2, 4 * 12, 48)
    assert not {"wk", "wv", "wq"} & set(weights)
    assert (model.head_dim, model.d_attn) == (24, 48)


def test_full_forward_against_the_reference(tiny):
    _, ref, model, weights = tiny
    toks = _tokens(300)
    want = np.asarray(ref.forward(TINY, weights, toks))
    got = np.asarray(model.apply(weights, jnp.asarray(toks)[None],
                                 jnp.arange(300)[None])[0])
    assert want.shape == (300, 128)
    np.testing.assert_allclose(got, want, atol=5e-5)
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    assert ok and share == 1.0 and worst < 1e-4


def test_the_dense_ffn_in_column_chunks_is_the_same(tiny, monkeypatch):
    """The reference widens the leading layer's FFN ``FFN_COLS``
    intermediate columns at a time; any chunking gives the same logits."""
    _, ref, _, weights = tiny
    toks = _tokens(40)
    whole = np.asarray(ref.forward(TINY, weights, toks))
    monkeypatch.setattr(ref, "FFN_COLS", 32)         # 96 columns: 3 chunks
    np.testing.assert_allclose(
        np.asarray(ref.forward(TINY, weights, toks)), whole, atol=2e-5)


def test_chunk_forward_past_a_start_against_the_reference(tiny):
    """``decode_chunk`` at ``pos0 > 0`` (keys and values multiplied out of
    the cached rows, the horizon bucket chosen as it runs), then absorbed
    ``decode_step``s, against the reference's full forward."""
    _, ref, model, weights = tiny
    toks = _tokens(200, seed=5)
    want = np.asarray(ref.forward(TINY, weights, toks))
    cache = model.init_cache(1, length=400)
    a, cache = model.decode_chunk(weights, jnp.asarray(toks[None, :128]), 0,
                                  cache)
    b, cache = model.decode_chunk(weights, jnp.asarray(toks[None, 128:192]),
                                  128, cache)
    np.testing.assert_allclose(np.asarray(a[0]), want[:128], atol=5e-5)
    np.testing.assert_allclose(np.asarray(b[0]), want[128:192], atol=5e-5)
    for t in range(192, 200):
        lg, cache = model.decode_step(weights, jnp.asarray(toks[t:t + 1]),
                                      t, cache)
        np.testing.assert_allclose(np.asarray(lg[0]), want[t], atol=5e-5)


def test_the_reference_honours_each_assumed_item(tiny):
    """Dropping a latent norm's scale, the shared rotary key, the YaRN
    scale or blend, the shared expert, the scaling factor or the held
    range, or choosing by groups, changes the reference's logits by far
    more than the check's tolerance: a program without one of them fails."""
    _, ref, _, weights = tiny
    toks = _tokens(96)
    want = np.asarray(ref.forward(TINY, weights, toks))

    def parts(cfg=TINY, **leaves):
        got = np.asarray(ref.forward(cfg, {**weights, **leaves}, toks))
        return checks.logits_agree(got, want, ref.MIN_SHARE)[0]

    def scaling(**kw):
        return {**TINY, "rope_scaling": {**TINY["rope_scaling"], **kw}}

    assert parts()
    assert not parts(kv_a_norm=jnp.ones_like(weights["kv_a_norm"]) * 3.0)
    assert not parts(q_a_norm=jnp.ones_like(weights["q_a_norm"]) * 3.0)
    # no rotary key: the last qk_rope_head_dim outputs of wkv_a
    assert not parts(wkv_a=weights["wkv_a"].at[:, :, 128:].set(0.0))
    assert not parts(scaling(mscale_all_dim=0))      # softmax scale x 1
    assert not parts(scaling(factor=1))              # no blend, no scale
    assert not parts(ws2=jnp.zeros_like(weights["ws2"]))
    assert not parts({**TINY, "routed_scaling_factor": 1.0})
    assert not parts({**TINY, "held_experts": [0, 3]})
    # the frequencies alone: the ramp's ends move with beta_fast/beta_slow
    a = ref.yarn(TINY)[0]
    b = ref.yarn(scaling(beta_slow=0.01))[0]
    assert a != b and ref.yarn(scaling(factor=1))[2] == 24 ** -0.5


def test_selection_is_one_function(tiny, monkeypatch):
    """The ``topk_method`` reading lives in ``select`` alone: the other
    reading (the best ``topk_group`` of ``n_group`` groups first) put there
    moves the reference."""
    _, ref, _, weights = tiny
    toks = _tokens(96)
    want = np.asarray(ref.forward(TINY, weights, toks))

    def grouped(scores, per_tok):
        t, e = scores.shape
        g = scores.reshape(t, TINY["n_group"], e // TINY["n_group"])
        best = jnp.sort(g.max(-1), -1)[:, -TINY["topk_group"]][:, None]
        keep = jnp.repeat(g.max(-1) >= best, e // TINY["n_group"], axis=-1)
        masked = jnp.where(keep, scores, -1.0)
        return masked >= jnp.sort(masked, -1)[:, -per_tok][:, None]

    monkeypatch.setattr(ref, "select", grouped)
    ref._gates_jit.clear_cache()
    try:
        got = np.asarray(ref.forward(TINY, weights, toks))
    finally:
        monkeypatch.undo()
        ref._gates_jit.clear_cache()
    assert not checks.logits_agree(got, want, ref.MIN_SHARE)[0]


def test_prefill_then_decode_through_the_engines_check(man, tiny, tmp_path):
    """The harness's own ``check_logits`` (prefill-insert at 5 to 290
    tokens through the published form, then batched absorbed decode steps
    through the latent cache) and a closed-loop window with its stream
    check, at tiny widths, from a throw-away root that holds the tiny files
    only; the new readers are asked and find what a CPU run has."""
    root = _tiny_root(tmp_path)
    tman = Manifest(root)
    for trace in (0, 1):
        last = run_cell(tman, "tiny-axk1", 2**31 + 77, 0.5, trace,
                        jax.devices()[:1])
        assert last["correct"] is True
        assert last["attempted"] > 0 and last["failed"] == 0
    assert "moe.rows_padding_pct.batch" in last["metrics"]
    assert last["metrics"][
        "kernels.flash_decode_live_visits_pct.batch"]["value"] == 100.0


def _tiny_root(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-axk1.json"), "w") as f:
        json.dump(TINY, f)
    mix = {"driver": "closed_loop", "shape_seed": 1, "callers": 5,
           "pool": 64, "warm_in_s": 0.2, "profile_s": 0.3,
           "prompt_tokens": {"median": 24, "sigma": 0.8, "min": 4,
                             "max": 160},
           "output_tokens": {"median": 6, "sigma": 0.5, "min": 2,
                             "max": 12}}
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-closed.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "tiny-axk1"
    bench.update(
        paths=["benchmark"], run_seconds=1,
        configs=[{"name": cell, "source": "tests", "reduced": [],
                  "file": "benchmark/configs/tiny-axk1.json",
                  "why": "tiny"}],
        workloads=[{"name": cell, "config": cell, "traffic": "tiny-closed",
                    "chips": 1, "why": "tiny"}])
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = [cell] if CELL in m.get("workloads", [CELL]) else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("lower", ["latent", "experts"])
def test_lower_precision_fails_the_check(tiny, lower):
    """The control: the reference with the cached latent rows (or its
    expert matmuls) in the next precision below bfloat16 is NOT within the
    check's limits of the reference itself, at the share the family
    states."""
    _, ref, _, weights = tiny
    toks = _tokens(300)
    want = np.asarray(ref.forward(TINY, weights, toks))
    got = np.asarray(ref.forward(TINY, weights, toks, lower=lower))
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    assert not ok and share < ref.MIN_SHARE and worst > checks.LOGIT_RTOL


def test_latent_decode_work_by_hand(man):
    cfg = man.config(CONFIG)
    assert mla_work.latent_row(cfg) == 576
    flops, nbytes = mla_work.latent_decode_work(cfg, 1000)
    assert nbytes == 1000 * 5 * 576 * 2
    assert flops == 1000 * 5 * 2 * 64 * (576 + 512)
    assert 120 < flops / nbytes < 122            # the v5e's ridge is 240
    # a run that was not traced, or a parent without the kernel: nothing
    facts = {"cfg": cfg, "snapshot": {"work": {}}, "trace": None}
    assert mla_work.mla_decode_roofline_pct(facts) is None
    assert mla_work.kv_live_positions(facts) is None
    assert mla_work.mla_decode_roofline_pct({"cfg": {}, "trace": None}) is None
    for name in ("kernels.mla_decode_roofline_pct.batch",
                 "decode_step.attn_latent_ms.batch",
                 "engine.kv_live_positions.batch"):
        assert man.module("layer_metrics", name).read(facts) is None


def test_the_cell_as_written(man):
    cfg = man.config(CONFIG)
    cell = man.cell(CELL)
    mix = man.traffic(cell["traffic"])
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "reason-long-closed-160")
    assert mix == {**mix, "driver": "closed_loop", "callers": 160,
                   "shape_seed": 32, "pool": 1024, "warm_in_s": 30.0,
                   "profile_s": 3.0,
                   "prompt_tokens": {"median": 1024, "sigma": 0.8,
                                     "min": 128, "max": 2048},
                   "output_tokens": {"median": 4096, "sigma": 0.5,
                                     "min": 1024, "max": 6144}}
    assert cfg["engine"] == {"n_slots": 128, "max_len": 8192,
                             "max_queue": 192}
    assert cfg["engine"]["max_queue"] >= mix["callers"]
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= cfg["engine"]["max_len"])
    # every width as published; the cuts are depth, experts held, vocabulary
    assert [cfg[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
        "num_attention_heads")] == [7168, 18432, 2048, 1536, 512, 128, 64,
                                    128, 8, 64]
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert cfg["reduced"]["n_routed_experts"]["published"] == 192
    assert cfg["held_experts"] == [0, cfg["n_routed_experts"]] == [0, 12]
    assert (cfg["topk_method"], cfg["n_group"], cfg["topk_group"]) == (
        "none", 8, 4)
    for item in ("pre-norm", "topk_method", "rotary", "yarn", "weights",
                 "published keys"):
        assert item in cfg["assumed"], item
    assert "16 chips" in cfg["stands_for"]
    entry = next(c for c in man.data["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    model = man.module("families", cfg["family"]).build_model(cfg)
    shapes = model.param_shapes()
    assert shapes["wkv_a"].shape == (4, 7168, 576)
    assert shapes["dense_wkv_a"].shape == (1, 7168, 576)
    assert shapes["wq_a"].shape == (4, 7168, 1536)
    assert shapes["wq_b"].shape == (4, 1536, 64 * 192)
    assert shapes["wkv_b"].shape == (4, 512, 64 * 256)
    assert shapes["wo"].shape == (4, 64 * 128, 7168)
    assert shapes["wg"].shape == (4, 7168, 192)
    assert shapes["w1"].shape == (4, 12, 7168, 2048)
    assert shapes["dense_w1"].shape == (1, 7168, 18432)
    assert shapes["head"].shape == (7168, 20480)
    assert "wg_b" not in shapes and "wk" not in shapes
    assert set(cfg["weights"]["init"]) <= set(shapes)
    assert set(cfg["weights"]["float32_leaves"]) <= set(shapes)
    n = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert 3.48e9 < n < 3.50e9                   # 3.49 B parameters held
    cache = jax.eval_shape(lambda: model.init_cache(128, length=8192))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (5, 128, 1, 8192, 640), "moe_counts": (2, 5)}
    assert model.attn_scale == pytest.approx(192 ** -0.5 * 1.8133, rel=1e-4)
    # the cell reports what ISSUE 32 names: membership, never a position
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert {"kernels.mla_decode_roofline_pct.batch",
            "decode_step.attn_latent_ms.batch",
            "engine.kv_live_positions.batch",
            "kernels.grouped_matmul_roofline_pct.batch",
            "device.idle_pct.batch", "decode_step.attn_ms.batch",
            "decode_step.moe_ms.batch", "moe.rows_padding_pct.batch",
            "kernels.flash_decode_live_visits_pct.batch"} <= names
    assert not {"kernels.flash_decode_roofline_pct.batch",
                "decode_step.attn_window_ms.batch",
                "decode_step.attn_full_ms.batch"} & names
    for name in ("kernels.mla_decode_roofline_pct.batch",
                 "decode_step.attn_latent_ms.batch",
                 "engine.kv_live_positions.batch"):
        entry = next(m for m in man.data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
