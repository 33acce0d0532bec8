"""The ``exaone_moe`` family (K-EXAONE): the plain reference against the
program at tiny widths on the CPU, seeded weights drawn as the benchmark
draws them; the share held; the multi-token-prediction module; a control in
lower precision that the check refuses; the grouped-matmul work count by
hand; and the cell's configuration as written."""

import copy
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import checks, exaone_moe_work, kernel_work
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.run import run_cell
from benchmark.weights import make_weights

CELL, CONFIG = "kexaone236b-reason-closed", "k-exaone-236b-a23b-serve"
TINY = {
    "family": "exaone_moe", "first_k_dense_replace": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 48, "intermediate_size": 96,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention",
                    "sliding_attention"],
    "max_position_embeddings": 4096,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_intermediate_size": 32, "mtp_layer_types": ["full_attention"],
    "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts": 4, "held_experts": [8, 4], "num_experts_per_tok": 4,
    "num_hidden_layers": 5, "num_key_value_heads": 2,
    "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 8, "sliding_windows": [8, 8, 8, None, 8],
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 128,
    "reduced": {"num_experts": {"published": 16, "here": 4, "why": "share"}},
    "compute_dtype": "float32",
    "weights": {"dtype": "float32",
                "float32_leaves": ["ln1_s", "ln2_s", "lnf_s", "wg", "wg_b"],
                "init": {"qn_s": "ones", "kn_s": "ones",
                         "dense_qn_s": "ones", "dense_kn_s": "ones",
                         "wg_b": "embedding"}},
    "engine": {"n_slots": 4, "max_len": 400, "max_queue": 64},
    "check": {"prompt_lengths": [5, 16, 150, 290], "decode_steps": 3,
              "stream_max_tokens": 64},
}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO_ROOT)


@pytest.fixture(scope="module")
def tiny(man):
    fam = man.module("families", "exaone_moe")
    ref = man.module("reference", "exaone_moe")
    model = fam.build_model(TINY, mtp_layers=1)
    init = {**TINY["weights"]["init"],
            **{"mtp_" + k: v for k, v in TINY["weights"]["init"].items()
               if not k.startswith("dense_")}}
    weights = make_weights(model, 2**31 + 5, "float32", init=init)
    # a selection bias large enough to move choices, norm scales off one
    rng = np.random.default_rng(0)
    weights = {k: (v * 6.0 if k.endswith("wg_b") else
                   v + 0.2 * rng.standard_normal(v.shape).astype(np.float32)
                   if k.endswith("_s") else v) for k, v in weights.items()}
    return fam, ref, model, weights


def _tokens(n, seed=3):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def test_family_builds_the_share_with_the_published_router(tiny):
    _, _, model, weights = tiny
    assert model.moe.n_experts == 16 and model.moe.held == (8, 4)
    assert weights["wg"].shape == (4, 48, 16)
    assert weights["w1"].shape == (4, 4, 48, 32)
    assert weights["dense_w1"].shape == (1, 48, 96)
    assert model.head_dim == 16 and model.d_attn == 64 != model.d_model
    assert model.attn_windows == (8, 8, 8, None, 8) and model._two_kind
    assert (model.qk_norm, model.rope_layers) == (True, "windowed")


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


def test_the_reference_honours_each_assumed_item(tiny):
    """Dropping the selection bias, the q/k norms' scales, the shared
    expert, the scaling factor or the held range changes the reference's
    logits by far more than the check's tolerance: a program without one
    of them fails."""
    _, ref, _, weights = tiny
    toks = _tokens(64)
    want = np.asarray(ref.forward(TINY, weights, toks))

    def parts(cfg=TINY, **leaves):
        got = np.asarray(ref.forward(cfg, {**weights, **leaves}, toks))
        return checks.logits_agree(got, want, ref.MIN_SHARE)[0]

    assert parts()
    assert not parts(wg_b=jnp.zeros_like(weights["wg_b"]))
    assert not parts(qn_s=jnp.ones_like(weights["qn_s"]) * 3.0)
    assert not parts(ws2=jnp.zeros_like(weights["ws2"]))
    assert not parts({**TINY, "routed_scaling_factor": 1.0})
    assert not parts({**TINY, "held_experts": [0, 4]})
    # rotary on the full layer too: the published pattern says none there
    rot = {**TINY, "layer_types": ["sliding_attention"] * 5}
    assert not parts(rot)


def test_prefill_then_decode_through_the_engines_check(man, tiny, tmp_path):
    """The harness's own ``check_logits`` (prefill-insert at 5 to 290
    tokens, past the window of 8 and the ring of 128, then batched decode
    steps) and a closed-loop window with its stream check, at tiny widths,
    from a throw-away root that holds the tiny files only."""
    root = _tiny_root(tmp_path)
    tman = Manifest(root)
    for trace in (0, 1):
        last = run_cell(tman, "tiny-exaone", 2**31 + 77, 0.5, trace,
                        jax.devices()[:1])
        assert last["correct"] is True
        assert last["attempted"] > 0 and last["failed"] == 0
    assert "moe.rows_padding_pct.batch" in last["metrics"]
    assert 0 < last["metrics"]["moe.rows_padding_pct.batch"]["value"] < 100


def _tiny_root(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    cfg = {k: v for k, v in TINY.items()}
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-exaone.json"), "w") as f:
        json.dump(cfg, f)
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
    cell = "tiny-exaone"
    bench.update(
        paths=["benchmark"], run_seconds=1,
        configs=[{"name": cell, "source": "tests", "reduced": [],
                  "file": "benchmark/configs/tiny-exaone.json",
                  "why": "tiny"}],
        workloads=[{"name": cell, "config": cell, "traffic": "tiny-closed",
                    "chips": 1, "why": "tiny"}])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            m["workloads"] = [cell]
        else:
            m["workloads"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_mtp_logits_against_the_reference(tiny):
    _, ref, model, weights = tiny
    toks = _tokens(70, seed=9)
    want = np.asarray(ref.mtp_forward(TINY, weights, toks))      # [69, V]
    pos = jnp.arange(69)[None]
    hidden, _ = model.apply_hidden(weights, jnp.asarray(toks[:-1])[None],
                                   pos, final_norm=False)
    got = np.asarray(model.mtp_logits(
        weights, hidden, jnp.asarray(toks[1:])[None], pos)[0])
    np.testing.assert_allclose(got, want, atol=5e-5)
    # it is a further prediction, not the main head's: different logits
    main = np.asarray(ref.forward(TINY, weights, toks[:-1]))
    assert np.abs(want - main).max() > 0.1
    # a serving model does not allocate the module
    fam = tiny[0]
    assert not any(k.startswith("mtp_")
                   for k in fam.build_model(TINY).param_shapes())


@pytest.mark.parametrize("lower", ["experts", "router"])
def test_lower_precision_fails_the_check(tiny, lower):
    """The control: the reference with its expert matmuls (or its router)
    in the next precision below bfloat16 is NOT within the check's limits
    of the reference itself, at the share the family states."""
    _, ref, _, weights = tiny
    toks = _tokens(300)
    want = np.asarray(ref.forward(TINY, weights, toks))
    got = np.asarray(ref.forward(TINY, weights, toks, lower=lower))
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    assert not ok and share < ref.MIN_SHARE and worst > checks.LOGIT_RTOL


def test_grouped_matmul_work_by_hand(man):
    cfg = man.config(CONFIG)
    flops, nbytes = exaone_moe_work.grouped_matmul_work(cfg, rows=128,
                                                        experts=16)
    assert flops == 128 * 2 * 3 * 6144 * 2048
    assert nbytes == 16 * 3 * 6144 * 2048 * 2 + 128 * 2 * 6144 * 2
    assert exaone_moe_work.sparse_layers(cfg) == 4
    work = {"moe_decode_layer_calls": 8, "moe_decode_pairs_held": 8 * 100,
            "moe_decode_experts_touched": 8 * 12}
    f, b = exaone_moe_work.decode_step_work(cfg, work)
    f1, b1 = exaone_moe_work.grouped_matmul_work(cfg, 100, 12)
    assert (f, b) == (4 * f1, 4 * b1)
    # a program that does not count (the parent): nothing, and no raise
    assert exaone_moe_work.decode_step_work(cfg, {}) is None
    facts = {"cfg": cfg, "snapshot": {"work": {}}, "trace": None}
    assert exaone_moe_work.grouped_matmul_roofline_pct(facts) is None
    assert exaone_moe_work.scope_word_ms(facts, "attn_window") is None
    # the decode kernel's bytes: one full layer at every key, four window
    # layers at the windowed count the span carries
    f, b = kernel_work.decode_attention_work(cfg, 1000, 300)
    assert b == 2 * 8 * 128 * 2 * (1 * 1000 + 4 * 300)
    assert kernel_work.decode_attention_work(cfg, 1000) is None


def test_the_cell_as_written(man):
    cfg = man.config(CONFIG)
    cell = man.cell(CELL)
    mix = man.traffic(cell["traffic"])
    assert (cell["chips"], cell["config"]) == (1, CONFIG)
    assert mix == {**mix, "driver": "closed_loop", "callers": 160,
                   "shape_seed": 27, "pool": 1024, "warm_in_s": 15.0,
                   "profile_s": 3.0,
                   "prompt_tokens": {"median": 1024, "sigma": 1.0,
                                     "min": 128, "max": 4096},
                   "output_tokens": {"median": 1024, "sigma": 0.8,
                                     "min": 128, "max": 4096}}
    # the queue takes the loop's opening burst: every caller at once
    assert cfg["engine"] == {"n_slots": 128, "max_len": 8192,
                             "max_queue": 192}
    assert cfg["engine"]["max_queue"] >= mix["callers"]
    # every width as published; the cuts are depth, experts held, vocabulary
    assert [cfg[k] for k in ("hidden_size", "head_dim", "intermediate_size",
                             "moe_intermediate_size", "num_experts_per_tok",
                             "sliding_window", "num_attention_heads",
                             "num_key_value_heads")] == [
        6144, 128, 18432, 2048, 8, 128, 64, 8]
    # depth (with the three lists that follow it), experts held, vocabulary
    assert sorted(cfg["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_experts", "num_hidden_layers",
        "sliding_windows", "vocab_size"]
    assert cfg["reduced"]["num_experts"]["published"] == 128
    assert cfg["held_experts"] == [0, cfg["num_experts"]] == [0, 16]
    assert cfg["layer_types"].count("full_attention") == 1
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert kernel_work.layer_windows(cfg) == [128, 128, 128, None, 128]
    for item in ("pre-norm", "qk_norm", "rotary", "selection bias",
                 "mtp layer"):
        assert item in cfg["assumed"], item
    assert "8 chips" in cfg["stands_for"]
    model = man.module("families", cfg["family"]).build_model(cfg)
    shapes = model.param_shapes()
    assert shapes["wg"].shape == (4, 6144, 128)
    assert shapes["w1"].shape == (4, 16, 6144, 2048)
    assert shapes["wq"].shape == (4, 6144, 8192)
    assert shapes["dense_w1"].shape == (1, 6144, 18432)
    assert shapes["head"].shape == (6144, 19200)
    assert set(cfg["weights"]["init"]) <= set(shapes)
    assert set(cfg["weights"]["float32_leaves"]) <= set(shapes)
    n = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert 3.70e9 < n < 3.73e9                   # 3.71 B parameters held
    # the cell reports what ISSUE 27 names, and the new readers exist
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    batch = {m["name"] for m in man.data["per_layer"]
             if m["name"].endswith(".batch")}
    assert names == batch and len(names) == 19
    assert {"kernels.grouped_matmul_roofline_pct.batch",
            "moe.rows_padding_pct.batch", "decode_step.attn_window_ms.batch",
            "decode_step.attn_full_ms.batch"} == {
        m["name"] for m in man.data["per_layer"][-4:]}
    # appended, nothing else: the accepted entries are the parent's, each
    # with this cell's name at the end of its list where it reports it
    for m in man.data["per_layer"][:-4]:
        if CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) == 2
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
