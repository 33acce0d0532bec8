"""The ``ouro`` family (Ouro-2.6B, a looped stack): the family builder at
the published keys (parameter count, leaves, cache shapes); the plain
reference against the program at tiny widths on the CPU, weights drawn as
the benchmark draws them, through the harness's own check and a
closed-loop window; the control in lower precision and the other
readings; ``loop_work``'s bytes by hand; the new readers on a made-up
trace; and the cell's entries as written."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import checks, loop_work
from benchmark import program_trace as pt
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.run import run_cell
from benchmark.weights import make_weights

CELL, CONFIG = "ouro2.6b-eval-closed", "ouro-2.6b-serve"
NEW = ("kernels.looped_decode_roofline_pct.batch",
       "decode_step.pass_weights_roofline_pct.batch")
TINY = {
    "family": "ouro", "model_type": "ouro", "vocab_size": 128,
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "hidden_act": "silu", "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "sliding_window": None, "use_sliding_window": False,
    "max_window_layers": 3, "tie_word_embeddings": False,
    "layer_types": ["full_attention"] * 3, "total_ut_steps": 4,
    "early_exit_threshold": 1, "reduced": {}, "compute_dtype": "float32",
    "weights": {"dtype": "float32",
                "float32_leaves": ["ln1_s", "ln1_out_s", "ln2_s",
                                   "ln2_out_s", "lnf_s"]},
    "engine": {"n_slots": 4, "max_len": 400, "max_queue": 64},
    "check": {"prompt_lengths": [5, 64, 150, 290], "decode_steps": 7},
}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO_ROOT)


@pytest.fixture(scope="module")
def tiny(man):
    fam = man.module("families", "ouro")
    ref = man.module("reference", "ouro")
    model = fam.build_model(TINY)
    weights = make_weights(model, 2**31 + 41, "float32")
    return fam, ref, model, weights


def _tokens(n, seed=3):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def test_family_builder_at_the_published_keys(man):
    cfg = man.config(CONFIG)
    model = man.module("families", cfg["family"]).build_model(cfg)
    shapes = model.param_shapes()
    assert shapes["wq"].shape == shapes["wo"].shape == (12, 2048, 2048)
    assert shapes["wk"].shape == shapes["wv"].shape == (12, 2048, 2048)
    assert shapes["w1"].shape == shapes["w3"].shape == (12, 2048, 5632)
    assert shapes["w2"].shape == (12, 5632, 2048)
    for k in ("ln1_s", "ln1_out_s", "ln2_s", "ln2_out_s"):
        assert shapes[k].shape == (12, 2048)
    assert shapes["head"].shape == (2048, 49152)
    assert "pos" not in shapes and "bq" not in shapes
    assert set(cfg["weights"]["float32_leaves"]) <= set(shapes)
    n = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert 817.9e6 < n < 818.1e6                 # 818 M parameters held
    # a layer's matrices 51.39 M
    assert loop_work.layer_parameters(cfg) == 4 * 2048**2 + 3 * 2048 * 5632
    eng = cfg["engine"]
    cache = jax.eval_shape(lambda: model.init_cache(eng["n_slots"],
                                                    length=eng["max_len"]))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((48, 24, 16, 1024, 128), "bfloat16"),
        "v": ((48, 24, 16, 1024, 128), "bfloat16")}
    gib = sum(v.size * v.dtype.itemsize for v in cache.values()) / 2**30
    assert gib == 9.0                            # 384 KiB a position
    assert (model.passes, model.norm_order, model.pass_norm,
            model.attn_bias, str(model.act_dtype),
            str(model.compute_dtype), model.rope_theta) == (
        4, "sandwich", True, False, "bfloat16", "bfloat16", 1e6)


def test_the_family_refuses_what_it_does_not_read(man):
    fam = man.module("families", "ouro")
    for bad in (dict(early_exit_threshold=0.9), dict(use_sliding_window=True),
                dict(layer_types=["full_attention"] * 2)):
        with pytest.raises(ValueError):
            fam.build_model({**TINY, **bad})


def test_full_forward_against_the_reference(tiny):
    _, ref, model, weights = tiny
    toks = _tokens(150)
    want = np.asarray(ref.forward(TINY, weights, toks))
    got = np.asarray(model.apply(weights, jnp.asarray(toks)[None],
                                 jnp.arange(150)[None])[0])
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    # float32 on both sides: far inside the check's 6%
    assert ok and share == 1.0 and worst < 1e-4


def test_lower_precision_and_other_readings_fail_the_check(tiny):
    """The control: the reference with every matmul's activation input in
    the next precision below bfloat16 (``lower=True``) is not within the
    check's limits of the reference itself; nor is any other reading of
    what the config does not say, nor a stack run fewer times."""
    _, ref, _, weights = tiny
    toks = _tokens(290)
    want = np.asarray(ref.forward(TINY, weights, toks))
    got = np.asarray(ref.forward(TINY, weights, toks, lower=True))
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    assert not ok and share < ref.MIN_SHARE and worst > checks.LOGIT_RTOL
    assert 0.5 <= ref.MIN_SHARE <= 1.0
    for kw in (dict(norm_order="pre"), dict(pass_norm=False),
               dict(passes=3), dict(passes=1)):
        other = np.asarray(ref.forward(TINY, weights, toks, **kw))
        assert not checks.logits_agree(other, want, ref.MIN_SHARE)[0], kw


def _tiny_root(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-ouro.json"), "w") as f:
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
    cell = "tiny-ouro"
    bench.update(
        paths=["benchmark"], run_seconds=1,
        configs=[{"name": cell, "source": "tests", "reduced": [],
                  "file": "benchmark/configs/tiny-ouro.json",
                  "why": "tiny"}],
        workloads=[{"name": cell, "config": cell, "traffic": "tiny-closed",
                    "chips": 1, "why": "tiny"}])
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = [cell] if CELL in m.get("workloads", [CELL]) else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_prefill_then_decode_through_the_engines_check(tmp_path):
    """The harness's own ``check_logits`` (prefill-insert at 5 to 290
    tokens, then batched decode steps through every pass's cache layers)
    and a closed-loop window with its stream check, at tiny widths, from a
    throw-away root that holds the tiny files only; every metric the cell
    lists is asked, and a CPU run has the counters'."""
    tman = Manifest(_tiny_root(tmp_path))
    for trace in (0, 1):
        last = run_cell(tman, "tiny-ouro", 2**31 + 77, 0.5, trace,
                        jax.devices()[:1])
        assert last["correct"] is True
        assert last["attempted"] > 0 and last["failed"] == 0
    assert last["metrics"][
        "kernels.flash_decode_live_visits_pct.batch"]["value"] == 100.0
    assert "engine.batch_occupancy_pct.batch" in last["metrics"]


def test_loop_work_by_hand(man):
    cfg = man.config(CONFIG)
    assert loop_work.cache_layers(cfg, 4) == 48
    flops, nbytes = loop_work.looped_decode_work(cfg, 24 * 480, 4)
    # K and V of every live position in 4 passes x 12 layers, bf16
    assert nbytes == 24 * 480 * 48 * 2 * 16 * 128 * 2 == 4_529_848_320
    assert flops == 4.0 * 16 * 128 * 48 * 24 * 480
    # a quarter of it is what a count of the weight layers alone gives
    from benchmark.kernel_work import decode_attention_work
    assert decode_attention_work(cfg, 24 * 480)[1] * 4 == nbytes
    flops, nbytes = loop_work.pass_weights_work(cfg, 4, 24)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert nbytes == (48 * layer + 2048 * 49152) * 2 == 5_133_828_096
    assert flops == 2.0 * 24 * (48 * layer + 2048 * 49152)
    # a run that was not traced, a configuration that does not loop, a
    # parent without the span argument: nothing, no raise
    facts = {"cfg": cfg, "snapshot": {"work": {}}, "trace": None}
    assert loop_work.looped_decode_roofline_pct(facts) is None
    assert loop_work.pass_weights_roofline_pct(facts) is None
    assert loop_work.looped_decode_roofline_pct(
        {"cfg": {}, "trace": None}) is None
    for name in NEW:
        assert man.module("layer_metrics", name).read(facts) is None
        assert man.module("layer_metrics", name).read({}) is None


SCAN = "jit(_decode_kernel)/while/body/layers/"


def _made_up_trace(passes=4):
    """Two decode spans of 20 ms: in each, ``flash_decode`` runs 48 x 0.1
    ms, the weight products under ``attn`` 2 ms and ``ffn`` 3.5 ms, the
    head 0.5 ms, a cache write 0.2 ms."""
    ops, spans, t = [], [], 0.0
    for rows, kv in ((24, 24 * 480), (12, 12 * 480)):
        loop = ({} if passes is None else
                {"passes": passes, "cache_layers": passes * 12})
        spans.append((pt.SPAN_PREFIX + "engine.decode", t, t + 20e-3,
                      {"n_active": rows, "k": 1, "kv_positions": kv,
                       **loop}))
        at = t
        for _ in range(48):
            ops.append((SCAN + "attn_core/attn_full/flash_decode/pallas_call",
                        at, at + 0.1e-3))
            at += 0.1e-3
        for scope, ms in (("attn/dot_general", 2.0), ("ffn/dot_general", 3.5),
                          ("kv_write/kv_write_row/pallas_call", 0.2)):
            ops.append((SCAN + scope, at, at + ms * 1e-3))
            at += ms * 1e-3
        ops.append(("jit(_decode_kernel)/head/dot_general", at,
                    at + 0.5e-3))
        t += 20e-3
    return ops, spans, t


def test_new_readers_on_a_made_up_trace(man, monkeypatch):
    cfg = man.config(CONFIG)
    ops, spans, hi = _made_up_trace()
    loaded = {"device_ops": {"/device:TPU:0": ops}, "modules": {},
              "spans": spans}
    monkeypatch.setattr(pt, "load", lambda path: loaded)
    monkeypatch.setattr(pt, "newest", lambda: "made-up")
    monkeypatch.setattr(pt, "_peaks", lambda: (197e12, 819e9))
    pt._tables.cache_clear()
    facts = {"cfg": cfg, "snapshot": {"work": {}},
             "trace": {"lo": 0.0, "hi": hi}}
    try:
        read = {n: man.module("layer_metrics", n).read(facts) for n in NEW}
    finally:
        pt._tables.cache_clear()
    # 24 x 480 positions in 48 layers, 4.53 GB / 819 GB/s = 5.531 ms in 4.8
    # ms -> 115.2%; half of it in the second span -> 57.6%; the median of
    # two is their mean
    cache_ms = 24 * 480 * 48 * 2 * 16 * 128 * 2 / 819e9 * 1e3
    assert read[NEW[0]] == pytest.approx(
        100 * (cache_ms + cache_ms / 2) / 2 / 4.8, rel=1e-6)
    # 5.135 GB of weights in 6 ms under attn + ffn + head, both spans
    weights_ms = 5_133_828_096 / 819e9 * 1e3
    assert read[NEW[1]] == pytest.approx(100 * weights_ms / 6.0, rel=1e-6)
    # spans that do not say they loop as the configuration does (the
    # parent's, or another pass count): nothing
    for passes in (None, 1):
        ops, spans, _ = _made_up_trace(passes)
        monkeypatch.setattr(pt, "load", lambda path: {
            **loaded, "device_ops": {"/device:TPU:0": ops}, "spans": spans})
        try:
            for n in NEW:
                assert man.module("layer_metrics", n).read(facts) is None
        finally:
            pt._tables.cache_clear()


def test_the_cell_as_written(man):
    cfg = man.config(CONFIG)
    cell = man.cell(CELL)
    mix = man.traffic(cell["traffic"])
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "eval-closed-30")
    assert mix == {**mix, "driver": "closed_loop", "callers": 30,
                   "shape_seed": 41, "pool": 2048, "warm_in_s": 15.0,
                   "profile_s": 3.0,
                   "prompt_tokens": {"median": 320, "sigma": 0.6,
                                     "min": 32, "max": 600},
                   "output_tokens": {"median": 256, "sigma": 0.5,
                                     "min": 64, "max": 400}}
    assert cfg["engine"] == {"n_slots": 24, "max_len": 1024,
                             "max_queue": 32}
    assert cfg["engine"]["max_queue"] >= mix["callers"]
    assert cfg["check"] == {"prompt_lengths": [37, 128, 300, 600],
                            "decode_steps": 31}
    # every request fits its slot, and the stream check's default block
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 1024
    assert sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 48
    assert cfg["layer_types"] == ["full_attention"] * 12
    assert cfg["total_ut_steps"] == 4 and cfg["early_exit_threshold"] == 1
    for item in ("sandwich norms", "projection biases", "norm between passes",
                 "exit gate", "activations", "weights", "published keys",
                 "n_slots", "max_len", "max_queue"):
        assert item in cfg["assumed"], item
    assert "4 pipeline stages" in cfg["stands_for"]
    entry = next(c for c in man.data["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # what the cell reports: membership, never a position
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert {"decode_step.device_ms.batch", "decode_step.attn_full_ms.batch",
            "kernels.flash_decode_live_visits_pct.batch",
            "device.idle_pct.batch"} <= names
    # one pass of the cache is what this reader counts: not reported here
    assert "kernels.flash_decode_roofline_pct.batch" not in names
    for name in NEW:          # the loop's own readers, by file
        assert os.path.exists(man.find("layer_metrics", name + ".py"))
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
