"""The ``olmo_hybrid`` family (Olmo-Hybrid-7B): the family builder at the
published keys (parameter count, leaves, cache shapes); the plain
reference against the program at tiny widths on the CPU, weights drawn as
the benchmark draws them, through the harness's own check and a
closed-loop window; the control in lower precision; ``gdn_work``'s bytes
by hand; the new readers on a made-up trace; and the cell's entries as
ISSUE 34 names them."""

import json
import os

import numpy as np
import pytest

import jax

from benchmark import checks, gdn_work
from benchmark import program_trace as pt
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.run import run_cell
from benchmark.weights import make_weights

CELL, CONFIG = "olmohybrid7b-chat-closed", "olmo-hybrid-7b-serve"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "family": "olmo_hybrid", "model_type": "olmo_hybrid", "vocab_size": 128,
    "hidden_size": 48, "intermediate_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 4096, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": PERIOD * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "reduced": {}, "compute_dtype": "float32",
    "weights": {"dtype": "float32",
                "float32_leaves": ["ln1_s", "ln2_s", "lnf_s", "qn_s", "kn_s",
                                   "lin_norm_s", "A_log", "dt_bias"],
                "init": {"A_log": "zeros", "dt_bias": "zeros",
                         "lin_norm_s": "ones", "qn_s": "ones",
                         "kn_s": "ones"}},
    "engine": {"n_slots": 4, "max_len": 400, "max_queue": 64},
    "check": {"prompt_lengths": [5, 64, 150, 290], "decode_steps": 7,
              "stream_max_tokens": 256},
}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO_ROOT)


@pytest.fixture(scope="module")
def tiny(man):
    fam = man.module("families", "olmo_hybrid")
    ref = man.module("reference", "olmo_hybrid")
    model = fam.build_model(TINY)
    weights = make_weights(model, 2**31 + 5, "float32",
                           init=TINY["weights"]["init"])
    return fam, ref, model, weights


def _tokens(n, seed=3):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def test_family_builder_at_the_published_keys(man):
    cfg = man.config(CONFIG)
    model = man.module("families", cfg["family"]).build_model(cfg)
    shapes = model.param_shapes()
    assert shapes["lin_qkv"].shape == (6, 3840, 2880 + 2880 + 5760)
    assert shapes["lin_conv"].shape == (6, 4, 11520)
    assert shapes["lin_ab"].shape == (6, 3840, 60)
    assert shapes["A_log"].shape == shapes["dt_bias"].shape == (6, 30)
    assert shapes["lin_z"].shape == (6, 3840, 5760)
    assert shapes["lin_norm_s"].shape == (6, 192)
    assert shapes["lin_o"].shape == (6, 5760, 3840)
    assert shapes["wq"].shape == shapes["wo"].shape == (2, 3840, 3840)
    assert shapes["qn_s"].shape == shapes["kn_s"].shape == (2, 3840)
    assert shapes["w1"].shape == (8, 3840, 11008)
    assert shapes["head"].shape == (3840, 100352)
    assert "pos" not in shapes and "ln1_b" not in shapes
    assert set(cfg["weights"]["init"]) <= set(shapes)
    assert set(cfg["weights"]["float32_leaves"]) <= set(shapes)
    n = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert 2.43e9 < n < 2.45e9                   # 2.44 B parameters held
    # a linear layer's mixer 88.75 M, a full layer's 58.98 M, the FFN 126.81
    mixer = sum(int(np.prod(shapes[k].shape[1:])) for k in model._LINEAR_KEYS)
    assert 88.7e6 < mixer < 88.8e6
    eng = cfg["engine"]
    cache = jax.eval_shape(lambda: model.init_cache(eng["n_slots"],
                                                    length=eng["max_len"]))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((2, 192, 30, 1024, 128), "bfloat16"),
        "v": ((2, 192, 30, 1024, 128), "bfloat16"),
        "s": ((6, 192, 15, 96, 384), "float32"),
        "conv": ((6, 192, 3 * 11520), "float32")}
    gib = {k: v.size * v.dtype.itemsize / 2**30 for k, v in cache.items()}
    assert 2.36 < gib["s"] < 2.38 and 5.62 < gib["k"] + gib["v"] < 5.64
    assert (model.norm_order, model.qk_norm, model.rope_layers,
            model.lin_gate, str(model.state_dtype), str(model.act_dtype),
            str(model.compute_dtype)) == (
        "post", "whole", "none", "silu", "float32", "float32", "bfloat16")
    assert not model._rope_on(None)


def test_full_forward_against_the_reference(tiny):
    _, ref, model, weights = tiny
    toks = _tokens(150)
    want = np.asarray(ref.forward(TINY, weights, toks))
    import jax.numpy as jnp
    got = np.asarray(model.apply(weights, jnp.asarray(toks)[None],
                                 jnp.arange(150)[None])[0])
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    # float32 on both sides: far inside the check's 6%
    assert ok and share == 1.0 and worst < 1e-3


def test_lower_precision_fails_the_check(tiny):
    """The control: the reference with its linear layers' matmul inputs in
    the next precision below bfloat16 (``lower="linear"``) is NOT within
    the check's limits of the reference itself, at the share the family
    states (on the chip at the published widths: no position of 256 within
    6%, PERF.md §6). A state carried in bfloat16 moves the logits too, by
    orders of magnitude more than the float32 program differs from the
    reference, but inside the harness's 6% (1.7% on the chip): that one is
    held by the tighter tolerance of tests/models/test_linear_attention.py."""
    _, ref, _, weights = tiny
    toks = _tokens(290)
    want = np.asarray(ref.forward(TINY, weights, toks))
    got = np.asarray(ref.forward(TINY, weights, toks, lower="linear"))
    ok, worst, share = checks.logits_agree(got, want, ref.MIN_SHARE)
    assert not ok and share < ref.MIN_SHARE and worst > checks.LOGIT_RTOL
    assert 0.5 <= ref.MIN_SHARE < 1.0
    low = np.asarray(ref.forward(TINY, weights, toks,
                                 state_dtype="bfloat16"))
    _, worst, _ = checks.logits_agree(low, want, ref.MIN_SHARE)
    assert worst > 3e-3
    # each other reading moves the reference by far more than the limit
    for kw in (dict(norm_order="pre"), dict(qk_norm="head"),
               dict(qk_norm=None), dict(rope_theta=1e4),
               dict(gate="sigmoid")):
        if kw.get("qk_norm") == "head":
            continue            # another scale shape: not these weights
        other = np.asarray(ref.forward(TINY, weights, toks, **kw))
        assert not checks.logits_agree(other, want, ref.MIN_SHARE)[0], kw


def _tiny_root(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-olmo.json"), "w") as f:
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
    cell = "tiny-olmo"
    bench.update(
        paths=["benchmark"], run_seconds=1,
        configs=[{"name": cell, "source": "tests", "reduced": [],
                  "file": "benchmark/configs/tiny-olmo.json",
                  "why": "tiny"}],
        workloads=[{"name": cell, "config": cell, "traffic": "tiny-closed",
                    "chips": 1, "why": "tiny"}])
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = [cell] if CELL in m.get("workloads", [CELL]) else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_prefill_then_decode_through_the_engines_check(tiny, tmp_path):
    """The harness's own ``check_logits`` (prefill-insert at 5 to 290
    tokens through the chunkwise form: under a block, a block exactly,
    several and a part; then batched decode steps through state and cache)
    and a closed-loop window with its stream check, at tiny widths, from a
    throw-away root that holds the tiny files only; every metric the cell
    lists is asked, and a CPU run has the counters'."""
    tman = Manifest(_tiny_root(tmp_path))
    for trace in (0, 1):
        last = run_cell(tman, "tiny-olmo", 2**31 + 77, 0.5, trace,
                        jax.devices()[:1])
        assert last["correct"] is True
        assert last["attempted"] > 0 and last["failed"] == 0
    assert last["metrics"][
        "kernels.flash_decode_live_visits_pct.batch"]["value"] == 100.0
    assert "engine.batch_occupancy_pct.batch" in last["metrics"]


def test_gdn_work_by_hand(man):
    cfg = man.config(CONFIG)
    assert gdn_work.state_numbers(cfg) == 30 * 96 * 192 == 552_960
    assert gdn_work.linear_layers(cfg) == 6
    flops, nbytes = gdn_work.gdn_decode_work(cfg, 192 * 6)
    # a float32 state read once and written once a live row a layer
    assert nbytes == 192 * 6 * 2 * 30 * 96 * 192 * 4 == 5_096_079_360
    assert flops == 192 * 6 * 7 * 552_960
    assert flops / nbytes < 1.0                  # the v5e's ridge is 240
    # a run that was not traced, a configuration without linear layers, a
    # parent without the kernel or the span argument: nothing, no raise
    facts = {"cfg": cfg, "snapshot": {"work": {}}, "trace": None}
    assert gdn_work.gdn_decode_roofline_pct(facts) is None
    assert gdn_work.prefill_scope_share_pct(facts) is None
    assert gdn_work.gdn_decode_roofline_pct({"cfg": {}, "trace": None}) is None
    for name in ("kernels.gdn_decode_roofline_pct.batch",
                 "decode_step.attn_linear_ms.batch",
                 "prefill.attn_linear_share_pct.batch"):
        assert man.module("layer_metrics", name).read(facts) is None


SCAN = "jit(_decode_kernel)/layers/"


def _made_up_trace():
    """Two decode spans of 10 ms and one prefill span, by hand: in each
    decode span the ``gdn_decode`` kernel runs 6 x 1 ms, other work under
    ``attn_linear`` 0.5 ms, ``flash_decode`` 2 ms; in the prefill span 3 ms
    of 12 are under ``attn_linear``."""
    ops, spans, t = [], [], 0.0
    for rows in (192 * 6, 96 * 6):
        spans.append((pt.SPAN_PREFIX + "engine.decode", t, t + 10e-3,
                      {"state_rows": rows, "n_active": rows // 6, "k": 1,
                       "kv_positions": 1000}))
        at = t
        for _ in range(6):
            ops.append((SCAN + "attn_core/attn_linear/gdn_decode/pallas_call",
                        at, at + 1e-3))
            at += 1e-3
        ops.append((SCAN + "attn_core/attn_linear/transpose", at,
                    at + 0.5e-3))
        ops.append((SCAN + "attn_core/attn_full/flash_decode/pallas_call",
                    at + 0.5e-3, at + 2.5e-3))
        ops.append((SCAN + "ffn/dot_general", at + 2.5e-3, at + 3.5e-3))
        t += 10e-3
    spans.append((pt.SPAN_PREFIX + "engine.prefill", t, t + 20e-3, {}))
    ops.append(("jit(_insert_kernel)/layers/attn_core/attn_linear/gdn_chunk/"
                "dot_general", t, t + 3e-3))
    ops.append(("jit(_insert_kernel)/layers/ffn/dot_general", t + 3e-3,
                t + 12e-3))
    return ops, spans, t + 20e-3


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
        read = {n: man.module("layer_metrics", n).read(facts) for n in (
            "kernels.gdn_decode_roofline_pct.batch",
            "decode_step.attn_linear_ms.batch",
            "prefill.attn_linear_share_pct.batch")}
    finally:
        pt._tables.cache_clear()
    # 6.5 ms under the scope in each span
    assert read["decode_step.attn_linear_ms.batch"] == pytest.approx(6.5)
    # 192 x 6 states in 6 ms: 5.096 GB / 819 GB/s = 6.222 ms -> 103.7%;
    # 96 x 6 in 6 ms -> 51.9%; the median of two is their mean
    assert read["kernels.gdn_decode_roofline_pct.batch"] == pytest.approx(
        (103.71 + 51.85) / 2, abs=0.05)
    assert read["prefill.attn_linear_share_pct.batch"] == pytest.approx(25.0)
    # a program without the scope or the span argument (the parent): nothing
    bare = {**loaded, "spans": [(n, s, e, {}) for n, s, e, _ in spans],
            "device_ops": {"/device:TPU:0": [
                (n.replace("attn_linear/", "").replace("gdn_decode", "k"),
                 s, e) for n, s, e in ops]}}
    monkeypatch.setattr(pt, "load", lambda path: bare)
    try:
        for n in read:
            assert man.module("layer_metrics", n).read(facts) is None
    finally:
        pt._tables.cache_clear()


def test_the_cell_as_written(man):
    cfg = man.config(CONFIG)
    cell = man.cell(CELL)
    mix = man.traffic(cell["traffic"])
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "chat-closed-240")
    assert mix == {**mix, "driver": "closed_loop", "callers": 240,
                   "shape_seed": 34, "pool": 2048, "warm_in_s": 15.0,
                   "profile_s": 3.0,
                   "prompt_tokens": {"median": 160, "sigma": 0.8,
                                     "min": 16, "max": 384},
                   "output_tokens": {"median": 320, "sigma": 0.6,
                                     "min": 32, "max": 640}}
    assert cfg["engine"] == {"n_slots": 192, "max_len": 1024,
                             "max_queue": 256}
    assert cfg["check"] == {"prompt_lengths": [37, 64, 300, 384],
                            "decode_steps": 63}
    # every request fits its slot, and the stream check's default block
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 1024
    assert sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 32
    assert cfg["layer_types"] == PERIOD * 2
    assert cfg["reduced"]["layer_types"]["published"] == PERIOD * 8
    for item in ("norm order", "qk norm", "rope", "output gate",
                 "state dtype", "activations", "weights", "published keys"):
        assert item in cfg["assumed"], item
    assert "4 pipeline stages" in cfg["stands_for"]
    assert cfg["weights"]["init"]["A_log"] == "zeros"
    entry = next(c for c in man.data["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # the cell reports what ISSUE 34 names: membership, never a position
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    new = {"decode_step.attn_linear_ms.batch",
           "kernels.gdn_decode_roofline_pct.batch",
           "prefill.attn_linear_share_pct.batch"}
    assert new | {
        "engine.batch_occupancy_pct.batch", "engine.itl_p50_ms.batch",
        "engine.emit_host_ms.batch", "engine.decide_host_ms.batch",
        "engine.prefill_padding_pct.batch",
        "engine.idle_unattributed_pct.batch", "device.idle_pct.batch",
        "device.unscoped_pct.batch", "decode_step.device_ms.batch",
        "decode_step.attn_ms.batch", "decode_step.moe_ms.batch",
        "decode_step.cache_io_ms.batch", "decode_step.attn_full_ms.batch",
        "prefill.device_share_pct.batch", "kernels.pallas_share_pct.batch",
        "kernels.flash_decode_live_visits_pct.batch"} == names
    assert not {"kernels.flash_decode_roofline_pct.batch",
                "engine.kv_live_positions.batch",
                "decode_step.attn_window_ms.batch",
                "decode_step.attn_latent_ms.batch",
                "kernels.mla_decode_roofline_pct.batch",
                "kernels.grouped_matmul_roofline_pct.batch",
                "moe.rows_padding_pct.batch"} & names
    for name in new:
        entry = next(m for m in man.data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["source"] == "device_trace"
    assert [m["name"] for m in man.data["per_layer"][-3:]] == [
        "decode_step.attn_linear_ms.batch",
        "kernels.gdn_decode_roofline_pct.batch",
        "prefill.attn_linear_share_pct.batch"]
    assert man.data["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
