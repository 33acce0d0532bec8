"""``benchmark/program_trace.py`` and ``benchmark/kernel_work.py`` on
hand-built event lists and tiny shapes; every reader this PR adds on empty
facts; and that the engine's spans reach a real ``jax.profiler`` trace."""

import pytest

from benchmark import kernel_work as kw
from benchmark import program_trace as pt
from benchmark import trace
from benchmark.manifest import REPO_ROOT, Manifest

FWD = "jit(step_impl)/jvp(layers)/while/body/closed_call/"
BWD = "jit(step_impl)/transpose(jvp(layers))/while/body/closed_call/"
NEW = [
    "lm_train_step.forward_ms", "lm_train_step.backward_ms",
    "lm_train_step.optimizer_ms", "lm_train_step.head_loss_ms",
    "kernels.flash_fwd_roofline_pct.train",
    "kernels.flash_bwd_roofline_pct.train", "device.unscoped_pct.train",
    "decode_step.attn_ms.batch", "decode_step.moe_ms.batch",
    "decode_step.cache_io_ms.batch",
    "kernels.flash_decode_roofline_pct.batch", "engine.emit_host_ms.batch",
    "engine.decide_host_ms.batch", "engine.prefill_padding_pct.batch",
    "engine.idle_unattributed_pct.batch", "device.unscoped_pct.batch"]


# -- names ---------------------------------------------------------------------

@pytest.mark.parametrize("op_name,scope", [
    (FWD + "attn/dot_general", "attn"),
    (BWD + "attn_core/flash_bwd_dq/pallas_call", "attn_core"),
    (FWD + "ffn/moe/moe_experts/vmap(dot_general)", "moe_experts"),
    (FWD + "ffn/moe/reduce_sum", "moe"),
    (FWD + "ffn/mul", "ffn"),
    ("jit(step_impl)/jvp(layers)/while/body/dynamic_slice", "layers"),
    ("jit(_decode_kernel)/layers/while/body/kv_write/dynamic_update_slice",
     "kv_write"),
    ("jit(step_impl)/transpose(jvp(head))/dot_general", "head"),
    ("jit(step_impl)/optimizer/mul", "optimizer"),
    ("jit(_decode_kernel)/sample/argmax", "sample"),
    ("jit(_scatter_row)/dynamic_update_slice", pt.UNSCOPED),
    ("%copy.58", pt.UNSCOPED),
    # a primitive is not a scope, and a scope is a whole word
    ("jit(f)/attention/headroom/transpose", pt.UNSCOPED),
])
def test_scope_of_is_the_innermost_of_the_vocabulary(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_is_backward_and_kernel_of():
    assert pt.is_backward(BWD + "attn/dot_general")
    assert not pt.is_backward(FWD + "attn/dot_general")
    # the transpose primitive of a forward operation is not a backward pass
    assert not pt.is_backward(FWD + "attn_core/transpose")
    assert pt.kernel_of(FWD + "attn_core/flash_fwd/pallas_call") == \
        "flash_fwd"
    assert pt.kernel_of(FWD + "attn/dot_general") is None


# -- device time ----------------------------------------------------------------

def step_ops(t0):
    """One hand-built step of 10 ms starting at ``t0`` (seconds): a
    ``while`` of 6 ms that holds its body's operations."""
    ms = 1e-3
    return [
        ("jit(s)/jvp(embed)/gather", t0, t0 + 1 * ms),
        ("jit(s)/jvp(layers)/while", t0 + 1 * ms, t0 + 7 * ms),
        (FWD + "attn/dot_general", t0 + 1 * ms, t0 + 2 * ms),
        (FWD + "attn_core/flash_fwd/pallas_call", t0 + 2 * ms, t0 + 4 * ms),
        ("jit(s)/jvp(layers)/while/body/dynamic_slice", t0 + 4 * ms,
         t0 + 4.5 * ms),
        (BWD + "attn_core/flash_bwd_dq/pallas_call", t0 + 5 * ms,
         t0 + 6 * ms),
        (BWD + "attn_core/flash_bwd_dkv/pallas_call", t0 + 6 * ms,
         t0 + 7 * ms),
        ("jit(s)/optimizer/mul", t0 + 7 * ms, t0 + 9 * ms),
        ("%copy.3", t0 + 9 * ms, t0 + 10 * ms),
    ]


def test_device_ms_by_scope_counts_self_time_once():
    by = pt.device_ms_by_scope(step_ops(0.0), 0.0, 1.0)
    assert by[("embed", False)] == pytest.approx(1.0)
    assert by[("attn", False)] == pytest.approx(1.0)
    assert by[("attn_core", False)] == pytest.approx(2.0)
    assert by[("attn_core", True)] == pytest.approx(2.0)
    # the while's own 6 ms minus its body's 5.5, plus the slice of its xs
    assert by[("layers", False)] == pytest.approx(0.5 + 0.5)
    assert by[("optimizer", False)] == pytest.approx(2.0)
    assert by[(pt.UNSCOPED, False)] == pytest.approx(1.0)
    assert sum(by.values()) == pytest.approx(10.0)
    assert pt.pick(by, pt.ATTN) == pytest.approx(5.0)
    assert pt.pick(by, backward=False,
                   exclude=("optimizer", pt.UNSCOPED)) == pytest.approx(5.0)
    assert pt.has_names(by)
    assert not pt.has_names(pt.device_ms_by_scope(
        [("%fusion.1", 0.0, 1.0)], 0.0, 1.0))


def test_device_ms_by_scope_clips_to_the_window():
    by = pt.device_ms_by_scope(step_ops(0.0), 0.008, 0.0095)
    assert by == {("optimizer", False): pytest.approx(1.0),
                  (pt.UNSCOPED, False): pytest.approx(0.5)}


def test_kernel_ms_and_ops_between():
    ops, starts = pt.by_start(step_ops(0.02) + step_ops(0.0))
    assert pt.kernel_ms(ops, 0.0, 1.0) == {
        "flash_fwd": pytest.approx(4.0), "flash_bwd_dq": pytest.approx(2.0),
        "flash_bwd_dkv": pytest.approx(2.0)}
    second = pt.ops_between(ops, starts, 0.02, 0.03)
    assert len(second) == 9 and min(s for _, s, _ in second) == 0.02


def test_whole_steps_keeps_the_big_program_and_drops_a_cut_one():
    modules = [("jit_step(1)", 0.000, 0.004),      # cut short by the start
               ("jit_step(1)", 0.010, 0.020),
               ("jit_convert(2)", 0.0205, 0.0206),
               ("jit_step(1)", 0.021, 0.031)]
    assert pt.whole_steps(modules) == [(0.010, 0.020), (0.021, 0.031)]
    assert pt.whole_steps([]) == []


# -- program spans ----------------------------------------------------------------

P = "elephas.engine."
SPANS = [                                       # one decode step, in ms
    (P + "step", 0.0, 10.0, {"step": 1, "action": "decode"}),
    (P + "reap", 0.1, 0.2, {}),
    (P + "decide", 0.2, 0.5, {}),
    (P + "decode", 0.5, 9.8, {"n_active": 2, "k": 1, "kv_positions": 100}),
    (P + "decode.dispatch", 0.6, 1.6, {}),
    (P + "decode.fetch", 1.6, 8.0, {}),
    (P + "decode.emit", 8.0, 9.7, {}),
]


def test_leaf_intervals_are_each_span_minus_its_children():
    own = dict(pt.leaf_intervals(SPANS))
    assert own[P + "decode.emit"] == [(8.0, 9.7)]
    assert trace.total(own[P + "decode"]) == pytest.approx(
        9.3 - 1.0 - 6.4 - 1.7)
    assert trace.total(own[P + "step"]) == pytest.approx(
        10.0 - 0.1 - 0.3 - 9.3)


def test_idle_by_leaf_span_splits_each_gap():
    ops = [("a", 1.2, 7.5)]                     # the decode program
    idle = pt.idle_by_leaf_span(ops, SPANS, -1.0, 11.0)
    # before the program: 1 ms before the step, 0.1 of the step itself,
    # reap, decide, 0.1 of decode's own, 0.6 of dispatch
    assert idle[pt.NO_SPAN] == pytest.approx(1.0 + 1.0)
    assert idle[P + "reap"] == pytest.approx(0.1)
    assert idle[P + "decide"] == pytest.approx(0.3)
    assert idle[P + "decode.dispatch"] == pytest.approx(0.6)
    assert idle[P + "decode.fetch"] == pytest.approx(0.5)
    assert idle[P + "decode.emit"] == pytest.approx(1.7)
    assert idle[P + "decode"] == pytest.approx(0.1 + 0.1)
    assert idle[P + "step"] == pytest.approx(0.1 + 0.2)
    assert sum(idle.values()) == pytest.approx(12.0 - 6.3)
    # trace.gaps_by_span would hand both gaps whole to the root
    assert pt.children_of(SPANS, SPANS[0], {P + "reap", P + "decide"}) == \
        pytest.approx(0.4)


# -- the readers' arithmetic, on a hand-built trace ----------------------------------

@pytest.fixture
def fake_trace(monkeypatch):
    """Two train steps and two decode spans as ``load`` would give them."""
    def install(ops, modules=(), spans=()):
        loaded = {"device_ops": {"/device:TPU:0": list(ops)},
                  "modules": {"/device:TPU:0": list(modules)},
                  "spans": list(spans)}
        monkeypatch.setattr(pt, "newest", lambda: "fake.xplane.pb")
        monkeypatch.setattr(pt, "load", lambda path: loaded)
        pt._tables.cache_clear()
        return {"trace": {"lo": 0.0, "hi": 0.04}, "chips": 1}
    yield install
    pt._tables.cache_clear()


def test_train_readers_on_two_hand_built_steps(fake_trace):
    facts = fake_trace(step_ops(0.0) + step_ops(0.02),
                       modules=[("jit_step(1)", 0.0, 0.01),
                                ("jit_step(1)", 0.02, 0.03)])
    man = Manifest(REPO_ROOT)
    read = {n: man.module("layer_metrics", n).read for n in NEW}
    assert read["lm_train_step.forward_ms"](facts) == pytest.approx(5.0)
    assert read["lm_train_step.backward_ms"](facts) == pytest.approx(2.0)
    assert read["lm_train_step.optimizer_ms"](facts) == pytest.approx(2.0)
    assert read["lm_train_step.head_loss_ms"](facts) == pytest.approx(0.0)
    assert read["device.unscoped_pct.train"](facts) == pytest.approx(10.0)
    # no decode span in a train trace
    assert read["decode_step.attn_ms.batch"](facts) is None


def test_a_program_without_names_reads_as_nothing(fake_trace):
    facts = fake_trace([("%fusion.7", 0.0, 0.01), ("%copy.2", 0.01, 0.02)],
                       modules=[("jit_step(1)", 0.0, 0.02)])
    man = Manifest(REPO_ROOT)
    for name in NEW:
        assert man.module("layer_metrics", name).read(facts) is None, name


def test_decode_readers_on_two_hand_built_spans(fake_trace):
    d = "jit(_decode_kernel)/layers/while/body/"
    ms = 1e-3

    def one(t0, kv):
        ops = [(d + "attn/dot_general", t0 + 1 * ms, t0 + 2 * ms),
               (d + "kv_write/dynamic_update_slice", t0 + 2 * ms,
                t0 + 3 * ms),
               (d + "dynamic_slice", t0 + 3 * ms, t0 + 5 * ms),
               (d + "attn_core/flash_decode/pallas_call", t0 + 5 * ms,
                t0 + 6 * ms),
               (d + "ffn/moe/moe_experts/dot_general", t0 + 6 * ms,
                t0 + 9 * ms)]
        spans = [(P + "step", t0, t0 + 10 * ms, {}),
                 (P + "reap", t0, t0 + 0.1 * ms, {}),
                 (P + "decide", t0 + 0.1 * ms, t0 + 0.3 * ms, {}),
                 (P + "decode", t0 + 0.5 * ms, t0 + 10 * ms,
                  {"kv_positions": kv}),
                 (P + "decode.emit", t0 + 9 * ms, t0 + 9.6 * ms, {})]
        return ops, spans

    (o1, s1), (o2, s2) = one(0.0, 1000), one(0.02, 3000)
    facts = fake_trace(o1 + o2, spans=s1 + s2)
    man = Manifest(REPO_ROOT)
    read = {n: man.module("layer_metrics", n).read for n in NEW}
    assert read["decode_step.attn_ms.batch"](facts) == pytest.approx(2.0)
    assert read["decode_step.moe_ms.batch"](facts) == pytest.approx(3.0)
    assert read["decode_step.cache_io_ms.batch"](facts) == pytest.approx(3.0)
    assert read["engine.emit_host_ms.batch"](facts) == pytest.approx(0.6)
    assert read["engine.decide_host_ms.batch"](facts) == pytest.approx(0.3)
    assert read["device.unscoped_pct.batch"](facts) == pytest.approx(0.0)
    # idle: 1 ms + 1 ms inside each step's spans, 10 ms between the steps
    # and 10 ms after the second, under no span
    assert read["engine.idle_unattributed_pct.batch"](facts) == \
        pytest.approx(100.0 * 20.0 / 24.0)


# -- kernel work --------------------------------------------------------------------

def test_attended_keys_and_attention_work_by_hand():
    assert kw.attended_keys(4) == 1 + 2 + 3 + 4
    assert kw.attended_keys(6, window=3) == 1 + 2 + 3 + 3 + 3 + 3
    assert kw.attended_keys(3, window=8) == 6
    # B=2, H=3, Dh=5, T=4: 4 operations per (query, key) pair and head dim
    assert kw.causal_attention_flops(2, 3, 5, 4) == 4 * 2 * 3 * 5 * 10
    assert kw.causal_attention_flops(2, 3, 5, 4, backward=True) == \
        2 * 4 * 2 * 3 * 5 * 10
    # q, o: 2*4*3*5 elements each; k, v: 2*4*1*5; 2 bytes each
    assert kw.causal_attention_bytes(2, 3, 1, 5, 4, 2) == \
        2 * (2 * 120 + 2 * 40)
    assert kw.causal_attention_bytes(2, 3, 1, 5, 4, 2, backward=True) == \
        2 * (4 * 120 + 4 * 40)


def test_decode_attention_work_by_hand():
    # 7 key positions, 2 KV heads of 4, 3 layers, 2 bytes: K and V
    assert kw.decode_attention_bytes(7, 2, 4, 2, 3) == 2 * 2 * 4 * 2 * 3 * 7
    assert kw.decode_attention_flops(7, 6, 4, 3) == 4 * 6 * 4 * 3 * 7


def test_least_seconds_and_roofline_share():
    assert kw.least_seconds(100.0, 10.0, 50.0, 10.0) == (2.0, "compute")
    assert kw.least_seconds(100.0, 40.0, 50.0, 10.0) == (4.0, "bandwidth")
    assert kw.roofline_pct(100.0, 10.0, 8.0, 50.0, 10.0) == 25.0
    assert kw.roofline_pct(100.0, 10.0, 0.0, 50.0, 10.0) is None


def test_the_cells_work_from_their_configurations():
    man = Manifest(REPO_ROOT)
    train = man.config("mistral-7b-v0.3-train")
    flops, nbytes = kw.train_attention_work(train, backward=False)
    assert flops == 2 * 4 * 2 * 32 * 128 * 4096 * 4097 / 2
    assert nbytes == 2 * 2 * (2 * 2 * 4096 * 32 * 128
                              + 2 * 2 * 4096 * 8 * 128)
    assert kw.train_attention_work(train, backward=True)[0] == 2 * flops
    # compute-bound on a v5e, by a wide margin
    assert kw.least_seconds(flops, nbytes, 197e12, 819e9)[1] == "compute"
    serve = man.config("mixtral-8x7b-v0.1-serve")
    flops, nbytes = kw.decode_attention_work(serve, 1000)
    assert nbytes == 2 * 8 * 128 * 2 * 4 * 1000
    assert kw.least_seconds(flops, nbytes, 197e12, 819e9)[1] == "bandwidth"


# -- the readers, and the manifest's new entries ----------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_nothing_in_empty_facts(name):
    read = Manifest(REPO_ROOT).module("layer_metrics", name).read
    assert read({}) is None
    assert read({"trace": None, "snapshot": {"engine": {}}}) is None


def test_new_entries_list_accepted_cells_and_sources():
    man = Manifest(REPO_ROOT)
    entries = {m["name"]: m for m in man.data["per_layer"]}
    train = ["mistral7b-train-1chip", "mistral7b-train-dp4"]
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == (train if name.endswith((".train", "_ms"))
                                  and "batch" not in name
                                  else ["mixtral8x7b-batch-closed"]), name
        assert m["unit"] == ("%" if "_pct" in name else "ms")
    assert entries["engine.prefill_padding_pct.batch"]["source"] == \
        "program_counter"
    assert entries["engine.emit_host_ms.batch"]["source"] == "program_span"
    assert entries["lm_train_step.forward_ms"]["source"] == "device_trace"
    # new entries were appended: the accepted ones keep their places
    assert [m["name"] for m in man.data["per_layer"]][-16:] == NEW


def test_cell_config_tells_the_train_cells_apart_by_chips():
    name = "kernels.flash_fwd_roofline_pct.train"
    assert pt.cell_config(name, {"chips": 4})["train"]["rows_per_chip"] == 2
    assert pt.cell_config(name, {}) is None          # two cells, no telling
    assert pt.cell_config("kernels.flash_decode_roofline_pct.batch",
                          {})["num_local_experts"] == 8


# -- the real profiler ---------------------------------------------------------------

def test_engine_spans_reach_a_real_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.models.transformer import TransformerLM
    from elephas_tpu.serving import ServingEngine

    model = TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=48)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    eng = ServingEngine(model, params, n_slots=2)
    eng.submit(np.arange(5, dtype=np.int32), 4)
    eng.drain(max_steps=20)                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    rid = eng.submit(np.arange(7, dtype=np.int32), 3)
    actions = [eng.step() for _ in range(3)]
    jax.profiler.stop_trace()
    assert actions == ["prefill", "decode", "decode"]

    loaded = pt.load(trace.find_xplane(str(tmp_path)))
    assert loaded["device_ops"] == {}            # no TPU plane on the CPU
    spans = loaded["spans"]
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[0], []).append(sp)
    assert by_name[P + "submit"][0][3]["request_id"] == rid
    assert by_name[P + "prefill"][0][3] == {"request_id": rid,
                                            "prompt_tokens": 7}
    steps = by_name[P + "step"]
    assert [s[3]["action"] for s in steps] == actions
    assert [s[3]["kv_positions"] for s in by_name[P + "decode"]] == [8, 9]
    # every child lies inside its step, and a leaf's own time is its own
    for name in ("reap", "decide", "decode", "decode.fetch", "decode.emit"):
        for _, s, e, _ in by_name[P + name]:
            assert any(s0 <= s and e <= e0 for _, s0, e0, _ in steps), name
    own = pt.leaf_intervals(spans)
    assert sum(trace.total(iv) for _, iv in own) == pytest.approx(
        trace.total(trace.union((s, e) for _, s, e, _ in spans)))
    # facts of an untraced run, or of a trace with no device: nothing
    assert pt.for_facts({"trace": None}) is None
