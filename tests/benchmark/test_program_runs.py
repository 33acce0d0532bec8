"""``benchmark/program_runs.py``: the join of the engine's ``launch`` numbers
to the executions on the device's ``XLA Modules`` line, and the seven
readers on it, against hand-counted values on made-up traces (what
``program_trace.load`` would give). That a v5e trace has such a line, what
it calls an execution and that the checks hold there is the chip run's to
show (PERF.md, section 6, PR 36): a CPU trace has no device plane, so the
one test with the real profiler here goes as far as the spans' arguments.
"""

import pytest

from benchmark import program_runs as pr
from benchmark import program_trace as pt
from benchmark.manifest import REPO_ROOT, Manifest

MS = 1e-3
P = pt.SPAN_PREFIX + "engine."
DEV = "/device:TPU:0"
SCAN = "jit(_decode_kernel)/layers/while/body/"
KERNEL = SCAN + "attn_core/flash_decode/pallas_call"
CELLS = ["mixtral8x7b-batch-closed", "kexaone236b-reason-closed",
         "axk1-reason-long-closed", "olmohybrid7b-chat-closed"]
NEW = ["decode_step.program_ms.batch", "engine.launch_lag_ms.batch",
       "engine.fetch_lag_ms.batch", "engine.step_gap_ms.batch",
       "decode_step.span_short_ms.batch",
       "prefill.programs_per_request.batch", "prefill.stall_ms.batch"]


class Made:
    """A trace being made up, in ms: ``run`` puts an execution on the
    modules line (and, for a decode program, its operations on the ops
    line: four 1 ms kernels and one matmul for the rest), ``span`` a
    program span."""

    def __init__(self):
        self.runs, self.ops, self.spans = [], [], []

    def run(self, program, s, ms, decode=False):
        self.runs.append((f"jit_{program}(123456789)", s * MS,
                          (s + ms) * MS))
        if decode:
            for i in range(4):
                self.ops.append((KERNEL, (s + i) * MS, (s + i + 1) * MS))
            self.ops.append((SCAN + "ffn/dot_general", (s + 4) * MS,
                             (s + ms) * MS))
        else:
            self.ops.append((f"jit({program})/op", s * MS, (s + ms) * MS))

    def span(self, name, s, e, **args):
        self.spans.append((P + name, s * MS, e * MS, args))

    def loaded(self):
        return {"device_ops": {DEV: list(self.ops)},
                "modules": {DEV: list(self.runs)},
                "spans": sorted(self.spans, key=lambda sp: (sp[1], -sp[2]))}


def decode_step(m, t, launch, run_ms=10.0, finished=0,
                program="_decode_kernel"):
    """One step of today's loop at ``t``: the host waits in ``fetch`` for
    the execution its ``dispatch`` enqueued. Execution from t + 0.6, the
    tokens on the host 1.0 ms after it ends, 0.6 ms of emit."""
    end = t + 0.6 + run_ms
    m.span("step", t - 0.3, end + 1.8)
    m.span("decide", t - 0.2, t - 0.1)
    m.span("decode", t, end + 1.7, k=1, kv_positions=1000)
    m.span("decode.dispatch", t + 0.1, t + 0.4, launch=launch,
           program=program)
    m.run(program, t + 0.6, run_ms, decode=True)
    m.span("decode.fetch", t + 0.4, end + 1.0, launch=launch)
    m.span("decode.emit", end + 1.0, end + 1.6,
           **({"launches": finished} if finished else {}))


def synchronous():
    """Today's loop: three decode steps 13 ms apart (the third 12 ms long,
    and a request ends in it: a park, launch 8, with no span of its own),
    an admission, two more decode steps. Before the first whole step the
    tail of one whose dispatch span began before the profiler did; after
    the last a dispatch whose execution the profiler cut."""
    m = Made()
    m.run("_decode_kernel", -12.4, 10.0, decode=True)          # launch 4
    m.span("decode.fetch", -12.0, -1.4, launch=4)
    for i, t in enumerate((0.0, 13.0, 26.0)):
        decode_step(m, t, 5 + i, run_ms=12.0 if i == 2 else 10.0,
                    finished=1 if i == 2 else 0)
    m.run("_scatter_row", 40.0, 0.006)                          # the park
    # the admission: insert 4 ms, two eager programs, select, set_row
    m.span("step", 41.7, 48.5, launches=0)
    m.span("prefill", 42.0, 48.3, request_id="req-9", prompt_tokens=100)
    m.span("prefill.insert", 42.1, 43.0, launch=9, program="_insert_kernel")
    m.run("_insert_kernel", 42.5, 4.0)
    m.run("convert_element_type", 46.6, 0.1)
    m.run("_threefry_seed", 46.8, 0.1)
    m.span("prefill.select_first", 43.0, 48.0, launch=10,
           program="_select_first")
    m.run("_select_first", 47.0, 0.5)
    m.span("prefill.set_row", 48.0, 48.2, launch=11, program="_scatter_row")
    m.run("_scatter_row", 48.5, 0.1)
    for i, t in enumerate((50.0, 63.0)):
        decode_step(m, t, 12 + i)
    m.span("decode.dispatch", 76.1, 76.4, launch=14,
           program="_decode_kernel")
    return m


def queued():
    """A loop that keeps one step queued: step ``k`` enqueues launch ``k``
    and then fetches launch ``k - 1``, so execution ``k - 1`` runs under
    the spans of launch ``k``. Executions back to back, the ``k``-th
    10 + k ms long; the span of launch ``k`` ends 1.0 ms after execution
    ``k`` starts, and the next span starts there."""
    m, at = Made(), 0.0
    starts = []
    for k in range(6):
        starts.append(at)
        m.run("_decode_kernel", at, 10.0 + k, decode=True)
        at += 10.0 + k
    for k in range(1, 6):
        s = starts[k - 1] + 1.0             # while execution k - 1 runs
        m.span("decode", s, starts[k] + 1.0, k=1, kv_positions=1000)
        m.span("decode.dispatch", s + 0.1, s + 0.4, launch=20 + k,
               program="_decode_kernel")
        if k > 1:                           # the one before: done at
            m.span("decode.fetch", s + 0.4, starts[k] + 0.3,   # starts[k]
                   launch=20 + k - 1)
        m.span("decode.emit", starts[k] + 0.3, starts[k] + 0.9)
    return m


@pytest.fixture
def install(monkeypatch):
    """``facts`` of a traced run whose trace is the made-up one."""
    def go(loaded):
        monkeypatch.setattr(pt, "newest", lambda: "made-up")
        monkeypatch.setattr(pt, "load", lambda path: loaded)
        pr._joined.cache_clear()
        pt._tables.cache_clear()
        return {"trace": {"lo": 0.0, "hi": 1.0}, "snapshot": {"work": {}}}
    yield go
    pr._joined.cache_clear()
    pt._tables.cache_clear()


def read(name, facts):
    return Manifest(REPO_ROOT).module("layer_metrics", name).read(facts)


# -- the join ------------------------------------------------------------

def test_join_pairs_by_launch_and_leaves_out_what_the_profiler_cut():
    loaded = synchronous().loaded()
    j = pr.join(loaded)
    # launch 4 has no dispatch span, launch 8 is a park without a span,
    # launch 14 has no execution
    assert [p.launch for p in j.pairs] == [5, 6, 7, 9, 10, 11, 12, 13]
    assert [p.program for p in j.pairs] == (
        ["_decode_kernel"] * 3 + ["_insert_kernel", "_select_first",
                                  "_scatter_row"] + ["_decode_kernel"] * 2)
    at = [round(j.runs[p.run][1] / MS, 3) for p in j.pairs]
    assert at == [0.6, 13.6, 26.6, 42.5, 47.0, 48.5, 50.6, 63.6]
    # set_row's execution is the second scatter on the line, not the park
    assert j.runs[j.pairs[5].run - 5][0].startswith("jit__scatter_row(")
    assert [p.fetch[3]["launch"] for p in pr.decode_pairs(j)] == [
        5, 6, 7, 12, 13]
    assert all(p.fetch is None for p in j.pairs[3:6])


@pytest.mark.parametrize("name,want", [
    # five joined decode executions: 10, 10, 12, 10, 10 ms
    ("decode_step.program_ms.batch", 10.0),
    # 0.5 ms from each dispatch span's start (the execution before ended
    # earlier every time)
    ("engine.launch_lag_ms.batch", 0.5),
    ("engine.fetch_lag_ms.batch", 1.0),
    # decode to decode with nothing between: 5 -> 6, 6 -> 7 and 12 -> 13,
    # 3.0 ms each; 7 -> 12 has the park and the admission between
    ("engine.step_gap_ms.batch", 3.0),
    # the third span holds the park's 0.006 ms as well, the others nothing
    # but their execution: the median of five is 0
    ("decode_step.span_short_ms.batch", 0.0),
    # insert, convert_element_type, _threefry_seed, select_first, set_row
    ("prefill.programs_per_request.batch", 5.0),
    # 42.0 to 48.6, of which 4.0 + 0.1 + 0.1 + 0.5 + 0.1 busy
    ("prefill.stall_ms.batch", 6.6 - 4.8),
])
def test_readers_against_hand_counts(install, name, want):
    facts = install(synchronous().loaded())
    assert read(name, facts) == pytest.approx(want, abs=1e-9)


def test_a_span_that_holds_another_programs_operation_reads_its_ms():
    j = pr.join(synchronous().loaded())
    short = pr.span_short_ms(j)
    # launch 7's decode span is open while the park it caused runs
    assert short == pytest.approx([0.0, 0.0, -0.006, 0.0, 0.0], abs=1e-9)
    counts = pr.kernel_counts(j)
    assert [c[0] for c in counts] == [5, 6, 7, 12, 13]
    assert all(own == seen == {"flash_decode": 4} for _, own, seen in counts)


def test_a_missing_execution_reads_as_nothing(install, capsys):
    loaded = synchronous().loaded()
    # launch 6's execution is not on the line: launch 6 would pair with
    # launch 7's, which ends after launch 6's fetch
    loaded["modules"][DEV] = [r for r in loaded["modules"][DEV]
                              if round(r[1] / MS, 3) != 13.6]
    assert pr.join(loaded) is None
    assert "launch 6 (_decode_kernel): its execution ends" in \
        capsys.readouterr().err
    facts = install(loaded)
    assert all(read(n, facts) is None for n in NEW)


def test_counts_that_disagree_read_as_nothing(capsys):
    m = synchronous()
    m.run("_decode_kernel", 41.0, 0.5, decode=True)   # one nobody launched
    assert pr.join(m.loaded()) is None
    assert "_decode_kernel: 6 executions between launch 5 and 13, for 5 " \
        "spans and at most 0 calls without one" in capsys.readouterr().err


def second_admission(m):
    """After ``synchronous()``'s last whole step: a request ends in it (a
    park, launch 14, in place of the cut dispatch) and another is admitted
    (launches 15 to 17)."""
    m.spans.pop()
    m.run("_scatter_row", 76.0, 0.006)
    m.span("prefill", 77.0, 83.3, request_id="req-10", prompt_tokens=50)
    m.span("prefill.insert", 77.1, 78.0, launch=15, program="_insert_kernel")
    m.run("_insert_kernel", 77.5, 4.0)
    m.span("prefill.select_first", 78.0, 83.0, launch=16,
           program="_select_first")
    m.run("_select_first", 82.0, 0.5)
    m.span("prefill.set_row", 83.0, 83.2, launch=17, program="_scatter_row")
    m.run("_scatter_row", 83.5, 0.1)
    return m


def test_a_park_between_two_admissions_is_within_the_count(capsys):
    # three row updates from launch 11 to 17, two of them with a span: the
    # launch numbers (14 is nobody's span) have room for the third
    j = pr.join(second_admission(synchronous()).loaded())
    assert [p.launch for p in j.pairs][-3:] == [15, 16, 17]
    assert pr.programs_per_request(j) == [5, 3]
    # ...and for no more than that
    m = second_admission(synchronous())
    m.run("_scatter_row", 76.05, 0.006)
    assert pr.join(m.loaded()) is None
    assert "_scatter_row: 4 executions between launch 11 and 17, for 2 " \
        "spans and at most 1 calls without one" in capsys.readouterr().err


def test_an_execution_before_its_dispatch_reads_as_nothing(capsys):
    loaded = synchronous().loaded()
    # launch 12's dispatch span is said to start after its execution did
    loaded["spans"] = [
        (n, (s + 5 * MS if a.get("launch") == 12 and "program" in a else s),
         e + (5 * MS if a.get("launch") == 12 and "program" in a else 0), a)
        for n, s, e, a in loaded["spans"]]
    assert pr.join(loaded) is None
    assert "launch 12 (_decode_kernel): its execution starts 4.500 ms " \
        "before its span" in capsys.readouterr().err


def test_two_clocks_may_disagree_by_less_than_a_step(install):
    loaded = synchronous().loaded()
    # the device's clock 0.63 ms ahead of the host's: every execution reads
    # 0.03 ms before its dispatch span, as one did on the chip
    loaded["modules"][DEV] = [(n, s - 0.63 * MS, e - 0.63 * MS)
                              for n, s, e in loaded["modules"][DEV]]
    loaded["device_ops"][DEV] = [(n, s - 0.63 * MS, e - 0.63 * MS)
                                 for n, s, e in loaded["device_ops"][DEV]]
    facts = install(loaded)
    # reported as it is, not clipped; what the device alone says stays
    assert read("engine.launch_lag_ms.batch", facts) == pytest.approx(-0.13)
    assert read("engine.fetch_lag_ms.batch", facts) == pytest.approx(1.63)
    assert read("engine.step_gap_ms.batch", facts) == pytest.approx(3.0)
    assert read("decode_step.program_ms.batch", facts) == pytest.approx(10.0)


def test_a_trace_whose_clocks_are_a_kernel_apart_reads_one_kernel_short(
        install):
    """What a run that reads a layer low looks like: the device's clock
    0.8 ms ahead, so each execution's first kernel (1 ms) starts 0.2 ms
    before its ``elephas.engine.decode`` span by the trace's clocks."""
    loaded = synchronous().loaded()
    for key in ("modules", "device_ops"):
        loaded[key][DEV] = [(n, s - 0.8 * MS, e - 0.8 * MS)
                            for n, s, e in loaded[key][DEV]]
    facts = install(loaded)
    assert read("decode_step.span_short_ms.batch", facts) == \
        pytest.approx(1.0)
    assert read("engine.launch_lag_ms.batch", facts) == pytest.approx(-0.3)
    assert read("engine.fetch_lag_ms.batch", facts) == pytest.approx(1.8)
    assert read("decode_step.program_ms.batch", facts) == pytest.approx(10.0)
    j = pr.for_facts(facts)
    assert all(own["flash_decode"] == 4 and seen["flash_decode"] == 3
               for _, own, seen in pr.kernel_counts(j))
    # the span-window readers give each step three kernels of four
    by_span = [km["flash_decode"]
               for _, _, km, _ in pt.tables(facts, "decode")]
    assert by_span == pytest.approx([3.0] * 5)


def test_an_execution_lost_in_the_middle_is_not_the_profilers_cut(capsys):
    loaded = synchronous().loaded()
    loaded["modules"][DEV] = [r for r in loaded["modules"][DEV]
                              if "_insert_kernel" not in r[0]]
    assert pr.join(loaded) is None
    assert "launch 9 (_insert_kernel) has no execution" in \
        capsys.readouterr().err


# -- one step kept queued --------------------------------------------------

def test_a_queued_loop_joins_to_the_same_pairs(install):
    loaded = queued().loaded()
    j = pr.join(loaded)
    # launch 20 + k is execution k, though it runs under launch 21 + k's
    # spans: the pairs of the synchronous loop
    assert [(p.launch, p.run) for p in j.pairs] == [
        (21, 1), (22, 2), (23, 3), (24, 4), (25, 5)]
    assert pr.program_ms(j) == pytest.approx([11, 12, 13, 14, 15])
    assert pr.step_gap_ms(j) == pytest.approx([0.0] * 4, abs=1e-9)
    assert pr.launch_lag_ms(j) == pytest.approx([0.0] * 5, abs=1e-9)
    assert pr.fetch_lag_ms(j) == pytest.approx([0.3] * 4)     # 21 .. 24
    # the span of launch 20 + k holds what starts in it: execution k - 1
    # but for its first kernel (9 + k - 1 ms), and execution k's first
    # kernel (1 ms), not execution k's 10 + k ms
    facts = install(loaded)
    by_span = [sum(by.values()) for _, by, _, _ in pt.tables(facts, "decode")]
    assert by_span == pytest.approx([10, 11, 12, 13, 14])
    # ...so every span reads one kernel's ms short: the planted miss
    assert pr.span_short_ms(j) == pytest.approx([1.0] * 5)
    assert read("decode_step.span_short_ms.batch", facts) == \
        pytest.approx(1.0)
    assert read("decode_step.program_ms.batch", facts) == pytest.approx(13.0)
    # and holds four kernels, as its execution does: three of them another's
    assert all(own == seen == {"flash_decode": 4}
               for _, own, seen in pr.kernel_counts(j))


# -- nothing to read ---------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_the_parents_trace_reads_as_nothing(install, name):
    loaded = synchronous().loaded()
    loaded["spans"] = [
        (n, s, e, {k: v for k, v in a.items()
                   if k not in ("launch", "program", "launches")})
        for n, s, e, a in loaded["spans"]]
    assert read(name, install(loaded)) is None


@pytest.mark.parametrize("name", NEW)
def test_an_untraced_run_reads_as_nothing(name):
    assert read(name, {}) is None
    assert read(name, {"trace": None, "snapshot": {"work": {}}}) is None


def test_a_trace_without_a_device_reads_as_nothing(install):
    facts = install({"device_ops": {}, "modules": {}, "spans":
                     synchronous().loaded()["spans"]})
    assert all(read(n, facts) is None for n in NEW)


# -- names, the manifest, the command -------------------------------------

@pytest.mark.parametrize("module,program", [
    ("jit__decode_kernel(2924632646694356351)", "_decode_kernel"),
    ("jit__fused_decode_kernel(1)", "_fused_decode_kernel"),
    ("jit_convert_element_type(15388027131515875373)",
     "convert_element_type"),
    ("jit__threefry_seed(5694549794985933706)", "_threefry_seed"),
])
def test_program_of_a_v5e_module_name(module, program):
    # the names are the probe's on the chip (PERF.md, section 3)
    assert pr.program_of(module) == program


def test_the_fused_program_is_not_taken_for_the_single_step():
    m = Made()
    decode_step(m, 0.0, 1, program="_fused_decode_kernel")
    decode_step(m, 13.0, 2)
    decode_step(m, 26.0, 3, program="_fused_decode_kernel")
    j = pr.join(m.loaded())
    assert [(p.launch, p.program, p.run) for p in j.pairs] == [
        (1, "_fused_decode_kernel", 0), (2, "_decode_kernel", 1),
        (3, "_fused_decode_kernel", 2)]
    assert pr.step_gap_ms(j) == pytest.approx([3.0, 3.0])


def test_the_entries_are_appended_for_the_four_serving_cells():
    man = Manifest(REPO_ROOT)
    entries = man.data["per_layer"]
    assert [m["name"] for m in entries[-7:]] == NEW
    for m in entries[-7:]:
        assert m["workloads"] == CELLS
        assert (m["moves"], m["better"]) == ("serve_tokens_per_s", "lower")
        assert m["source"] == ("device_trace" if m["name"] == NEW[0]
                               else "program_span")
        assert m["layer"] == ("model step, serving" if m["name"].startswith(
            "decode_step.") else "serving engine")
        assert m["unit"] == ("programs" if "programs" in m["name"] else "ms")
    known = {m["source"] for m in entries[:-7]}
    assert {m["source"] for m in entries[-7:]} <= known
    for cell in CELLS:
        assert set(NEW) <= {m["name"] for m in
                            man.metrics_for(cell, "per_layer")}
    for cell in ("mistral7b-train-1chip", "mistral7b-train-dp4"):
        assert not set(NEW) & {m["name"] for m in
                               man.metrics_for(cell, "per_layer")}


def test_the_command_prints_the_pairs(monkeypatch, capsys):
    loaded = synchronous().loaded()
    monkeypatch.setattr(pr.trace, "find_xplane", lambda d: "made-up")
    monkeypatch.setattr(pt, "load", lambda path: loaded)
    assert pr.main(["somewhere"]) == 0
    out = capsys.readouterr().out
    assert "8 launches joined to 12 executions" in out
    assert "program 10.000 | step gap 3.000 = launch lag 0.500 + fetch " \
        "lag 1.000 + emit 0.600 + decide 0.100" in out
    assert "flash_decode               4 |   4: 5" in out
    assert "an admission: 5.0 executions, 1.800 ms of device idle" in out
    # five operations an execution, each with its path
    assert out.rstrip().endswith("5      0: 5")
    bare = {**loaded, "spans": [(n, s, e, {}) for n, s, e, _ in
                                loaded["spans"]]}
    monkeypatch.setattr(pt, "load", lambda path: bare)
    assert pr.main(["somewhere"]) == 1


# -- the real profiler ------------------------------------------------------

def test_launch_and_program_reach_a_real_profiler_trace(tmp_path):
    """As far as a CPU goes: the spans' new arguments come back from a
    ``jax.profiler`` trace with their types, in launch order, and with no
    device plane the join gives nothing. The modules line is the chip's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace
    from elephas_tpu.models.transformer import TransformerLM
    from elephas_tpu.serving import ServingEngine

    model = TransformerLM(vocab=17, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=48)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    eng = ServingEngine(model, params, n_slots=2)
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.drain(max_steps=20)                      # compile outside the trace
    before = eng.snapshot()["work"]["programs_launched"]
    assert before == 5                  # insert, select, set_row, decode, park
    jax.profiler.start_trace(str(tmp_path))
    eng.submit(np.arange(7, dtype=np.int32), 3)
    actions = [eng.step() for _ in range(3)]
    jax.profiler.stop_trace()
    assert actions == ["prefill", "decode", "decode"]

    loaded = pt.load(trace.find_xplane(str(tmp_path)))
    named = [(sp[0][len(P):], sp[3]["launch"], sp[3]["program"])
             for sp in loaded["spans"] if "program" in sp[3]]
    assert named == [("prefill.insert", 6, "_insert_kernel"),
                     ("prefill.select_first", 7, "_select_first"),
                     ("prefill.set_row", 8, "_scatter_row"),
                     ("decode.dispatch", 9, "_decode_kernel"),
                     ("decode.dispatch", 10, "_decode_kernel")]
    assert [sp[3] for sp in pt.named(loaded["spans"], pr.FETCH)] == [
        {"launch": 9}, {"launch": 10}]
    emits = pt.named(loaded["spans"], P + "decode.emit")
    assert [sp[3] for sp in emits] == [{}, {"launches": 1}]
    assert eng.snapshot()["work"]["programs_launched"] == 11
    assert loaded["modules"] == {} and pr.join(loaded) is None
