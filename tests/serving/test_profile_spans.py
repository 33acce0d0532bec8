"""The spans ``ServingEngine`` opens while a profile runs (names, nesting,
arguments, none per row or per token, the ``launch`` and ``program`` of
every compiled program the engine calls) and the ``work`` counters, against
hand-counted values. ``TraceAnnotation`` is patched to a recorder; that the
real one reaches a ``jax.profiler`` trace is
``tests/benchmark/test_program_trace.py``'s to show."""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from elephas_tpu.models.transformer import TransformerLM
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving import cache as cache_mod
from elephas_tpu.serving import engine as engine_mod
from elephas_tpu.serving.cache import program_name
from elephas_tpu.serving.engine import ModelDrafter

pytestmark = pytest.mark.serving

V = 17
P = "elephas.engine."
DECODE = [(0, P + "step"), (1, P + "reap"), (1, P + "decide"),
          (1, P + "decode"), (2, P + "decode.dispatch"),
          (2, P + "decode.fetch"), (2, P + "decode.emit")]
PREFILL = [(0, P + "step"), (1, P + "reap"), (1, P + "decide"),
           (1, P + "prefill"), (2, P + "prefill.insert"),
           (2, P + "prefill.select_first"), (2, P + "prefill.set_row"),
           (2, P + "prefill.fetch")]


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span with its
    depth and arguments, in the order they opened."""

    log, depth = [], 0

    def __init__(self, name, **kwargs):
        self.row = {"name": name, "args": dict(kwargs), "depth": None}

    def __enter__(self):
        self.row["depth"] = Recorder.depth
        Recorder.depth += 1
        Recorder.log.append(self.row)
        return self

    def __exit__(self, *exc):
        Recorder.depth -= 1
        return False

    def set_metadata(self, **kwargs):
        self.row["args"].update(kwargs)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture
def spans(monkeypatch):
    Recorder.log, Recorder.depth = [], 0
    monkeypatch.setattr(engine_mod, "_span", Recorder)
    return Recorder.log


def take(spans):
    """The spans recorded since the last call, as (depth, name)."""
    out = [(r["depth"], r["name"]) for r in spans]
    rows = list(spans)
    del spans[:]
    return out, rows


def _engine(**kw):
    model = TransformerLM(vocab=V, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=48)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    return ServingEngine(model, params, clock=FakeClock(), **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, V, size=(n,)).astype(
        np.int32)


def test_submit_prefill_and_decode_span_trees(spans):
    eng = _engine(n_slots=4)
    rid = eng.submit(_prompt(5), 4)
    tree, rows = take(spans)
    assert tree == [(0, P + "submit")]
    assert rows[0]["args"] == {"request_id": rid}

    assert eng.step() == "prefill"
    tree, rows = take(spans)
    assert tree == PREFILL
    assert rows[0]["args"] == {"step": 1, "action": "prefill"}
    assert rows[3]["args"] == {"request_id": rid, "prompt_tokens": 5}

    assert eng.step() == "decode"
    tree, rows = take(spans)
    assert tree == DECODE
    assert rows[0]["args"] == {"step": 2, "action": "decode"}
    # one live row whose carry token sits at position 5: keys 0..5
    # ...in the one block of each of the two layers' 48-row caches, copied
    # up to its live rows in granules of 16
    assert rows[3]["args"] == {"n_active": 1, "k": 1, "kv_positions": 6,
                               "kv_blocks_live": 2, "kv_blocks_walked": 2,
                               "kv_positions_copied": 2 * 16}


def test_no_span_per_row_or_per_token(spans):
    eng = _engine(n_slots=4)
    for i, n in enumerate((3, 5, 7)):
        eng.submit(_prompt(n, i), 6)
    for _ in range(3):
        assert eng.step() == "prefill"
    take(spans)
    for _ in range(4):
        assert eng.step() == "decode"
        tree, rows = take(spans)
        assert tree == DECODE                 # three rows, seven spans
        assert rows[3]["args"]["n_active"] == 3


def test_a_rejected_submit_closes_its_span(spans):
    eng = _engine(n_slots=1)
    with pytest.raises(Exception):
        eng.submit(_prompt(3), 0)
    tree, _ = take(spans)
    assert tree == [(0, P + "submit")] and Recorder.depth == 0


def test_idle_step_has_no_action_span(spans):
    eng = _engine(n_slots=1)
    assert eng.step() == "idle"
    tree, rows = take(spans)
    assert tree == DECODE[:3]
    assert rows[0]["args"]["action"] == "idle"


def test_chunked_prefill_spans(spans):
    eng = _engine(n_slots=2, prefill_chunk=8)
    rid = eng.submit(_prompt(20), 2)
    take(spans)
    assert eng.step() == "prefill"            # opens the chunk train
    tree, rows = take(spans)
    assert tree == PREFILL[:4] + [(2, P + "prefill_chunk"),
                                  (3, P + "prefill.insert")]
    assert rows[4]["args"] == {"request_id": rid, "pos0": 0,
                               "chunk_tokens": 8}
    assert eng.step() == "prefill_chunk"
    tree, rows = take(spans)
    assert tree == PREFILL[:3] + [(1, P + "prefill_chunk"),
                                  (2, P + "prefill.insert")]
    assert rows[3]["args"]["pos0"] == 8
    assert eng.step() == "prefill_chunk"      # the last chunk goes live
    tree, _ = take(spans)
    assert tree[-3:] == [(1, P + "prefill.select_first"),
                         (1, P + "prefill.set_row"),
                         (1, P + "prefill.fetch")]


def test_speculative_round_uses_the_decode_spans(spans):
    eng = _engine(n_slots=2, speculate_k=3)
    eng.submit(_prompt(6), 8)
    assert eng.step() == "prefill"
    take(spans)
    assert eng.step() == "decode"
    tree, rows = take(spans)
    assert tree == DECODE
    # carry + 2 drafts: queries at positions 6, 7, 8 see 7 + 8 + 9 keys
    assert rows[3]["args"] == {"n_active": 1, "k": 3, "kv_positions": 24,
                               "speculative": 1}
    work = eng.snapshot()["work"]
    assert work["decode_kv_positions"] == 24
    # a verify chunk is not the decode kernel's: no blocks on the span
    # (above) or in the counters
    assert work["decode_kv_blocks_live"] == 0 == work["decode_kv_blocks_walked"]


def test_work_counters_against_hand_counts():
    eng = _engine(n_slots=4)
    eng.submit(_prompt(5), 3)                 # bucket 8
    eng.submit(_prompt(11, 1), 2)             # bucket 16
    assert eng.snapshot()["work"] == {
        "programs_launched": 0, "decode_kv_positions": 0,
        "decode_kv_blocks_live": 0, "decode_kv_blocks_walked": 0,
        "decode_kv_positions_copied": 0,
        "prefill_tokens": 0, "prefill_padded_tokens": 0}
    assert [eng.step() for _ in range(2)] == ["prefill", "prefill"]
    work = eng.snapshot()["work"]
    assert (work["prefill_tokens"], work["prefill_padded_tokens"]) == (16, 24)
    eng.drain(max_steps=50)
    # step 1: rows at positions 5 and 11 attend 6 + 12 keys (and the
    # second request is done); step 2: the first row alone, 7 keys
    work = eng.snapshot()["work"]
    assert work["decode_kv_positions"] == 6 + 12 + 7
    # one block a row and layer: (2 rows + 1 row) x 2 layers, each copied
    # up to its live rows (6, 12, then 7) in granules of 16
    assert work["decode_kv_blocks_live"] == 6 == work["decode_kv_blocks_walked"]
    assert work["decode_kv_positions_copied"] == 6 * 16
    assert eng.snapshot()["engine"]["decode_steps"] == 2


def test_fused_block_counts_every_fused_step():
    eng = _engine(n_slots=2, fuse_k=4)
    eng.submit(_prompt(4), 6)
    assert eng.step() == "prefill"
    assert eng.step() == "decode"             # one program, four steps
    snap = eng.snapshot()
    assert snap["fastpath"]["fused_steps"] == 4
    # queries at positions 4..7 see 5 + 6 + 7 + 8 keys
    assert snap["work"]["decode_kv_positions"] == 26
    assert snap["work"]["decode_kv_blocks_live"] == 4 * 2   # steps x layers
    assert snap["work"]["decode_kv_blocks_walked"] == 4 * 2


def test_block_counters_follow_each_rows_position(spans):
    """A cache of several 256-row blocks: a row attends (and the kernel
    visits) the blocks up to its position's, in every layer."""
    model = TransformerLM(vocab=V, d_model=16, n_heads=4, n_layers=2,
                          d_ff=32, max_len=600)
    params = {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}
    eng = ServingEngine(model, params, clock=FakeClock(), n_slots=4)
    for i, n in enumerate((5, 255, 256, 520)):
        eng.submit(_prompt(n, i), 3)
    while eng.step() != "decode":
        pass
    args = [r["args"] for r in spans if r["name"] == P + "decode"][-1]
    # rows at positions 5, 255, 256 and 520 of a 768-row cache
    assert args["kv_blocks_live"] == (1 + 1 + 2 + 3) * 2
    assert args["kv_blocks_walked"] == args["kv_blocks_live"]
    assert eng.snapshot()["work"]["decode_kv_blocks_walked"] == 14
    # every block but a row's last whole, the last up to the granule of 16
    # that holds its position: 16, 256, 256 + 16, 512 + 16 a layer
    copied = (16 + 256 + 272 + 528) * 2
    assert args["kv_positions_copied"] == copied
    assert eng.snapshot()["work"]["decode_kv_positions_copied"] == copied


# -- launch numbers ------------------------------------------------------

OWN = ("_decode_kernel", "_fused_decode_kernel", "_verify_kernel",
       "_select_first", "_scatter_row", "_draft_propose_kernel",
       "_draft_insert_kernel")


@pytest.fixture
def calls(monkeypatch, spans):
    """Every call of a compiled program of the engine's own, by name and in
    order, counted where the program is called and not where the engine
    counts: the wrappers stand where the jitted functions stood."""
    seen = []

    def counted(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            seen.append(fn.__name__)
            return fn(*args, **kwargs)
        return wrapper

    for name in OWN:
        monkeypatch.setattr(engine_mod, name,
                            counted(getattr(engine_mod, name)))
    monkeypatch.setattr(cache_mod, "_insert_kernel",
                        counted(cache_mod._insert_kernel))
    return seen


def _drafter():
    model = TransformerLM(vocab=V, d_model=16, n_heads=4, n_layers=1,
                          d_ff=32, max_len=48)
    return ModelDrafter(model, {k: jnp.asarray(v)
                                for k, v in model.init(seed=2).items()})


# (engine arguments, (prompt length, max_new) per request, the programs the
# spans of the first steps must name, bare calls included as None)
CASES = {
    "prefill": ({}, [(5, 3)],
                ["_insert_kernel", "_select_first", "_scatter_row"]),
    "decode": ({}, [(5, 3)],
               ["_insert_kernel", "_select_first", "_scatter_row",
                "_decode_kernel", "_decode_kernel"]),
    "fused_block": ({"fuse_k": 4}, [(4, 9)],
                    ["_insert_kernel", "_select_first", "_scatter_row",
                     "_fused_decode_kernel", "_fused_decode_kernel"]),
    "speculative_round": ({"speculate_k": 3}, [(6, 8)],
                          ["_insert_kernel", "_select_first", "_scatter_row",
                           "_verify_kernel"]),
    # the draft model's insert and rollout have no span of their own
    "draft_model": ({"speculate_k": 3, "drafter": _drafter}, [(6, 8)],
                    ["_insert_kernel", "_select_first", None, "_scatter_row",
                     None, "_verify_kernel"]),
    # a chunk that is not the last parks the row at its write head: bare
    "chunked_prefill": ({"prefill_chunk": 8}, [(20, 2)],
                        ["_insert_kernel", None, "_insert_kernel", None,
                         "_insert_kernel", "_select_first", "_scatter_row"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_launch_rises_by_one_for_each_enqueued_program(case, spans, calls):
    kw, requests, first = CASES[case]
    kw = {k: v() if callable(v) else v for k, v in kw.items()}
    eng = _engine(n_slots=2, **kw)
    for i, (n, max_new) in enumerate(requests):
        eng.submit(_prompt(n, i), max_new)
    eng.drain(max_steps=60)
    named = [r["args"] for r in spans if "program" in r["args"]]
    # every span that wraps a call says which call it was, by the count
    # the WRAPPERS kept: the launch-th program called was that one
    assert named and all(calls[a["launch"] - 1] == a["program"]
                         for a in named)
    assert [a["launch"] for a in named] == sorted({a["launch"]
                                                   for a in named})
    by_launch = {a["launch"]: a["program"] for a in named}
    assert [by_launch.get(i + 1) for i in range(len(first))] == first
    # ...and the calls that no span of their own carries are shown on the
    # spans they happened in, the step's among them: together, every call
    # the engine made
    bare = sum(r["args"].get("launches", 0) for r in spans
               if r["name"] == P + "step")
    assert len(named) + bare == len(calls)
    inner = sum(r["args"].get("launches", 0) for r in spans
                if r["name"] in (P + "prefill", P + "decode.emit",
                                 P + "decode.dispatch"))
    # (all but the park between two chunks of a train, the step's alone)
    assert bare - inner == (1 if case == "chunked_prefill" else 0)
    assert eng.snapshot()["work"]["programs_launched"] == len(calls)


def test_program_name_reads_through_partials():
    inner = functools.partial(engine_mod._fused_decode_kernel, "model")
    assert program_name(functools.partial(inner, n_steps=4)) == \
        "_fused_decode_kernel"
    assert program_name(engine_mod._scatter_row) == "_scatter_row"
    eng = _engine(n_slots=1, paged=True)
    assert program_name(eng._decode_fn) == "decode_fn"
    assert program_name(eng.kv.insert_program()) == "_paged_insert_kernel"
    assert program_name(_engine(n_slots=1).kv.insert_program()) == \
        "_insert_kernel"


def test_fetch_carries_its_dispatchs_launch(spans):
    eng = _engine(n_slots=2, fuse_k=2)
    eng.submit(_prompt(5), 7)
    eng.drain(max_steps=30)
    rows = [r for r in spans if r["name"] in (P + "decode.dispatch",
                                              P + "decode.fetch")]
    assert len(rows) >= 6 and len(rows) % 2 == 0
    for dispatch, fetch in zip(rows[::2], rows[1::2]):
        assert dispatch["name"] == P + "decode.dispatch"
        assert fetch["args"] == {"launch": dispatch["args"]["launch"]}
        assert set(dispatch["args"]) == {"launch", "program"}


def test_prefill_fetch_carries_its_selections_launch(spans):
    eng = _engine(n_slots=2)
    eng.submit(_prompt(5), 3)
    take(spans)
    assert eng.step() == "prefill"
    _, rows = take(spans)
    select, set_row, fetch = rows[-3:]
    # the read comes after the row write, and waits for the selection
    assert fetch["name"] == P + "prefill.fetch"
    assert fetch["args"] == {"launch": select["args"]["launch"]}
    assert set_row["args"]["launch"] == select["args"]["launch"] + 1


def test_emit_launches_are_the_requests_that_finished_in_it(spans):
    eng = _engine(n_slots=4)
    for i, max_new in enumerate((3, 3, 5)):
        eng.submit(_prompt(4 + i, i), max_new)
    done = []
    while len(done) < 3:
        before = set(eng._finished)
        action = eng.step()
        rows = list(spans)
        del spans[:]
        if action != "decode":
            continue
        emit = [r for r in rows if r["name"] == P + "decode.emit"][0]
        finished = set(eng._finished) - before
        done.extend(finished)
        # a park a finished request, and the key is not there without one
        assert emit["args"] == ({"launches": len(finished)} if finished
                                else {})
    # two finished together in one step, one alone
    assert len(done) == 3


def test_programs_launched_is_the_last_launch(spans):
    eng = _engine(n_slots=2)
    eng.submit(_prompt(5), 4)
    seen = 0
    for _ in range(3):                 # prefill, decode, decode: none ends
        eng.step()
        seen = max([seen] + [r["args"]["launch"] for r in spans
                             if "launch" in r["args"]])
        assert eng.snapshot()["work"]["programs_launched"] == seen
    assert seen == 5


def test_a_first_token_that_ends_the_request_shows_on_prefill(spans):
    eng = _engine(n_slots=1)
    rid = eng.submit(_prompt(5), 1)             # done at its first token
    take(spans)
    assert eng.step() == "prefill"
    tree, rows = take(spans)
    assert tree == PREFILL                      # and still no span for it
    assert rows[3]["args"] == {"request_id": rid, "prompt_tokens": 5,
                               "launches": 1}
    assert eng.snapshot()["work"]["programs_launched"] == 4
