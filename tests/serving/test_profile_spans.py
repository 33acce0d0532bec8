"""The spans ``ServingEngine`` opens while a profile runs (names, nesting,
arguments, none per row or per token) and the ``work`` counters, against
hand-counted values. ``TraceAnnotation`` is patched to a recorder; that the
real one reaches a ``jax.profiler`` trace is
``tests/benchmark/test_program_trace.py``'s to show."""

import numpy as np
import pytest

import jax.numpy as jnp

from elephas_tpu.models.transformer import TransformerLM
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving import engine as engine_mod

pytestmark = pytest.mark.serving

V = 17
P = "elephas.engine."
DECODE = [(0, P + "step"), (1, P + "reap"), (1, P + "decide"),
          (1, P + "decode"), (2, P + "decode.dispatch"),
          (2, P + "decode.fetch"), (2, P + "decode.emit")]
PREFILL = [(0, P + "step"), (1, P + "reap"), (1, P + "decide"),
           (1, P + "prefill"), (2, P + "prefill.insert"),
           (2, P + "prefill.select_first"), (2, P + "prefill.set_row")]


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
    # ...in the one block of each of the two layers' 48-row caches
    assert rows[3]["args"] == {"n_active": 1, "k": 1, "kv_positions": 6,
                               "kv_blocks_live": 2, "kv_blocks_walked": 2}


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
    assert tree[-2:] == [(1, P + "prefill.select_first"),
                         (1, P + "prefill.set_row")]


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
        "decode_kv_positions": 0, "decode_kv_blocks_live": 0,
        "decode_kv_blocks_walked": 0, "prefill_tokens": 0,
        "prefill_padded_tokens": 0}
    assert [eng.step() for _ in range(2)] == ["prefill", "prefill"]
    work = eng.snapshot()["work"]
    assert (work["prefill_tokens"], work["prefill_padded_tokens"]) == (16, 24)
    eng.drain(max_steps=50)
    # step 1: rows at positions 5 and 11 attend 6 + 12 keys (and the
    # second request is done); step 2: the first row alone, 7 keys
    work = eng.snapshot()["work"]
    assert work["decode_kv_positions"] == 6 + 12 + 7
    # one block a row and layer: (2 rows + 1 row) x 2 layers
    assert work["decode_kv_blocks_live"] == 6 == work["decode_kv_blocks_walked"]
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
