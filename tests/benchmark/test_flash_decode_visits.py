"""``kernels.flash_decode_live_visits_pct.batch``: the reader against
hand-made counters (nothing where a program has none), its entry in
``BENCHMARK.json``, and the number a traced closed-loop window reports at
tiny widths, from a throw-away root."""

import json
import os

import pytest

import jax

from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.run import run_cell

from ._tiny import CONFIGS, MIXES

METRIC = "kernels.flash_decode_live_visits_pct.batch"
CELLS = ["mixtral8x7b-batch-closed", "kexaone236b-reason-closed"]


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO_ROOT)


@pytest.mark.parametrize("facts", [
    {},                                                  # no snapshot
    {"snapshot": {}},
    # the parent's program: positions counted, blocks not
    {"snapshot": {"work": {"decode_kv_positions": 4096,
                           "prefill_tokens": 10,
                           "prefill_padded_tokens": 16}}},
    # a decode that another kernel runs (paged, sharded): never counted
    {"snapshot": {"work": {"decode_kv_positions": 4096,
                           "decode_kv_blocks_live": 0,
                           "decode_kv_blocks_walked": 0}}},
], ids=["no_snapshot", "no_work", "parent", "other_kernel"])
def test_reader_reports_nothing_without_the_counters(man, facts):
    assert man.module("layer_metrics", METRIC).read(facts) is None


@pytest.mark.parametrize("live,walked,want", [
    (1075, 1075, 100.0),      # live blocks only
    (8600, 32768, 26.24512),  # the K-EXAONE cell's full layer on a static grid
    (1, 3, 100.0 / 3),
])
def test_reader_gives_the_share_of_visits(man, live, walked, want):
    facts = {"snapshot": {"work": {"decode_kv_blocks_live": live,
                                   "decode_kv_blocks_walked": walked}}}
    got = man.module("layer_metrics", METRIC).read(facts)
    assert got == pytest.approx(want, rel=1e-6) and got <= 100.0


def test_entry_is_appended_for_both_serving_cells(man):
    entry = man.data["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": CELLS}
    for cell in CELLS:
        assert METRIC in {m["name"]
                          for m in man.metrics_for(cell, "per_layer")}
        assert "serve_tokens_per_s" in {
            m["name"] for m in man.metrics_for(cell, "end_to_end")}
    assert os.path.exists(man.find("layer_metrics", METRIC + ".py"))


def test_a_traced_window_reports_every_visit_live(tmp_path):
    """The engine's counters through the harness: a tiny expert model on a
    96-row cache (one block a row and layer), closed loop, traced."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(root, "benchmark", "configs", "tiny.json"),
              "w") as f:
        json.dump(CONFIGS["tiny-moe-serve"], f)
    with open(os.path.join(root, "benchmark", "traffic", "tiny-closed.json"),
              "w") as f:
        json.dump(MIXES["tiny-closed"], f)
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench.update(
        run_seconds=1,
        configs=[{"name": "tiny", "source": "tests", "reduced": [],
                  "file": "benchmark/configs/tiny.json", "why": "tiny"}],
        workloads=[{"name": "tiny", "config": "tiny",
                    "traffic": "tiny-closed", "chips": 1, "why": "tiny"}])
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = (["tiny"] if CELLS[0] in m.get("workloads", CELLS)
                          else [])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    last = run_cell(Manifest(root), "tiny", 2**31 + 28, 0.5, 1,
                    jax.devices()[:1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"][METRIC]["value"] == 100.0
