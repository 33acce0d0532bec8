"""BENCHMARK.json against the contract, and every file it names."""

import os
import re

import pytest

from benchmark.manifest import REPO_ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO_ROOT)


def all_metrics(man):
    return man.data["end_to_end"] + man.data["per_layer"]


def test_top_level_keys(man):
    assert set(man.data) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= man.data["run_seconds"] <= 51
    assert man.data["paths"] == ["benchmark", "tests/benchmark"]
    assert os.path.getsize(os.path.join(REPO_ROOT, "BENCHMARK.json")) < 65536


def test_names_and_units_use_allowed_characters(man):
    names = [m["name"] for m in all_metrics(man)]
    names += [w["name"] for w in man.data["workloads"]]
    names += [w["traffic"] for w in man.data["workloads"]]
    names += [c["name"] for c in man.data["configs"]]
    names += [k for c in man.data["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in all_metrics(man):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    metric_names = [m["name"] for m in all_metrics(man)]
    assert len(set(metric_names)) == len(metric_names)


def test_entries_hold_just_the_contract_keys(man):
    for c in man.data["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man.data["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in all_metrics(man):
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in man.data["workloads"]}
    for m in man.data["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in man.data["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_every_named_file_exists(man):
    for w in man.data["workloads"]:
        cfg = man.config(w["config"])
        mix = man.traffic(w["traffic"])
        assert os.path.exists(man.find("drivers", mix["driver"] + ".py"))
        assert os.path.exists(man.find("families", cfg["family"] + ".py"))
        assert os.path.exists(man.find("reference", cfg["family"] + ".py"))
        for m in man.metrics_for(w["name"], "per_layer"):
            assert os.path.exists(
                man.find("layer_metrics", m["name"] + ".py")), m["name"]
    files = [c["file"] for c in man.data["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in man.data["workloads"]}
    assert used == {c["name"] for c in man.data["configs"]}


def test_every_cell_reports_setup_another_metric_and_a_layer(man):
    for w in man.data["workloads"]:
        e2e = [m["name"] for m in man.metrics_for(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert man.metrics_for(w["name"], "per_layer"), w["name"]


def test_moves_is_reported_wherever_the_metric_is(man):
    cells = [w["name"] for w in man.data["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in man.data["end_to_end"]}
    for m in man.data["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)


def test_at_most_a_quarter_of_the_cells_take_four_chips(man):
    cells = man.data["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in cells)
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)


def test_configurations_keep_the_published_widths(man):
    width = {"hidden_size": 4096, "intermediate_size": 14336,
             "num_attention_heads": 32, "num_key_value_heads": 8,
             "max_position_embeddings": 32768, "rope_theta": 1e6,
             "sliding_window": None, "rms_norm_eps": 1e-5}
    for c in man.data["configs"]:
        cfg = man.config(c["name"])
        for k, v in width.items():
            assert cfg[k] == v, (c["name"], k)
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert "v0.3" in c["source"] or "Mixtral" in c["source"]
        assert len(c["source"]) <= 200
    moe = man.config("mixtral-8x7b-v0.1-serve")
    assert (moe["num_local_experts"], moe["num_experts_per_tok"],
            moe["vocab_size"]) == (8, 2, 32000)
    assert man.config("mistral-7b-v0.3-train")["vocab_size"] == 32768


def test_the_peaks_table_has_no_default():
    from benchmark.peaks import lm_train_flops_per_token, peaks_for

    assert peaks_for("TPU v5 lite") == (197e12, 819e9, 16e9)
    with pytest.raises(ValueError):
        peaks_for("cpu")
    cfg = Manifest(REPO_ROOT).config("mistral-7b-v0.3-train")
    # 3 x 2 x (matmul parameters + head) + causal attention, by hand
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    want = 3 * (2 * (cfg["num_hidden_layers"] * layer + 4096 * 32768)
                + cfg["num_hidden_layers"] * 4 * 4096 * (4096 + 1) / 2)
    assert lm_train_flops_per_token(cfg, 4096) == pytest.approx(want)
