"""``BENCHMARK.json`` against the contract, and every file it names."""

import json
import os

import pytest

from benchmarks.harness import spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = spec.load_benchmark(REPO)
ENTRIES = [(g, e["name"]) for g in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in BENCH[g]]


def test_benchmark_json_has_exactly_the_contracts_keys_and_is_small():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert spec.validate(BENCH) == []


@pytest.mark.parametrize("group,name", ENTRIES)
def test_names_and_units_use_the_allowed_characters(group, name):
    entry = next(e for e in BENCH[group] if e["name"] == name)
    assert spec.NAME_RE.match(name)
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[group]
    assert set(entry) <= allowed
    if "unit" in entry:
        assert spec.UNIT_RE.match(entry["unit"])
    for key in ("why", "layer") + (("source",) if group == "configs" else ()):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def test_validate_catches_what_the_contract_refuses():
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["unit"] = "tokens per second"
    bad["end_to_end"][1]["bound"] = 0.5
    bad["per_layer"][0]["moves"] = "nothing"
    complaints = " ".join(spec.validate(bad))
    for word in ("has space", "tokens per second", "bound", "moves"):
        assert word in complaints


def test_the_cells_are_the_issues_four_in_its_order():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "bert_large_s128_1chip", "gpt2_large_chat_steady",
        "gpt2_large_longprompt_closed", "bert_large_s128_zero2_dp4"]
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 1, 1, 4]
    tail = next(m for m in BENCH["end_to_end"]
                if m["name"] == "serve_itl_tail_ms")
    assert tail["workloads"] == ["gpt2_large_chat_steady"]
    assert "serve_itl_p95_ms" not in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_by_name(cell_name):
    cell = spec.load_cell(REPO, cell_name)
    entry = cell.config_entry
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert cell.config["name"] == entry["name"]
    assert cell.config["source"] == entry["source"]
    assert cell.config["reduced"] == entry["reduced"]
    assert isinstance(cell.config["assumed"], dict)
    assert cell.traffic["name"] == cell.traffic_name
    assert cell.traffic["why"] and cell.traffic["who"]
    # the cell reports set-up, another end-to-end metric and a layer metric
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer()
    for m in cell.per_layer():
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader_file_of_its_own(metric):
    reader = spec.load_reader(os.path.join(REPO, "benchmarks"), metric)
    assert callable(reader.read) and reader.__doc__


def test_no_width_is_reduced():
    for c in BENCH["configs"]:
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))
            assert "hidden" not in key.replace("hidden_dropout_prob", "")


def test_limits_of_correct_live_in_the_configuration_file_alone():
    """A traffic file, which later PRs may add, cannot loosen ``correct``:
    the four-chip cell's own limit is keyed by chips inside the
    configuration's file."""
    one = spec.load_cell(REPO, "bert_large_s128_1chip")
    four = spec.load_cell(REPO, "bert_large_s128_zero2_dp4")
    base = one.config["check"]["limits"]
    assert one.limits == base
    assert four.limits == dict(
        base, **one.config["check"]["limits_at_chips"]["4"])
    assert four.limits["first_grad_sketch_gap"] < base["first_grad_sketch_gap"]
    one.traffic["check_limits"] = {"change_norm_gap": 10.0}
    assert one.limits == base
    for w in BENCH["workloads"]:
        assert "check_limits" not in spec.load_cell(REPO, w["name"]).traffic
