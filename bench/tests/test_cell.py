"""Cells, configurations, traffic mixes, limits and per-layer readers are
found by name in files of their own, and a new one is added by adding
files, without editing one that exists."""
import json

import pytest

from bench.cell import ROOT, load_benchmark, load_cell
from bench.model import model_fields, program_config
from bench.tests.tiny import write_tree


def test_every_real_cell_loads():
    bench = load_benchmark()
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.chips == w["chips"] == 1
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer and set(cell.readers) == {
            m["name"] for m in cell.per_layer}
        for m in cell.per_layer:
            assert m["moves"] in names      # it moves a metric the cell reports
        assert set(cell.limits["numbers"]) >= {"mean_gap"}


def test_config_files_match_the_program():
    bench = load_benchmark()
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        cfg = program_config(conf)
        for field, value in model_fields(conf).items():
            assert getattr(cfg, field) == value
        assert conf["serve"]["n_slots"] == conf["slots_fit"]["chosen"]


def test_an_added_cell_and_metric_are_found(tmp_path):
    root = write_tree(tmp_path)
    cell = load_cell("tiny.open", root)
    assert cell.traffic["loop"] == "open"
    assert cell.config["name"] == "tiny"
    # a metric without a workloads list goes to every cell that reports
    # the metric it moves: only the open-loop cell reports ttft_p95_ms
    assert set(cell.readers) == {"queue_wait_p95_ms", "requests_seen"}
    assert "requests_seen" not in load_cell("tiny.closed", root).readers
    rec = type("R", (), {"logs": {"a": 1, "b": 2}})
    ctx = type("C", (), {"record": rec})
    assert cell.readers["requests_seen"](ctx) == 2.0


def test_unknown_cell_is_an_error(tmp_path):
    root = write_tree(tmp_path)
    with pytest.raises(KeyError):
        load_cell("tiny.nothing", root)
