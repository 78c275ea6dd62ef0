"""The harness end to end at smoke widths on the CPU: the last line has
the contract's shape, and a run without a chip prints no result."""
from bench import run
from bench.tests.tiny import run_cell, write_tree

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _common(line):
    assert list(line)[-1] == "checks"           # the comparisons come last
    assert set(KEYS) <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert line["metrics"]["setup_s"]["unit"] == "s"
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_closed_loop_interpret_mode(tmp_path, capsys, own_compile_cache):
    line = run_cell(write_tree(tmp_path), "tiny.closed", capsys,
                    use_pallas=True)
    _common(line)
    assert set(line["metrics"]) == {"tok_per_s", "setup_s"}
    assert line["metrics"]["tok_per_s"]["value"] > 0


def test_open_loop(tmp_path, capsys, own_compile_cache):
    line = run_cell(write_tree(tmp_path), "tiny.open", capsys)
    _common(line)
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert line["metrics"]["ttft_p95_ms"]["value"] > 0


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "olmo-1b.batch-decode-640", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == ""
    assert "no TPU" in captured.err
