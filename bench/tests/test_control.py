"""The control of the correctness check comes out not correct: the
reference computed with float8 operands, put in the program's place, reads
gaps above the limits that the program itself meets."""
from bench import check, run
from bench.model import build_engine
from bench.tests.tiny import write_tree
from repro.obs import EventTrace


def test_fp8_reference_fails_the_limits(tmp_path, own_compile_cache):
    cell, _ = run.start("tiny.closed", write_tree(tmp_path), False)
    seed = 2**31 + 77
    events = EventTrace(keep=True)
    eng, largs = build_engine(cell.config, seed, events)
    eng.warmup(max_prompt_len=largs.max_prompt_len)
    driver = run.serve(eng, events, cell, seed, 2.0)
    reqs = run.served(driver.record, seed, cell.traffic)
    assert sum(len(r[1]) for r in reqs) >= 30

    program = check.judge(
        check.numbers(run.reference_gaps(cell.config, seed, reqs)),
        cell.limits)
    control = check.judge(
        check.numbers(run.reference_gaps(cell.config, seed, reqs,
                                         control="fp8")), cell.limits)
    assert all(c["value"] <= c["limit"] for c in program.values())
    assert any(c["value"] > c["limit"] for c in control.values())
