"""A benchmark tree at smoke widths, written to a temporary directory:
a ``BENCHMARK.json`` with one tiny configuration and two cells (a closed
and an open loop), their traffic and limits files, and the real
per-layer readers beside one added in the temporary tree alone.  The
harness finds all of it by name, as it finds the real cells."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny",
    "source": "smoke widths of the dense decoder family",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "intermediate_size": 128, "vocab_size": 256,
    "tie_word_embeddings": False,
    "reduced": [],
    "program": {
        "arch": "yi-9b",
        "from_published": {"d_model": "hidden_size", "d_ff": "intermediate_size",
                           "n_heads": "num_attention_heads",
                           "n_kv_heads": "num_key_value_heads",
                           "n_layers": "num_hidden_layers",
                           "vocab_size": "vocab_size",
                           "tie_embeddings": "tie_word_embeddings"},
        "fixed": {"d_head": 16, "norm": "rmsnorm", "act": "silu",
                  "rope_theta": 10000.0, "dtype": "float32",
                  "param_dtype": "float32"}},
    "reference": "dense_decoder",
    "weights": {"projection_seed": 7},
    "swan": {"k_max": 8, "buffer": 16, "mode": "topk", "quantize": False},
    "serve": {"max_seq": 128, "n_slots": 4, "page_size": 16,
              "prefill_chunk": 16, "prefill_slots": 2, "max_prompt_len": 32},
}

TRAFFIC = {
    "closed": {"loop": "closed", "clients": "n_slots", "pool": 64,
               "prompt_tokens": {"dist": "uniform", "min": 17, "max": 32},
               "output_tokens": {"dist": "uniform", "min": 6, "max": 30},
               "k": [8, 4], "preroll": {"completions": 2},
               "drain_seconds": 0,
               "trace": {"offset_share": 0.3, "seconds": 1.0},
               "check": {"tokens": 60, "max_requests": 3}},
    "open": {"loop": "open", "arrivals": {"process": "poisson",
                                          "rate_per_s": 4.0},
             "prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.3, "min": 17, "max": 32},
             "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
             "k": [8, 4], "preroll": {"seconds": 0.5}, "drain_seconds": 60,
             "trace": {"offset_share": 0.3, "seconds": 1.0},
             "check": {"tokens": 30, "max_requests": 4}},
}

# float32 engine against the float32 reference: gaps of rounding alone
LIMITS = {"numbers": {"mean_gap": {"limit": 0.01}}}

ADDED_METRIC = '''"""A metric that exists only in this tree."""


def read(ctx):
    return float(len(ctx.record.logs))
'''


def benchmark() -> dict:
    e2e = [{"name": "tok_per_s", "unit": "tokens/s", "better": "higher",
            "bound": 0.05, "source": "host_clock",
            "workloads": ["tiny.closed"]},
           {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock",
            "workloads": ["tiny.open"]},
           {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock",
            "workloads": ["tiny.open"]},
           {"name": "setup_s", "unit": "s", "better": "lower",
            "bound": 0.25, "source": "host_clock"}]
    per_layer = [
        {"name": "kv_bytes_per_token", "unit": "bytes", "better": "lower",
         "source": "program_counter", "layer": "page pool",
         "moves": "tok_per_s", "workloads": ["tiny.closed"]},
        {"name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "scheduler",
         "moves": "ttft_p95_ms", "workloads": ["tiny.open"]},
        {"name": "requests_seen", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "scheduler",
         "moves": "ttft_p95_ms"}]
    return {"command": ["python3", "bench/run.py"], "paths": ["bench"],
            "run_seconds": 2,
            "configs": [{"name": "tiny", "source": "smoke",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "smoke widths"}],
            "workloads": [
                {"name": "tiny.closed", "config": "tiny",
                 "traffic": "tiny-closed", "chips": 1, "why": "closed"},
                {"name": "tiny.open", "config": "tiny",
                 "traffic": "tiny-open", "chips": 1, "why": "open"}],
            "end_to_end": e2e, "per_layer": per_layer}


def write_tree(root: Path) -> Path:
    """Write the tiny benchmark under ``root``; returns ``root``."""
    b = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (b / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "layer_metrics", b / "layer_metrics")
    (b / "layer_metrics" / "requests_seen.py").write_text(ADDED_METRIC)
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark(), indent=1))
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    for mix, spec in TRAFFIC.items():
        (b / "traffic" / f"tiny-{mix}.json").write_text(json.dumps(spec))
        (b / "limits" / f"tiny.{mix}.json").write_text(json.dumps(LIMITS))
    return root


def run_cell(root: Path, cell: str, capsys, *, seconds: float = 2.0,
             seed: int = 2**31 + 4242, use_pallas=None) -> dict:
    """Drive one tiny cell through the harness, skipping only its look for
    a chip, and return the parsed last line of its standard output."""
    from bench import run
    run.run(["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0"], root=root, require_chip=False,
            use_pallas=use_pallas)
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
