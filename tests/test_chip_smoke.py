"""chip_smoke.py's control flow on the CPU, at smoke widths.

The script itself refuses to run without a TPU; these tests drive its phase
functions with the Pallas kernels forced on (``use_pallas=True``) and
therefore in interpret mode, so the launcher entry points, the warmup and
zero-compile check, the kernel-vs-XLA comparison and the four-device
data-parallel comparison are exercised in tier-1 without a chip."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

# smoke-width olmo-1b: ring b = max_seq // 4 = 32, so 33..40-token prompts
# leave a sparse prefix for the kernels to read
FLAGS = ("--engine", "--swan", "--paged", "--page-size", "16",
         "--prefill-chunk", "16", "--prefill-slots", "2", "--max-seq", "128",
         "--batch", "4", "--warmup", "--async-fetch")
PROMPTS = (40, 33, 20, 5, 17)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_main_path_in_interpret_mode(chip_smoke):
    out = chip_smoke.run_main(
        0, arch="olmo-1b", smoke=True, prompt_lens=PROMPTS, new_tokens=6,
        serve_flags=FLAGS, use_pallas=True, expect_interpret=True,
        compare=dict(steps=2, max_seq=128))
    kern = out["kernel"]
    assert kern["engine"].use_pallas and kern["engine"]._pallas_interpret
    assert kern["warmup_executables"] > 0
    assert kern["compiles_after_warmup"] == 0
    assert kern["n_tokens"] == 6 * len(PROMPTS)
    assert not out["xla"]["engine"].use_pallas
    cmp = out["compare"]
    assert cmp["ok"] and len(cmp["requests"]) == len(PROMPTS)
    # every request is compared: prompts longer and shorter than the ring,
    # and every per-request k
    assert {r["prompt"] for r in cmp["requests"].values()} == set(PROMPTS)
    assert len({r["k"] for r in cmp["requests"].values()}) == 3
    assert all(len(r["kernel_xla"]) == 2 and len(r["margin"]) == 2
               for r in cmp["requests"].values())
    agree = out["agreement"]
    assert agree["total"] == 6 * (len(PROMPTS) - 1)
    assert set(agree["parted"]) <= {f"req{i}" for i in range(len(PROMPTS))}


def test_chip_smoke_phase_refuses_wrong_resolution(chip_smoke):
    # on the CPU the engine resolves interpret mode; a check that expects
    # compiled kernels must fail, not pass silently
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        chip_smoke.run_main(
            0, arch="olmo-1b", smoke=True, prompt_lens=(5,), new_tokens=1,
            serve_flags=FLAGS, use_pallas=True, expect_interpret=False)


_FOUR = r"""
import importlib.util, json, sys
sys.path.insert(0, {src!r})
spec = importlib.util.spec_from_file_location("chip_smoke", {smoke!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
out = cs.run_four_chips(0, arch="olmo-1b", smoke=True, prompt_lens={prompts!r},
                        new_tokens=6, serve_flags={flags!r}, use_pallas=True,
                        expect_interpret=True)
print(json.dumps({{"same": out["dp"]["tokens"] == out["one"]["tokens"],
                  "dp": out["dp"]["engine"].dp,
                  "n": out["dp"]["n_tokens"]}}))
"""


def test_chip_smoke_four_chip_phase_on_virtual_devices():
    script = _FOUR.format(src=os.path.join(REPO, "src"), smoke=SMOKE,
                          flags=FLAGS, prompts=PROMPTS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res == {"same": True, "dp": 4, "n": 6 * len(PROMPTS)}
