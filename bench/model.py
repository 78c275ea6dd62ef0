"""Build the system under test from a configuration file and a seed.

The weights are the benchmark's own: the configuration's reference
module (``bench/references/<reference>.py``) makes them from ``--seed``
in one jitted call on the device, in the dtype they are served in, in
the program's parameter layout.  The SWAN bases are random orthogonal
per (layer, KV head) (the paper's random-projection ablation): ``P_VO``
from ``--seed``, ``P_QK`` from the configuration's fixed
``projection_seed``.  ``P_QK`` must not follow the seed: the engine's
jitted steps close over it, so it is a constant inside every compiled
executable, and a basis that changed with the seed would change every
persistent-cache key and make every run compile cold.  ``P_VO`` is
folded into the weights, which are arguments.

From there the path is the launcher's (``repro.launch.serve``):
``api.absorb``, then ``build_engine`` with ``--engine --swan --paged
--prefill-chunk --prefill-slots --warmup --async-fetch`` and
``use_pallas=None`` (compiled Pallas kernels on a TPU).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent


def model_fields(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The program's model fields as run: published keys mapped through
    ``program.from_published``, plus ``program.fixed``."""
    prog = conf["program"]
    fields = {f: conf[key] for f, key in prog["from_published"].items()}
    fields.update(prog.get("fixed", {}))
    return fields


def reference_module(conf: Dict[str, Any]):
    path = BENCH / "references" / f"{conf['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + conf["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    included)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_weights(conf: Dict[str, Any], seed: int):
    m = model_fields(conf)
    ref = reference_module(conf)
    return jax.jit(lambda key: ref.init_weights(m, key))(seed_key(seed))


def _orthogonal(key, n: int, d: int):
    g = jax.random.normal(key, (n, d, d), jnp.float32)
    q, r = jnp.linalg.qr(g)
    return q * jnp.sign(jnp.diagonal(r, axis1=-2, axis2=-1))[:, None, :]


def make_projections(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    m = model_fields(conf)
    L, Kv, dh = m["n_layers"], m["n_kv_heads"], m["d_head"]

    @jax.jit
    def bases(kq, kv):
        return {"p_qk": _orthogonal(kq, L * Kv, dh).reshape(L, Kv, dh, dh),
                "p_vo": _orthogonal(kv, L * Kv, dh).reshape(L, Kv, dh, dh)}

    fixed = conf["weights"]["projection_seed"]
    return bases(jax.random.fold_in(seed_key(fixed), 1),
                 jax.random.fold_in(seed_key(seed), 2))


def program_config(conf: Dict[str, Any]):
    from repro.configs import get_config
    return get_config(conf["program"]["arch"]).replace(**model_fields(conf))


def swan_config(conf: Dict[str, Any]):
    from repro.configs import SwanConfig
    s = conf["swan"]
    return SwanConfig(k_max=s["k_max"], buffer=s["buffer"], mode=s["mode"],
                      quantize=s["quantize"])


def serve_argv(conf: Dict[str, Any]) -> List[str]:
    """The launcher's command line for this configuration."""
    s, sv = conf["swan"], conf["serve"]
    argv = ["--arch", conf["program"]["arch"], "--engine", "--swan",
            "--paged", "--warmup", "--async-fetch",
            "--k", str(s["k_max"]), "--buffer", str(s["buffer"]),
            "--max-seq", str(sv["max_seq"]), "--batch", str(sv["n_slots"]),
            "--page-size", str(sv["page_size"]),
            "--prefill-chunk", str(sv["prefill_chunk"]),
            "--prefill-slots", str(sv["prefill_slots"]),
            "--max-prompt-len", str(sv["max_prompt_len"])]
    return argv


def check_layout(conf: Dict[str, Any], weights) -> None:
    """The benchmark's weights must have the program's layout."""
    from repro.models import get_model
    cfg = program_config(conf)
    want = get_model(cfg).abstract_params(cfg)
    got = jax.eval_shape(lambda: weights)
    sig = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                           t)
    if sig(want) != sig(got):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")


def build_engine(conf: Dict[str, Any], seed: int, trace, *,
                 use_pallas: Optional[bool] = None):
    """Weights and bases from ``seed``, absorbed and served by the
    launcher's engine.  Returns ``(engine, launcher args)``."""
    from repro.launch import serve
    from repro.models import get_model
    args = serve.parse_args(serve_argv(conf))
    cfg = program_config(conf)
    weights = make_weights(conf, seed)
    check_layout(conf, weights)
    pj = make_projections(conf, seed)
    params = get_model(cfg).absorb(weights, cfg, pj)
    del weights
    eng = serve.build_engine(cfg, params, swan_config(conf), pj,
                             args, trace=trace, use_pallas=use_pallas)
    return eng, args


def set_weights(eng, conf: Dict[str, Any], seed: int) -> None:
    """Serve another seed's weights with an engine that has drained:
    ``P_QK`` is seed-independent, so only the (argument) weights change
    and every compiled executable is reused."""
    from repro.models import get_model
    if not eng.done:
        raise RuntimeError("swap weights only on a drained engine")
    cfg = program_config(conf)
    eng.params = None
    weights = make_weights(conf, seed)
    pj = make_projections(conf, seed)
    eng.params = get_model(cfg).absorb(weights, cfg, pj)
