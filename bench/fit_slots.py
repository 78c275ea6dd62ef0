"""How many slots fit one chip: compile a configuration's paged decode and
chunk steps for a described TPU v5e, without the chip, and print each
program's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/fit_slots.py --config olmo-1b --slots 32 16

The steps are the engine's own: ``api.decode_step`` (with the device
argmax) over the widest page-table bucket, and ``api.prefill_chunk`` with
``prefill_slots`` lanes of ``prefill_chunk`` tokens over the widest page
bucket a prompt of ``max_prompt_len`` reaches, both with the Pallas
kernels compiled, the state donated, and ``P_QK`` closed over as the
engine closes over it.  Only shapes are built; nothing is allocated or
run.  A configuration's ``serve.n_slots`` is the largest power of two up
to 32 whose two programs fit 16 GB (arguments + outputs + temporaries -
aliased), and the file records the readings under ``slots_fit``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def analyse(conf: dict, n_slots: int, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from bench.model import program_config, swan_config
    from repro.models import get_model

    one = SingleDeviceSharding(topo.devices[0])
    cfg, swan, sv = program_config(conf), swan_config(conf), conf["serve"]
    api = get_model(cfg)
    ps, max_seq = sv["page_size"], sv["max_seq"]
    pps = max_seq // ps
    n_pages = -(-(n_slots * pps + 1) // 8) * 8        # the engine's default
    sds = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = sds(api.abstract_params(cfg))
    state = sds(jax.eval_shape(lambda: api.init_paged_state(
        cfg, swan, n_slots, max_seq, n_pages, ps)))
    eye = np.broadcast_to(np.eye(cfg.d_head, dtype=np.float32),
                          (cfg.n_layers, cfg.n_kv_heads, cfg.d_head,
                           cfg.d_head))
    pj = {"p_qk": jnp.asarray(eye)}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)

    def decode_fn(p, token, pos, k_act, page_tab, st):
        logits, st = api.decode_step(p, cfg, token, pos, st, swan, pj,
                                     k_active=k_act, page_tab=page_tab,
                                     use_pallas=True, pallas_interpret=False)
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), st

    def chunk_fn(p, tokens, st, slot, start, k_act, true_len, page_tab):
        logits, st = api.prefill_chunk(
            p, cfg, {"tokens": tokens}, st, slot, start, swan, pj,
            true_len=true_len, prefix_len=None, k_active=k_act,
            page_tab=page_tab, use_pallas=True, pallas_interpret=False)
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), st

    lanes, C = sv["prefill_slots"], sv["prefill_chunk"]
    chunk_w = min(_pow2(-(-sv["max_prompt_len"] // ps)), pps)
    progs = {
        "decode": (jax.jit(decode_fn, donate_argnums=(5,)),
                   (params, i32(n_slots), i32(n_slots), i32(n_slots),
                    i32(n_slots, pps), state)),
        "chunk": (jax.jit(chunk_fn, donate_argnums=(2,)),
                  (params, i32(lanes, C), state, i32(lanes), i32(lanes),
                   i32(lanes), i32(lanes), i32(n_slots, chunk_w))),
    }
    out = {"n_slots": n_slots, "n_pages": n_pages}
    for name, (fn, operands) in progs.items():
        try:
            ma = fn.lower(*operands).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:   # the compiler's refusal
            out[name] = {"refused": str(e).split("\n")[0]}
            continue
        r = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
        r["total_bytes"] = (r["argument_size_in_bytes"]
                            + r["output_size_in_bytes"]
                            - r["alias_size_in_bytes"]
                            + r["temp_size_in_bytes"])
        out[name] = r
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, nargs="+", default=[32, 16, 8])
    opts = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(BENCH / "configs" / f"{opts.config}.json") as fh:
        conf = json.load(fh)
    for n in opts.slots:
        print(json.dumps(analyse(conf, n, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
