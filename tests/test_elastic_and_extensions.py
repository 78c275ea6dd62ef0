"""Elastic checkpoint resharding + whisper cross-attention SWAN extension
+ int8 grad sync on a real multi-device mesh (subprocess)."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SwanConfig, get_smoke_config
from repro.launch.io import make_batch
from repro.models import get_model
from repro.runtime.serve_loop import calibrate_swan


_ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh
import numpy as np

# save on a (2,4) mesh layout, restore onto (4,2) — elastic re-mesh
tree = {"w": jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32),
        "b": jnp.arange(32, dtype=jnp.float32)}
mesh1 = make_mesh((2, 4), ("data", "model"))
sh1 = {"w": NamedSharding(mesh1, P("data", "model")),
       "b": NamedSharding(mesh1, P("model"))}
tree1 = jax.device_put(tree, sh1)

ck = Checkpointer("/tmp/repro_elastic_ckpt", keep=1)
ck.save(1, tree1)

mesh2 = make_mesh((4, 2), ("data", "model"))
sh2 = {"w": NamedSharding(mesh2, P("model", "data")),
       "b": NamedSharding(mesh2, P(None))}
tree2 = ck.restore(1, tree, shardings=sh2)
ok_val = bool(jnp.all(tree2["w"] == tree["w"]))
ok_shard = tree2["w"].sharding.spec == P("model", "data")
print(json.dumps({"ok_val": ok_val, "ok_shard": bool(ok_shard)}))
"""


def test_elastic_reshard_restore():
    out = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-1500:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok_val"] and rec["ok_shard"], rec


def test_whisper_cross_attn_swan_extension():
    """compress_cross_attn winnows the static cross-attention cache; at
    full retention the output must match the uncompressed cross cache."""
    cfg = get_smoke_config("whisper-small").replace(dtype="float32",
                                                    param_dtype="float32")
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, 2, 10)
    pj = calibrate_swan(api, cfg, params, batch)
    absorbed = api.absorb(params, cfg, pj)

    def serve(compress_cross, k_max):
        swan = SwanConfig(k_max=k_max, buffer=4, mode="topk",
                          compress_cross_attn=compress_cross)
        st = api.init_serve_state(cfg, swan, 2, 24)
        lg, st = api.prefill(absorbed, cfg, batch, st, swan, pj)
        tok = jnp.argmax(lg[:, -1], -1)
        lg2, st = api.decode_step(absorbed, cfg, tok, 10, st, swan, pj)
        return lg2

    full = serve(False, cfg.d_head)
    full_cc = serve(True, cfg.d_head)      # full retention: lossless
    np.testing.assert_allclose(np.asarray(full), np.asarray(full_cc),
                               atol=2e-4, rtol=1e-3)
    comp = serve(True, cfg.d_head // 2)    # compressed: runs, no NaN
    assert not bool(jnp.any(jnp.isnan(comp)))


_INT8_DP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.runtime.grad_compress import dp_int8_allreduce
from repro.sharding.api import shard_map_unchecked

mesh = make_mesh((4,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 256))  # per-shard rows

def f(g):
    return dp_int8_allreduce({"w": g}, "data")["w"]

out = jax.jit(shard_map_unchecked(f, mesh, (P("data"),), P("data")))(g)
# every shard's output row == mean of all rows (up to int8 error)
mean = g.mean(axis=0)
err = float(jnp.max(jnp.abs(out - mean[None])))
bound = float(jnp.max(jnp.abs(g))) / 127.0
print(json.dumps({"err": err, "bound": bound}))
"""


def test_int8_allreduce_multidevice():
    out = subprocess.run([sys.executable, "-c", _INT8_DP_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-1500:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] <= rec["bound"] + 1e-6, rec
