"""Plain reference of the dense decoder family the benchmark serves
(OLMo-1B: non-parametric LayerNorm, tied embeddings; Yi-9B: RMSNorm,
grouped KV heads, untied head), with SWAN's cache semantics, in float32.

It imports nothing of the program and takes nothing the program made.
The weights come from :func:`init_weights` (the benchmark's own seeded
init, in the program's parameter layout) and the SWAN bases from
``bench/model.py``; the forward pass is written out in ``jax.numpy`` at
``highest`` matmul precision, one sequence at a time, layer by layer.

What SWAN makes a query at position ``t`` see (paper Algorithm 1, as the
serve engine schedules it):

* keys and values are rotated per KV head: ``k̂ = RoPE(k) P_QK``,
  ``q̂ = RoPE(q) P_QK``, ``v̂ = v P_VO``;
* a token older than the dense window is *winnowed*: only its ``k``
  largest-magnitude rotated components survive (``k`` is the request's
  retention), on both sides;
* the dense window starts at ``D(t)``: during prefill, which runs in
  chunks of ``C`` tokens from position 0, a query in the chunk starting
  at ``s`` sees ``[max(s - b, 0), t]`` densely (the ring as it stood at
  the chunk's start, and the chunk); a decoded token at ``t`` sees
  ``[max(t + 1 - b, 0), t]`` (the ring of the newest ``b``);
* the attention output is rotated back, ``o = ô P_VOᵀ``.

``mode="fp8"`` computes every linear layer with operands rounded to
scaled float8 e4m3, per row of activations and per column of weights:
the precision below the configuration's bf16, used only as the control
of the correctness check.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _std_normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def init_weights(m: Dict[str, Any], key) -> Dict[str, Any]:
    """Seeded random weights in the program's layout (stacked layers),
    in the model dtype.  Fan-in scaled normals; norm scales are ones; the
    embedding (and the untied head) have standard deviation
    ``d_model ** -0.5`` so that logits start near unit variance."""
    L, d, H, Kv, dh, ff, V = (m["n_layers"], m["d_model"], m["n_heads"],
                              m["n_kv_heads"], m["d_head"], m["d_ff"],
                              m["vocab_size"])
    dt = jnp.dtype(m.get("param_dtype", "bfloat16"))
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return _std_normal(next(ks), shape, fan_in ** -0.5).astype(dt)

    def norm(shape):
        return {"scale": jnp.ones(shape, dt)} if m["norm"] == "rmsnorm" else {}

    p = {
        "embed": w((V, d), d),
        "layers": {
            "ln1": norm((L, d)),
            "attn": {"wq": w((L, d, H * dh), d), "wk": w((L, d, Kv * dh), d),
                     "wv": w((L, d, Kv * dh), d),
                     "wo": w((L, H * dh, d), H * dh)},
            "ln2": norm((L, d)),
            "mlp": {"w_gate": w((L, d, ff), d), "w_up": w((L, d, ff), d),
                    "w_down": w((L, ff, d), ff)},
        },
        "ln_f": norm((d,)),
    }
    if not m["tie_embeddings"]:
        p["head"] = w((d, V), d)
    return p


def _norm(m, scale, x):
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * scale["scale"].astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def _rope(x, pos, theta):
    """Half-split rotary embedding; x [T, heads, dh], pos [T]."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _f8(x, axis):
    """Round to float8 e4m3, scaled along ``axis`` to its range."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 448
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _winnow(x, k):
    """Keep the ``k`` largest-magnitude components of each vector."""
    rank = jnp.argsort(jnp.argsort(-jnp.abs(x), axis=-1), axis=-1)
    return jnp.where(rank < k, x, 0.0)


def make_scorer(m: Dict[str, Any], swan: Dict[str, Any], chunk: int,
                mode: str = "f32"):
    """A jitted ``score(W, P, tokens, targets, n_prompt, k)`` over one
    padded sequence ``tokens [T]``: returns ``(gap [T], top [T])``, where
    ``gap[t]`` is how far the logit of ``targets[t]`` lies below the best
    logit at position ``t``, and ``top[t]`` the token this precision puts
    first there."""
    L, H, Kv, dh = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    G, b = H // Kv, swan["buffer"]
    theta = float(m["rope_theta"])
    f32 = jnp.float32

    def mm(a, w):
        w = w.astype(f32)
        if mode == "fp8":
            a, w = _f8(a, -1), _f8(w, 0)
        return a @ w

    def score(W, P, tokens, targets, n_prompt, k):
        T = tokens.shape[0]
        pos = jnp.arange(T)
        dense_from = jnp.where(
            pos < n_prompt, jnp.maximum((pos // chunk) * chunk - b, 0),
            jnp.maximum(pos + 1 - b, 0))
        causal = pos[None, :] <= pos[:, None]                      # [t, j]
        sparse = pos[None, :] < dense_from[:, None]

        def layer(x, xs):
            lw, pqk, pvo = xs
            h = _norm(m, lw["ln1"], x)
            q = _rope(mm(h, lw["attn"]["wq"]).reshape(T, H, dh), pos, theta)
            kk = _rope(mm(h, lw["attn"]["wk"]).reshape(T, Kv, dh), pos, theta)
            v = mm(h, lw["attn"]["wv"]).reshape(T, Kv, dh)
            qh = jnp.einsum("tjgd,jde->jgte", q.reshape(T, Kv, G, dh), pqk)
            kh = jnp.einsum("tjd,jde->jte", kk, pqk)
            vh = jnp.einsum("tjd,jde->jte", v, pvo)
            ks, vs = _winnow(kh, k), _winnow(vh, k)

            def head(args):
                qj, kj, ksj, vj, vsj = args                    # [G,T,dh], [T,dh]
                s = jnp.where(sparse, jnp.einsum("gte,se->gts", qj, ksj),
                              jnp.einsum("gte,se->gts", qj, kj))
                s = jnp.where(causal, s / math.sqrt(dh), -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                return (jnp.einsum("gts,se->gte", jnp.where(sparse, p, 0.0), vsj)
                        + jnp.einsum("gts,se->gte", jnp.where(sparse, 0.0, p), vj))

            oh = jax.lax.map(head, (qh, kh, ks, vh, vs))           # [Kv,G,T,dh]
            o = jnp.einsum("jgte,jde->tjgd", oh, pvo).reshape(T, H * dh)
            x = x + mm(o, lw["attn"]["wo"])
            h = _norm(m, lw["ln2"], x)
            mw = lw["mlp"]
            x = x + mm(jax.nn.silu(mm(h, mw["w_gate"])) * mm(h, mw["w_up"]),
                       mw["w_down"])
            return x, None

        x = W["embed"][tokens].astype(f32)
        x, _ = jax.lax.scan(layer, x, (W["layers"], P["p_qk"], P["p_vo"]))
        x = _norm(m, W["ln_f"], x)
        head_w = W["embed"].T if m["tie_embeddings"] else W["head"]
        logits = mm(x, head_w)
        best = jnp.max(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return best - tgt, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return score(*args)

    return jax.jit(run)
