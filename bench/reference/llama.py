"""Plain float32 reference of a Llama-architecture decoder (Yi-6B).

Pre-norm blocks: RMSNorm, grouped-query attention with rotary embeddings on
the two halves of each head (``rope_theta`` as published), SwiGLU MLP;
final RMSNorm and an untied LM head. Weights follow the served layout:
``wi`` holds the gate projection then the up projection side by side.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import common as C


def dims(hf: dict) -> dict:
    h = hf["num_attention_heads"]
    return dict(d=hf["hidden_size"], h=h, kv=hf["num_key_value_heads"],
                hd=hf.get("head_dim") or hf["hidden_size"] // h,
                f=hf["intermediate_size"], v=hf["vocab_size"],
                layers=hf["num_hidden_layers"], theta=float(hf["rope_theta"]),
                eps=float(hf["rms_norm_eps"]))


def param_shapes(hf: dict) -> dict:
    """The served weight layout, in the published dtype."""
    m = dims(hf)
    dtype = jnp.dtype(hf["torch_dtype"])
    d, hd = m["d"], m["hd"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    layer = {
        "mixer": {"wq": s(d, m["h"] * hd), "wk": s(d, m["kv"] * hd),
                  "wv": s(d, m["kv"] * hd), "wo": s(m["h"] * hd, d)},
        "ffn": {"wi": s(d, 2 * m["f"]), "wo": s(m["f"], d)},
        "norm1": {"scale": s(d)}, "norm2": {"scale": s(d)},
    }
    return {"embed": {"tok": s(m["v"], d), "head": s(d, m["v"])},
            "final_norm": {"scale": s(d)},
            "segments": {"seg0": {"0": C.stacked_shapes(layer, m["layers"])}}}


@partial(jax.jit, static_argnames=("quant", "h", "kv", "hd", "theta", "eps"))
def _layer(x, p, pos, quant, *, h, kv, hd, theta, eps):
    f32 = C.F32
    n = x.shape[0]
    a = C.rmsnorm(x, p["norm1"]["scale"], eps)
    q = C.mm(a, p["mixer"]["wq"].astype(f32), quant).reshape(n, h, hd)
    k = C.mm(a, p["mixer"]["wk"].astype(f32), quant).reshape(n, kv, hd)
    v = C.mm(a, p["mixer"]["wv"].astype(f32), quant).reshape(n, kv, hd)
    q, k = C.rope(q, pos, theta), C.rope(k, pos, theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    o = C.causal_attention(q, k, v, C.head_dim_scale(hd), quant)
    x = x + C.mm(o.reshape(n, h * hd), p["mixer"]["wo"].astype(f32), quant)
    b = C.rmsnorm(x, p["norm2"]["scale"], eps)
    return x + C.swiglu(b, p["ffn"]["wi"], p["ffn"]["wo"], quant)


def logits(params, hf: dict, tokens, rows, quant=None):
    m = dims(hf)
    layer = partial(_layer, h=m["h"], kv=m["kv"], hd=m["hd"],
                    theta=m["theta"], eps=m["eps"])
    return C.forward_rows(params, tokens, rows,
                          lambda x, p, pos, q: layer(x, p, pos, q),
                          m["layers"], m["eps"], quant)
