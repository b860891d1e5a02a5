"""Plain float32 reference of the MiniCPM3 decoder (multi-head latent attention).

Per block: RMSNorm; queries through a low-rank bottleneck (``q_lora_rank``,
RMSNorm on the latent) split into a no-position part and a rotary part;
keys and values from one compressed latent (``kv_lora_rank``, RMSNorm) plus
a single shared rotary key; causal softmax attention with scale
1/sqrt(nope + rope); SwiGLU MLP. Final RMSNorm and an untied LM head.

Departures from the published model, shared with the served program: no
muP scaling (``scale_emb``, ``scale_depth``, ``dim_model_base``) and no
longrope scaling of the rotary frequencies.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import common as C


def dims(hf: dict) -> dict:
    return dict(d=hf["hidden_size"], h=hf["num_attention_heads"],
                ql=hf["q_lora_rank"], kvl=hf["kv_lora_rank"],
                nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                vd=hf["v_head_dim"], f=hf["intermediate_size"],
                v=hf["vocab_size"], layers=hf["num_hidden_layers"],
                theta=float(hf["rope_theta"]), eps=float(hf["rms_norm_eps"]))


def param_shapes(hf: dict) -> dict:
    """The served weight layout, in the published dtype."""
    m = dims(hf)
    dtype = jnp.dtype(hf["torch_dtype"])
    d, h = m["d"], m["h"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    layer = {
        "mixer": {"wq_a": s(d, m["ql"]), "q_norm": {"scale": s(m["ql"])},
                  "wq_b": s(m["ql"], h * (m["nope"] + m["rope"])),
                  "wkv_a": s(d, m["kvl"] + m["rope"]),
                  "kv_norm": {"scale": s(m["kvl"])},
                  "wkv_b": s(m["kvl"], h * (m["nope"] + m["vd"])),
                  "wo": s(h * m["vd"], d)},
        "ffn": {"wi": s(d, 2 * m["f"]), "wo": s(m["f"], d)},
        "norm1": {"scale": s(d)}, "norm2": {"scale": s(d)},
    }
    return {"embed": {"tok": s(m["v"], d), "head": s(d, m["v"])},
            "final_norm": {"scale": s(d)},
            "segments": {"seg0": {"0": C.stacked_shapes(layer, m["layers"])}}}


@partial(jax.jit, static_argnames=("quant", "h", "nope", "rope", "vd", "kvl",
                                   "theta", "eps"))
def _layer(x, p, pos, quant, *, h, nope, rope, vd, kvl, theta, eps):
    f32 = C.F32
    n = x.shape[0]
    mx = p["mixer"]
    a = C.rmsnorm(x, p["norm1"]["scale"], eps)
    ql = C.rmsnorm(C.mm(a, mx["wq_a"].astype(f32), quant),
                   mx["q_norm"]["scale"], eps)
    q = C.mm(ql, mx["wq_b"].astype(f32), quant).reshape(n, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], C.rope(q[..., nope:], pos, theta)], -1)
    kv = C.mm(a, mx["wkv_a"].astype(f32), quant)
    ckv = C.rmsnorm(kv[:, :kvl], mx["kv_norm"]["scale"], eps)
    kr = C.rope(kv[:, None, kvl:], pos, theta)  # (n, 1, rope)
    kvb = C.mm(ckv, mx["wkv_b"].astype(f32), quant).reshape(n, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(kr, (n, h, rope))], -1)
    o = C.causal_attention(q, k, kvb[..., nope:],
                           C.head_dim_scale(nope + rope), quant)
    x = x + C.mm(o.reshape(n, h * vd), mx["wo"].astype(f32), quant)
    b = C.rmsnorm(x, p["norm2"]["scale"], eps)
    return x + C.swiglu(b, p["ffn"]["wi"], p["ffn"]["wo"], quant)


def logits(params, hf: dict, tokens, rows, quant=None):
    m = dims(hf)
    layer = partial(_layer, h=m["h"], nope=m["nope"], rope=m["rope"],
                    vd=m["vd"], kvl=m["kvl"], theta=m["theta"], eps=m["eps"])
    return C.forward_rows(params, tokens, rows,
                          lambda x, p, pos, q: layer(x, p, pos, q),
                          m["layers"], m["eps"], quant)
