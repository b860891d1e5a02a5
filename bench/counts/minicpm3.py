"""Least work of the MiniCPM3 decoder (multi-head latent attention), from its
published sizes. Bytes are of bfloat16 weights and caches.

Decode attention is counted in its absorbed form: the query's no-position
part is taken into the latent space once (its flops are the ``wkv_b``
weights', counted with the block's matrices), scores are taken against the
cached latent and shared rotary key (kv_lora + rope wide per position, one
copy for all heads), and values are summed in latent space before the
up-projection. Prefill attention is counted in the expanded form, which
needs fewer flops per query-key pair. Only filled positions count.
"""
from __future__ import annotations

BYTES = 2  # bfloat16


def dims(hf: dict) -> dict:
    return dict(d=hf["hidden_size"], h=hf["num_attention_heads"],
                ql=hf["q_lora_rank"], kvl=hf["kv_lora_rank"],
                nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                vd=hf["v_head_dim"], f=hf["intermediate_size"],
                v=hf["vocab_size"], layers=hf["num_hidden_layers"])


def layer_params(hf: dict) -> int:
    """Matrix weights of one block."""
    m = dims(hf)
    d, h = m["d"], m["h"]
    attn = (d * m["ql"] + m["ql"] * h * (m["nope"] + m["rope"])
            + d * (m["kvl"] + m["rope"]) + m["kvl"] * h * (m["nope"] + m["vd"])
            + h * m["vd"] * d)
    return attn + 3 * d * m["f"]


def weight_bytes(hf: dict) -> int:
    """What one decode step must read: every block's weights and norms, the
    final norm and the LM head."""
    m = dims(hf)
    per_layer = layer_params(hf) + 2 * m["d"] + m["ql"] + m["kvl"]
    return BYTES * (m["layers"] * per_layer + m["d"] + m["d"] * m["v"])


def embed_row_bytes(hf: dict) -> int:
    return BYTES * dims(hf)["d"]


def token_flops(hf: dict) -> int:
    return 2 * dims(hf)["layers"] * layer_params(hf)


def head_flops(hf: dict) -> int:
    m = dims(hf)
    return 2 * m["d"] * m["v"]


def decode_attn(hf: dict, n: int) -> tuple[int, int]:
    """(flops, bytes) of one query's absorbed attention in one layer against
    n filled positions: scores over kv_lora + rope, values over kv_lora; the
    latent cache read once for all heads, the absorbed query read and the
    latent output written."""
    m = dims(hf)
    w = m["kvl"] + m["rope"]
    flops = 2 * m["h"] * n * w + 2 * m["h"] * n * m["kvl"]
    nbytes = BYTES * (n * w + m["h"] * w + m["h"] * m["kvl"])
    return flops, nbytes


def prefill_attn(hf: dict, p: int) -> tuple[int, int]:
    """(flops, bytes) of causal attention over a p-token prompt in one layer:
    expanded heads for the flops, the latent input for the bytes (the least
    of each); queries read and outputs written once."""
    m = dims(hf)
    pairs = p * (p + 1) // 2
    flops = 2 * m["h"] * pairs * (m["nope"] + m["rope"] + m["vd"])
    nbytes = BYTES * p * (m["h"] * (m["nope"] + m["rope"])
                          + m["kvl"] + m["rope"] + m["h"] * m["vd"])
    return flops, nbytes


def kv_write_bytes(hf: dict) -> int:
    m = dims(hf)
    return BYTES * (m["kvl"] + m["rope"])


def act_elems(hf: dict) -> int:
    return dims(hf)["f"]
