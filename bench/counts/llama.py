"""Least work of a Llama-architecture decoder (grouped-query attention), from
its published sizes. Bytes are of bfloat16 weights and caches. Attention
counts only the cache positions that hold tokens: a query at position n-1
attends to n positions, whatever length the cache was allocated at."""
from __future__ import annotations

BYTES = 2  # bfloat16


def dims(hf: dict) -> dict:
    h = hf["num_attention_heads"]
    return dict(d=hf["hidden_size"], h=h, kv=hf["num_key_value_heads"],
                hd=hf.get("head_dim") or hf["hidden_size"] // h,
                f=hf["intermediate_size"], v=hf["vocab_size"],
                layers=hf["num_hidden_layers"])


def layer_params(hf: dict) -> int:
    """Matrix weights of one block."""
    m = dims(hf)
    d, hd = m["d"], m["hd"]
    attn = d * m["h"] * hd + 2 * d * m["kv"] * hd + m["h"] * hd * d
    return attn + 3 * d * m["f"]


def weight_bytes(hf: dict) -> int:
    """What one decode step must read: every block's weights and norms, the
    final norm and the LM head (embedding rows are counted per token)."""
    m = dims(hf)
    per_layer = layer_params(hf) + 2 * m["d"]
    return BYTES * (m["layers"] * per_layer + m["d"] + m["d"] * m["v"])


def embed_row_bytes(hf: dict) -> int:
    return BYTES * dims(hf)["d"]


def token_flops(hf: dict) -> int:
    """Matrix flops of one token through every block (no attention scores,
    no LM head)."""
    return 2 * dims(hf)["layers"] * layer_params(hf)


def head_flops(hf: dict) -> int:
    m = dims(hf)
    return 2 * m["d"] * m["v"]


def decode_attn(hf: dict, n: int) -> tuple[int, int]:
    """(flops, bytes) of one query's attention in one layer against n
    filled positions: scores and weighted values; the keys and values read,
    the query read and the output written."""
    m = dims(hf)
    flops = 2 * m["h"] * n * m["hd"] * 2
    nbytes = BYTES * (n * m["kv"] * m["hd"] * 2 + 2 * m["h"] * m["hd"])
    return flops, nbytes


def prefill_attn(hf: dict, p: int) -> tuple[int, int]:
    """(flops, bytes) of causal attention over a p-token prompt in one
    layer: p (p + 1) / 2 query-key pairs; q, k, v read and o written once."""
    m = dims(hf)
    pairs = p * (p + 1) // 2
    flops = 2 * m["h"] * pairs * m["hd"] * 2
    nbytes = BYTES * p * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    return flops, nbytes


def kv_write_bytes(hf: dict) -> int:
    """Cache bytes one token writes in one layer."""
    m = dims(hf)
    return BYTES * 2 * m["kv"] * m["hd"]


def act_elems(hf: dict) -> int:
    """Elements through the MLP activation per token per layer."""
    return dims(hf)["f"]
