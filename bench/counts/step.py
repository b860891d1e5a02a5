"""Least work of one engine step, summed from an architecture's counts.

A step record (``harness.drive``) holds ``steps`` (decode steps in the
tick), ``live`` (per slot that decoded in the tick, its start position and
the tokens it decoded; its j-th decoded token attends to position + j + 1
filled positions) and ``admitted`` (the true prompt lengths prefilled in
the step). Only real tokens count: padding rows of a bucket, idle slots and
decode steps past a slot's last token are not work.
"""
from __future__ import annotations


def decode_steps(step: dict) -> int:
    """Decode steps in which some slot produced a token."""
    return max((n for _, n in step["live"]), default=0)


def flops(arch, hf: dict, step: dict) -> int:
    """Model flops of the step: every admitted prompt (its logits only at
    the last position) and every decoded token."""
    n_layers = hf["num_hidden_layers"]
    tok, head = arch.token_flops(hf), arch.head_flops(hf)
    total = 0
    for p in step["admitted"]:
        total += p * tok + head + n_layers * arch.prefill_attn(hf, p)[0]
    for pos, n in step["live"]:
        for j in range(n):
            total += tok + head + n_layers * arch.decode_attn(hf, pos + j + 1)[0]
    return total


def decode_bytes(arch, hf: dict, step: dict) -> int:
    """HBM bytes the step's decode must move: the weights once per decode
    step that produced a token, and per decoded token its embedding row,
    its filled cache positions read and its new cache row written in every
    layer."""
    n_layers = hf["num_hidden_layers"]
    total = decode_steps(step) * arch.weight_bytes(hf)
    kv_w, row = arch.kv_write_bytes(hf), arch.embed_row_bytes(hf)
    for pos, n in step["live"]:
        for j in range(n):
            total += row + n_layers * (arch.decode_attn(hf, pos + j + 1)[1]
                                       + kv_w)
    return total
