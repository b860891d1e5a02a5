"""Least time of the library activation kernel (the MLP's SiLU walk) in one
engine step: every real token's ``act_elems`` elements read and written
once in bfloat16 in every layer, over the HBM bandwidth (the kernel does a
few integer operations per element; bandwidth bounds it)."""
from __future__ import annotations

BYTES = 2  # bfloat16 in, bfloat16 out


def least_s(arch, hf: dict, step: dict, peaks: dict) -> float:
    rows = sum(n for _, n in step["live"]) + sum(step["admitted"])
    nbytes = (rows * arch.act_elems(hf) * 2 * BYTES
              * hf["num_hidden_layers"])
    return nbytes / peaks["hbm_bytes_per_s"]
