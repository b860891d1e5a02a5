"""Operation and byte counts from shapes, against hand-worked small cases:
only filled cache positions count, MLA decode is counted absorbed."""
from __future__ import annotations

import json
import math

import jax
import pytest

from bench.counts import act_lib, flash_lib, step
from bench.harness import ROOT, module

LLAMA = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "intermediate_size": 10, "vocab_size": 100,
         "num_hidden_layers": 3}
MLA = {"hidden_size": 8, "num_attention_heads": 4, "q_lora_rank": 6,
       "kv_lora_rank": 16, "qk_nope_head_dim": 4, "qk_rope_head_dim": 8,
       "v_head_dim": 5, "intermediate_size": 10, "vocab_size": 100,
       "num_hidden_layers": 3}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


@pytest.fixture(scope="module")
def llama():
    return module("counts", "llama")


@pytest.fixture(scope="module")
def mla():
    return module("counts", "minicpm3")


def test_llama_decode_attention_counts_filled_positions(llama):
    # 4 heads x 10 positions x (QK 16 + PV 16) x 2 flops
    assert llama.decode_attn(LLAMA, 10)[0] == 2 * 4 * 10 * 16 * 2
    # K and V of 2 kv heads at 10 positions, plus q in and o out, bf16
    assert llama.decode_attn(LLAMA, 10)[1] == 2 * (10 * 2 * 16 * 2
                                                    + 2 * 4 * 16)


def test_llama_prefill_is_causal(llama):
    f, b = llama.prefill_attn(LLAMA, 3)  # 6 query-key pairs
    assert f == 2 * 4 * 6 * 16 * 2
    assert b == 2 * 3 * (2 * 4 * 16 + 2 * 2 * 16)


def test_mla_decode_is_absorbed(mla):
    f, b = mla.decode_attn(MLA, 10)
    # scores over kv_lora + rope = 24, values over kv_lora = 16, per head
    assert f == 2 * 4 * 10 * 24 + 2 * 4 * 10 * 16
    # the latent cache is read once for all heads
    assert b == 2 * (10 * 24 + 4 * 24 + 4 * 16)


def test_mla_prefill_uses_expanded_flops(mla):
    f, _ = mla.prefill_attn(MLA, 2)  # 3 pairs
    assert f == 2 * 4 * 3 * (4 + 8 + 5)


@pytest.mark.parametrize("arch,cfg", [("llama", "yi_6b.interp"),
                                      ("minicpm3", "minicpm3_4b.interp")])
def test_weight_bytes_match_the_served_layout(arch, cfg):
    """Everything a decode step reads = all weights but the embedding
    table, in bfloat16 (the reference's layout, checked against the
    program's at run time)."""
    hf = json.loads((ROOT / "bench" / "configs" / f"{cfg}.json").read_text())
    shapes = module("reference", arch).param_shapes(hf)
    total = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    tok = math.prod(shapes["embed"]["tok"].shape)
    assert module("counts", arch).weight_bytes(hf) == 2 * (total - tok)


def test_step_counts_sum_over_live_slots_and_decode_steps(llama):
    s = {"steps": 2, "live": [(5, 2), (9, 2)], "admitted": [3]}
    L = 3
    tok, head = llama.token_flops(LLAMA), llama.head_flops(LLAMA)
    want = 3 * tok + head + L * llama.prefill_attn(LLAMA, 3)[0]
    for n in (6, 7, 10, 11):  # positions 5, 9 plus the new token, 2 steps
        want += tok + head + L * llama.decode_attn(LLAMA, n)[0]
    assert step.flops(llama, LLAMA, s) == want
    b = 2 * llama.weight_bytes(LLAMA)
    for n in (6, 7, 10, 11):
        b += llama.embed_row_bytes(LLAMA) + L * (
            llama.decode_attn(LLAMA, n)[1] + llama.kv_write_bytes(LLAMA))
    assert step.decode_bytes(llama, LLAMA, s) == b


def test_a_slot_that_finishes_partway_through_a_tick_counts_its_tokens(llama):
    """A tick of 4 decode steps in which one slot decodes 4 tokens and
    another only 1: 5 tokens of work, weights read in 4 steps, and the
    flash kernel's last 3 calls hold one query each."""
    s = {"steps": 4, "live": [(10, 4), (20, 1)], "admitted": []}
    L, tok, head = 3, llama.token_flops(LLAMA), llama.head_flops(LLAMA)
    ns = (11, 12, 13, 14, 21)
    assert step.flops(llama, LLAMA, s) == sum(
        tok + head + L * llama.decode_attn(LLAMA, n)[0] for n in ns)
    assert step.decode_bytes(llama, LLAMA, s) == 4 * llama.weight_bytes(
        LLAMA) + sum(llama.embed_row_bytes(LLAMA) + L * (
            llama.decode_attn(LLAMA, n)[1] + llama.kv_write_bytes(LLAMA))
            for n in ns)
    calls = [[11, 21], [12], [13], [14]]
    want = 0.0
    for call in calls:
        f = sum(llama.decode_attn(LLAMA, n)[0] for n in call)
        b = sum(llama.decode_attn(LLAMA, n)[1] for n in call)
        want += L * max(f / 100.0, b / 10.0)
    assert flash_lib.least_s(llama, LLAMA, s, PEAKS) == pytest.approx(want)
    assert act_lib.least_s(llama, LLAMA, s, PEAKS) == pytest.approx(
        5 * 10 * 4 * 3 / 10.0)


def test_flash_least_time_takes_the_larger_bound_per_call(llama):
    s = {"steps": 1, "live": [(4, 1)], "admitted": []}
    f, b = llama.decode_attn(LLAMA, 5)
    assert flash_lib.least_s(llama, LLAMA, s, PEAKS) == pytest.approx(
        3 * max(f / 100.0, b / 10.0))
    idle = {"steps": 4, "live": [], "admitted": []}
    assert flash_lib.least_s(llama, LLAMA, idle, PEAKS) == 0.0


def test_activation_least_time_counts_real_rows_only(llama):
    s = {"steps": 2, "live": [(1, 2), (2, 2)], "admitted": [7]}
    rows = 2 * 2 + 7
    assert act_lib.least_s(llama, LLAMA, s, PEAKS) == pytest.approx(
        rows * 10 * 4 * 3 / 10.0)
