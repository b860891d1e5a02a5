"""Compile-only rehearsal of the serving-path kernels for a TPU v5e.

Every kernel on the fused serving path is lowered with ``interpret=False``
and compiled by the installed TPU compiler (Mosaic) for one chip of a
*described* ``v5e:2x2`` topology — no chip is attached, nothing runs. A
kernel that Mosaic would reject on the chip (unsupported shape casts,
non-MXU matmul operand types, blocks that break the (8, 128) tiling rule,
unlowerable primitives) fails here, at the real model widths:

* yi_6b decode and prefill attention: head_dim 128, GQA group 8, a
  4096-slot KV cache, 512-token prefill buckets; decode folds the group
  into the rows of one tile per kv stripe, also at group 6 (48 heads over
  8 kv heads, Mixtral-8x22B / Minitron-8B), where the rows pad to 8;
* minicpm3_4b's MLA attention: prefill expanded (Dk 96 != Dv 64), decode
  absorbed (``mla_flash_lib``: 16 slots, the 40 query heads the rows of
  one program over a 4096-position latent stripe, keys 256 + 32, values
  the 256-wide latent);
* rmsnorm at d_model 4096, softmax on aligned and unaligned widths;
* the fused ROM walk over the full default manifest with uniform (v1)
  slots, and with a segmented (v2) slot, plus the per-slot ROM read;
* the served decode tick of yi_6b and minicpm3_4b (2 layers, 16 slots x
  4096): the cache stack stays one buffer through the layer and step
  loops, written one token row at a time.

The topology and the shardings built from it live in module fixtures, so
the TPU library is loaded only by the test process that runs this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import DEFAULT_LIBRARY_KINDS, InterpLibrary, default_explorer
from repro.api.config import spec_for
from repro.kernels.flashattn.ops import attention_fused_library
from repro.kernels.interp.kernel import rom_eval_2d
from repro.kernels.interp.ops import library_walk
from repro.kernels.rmsnorm.ops import approx_rmsnorm_library
from repro.kernels.softmax.ops import approx_softmax_library, lib_meta


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep it off so nothing warns."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def lib():
    return default_explorer().compile()


@pytest.fixture(scope="module")
def lib_v2():
    """The default manifest with its tanh slot replaced by a segmented
    (ROM v2) design."""
    from repro.segment import explore_segmented, min_uniform_depth

    spec = spec_for("tanh", 8)
    seg = explore_segmented(spec, max_depth=min_uniform_depth(
        spec, engine="batched"), engine="batched")
    ex = default_explorer()
    designs = [seg if k == "tanh" else ex.get_table(k)
               for k in DEFAULT_LIBRARY_KINDS]
    return InterpLibrary.from_designs(designs, list(DEFAULT_LIBRARY_KINDS))


def _compile(fn, args, sharding):
    """Lower ``fn`` on shape-only arguments placed on ``sharding`` and
    compile it for that (described) device; returns the HLO text."""
    sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    text = jax.jit(fn).lower(*sds).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# ROM reads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("version", ["v1", "v2"])
def test_library_walk_compiles(one_chip, no_cache, lib, lib_v2, version):
    library = lib if version == "v1" else lib_v2
    walk, dp = library.walk_rows()
    codes = _sds((64, 128), jnp.int32)

    def fn(codes, fids, coeffs, walk, dp):
        return library_walk(codes, fids, coeffs, walk, dp, use_kernel=True,
                            interpret=False)

    _compile(fn, (codes, codes, library.coeffs, walk, dp), one_chip)


@pytest.mark.parametrize("kind", ["exp2neg", "rsqrt", "tanh"])
def test_rom_read_compiles(one_chip, no_cache, lib_v2, kind):
    """The per-slot read every fused consumer inlines (tanh is the
    segmented slot)."""
    m = lib_v2.meta(kind)
    lm = lib_meta(lib_v2, kind)

    def fn(codes, rom):
        return rom_eval_2d(codes, rom.reshape(-1, 3), fid=lm["fid"],
                           r_max=lib_v2.coeffs.shape[1], **lm["eval"],
                           interpret=False)

    assert (m.seg_depth > 0) == (kind == "tanh")
    _compile(fn, (_sds((64, 128), jnp.int32), lib_v2.coeffs), one_chip)


# ---------------------------------------------------------------------------
# fused consumers at model widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,d", [(8, 4096), (2048, 4096), (8, 2560)])
def test_rmsnorm_compiles(one_chip, no_cache, lib, rows, d):
    def fn(x, gamma, library):
        return approx_rmsnorm_library(x, gamma, library, use_kernel=True,
                                      interpret=False)

    _compile(fn, (_sds((rows, d), jnp.bfloat16), _sds((d,), jnp.float32),
                  lib), one_chip)


@pytest.mark.parametrize("shape", [(8, 32, 4096), (4, 8, 100)])
def test_softmax_compiles(one_chip, no_cache, lib, shape):
    def fn(x, library):
        return approx_softmax_library(x, library, use_kernel=True,
                                      interpret=False)

    _compile(fn, (_sds(shape, jnp.float32), lib), one_chip)


# (batch, q len, kv len, heads, kv heads, Dk, Dv, Dr): Dr > 0 is the
# absorbed-latent form, keys Dk + Dr in two parts and values the keys' Dk
ATTN = {
    "yi_6b-decode": (8, 1, 4096, 32, 4, 128, 128, 0),
    "yi_6b-prefill": (2, 512, 512, 32, 4, 128, 128, 0),
    "mixtral_8x22b-decode": (8, 1, 4096, 48, 8, 128, 128, 0),
    "minicpm3_4b-mla-decode": (16, 1, 4096, 40, 1, 256, 256, 32),
    "minicpm3_4b-mla-prefill": (1, 256, 256, 40, 40, 96, 64, 0),
}


@pytest.mark.parametrize("case", sorted(ATTN))
def test_flash_compiles(one_chip, no_cache, lib, case):
    """Each call compiles with its whole K/V stripes VMEM-resident (the
    compiler refuses a program whose blocks overflow VMEM). The absorbed
    decode runs one program per slot: its query operands are (slots, 40
    rows, D) tiles."""
    b, sq, sk, h, kvh, dk, dv, dr = ATTN[case]

    def fn(q, k, v, q_pos, kv_pos, library, *rope):
        kw = {}
        if rope:
            v = k
            kw = dict(q_rope=rope[0], k_rope=rope[1], scale=96 ** -0.5)
        return attention_fused_library(q, k, v, library, causal=True,
                                       q_pos=q_pos, kv_pos=kv_pos,
                                       use_kernel=True, interpret=False, **kw)

    rope = ((_sds((b, sq, h, dr), jnp.bfloat16),
             _sds((b, sk, kvh, dr), jnp.bfloat16)) if dr else ())
    text = _compile(fn, (_sds((b, sq, h, dk), jnp.bfloat16),
                         _sds((b, sk, kvh, dk), jnp.bfloat16),
                         _sds((b, sk, kvh, dv), jnp.bfloat16),
                         _sds((b, sq), jnp.int32), _sds((b, sk), jnp.int32),
                         lib, *rope), one_chip)
    if dr:
        call = next(line for line in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in line)
        assert "mla_flash_lib" in call.split("=", 1)[0]
        assert f"bf16[{b},{h},{dk}]" in call and f"bf16[{b},{h},{dr}]" in call


def test_served_kernels_carry_their_names(one_chip, no_cache, lib):
    """Each kernel of the served path is named in the compiled program (the
    instruction name the device trace's ``XLA Ops`` events carry), not left
    as an anonymous ``closed_call``; the activation kernel keeps its
    ``_library_eval`` prefix."""
    import re

    walk, dp = lib.walk_rows()

    def fn(q, k, v, q_pos, kv_pos, x, gamma, codes, library, walk, dp):
        o = attention_fused_library(q, k, v, library, q_pos=q_pos,
                                    kv_pos=kv_pos, use_kernel=True,
                                    interpret=False)
        # absorbed MLA decode: 32 query heads over one latent stripe
        lat = k[:, :, :1]
        m = attention_fused_library(q, lat, lat, library,
                                    q_pos=q_pos, kv_pos=kv_pos,
                                    q_rope=q[..., :32], k_rope=k[:, :, :1, :32],
                                    scale=96 ** -0.5, use_kernel=True,
                                    interpret=False)
        y = approx_rmsnorm_library(x, gamma, library, use_kernel=True,
                                   interpret=False)
        z = approx_softmax_library(x.astype(jnp.float32), library,
                                   use_kernel=True, interpret=False)
        e = library.eval_fused(codes, codes, use_kernel=True,
                               interpret=False)
        w = library_walk(codes, codes, library.coeffs, walk, dp,
                         use_kernel=True, interpret=False)
        return o, m, y, z, e, w

    text = _compile(fn, (_sds((8, 1, 32, 128), jnp.bfloat16),
                         _sds((8, 512, 4, 128), jnp.bfloat16),
                         _sds((8, 512, 4, 128), jnp.bfloat16),
                         _sds((8, 1), jnp.int32), _sds((8, 512), jnp.int32),
                         _sds((8, 4096), jnp.bfloat16),
                         _sds((4096,), jnp.float32),
                         _sds((64, 128), jnp.int32), lib, walk, dp),
                    one_chip)
    instr = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
    names = {re.sub(r"\.\d+$", "", instr.match(line).group(1))
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert names == {"flash_lib", "mla_flash_lib", "rmsnorm_lib",
                     "softmax_lib", "_library_eval", "_library_walk"}, names


# ---------------------------------------------------------------------------
# the engine tick: the cache stack stays one buffer through both loops
# ---------------------------------------------------------------------------

# the temporaries (bytes) of these ticks (2 layers, 16 slots x 4096,
# horizon 2, interp-fused, jax 0.9.0 / libtpu 0.0.34) when the layer loop
# took the caches as xs and returned them as ys: each decode step wrote
# every layer's whole slice back and copied the stack
XS_YS_TEMP_BYTES = {"yi_6b": 489_127_936, "minicpm3_4b": 126_959_616}

_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(%([\w.\-]+)(?:, %([\w.\-]+))?")


def _stack_ops(text: str, stacks: set) -> list:
    """(op, dims, update dims) of every ``copy`` and
    ``dynamic-update-slice`` outside the entry computation (in the step loop, the layer loop and
    what they call) whose result has a stacked cache's dims."""
    dims, ops, entry = {}, [], False
    for line in text.splitlines():
        if not line.startswith(" "):
            entry = line.startswith("ENTRY")
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op, _, update = m.groups()
        dims[name] = tuple(int(d) for d in shape.split(",") if d)
        if (not entry and op in ("copy", "dynamic-update-slice")
                and dims[name] in stacks):
            ops.append((op, dims[name], update))
    return [(op, d, dims.get(update)) for op, d, update in ops]


@pytest.mark.parametrize("arch", sorted(XS_YS_TEMP_BYTES))
def test_tick_carries_cache_stack_in_place(one_chip, no_cache, lib,
                                           monkeypatch, arch):
    """The served tick at published widths (2 layers, 16 slots x 4096,
    horizon 2), kernel path forced: its one scanned segment is traced with
    the stack carried, no step copies the stack and no layer writes its
    whole slice back. Every write into a stack inside the loops
    is one token row (layer, slot and position extents 1); the
    temporaries fall against the xs/ys loop's by at least 80% of one
    stacked cache."""
    import math

    from repro.configs.base import get_config
    from repro.models import transformer as tf
    from repro.numerics.ops import CACHE_INPLACE_KEY, count_attention_sites
    from repro.serve.engine import make_engine_tick

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config(arch).replace(n_layers=2, numerics="interp-fused")
    slots, cache_len = 16, 4096
    caches = tf.cache_shapes(cfg, slots, cache_len)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    tick = jax.jit(make_engine_tick(cfg, 2), donate_argnums=(1, 2, 4))
    with count_attention_sites({}) as sites:
        lowered = tick.lower(place(tf.model_shapes(cfg)),
                             place(_sds((slots, 1), jnp.int32)),
                             place(_sds((slots,), jnp.int32)),
                             place(_sds((slots,), jnp.bool_)), place(caches),
                             library=place(lib))
    assert sites[CACHE_INPLACE_KEY] == len(tf.layer_plan(cfg)) == 1
    exe = lowered.compile()
    text = exe.as_text()
    assert "mla_flash_lib" in text if cfg.mla else "flash_lib" in text
    leaves = jax.tree.leaves(caches)
    stacks = {a.shape for a in leaves}
    writes = _stack_ops(text, stacks)
    assert writes and not [w for w in writes if w[0] == "copy"], writes
    for _, stack, upd in writes:
        # (layers, slots, [kv heads,] positions, [width]): pos has rank 3
        at = len(stack) - (1 if len(stack) == 3 else 2)
        assert upd and (upd[0], upd[1], upd[at]) == (1, 1, 1), (stack, upd)
    stack_bytes = sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves)
    temp = exe.memory_analysis().temp_size_in_bytes
    assert temp <= XS_YS_TEMP_BYTES[arch] - 0.8 * stack_bytes, (temp,
                                                                 stack_bytes)


# ---------------------------------------------------------------------------
# design-space kernels: off the serving path, not Mosaic-compilable yet
# ---------------------------------------------------------------------------

@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Mosaic: 'The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array' for the (1, 3n) row blocks, and behind it 'Unimplemented "
    "primitive in Pallas TPU lowering: dynamic_slice' for the per-offset "
    "lane slices; the dspace engines refuse to run on a TPU instead"))
def test_dspace_envelopes_compile(one_chip, no_cache):
    from repro.kernels.dspace.kernel import envelopes_parity_batched

    def fn(lo, hi):
        return envelopes_parity_batched(lo, hi, interpret=False)

    _compile(fn, (_sds((64, 256), jnp.float32), _sds((64, 256), jnp.float32)),
             one_chip)
