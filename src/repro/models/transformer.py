"""Model assembly: blocks -> scanned segments -> LM with loss / prefill /
decode. Covers all six families (dense, moe, ssm, hybrid, encdec, vlm).

Layer stacks are grouped into *segments* of identical parameter structure and
executed with ``lax.scan`` over stacked parameters, keeping HLO size (and
512-device compile time) independent of depth. Hybrid (Jamba) uses a period-8
macro-block so the 1:7 attn:mamba interleave with alternating MoE still
scans. ``jax.checkpoint`` wraps each scanned body per ``cfg.remat``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.launch.sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (Params, ShapeTree, apply_mlp, apply_norm,
                                 embed_shapes, embed_tokens, init_tree,
                                 lm_logits, mlp_shapes, norm_shapes, pdtype,
                                 spec, stack_specs)
from repro.numerics.ops import CACHE_INPLACE_KEY, note_attention_site

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerKind:
    mixer: str  # attn | mla | ssm
    ffn: str | None  # mlp | moe | None
    mlp_ff: int = 0  # dense MLP hidden size when ffn == "mlp"


@dataclasses.dataclass(frozen=True)
class Segment:
    pattern: tuple[LayerKind, ...]  # sub-layers inside one scanned step
    repeat: int  # scan length


def layer_plan(cfg) -> list[Segment]:
    if cfg.family == "ssm":
        return [Segment((LayerKind("ssm", None),), cfg.n_layers)]
    if cfg.family == "hybrid":
        period = cfg.attn_period
        assert cfg.n_layers % period == 0
        pattern = []
        for i in range(period):
            mixer = "attn" if i == period // 2 else "ssm"
            ffn = "moe" if (cfg.moe and i % cfg.moe.every == 1) else "mlp"
            pattern.append(LayerKind(mixer, ffn, cfg.d_ff))
        return [Segment(tuple(pattern), cfg.n_layers // period)]
    mixer = "mla" if cfg.mla is not None else "attn"
    if cfg.family == "moe":
        segs = []
        n = cfg.n_layers
        if cfg.first_dense_ff:
            segs.append(Segment((LayerKind(mixer, "mlp", cfg.first_dense_ff),), 1))
            n -= 1
        segs.append(Segment((LayerKind(mixer, "moe"),), n))
        return segs
    # dense / vlm / encdec-decoder
    return [Segment((LayerKind(mixer, "mlp", cfg.d_ff),), cfg.n_layers)]


# ---------------------------------------------------------------------------
# block shapes
# ---------------------------------------------------------------------------

def _mixer_shapes(kind: LayerKind, cfg) -> ShapeTree:
    if kind.mixer == "ssm":
        return ssm_mod.ssm_shapes(cfg)
    if kind.mixer == "mla":
        return attn.mla_shapes(cfg)
    return attn.gqa_shapes(cfg)


def block_shapes(kind: LayerKind, cfg, cross: bool = False) -> ShapeTree:
    out: ShapeTree = {"norm1": norm_shapes(cfg), "mixer": _mixer_shapes(kind, cfg)}
    if cross:
        out["norm_x"] = norm_shapes(cfg)
        out["cross"] = attn.cross_shapes(cfg)
    if kind.ffn is not None:
        out["norm2"] = norm_shapes(cfg)
        out["ffn"] = (moe_mod.moe_shapes(cfg) if kind.ffn == "moe"
                      else mlp_shapes(cfg, kind.mlp_ff))
    return out


def segment_shapes(seg: Segment, cfg, cross: bool = False) -> ShapeTree:
    inner = {str(i): block_shapes(k, cfg, cross) for i, k in enumerate(seg.pattern)}
    return stack_specs(inner, seg.repeat) if seg.repeat > 1 else inner


# ---------------------------------------------------------------------------
# block apply (train path + cache-emitting / cache-consuming variants)
# ---------------------------------------------------------------------------

def apply_block(p: Params, kind: LayerKind, h, positions, cfg, numerics,
                mode: str = "train", cache=None, cache_len: int = 0,
                cross_kv=None, pos=None, layer=None):
    """Returns (h, new_cache, aux_loss). ``cross_kv`` is the *encoder hidden
    state* (B, S_src, d); per-layer K/V are derived from it inside the block
    so scanned decoder stacks keep one parameter structure. ``layer``
    (decode): ``cache`` is the scanned segment's layer stack, this block
    its layer ``layer``; the block writes its token into the stack in place
    and returns the whole stack."""
    # Megatron sequence parallelism (perf iteration C1): the residual stream
    # is sharded along seq over the model axis between mixer/FFN bodies —
    # norms/adds run 1/16th-size, the scan carry shrinks 16x, and GSPMD
    # materializes the all-gather(seq) / reduce-scatter(seq) pair around the
    # head-sharded attention and TP MLP exactly like Megatron-SP. No-op when
    # seq doesn't divide the axis (decode S=1, whisper enc 1500).
    h = constrain(h, ("batch", "seq", None))
    # C2: pin the norm OUTPUT to the seq shard too — otherwise GSPMD hoists
    # the all-gather above the norm and the f32 norm intermediates run at
    # full sequence length inside the layer scan (measured 38x
    # f32[B,S,d] = 2.1 GB/op on qwen-110b). Megatron-SP gathers the bf16
    # norm output, 4x smaller and 1/16th as often.
    x = constrain(apply_norm(p["norm1"], h, cfg, numerics),
                  ("batch", "seq", None))
    new_cache = None
    if kind.mixer == "ssm":
        if mode == "train":
            y = ssm_mod.ssm_train(p["mixer"], x, cfg, numerics)
        elif mode == "prefill":
            y, new_cache = ssm_mod.ssm_prefill(p["mixer"], x, cfg, numerics)
        else:
            y, new_cache = ssm_mod.ssm_decode(p["mixer"], x, cache, cfg,
                                              numerics, layer)
    elif kind.mixer == "mla":
        if mode == "train":
            y = attn.mla_train(p["mixer"], x, positions, cfg, numerics)
        elif mode == "prefill":
            y, new_cache = attn.mla_prefill(p["mixer"], x, positions, cfg, numerics, cache_len)
        else:
            y, new_cache = attn.mla_decode(p["mixer"], x, pos, cache, cfg,
                                           numerics, layer)
    else:
        if mode == "train":
            y = attn.gqa_train(p["mixer"], x, positions, cfg, numerics)
        elif mode == "prefill":
            y, new_cache = attn.gqa_prefill(p["mixer"], x, positions, cfg, numerics, cache_len)
        else:
            y, new_cache = attn.gqa_decode(p["mixer"], x, pos, cache, cfg,
                                           numerics, layer)
    h = h + y
    if cross_kv is not None:
        xc = apply_norm(p["norm_x"], h, cfg, numerics)
        kv = attn.cross_kv(p["cross"], cross_kv, cfg)
        h = h + attn.cross_apply(p["cross"], xc, kv, cfg, numerics)
    aux = jnp.zeros((), jnp.float32)
    if kind.ffn is not None:
        x2 = constrain(apply_norm(p["norm2"], h, cfg, numerics),
                       ("batch", "seq", None))
        if kind.ffn == "moe":
            y2, probs = moe_mod.moe_block(p["ffn"], x2, cfg, numerics,
                                          return_probs=True)
            if mode == "train":
                aux = moe_mod.load_balance_loss_from_probs(probs, cfg)
        else:
            y2 = apply_mlp(p["ffn"], x2, cfg, numerics)
        h = h + y2
    h = constrain(h, ("batch", "seq", None))
    return h, new_cache, aux


def _maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def apply_segment(p_seg: Params, seg: Segment, h, positions, cfg, numerics,
                  mode: str = "train", caches=None, cache_len: int = 0,
                  cross_kv=None, pos=None, layer_offset: int = 0):
    """Scan a segment. caches: pytree stacked over `repeat` (or None).

    ``numerics`` is either one backend for the whole segment (the
    homogeneous path) or a plan-resolved object exposing ``for_layer(i)``
    (``repro.plan.numerics.PlanNumerics``); ``layer_offset`` is the global
    index of this segment's first layer. Heterogeneous plans split the scan
    into runs of consecutive layers with identical assignments — a uniform
    plan collapses to a single run over the unsliced stack, i.e. exactly
    the homogeneous program.

    Decode carries the stacked caches through the layer loop (carry, not
    xs/ys): each layer writes its token's rows into the stack in place at
    its layer index and reads its slice for attention, so no layer writes
    its whole slice back and no step copies the stack; every group of a
    split plan scans the one stack at its offset. Counted once per traced
    segment under ``CACHE_INPLACE_KEY``.

    Returns (h, stacked caches or None, aux sum).
    """
    npat = len(seg.pattern)

    def make_body(layer_nums):
        def body(carry, xs):
            h_in = carry
            p_layer, cache_layer = xs
            aux_sum = jnp.zeros((), jnp.float32)
            new_caches = {}
            for i, kind in enumerate(seg.pattern):
                c_i = cache_layer[str(i)] if cache_layer is not None else None
                h_out, nc, aux = apply_block(
                    p_layer[str(i)], kind, h_in, positions, cfg,
                    layer_nums[i], mode=mode, cache=c_i, cache_len=cache_len,
                    cross_kv=cross_kv, pos=pos)
                h_in = h_out
                new_caches[str(i)] = nc
                aux_sum = aux_sum + aux
            return h_in, (new_caches, aux_sum)
        return body

    def make_decode_body(layer_nums):
        def body(carry, xs):
            h_in, stack = carry
            p_layer, layer = xs
            stack = dict(stack)
            for i, kind in enumerate(seg.pattern):
                h_in, stack[str(i)], _ = apply_block(
                    p_layer[str(i)], kind, h_in, positions, cfg,
                    layer_nums[i], mode=mode, cache=stack[str(i)],
                    cross_kv=cross_kv, pos=pos, layer=layer)
            return (h_in, stack), None
        return body

    plan_aware = hasattr(numerics, "for_layer")

    def nums_at(r: int):
        if not plan_aware:
            return (numerics,) * npat
        return tuple(numerics.for_layer(layer_offset + r * npat + j)
                     for j in range(npat))

    if seg.repeat == 1:
        h, (ncache, aux) = make_body(nums_at(0))(h, (p_seg, caches))
        return h, ncache, aux

    # runs of consecutive scan steps whose per-position numerics agree
    # (plan backends are interned, so equal assignments compare identical)
    groups: list[list] = []  # [start, length, layer_nums]
    for r in range(seg.repeat):
        nt = nums_at(r)
        if groups and groups[-1][2] == nt:
            groups[-1][1] += 1
        else:
            groups.append([r, 1, nt])

    if mode == "decode":
        note_attention_site(CACHE_INPLACE_KEY)
        for start, length, nt in groups:
            p_sl = p_seg if len(groups) == 1 else jax.tree.map(
                lambda x: jax.lax.slice_in_dim(x, start, start + length,
                                               axis=0), p_seg)
            (h, caches), _ = jax.lax.scan(
                make_decode_body(nt), (h, caches),
                (p_sl, jnp.arange(start, start + length, dtype=jnp.int32)))
        return h, caches, jnp.zeros((), jnp.float32)

    if len(groups) == 1:
        body_fn = (_maybe_remat(make_body(groups[0][2]), cfg)
                   if mode == "train" else make_body(groups[0][2]))
        h, (ncaches, auxs) = jax.lax.scan(body_fn, h, (p_seg, caches))
        return h, ncaches, auxs.sum()

    aux_total = jnp.zeros((), jnp.float32)
    nc_parts = []
    for start, length, nt in groups:
        def sl(x, start=start, length=length):
            return jax.lax.slice_in_dim(x, start, start + length, axis=0)
        p_sl = jax.tree.map(sl, p_seg)
        c_sl = jax.tree.map(sl, caches) if caches is not None else None
        body_fn = (_maybe_remat(make_body(nt), cfg) if mode == "train"
                   else make_body(nt))
        h, (nc, auxs) = jax.lax.scan(body_fn, h, (p_sl, c_sl))
        nc_parts.append(nc)
        aux_total = aux_total + auxs.sum()
    ncaches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *nc_parts)
    return h, ncaches, aux_total


# ---------------------------------------------------------------------------
# encoder (whisper): non-causal full-attention stack over stub frame embeddings
# ---------------------------------------------------------------------------

def encoder_shapes(cfg) -> ShapeTree:
    enc = cfg.encoder
    dt = pdtype(cfg)
    kind = LayerKind("attn", "mlp", cfg.d_ff)
    layer = {"norm1": norm_shapes(cfg), "mixer": attn.gqa_shapes(cfg),
             "norm2": norm_shapes(cfg), "ffn": mlp_shapes(cfg, cfg.d_ff)}
    return {
        "pos": spec((enc.source_len, cfg.d_model), dt),
        "layers": stack_specs(layer, enc.n_layers),
        "final_norm": norm_shapes(cfg),
    }


def encoder_forward(p: Params, frames: jax.Array, cfg, numerics) -> jax.Array:
    """frames: (B, S_src, d) stub frame/patch embeddings -> encoder hidden."""
    b, s, _ = frames.shape
    frames = frames.astype(pdtype(cfg))  # stub inputs arrive f32
    h = frames + p["pos"][:s].astype(frames.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(h_in, p_layer):
        x = apply_norm(p_layer["norm1"], h_in, cfg, numerics)
        y = attn.gqa_train(p_layer["mixer"], x, positions, cfg, numerics, causal=False)
        h_mid = h_in + y
        x2 = apply_norm(p_layer["norm2"], h_mid, cfg, numerics)
        return h_mid + apply_mlp(p_layer["ffn"], x2, cfg, numerics), None

    h, _ = jax.lax.scan(body, h, p["layers"])
    return apply_norm(p["final_norm"], h, cfg, numerics)


# ---------------------------------------------------------------------------
# full-model parameter tree
# ---------------------------------------------------------------------------

def model_shapes(cfg) -> ShapeTree:
    dt = pdtype(cfg)
    cross = cfg.family == "encdec"
    out: ShapeTree = {
        "embed": embed_shapes(cfg),
        "segments": {f"seg{i}": segment_shapes(seg, cfg, cross)
                     for i, seg in enumerate(layer_plan(cfg))},
        "final_norm": norm_shapes(cfg),
    }
    if cfg.learned_pos:
        out["pos"] = spec((cfg.max_pos, cfg.d_model), dt)
    if cfg.encoder is not None:
        out["encoder"] = encoder_shapes(cfg)
    if cfg.frontend == "vision_stub":
        out["projector"] = {
            "norm": {"scale": spec((cfg.frontend_dim,), dt),
                     "bias": spec((cfg.frontend_dim,), dt)},
            "w1": spec((cfg.frontend_dim, cfg.d_model), dt),
            "b1": spec((cfg.d_model,), dt),
            "w2": spec((cfg.d_model, cfg.d_model), dt),
            "b2": spec((cfg.d_model,), dt),
        }
    return out


def init_params(key: jax.Array, cfg) -> Params:
    return init_tree(key, model_shapes(cfg))


def _project_frontend(p: Params, emb: jax.Array, cfg, numerics) -> jax.Array:
    """InternVL-style MLP projector: patch embeddings -> d_model tokens."""
    pr = p["projector"]
    xf = emb.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    x = ((xf - mu) * jax.lax.rsqrt(var + 1e-5) * pr["norm"]["scale"]
         + pr["norm"]["bias"]).astype(emb.dtype)
    h = numerics.gelu(x @ pr["w1"] + pr["b1"])
    return (h @ pr["w2"] + pr["b2"]).astype(emb.dtype)


def _embed_inputs(p: Params, tokens: jax.Array, positions: jax.Array, cfg,
                  numerics, frontend_emb=None) -> jax.Array:
    h = embed_tokens(p["embed"], tokens)
    if frontend_emb is not None and cfg.frontend == "vision_stub":
        patches = _project_frontend(p, frontend_emb, cfg, numerics)
        n = patches.shape[1]
        h = jnp.concatenate([patches.astype(h.dtype), h[:, n:]], axis=1)
    if cfg.learned_pos:
        h = h + p["pos"][positions].astype(h.dtype)
    return constrain(h, ("batch", "seq", None))


# ---------------------------------------------------------------------------
# train-mode forward + chunked cross-entropy loss
# ---------------------------------------------------------------------------

def backbone(p: Params, h, positions, cfg, numerics, mode="train",
             caches=None, cache_len: int = 0, cross_kv=None, pos=None):
    """Run all segments. Returns (h, caches-per-segment, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_caches = {}
    offset = 0
    for i, seg in enumerate(layer_plan(cfg)):
        name = f"seg{i}"
        c = caches[name] if caches is not None else None
        h, nc, a = apply_segment(p["segments"][name], seg, h, positions, cfg,
                                 numerics, mode=mode, caches=c,
                                 cache_len=cache_len, cross_kv=cross_kv,
                                 pos=pos, layer_offset=offset)
        new_caches[name] = nc
        aux = aux + a
        offset += seg.repeat * len(seg.pattern)
    h = apply_norm(p["final_norm"], h, cfg, numerics)
    return h, new_caches, aux


def forward(p: Params, tokens: jax.Array, cfg, numerics,
            frontend_emb=None, enc_frames=None) -> jax.Array:
    """Training-shaped forward -> logits (B, S, V). For large-vocab training
    use ``loss_fn`` instead (chunked CE, never materializes full logits)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cross = encoder_forward(p["encoder"], enc_frames, cfg, numerics) if enc_frames is not None else None
    h = _embed_inputs(p, tokens, positions, cfg, numerics, frontend_emb)
    h, _, _ = backbone(p, h, positions, cfg, numerics, cross_kv=cross)
    return lm_logits(p["embed"], h)


def chunked_ce_loss(p_embed: Params, h: jax.Array, labels: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """Mean CE over masked tokens; logits materialized LOSS_CHUNK sequence
    positions at a time (vocab up to 256k x 1M tokens never forms a (B, S, V)
    buffer). Chunks run along the *sequence* axis so the batch axis keeps its
    DP sharding inside the scan — chunking along flattened global tokens
    would turn the scan axis into the sharded axis and replicate the LM-head
    matmul on every data shard (measured: ~1000x collective blow-up)."""
    b, s, d = h.shape
    chunk = min(LOSS_CHUNK, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = h.shape[1] // chunk
    mask = mask.astype(jnp.float32)

    def body(carry, xs):
        hc, lc, mc = xs  # (B, chunk, d), (B, chunk), (B, chunk)
        logits = lm_logits(p_embed, hc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((lse - gold) * mc), None

    xs = (h.reshape(b, n, chunk, d).transpose(1, 0, 2, 3),
          labels.reshape(b, n, chunk).transpose(1, 0, 2),
          mask.reshape(b, n, chunk).transpose(1, 0, 2))
    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32), xs)
    return total / jnp.maximum(mask.sum(), 1.0)


AUX_WEIGHT = 0.01


def loss_fn(p: Params, batch: dict, cfg, numerics) -> tuple[jax.Array, dict]:
    """batch: tokens (B,S) int32, labels (B,S) int32, mask (B,S) -- plus
    optional frontend_emb / enc_frames for vlm / encdec."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cross = (encoder_forward(p["encoder"], batch["enc_frames"], cfg, numerics)
             if cfg.encoder is not None else None)
    h = _embed_inputs(p, tokens, positions, cfg, numerics,
                      batch.get("frontend_emb"))
    h, _, aux = backbone(p, h, positions, cfg, numerics, cross_kv=cross)
    ce = chunked_ce_loss(p["embed"], h, batch["labels"], batch["mask"])
    loss = ce + AUX_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving: cache specs, prefill, decode
# ---------------------------------------------------------------------------

def _kind_cache_spec(kind: LayerKind, cfg, b: int, cache_len: int, dtype):
    if kind.mixer == "ssm":
        return ssm_mod.ssm_state_specs(cfg, b, dtype)
    if kind.mixer == "mla":
        return attn.mla_cache_specs(cfg, b, cache_len, dtype)
    return attn.gqa_cache_specs(cfg, b, cache_len, dtype)


def cache_shapes(cfg, b: int, cache_len: int) -> ShapeTree:
    dt = pdtype(cfg)
    out = {}
    for i, seg in enumerate(layer_plan(cfg)):
        inner = {str(j): _kind_cache_spec(k, cfg, b, cache_len, dt)
                 for j, k in enumerate(seg.pattern)}
        out[f"seg{i}"] = stack_specs(inner, seg.repeat) if seg.repeat > 1 else inner
    return out


def splice_cache(cfg, pool: Params, one: Params, slot: int) -> Params:
    """Write one request's prefilled cache (batch size 1) into ``slot`` of a
    pooled cache along the *batch* axis.

    Scanned segments stack their cache leaves with a leading layer axis
    (``stack_specs``), so the batch axis is 1 there and 0 for unscanned
    segments — a naive tree-wide ``axis=0`` splice would hit the layer axis
    (and silently clamp the slot index to 0 for every slot past the layer
    count, corrupting the whole pool).
    """
    out = {}
    for i, seg in enumerate(layer_plan(cfg)):
        name = f"seg{i}"
        ax = 1 if seg.repeat > 1 else 0
        out[name] = jax.tree.map(
            lambda p, o: jax.lax.dynamic_update_slice_in_dim(
                p, o.astype(p.dtype), slot, axis=ax),
            pool[name], one[name])
    return out


def init_cache(cfg, b: int, cache_len: int) -> Params:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype) if s.dtype != jnp.int32
                        else jnp.full(s.shape, -1, jnp.int32), cache_shapes(cfg, b, cache_len))


def prefill(p: Params, tokens: jax.Array, cfg, numerics, cache_len: int,
            frontend_emb=None, enc_frames=None):
    """Process the prompt; returns (last-position logits, caches, cross)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cross = (encoder_forward(p["encoder"], enc_frames, cfg, numerics)
             if cfg.encoder is not None else None)
    h = _embed_inputs(p, tokens, positions, cfg, numerics, frontend_emb)
    h, caches, _ = backbone(p, h, positions, cfg, numerics, mode="prefill",
                            cache_len=cache_len, cross_kv=cross)
    logits = lm_logits(p["embed"], h[:, -1:])
    return logits, caches, cross


def mask_cache_tail(caches: Params, true_lens: jax.Array) -> Params:
    """Mark every cache position row at or past each batch row's true length
    as *empty* (``pos = -1``, the ``init_cache`` sentinel the attention mask
    treats as dead).

    A padded (bucketed) prefill writes the pad suffix's K/V rows with live
    position values — a later decode step would attend to that garbage. The
    K/V rows themselves can stay: with their ``pos`` slot at -1 the mask
    assigns them ``NEG`` scores, and decode overwrites row ``p`` in place
    when the sequence actually reaches position ``p``. Only positional
    (attention) caches carry a ``pos`` leaf; SSM state is not positional and
    cannot be padded-prefilled at all (callers gate on :func:`packable`).
    """
    lens = jnp.asarray(true_lens, jnp.int32)

    def one(path, leaf):
        field = str(getattr(path[-1], "key", path[-1])).lstrip(".")
        if field != "pos":
            return leaf
        # (B, S) — or (L, B, S) for scan-stacked segments; the (B, S)
        # validity mask broadcasts across the leading layer axis either way
        valid = jnp.arange(leaf.shape[-1], dtype=jnp.int32) < lens[:, None]
        return jnp.where(valid, leaf, jnp.int32(-1))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [one(path, leaf) for path, leaf in flat])


def _padding_refusal(cfg) -> str | None:
    """Why ``cfg`` cannot be prefilled right-padded, or None if it can."""
    if cfg.encoder is not None or cfg.frontend is not None:
        return "encoder/frontend configs must use exact-length prefill"
    if cfg.sliding_window is not None:
        return "sliding-window caches wrap; use exact-length prefill"
    kinds = [k for seg in layer_plan(cfg) for k in seg.pattern]
    if any(k.mixer == "ssm" for k in kinds):
        return ("SSM state is cumulative, a pad suffix corrupts it; use "
                "exact-length prefill")
    if any(k.ffn == "moe" for k in kinds):
        return ("expert capacity grows with the padded length and keeps "
                "tokens an exact-length prefill drops; use exact-length "
                "prefill")
    return None


def packable(cfg) -> bool:
    """Can ``cfg`` be prefilled right-padded (:func:`prefill_padded`), and
    so packed several prompts to one bucketed dispatch? True for attention-
    cache decoders with no sliding window, no encoder, no front end, no SSM
    mixer and no capacity-bounded MoE layer."""
    return _padding_refusal(cfg) is None


def prefill_padded(p: Params, tokens: jax.Array, true_lens: jax.Array, cfg,
                   numerics, cache_len: int):
    """Bucketed prefill: ``tokens`` is (B, S_bucket) with each row right-
    padded to the bucket length and ``true_lens`` (B,) giving the real
    prompt lengths. Returns (per-row logits at position ``true_len - 1``
    (B, 1, V), caches with the pad tails masked dead, None).

    Positions run 0..S_bucket-1 exactly as a full-length prefill would:
    causality already guarantees every row below its true length computes
    the same values as an exact-length prefill of that prompt (the pad
    suffix can only influence *later* positions), so the gathered logits
    match the exact path and :func:`mask_cache_tail` is the only repair the
    caches need. Restricted to :func:`packable` configs: SSM state is
    cumulative (a pad token pollutes it for good), sliding-window caches
    wrap ``pos % cache_len``, encoder/frontend extras carry no per-row
    length, and MoE expert capacity scales with the padded length — callers
    fall back to exact-length prefill there.
    """
    why = _padding_refusal(cfg)
    if why is not None:
        raise ValueError(f"prefill_padded: {why}")
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h = _embed_inputs(p, tokens, positions, cfg, numerics)
    h, caches, _ = backbone(p, h, positions, cfg, numerics, mode="prefill",
                            cache_len=cache_len)
    idx = (jnp.asarray(true_lens, jnp.int32) - 1)[:, None, None]
    logits = lm_logits(p["embed"], jnp.take_along_axis(h, idx, axis=1))
    return logits, mask_cache_tail(caches, true_lens), None


def extract_cache_row(cfg, pool: Params, i) -> Params:
    """Slice batch row ``i`` out of a pooled cache, keeping the batch dim —
    the inverse of :func:`splice_cache`'s insertion, with the same per-
    segment batch-axis bookkeeping (scan-stacked segments lead with a layer
    axis). ``i`` may be a traced index."""
    out = {}
    for si, seg in enumerate(layer_plan(cfg)):
        name = f"seg{si}"
        ax = 1 if seg.repeat > 1 else 0
        out[name] = jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1, axis=ax),
            pool[name])
    return out


def decode_step(p: Params, token: jax.Array, pos: jax.Array, caches, cfg,
                numerics, cross=None):
    """token: (B, 1) int32; pos: scalar int32 (uniform across the batch) or
    (B,) per-slot positions (continuous batching: each slot decodes at its own
    next position). Returns (logits, new caches)."""
    b = token.shape[0]
    pos, positions = attn._decode_positions(pos, b)
    h = _embed_inputs(p, token, positions, cfg, numerics)
    h, caches, _ = backbone(p, h, positions, cfg, numerics, mode="decode",
                            caches=caches, cross_kv=cross, pos=pos)
    return lm_logits(p["embed"], h), caches
