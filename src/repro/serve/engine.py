"""Serving: jit'd prefill/decode steps + a fault-tolerant continuous-batching engine.

``make_serve_step`` builds the decode function the dry-run lowers for the
``decode_32k`` / ``long_500k`` cells: one new token against a seq_len-deep
KV cache (or SSM state), exactly as the shape table specifies.

``ServeEngine`` is a continuous-batching driver: a fixed pool of B
slots, each slot holding one request's cache rows; finished requests free
their slot and a queued request is prefilled into it. Slot state lives in
the batched cache pytree — insertion is a per-slot dynamic_update on the
batch axis.

Interp numerics serve from a compiled :class:`repro.api.InterpLibrary`: the
engine compiles the full library manifest at construction (or accepts a
preloaded artifact, e.g. ``InterpLibrary.load(...)`` — then serving makes
zero exploration calls) and threads it through the jitted prefill/decode
steps as an explicit pytree argument, alongside params and caches. That is
what makes the deployed tables shardable (replicated leaf), donatable and
checkpointable instead of ambient global state.

The default engine is *fused* (DESIGN.md §12): one jitted multi-slot tick
per chunk of decode steps — greedy argmax and the per-slot position bump
happen inside the program, the KV cache (and slot state) buffers are
**donated** so XLA updates them in place instead of copying every tick, and
interp numerics lower through the library-bound fused kernels (ROM gather +
Horner inside softmax/rmsnorm/attention). ``fused=False`` decodes one
dispatch per token with a host argmax, on unfused numerics: the serial rung
of the degradation ladder.

Admission has one path whatever the engine's mode: the queue front drains
into free slots in ascending order, each request gets an admission length
(its prefill bucket where the engine pads — fused, a bucket table set, a
:func:`~repro.models.transformer.packable` config — else its own prompt
length), and each group of same-length requests is one dispatch of
:func:`make_engine_admit`: prefill, pool splice, greedy first token and
slot state in one program.

Since ISSUE 7 the engine carries the serving-robustness layer
(DESIGN.md §14):

  * request lifecycle guarantees — bounded-queue backpressure and
    per-request deadlines with typed :class:`Rejected` errors;
  * an in-program NaN/Inf watchdog sentinel reduced inside the fused scan
    (one extra scalar riding the existing token download, zero extra
    dispatches) that retires a poisoned slot with a structured error
    instead of streaming garbage;
  * a degradation ladder — fused → serial (domain-guarded numerics) →
    exact — walked on repeated watchdog trips, and jumped straight to
    exact on a resident-ROM integrity failure
    (:meth:`InterpLibrary.verify_resident`);
  * a crash-recoverable admission/token journal
    (:mod:`repro.serve.journal`) with :meth:`ServeEngine.resume`.

Since ISSUE 10 the engine also carries the sharded, AOT-warmed serving tier
(DESIGN.md §17):

  * ``mesh=`` — a ``("data", "tp")`` serve mesh
    (:func:`repro.launch.mesh.make_serve_mesh`): the KV pool is sharded
    slot-wise over ``data`` and KV-head-wise over ``tp``, weights follow
    ``sharding.SERVE_PARAM_RULES`` (tensor-parallel, data-replicated), and
    the library ROM(s) are replicated per device — ROM verification and the
    degradation ladder operate on the sharded state unchanged;
  * ``aot_buckets=`` — AOT warm-up (:mod:`repro.serve.aot`): the decode
    tick and a grid of packed bucketed-prefill admission programs are
    ``jit.lower().compile()``d at construction, so steady-state serving
    never pays a compile (``stats["aot_hits"]``/``["aot_misses"]``); short
    prompts pack several-to-one into a padded prefill dispatch
    (:func:`repro.models.transformer.prefill_padded`);
  * ``async_host=`` — the host pipeline (:mod:`repro.serve.pipeline`):
    detokenize + journal bookkeeping move to a background worker behind a
    bounded queue; the main thread's per-tick host work shrinks to the (B,)
    watchdog-sentinel download.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import InterpLibrary, LibraryIntegrityError, default_explorer
from repro.faults.inject import crashpoint
from repro.launch import sharding as shlib
from repro.models import transformer as tf
from repro.numerics.ops import (ATTN_ABSORB_KEY, ATTN_FALLBACK_KEY,
                                ATTN_FOLD_KEY, CACHE_INPLACE_KEY,
                                INTERP_BACKENDS, count_attention_sites,
                                get_numerics)
from repro.serve import aot as aot_mod
from repro.serve import spans as span_lib
from repro.serve.journal import ServeJournal, load_requests
from repro.serve.pipeline import HostPipeline


def _interp(cfg) -> bool:
    """Does this config's numerics backend consult an InterpLibrary?
    Covers the plain, explicitly-fused and degraded-guarded backend names —
    and per-layer plans (DESIGN.md §16), which consult one library per
    distinct slot as long as any site assignment is non-exact."""
    plan = getattr(cfg, "plan", None)
    if plan is not None:
        return plan.uses_interp
    return cfg.numerics in INTERP_BACKENDS


def make_serve_step(cfg, fused: bool = False) -> Callable:
    """decode_step(params, token (B,1), pos () or (B,), caches, cross=None,
    library=None) -> (logits, caches). ``pos`` may be a scalar (uniform
    batch) or a per-slot position vector — continuous batching decodes every
    live slot at its *own* next position. ``library`` is a jit-traced pytree:
    swapping artifacts does not retrace, and the leaf obeys the caller's
    sharding/donation just like params. ``fused=True`` lowers interp
    numerics through the library-bound fused kernels."""

    def step(params, token, pos, caches, cross=None, library=None):
        numerics = get_numerics(cfg, library, fused=fused)
        return tf.decode_step(params, token, pos, caches, cfg, numerics, cross=cross)

    return step


def make_prefill(cfg, cache_len: int, fused: bool = False) -> Callable:
    def pf(params, tokens, frontend_emb=None, enc_frames=None, library=None):
        numerics = get_numerics(cfg, library, fused=fused)
        return tf.prefill(params, tokens, cfg, numerics, cache_len,
                          frontend_emb=frontend_emb, enc_frames=enc_frames)

    return pf


def make_engine_admit(cfg, cache_len: int, pack: int,
                      fused: bool) -> Callable:
    """Admission: prefill ``pack`` prompts, splice each into its slot, take
    each greedy first token and set its slot state — ONE dispatch.

    admit(params, prompts (P, S), true_lens (P,), slots (P,), pool,
    tok (B,1), pos (B,), live (B,), library=None) -> (firsts (P,), pool,
    tok, pos, live). A :func:`~repro.models.transformer.packable` config
    prefills through :func:`~repro.models.transformer.prefill_padded`: rows
    are right-padded to ``S`` (pad id 0 — any in-vocab id works, the pad
    tail is causally invisible and its cache rows are masked dead); any
    other config prefills one unpadded prompt (``P = 1``, ``S`` its length).
    The splice loop unrolls over the static pack size with traced slot
    indices, so one compiled program serves every slot assignment. The
    engine donates ``pool`` and the slot-state vectors: the splice lands in
    place. ``fused`` picks the numerics as the engine's do."""
    padded = tf.packable(cfg)

    def admit(params, prompts, true_lens, slots, pool, tok, pos, live,
              library=None):
        numerics = get_numerics(cfg, library, fused=fused and _interp(cfg))
        if padded:
            logits, cache_p, _ = tf.prefill_padded(
                params, prompts, true_lens, cfg, numerics, cache_len)
        else:
            logits, cache_p, _ = tf.prefill(params, prompts, cfg, numerics,
                                            cache_len)
        firsts = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)  # (P,)
        for i in range(pack):
            one = tf.extract_cache_row(cfg, cache_p, i)
            pool = tf.splice_cache(cfg, pool, one, slots[i])
            tok = tok.at[slots[i], 0].set(firsts[i])
            pos = pos.at[slots[i]].set(true_lens[i])
            live = live.at[slots[i]].set(True)
        return firsts, pool, tok, pos, live

    return admit


def make_engine_tick(cfg, steps: int) -> Callable:
    """The fused serve tick: ``steps`` greedy decode steps for every live
    slot in ONE dispatch.

    tick(params, tok (B,1), pos (B,), live (B,), caches, cross=None,
    library=None) -> (toks (steps, B), tok, pos, ok (B,), caches). The
    decode → argmax → feed-back loop runs as a ``lax.scan`` inside the
    program, so the host neither uploads tokens nor round-trips logits
    between steps; dead slots (live=False) keep decoding placeholder
    garbage at a frozen position that admission later overwrites (standard
    slot padding). Interp numerics lower through the library-bound fused
    kernels.

    ``ok`` is the watchdog sentinel (DESIGN.md §14): per-slot all-finite
    logits across the whole scan, reduced *inside* the program (dead slots
    masked healthy) and downloaded alongside the token block — a poisoned
    datapath is detected with zero additional dispatches."""

    def tick(params, tok, pos, live, caches, cross=None, library=None):
        numerics = get_numerics(cfg, library, fused=_interp(cfg))

        def body(carry, _):
            tok, pos, ok, caches = carry
            logits, caches = tf.decode_step(params, tok, pos, caches, cfg,
                                            numerics, cross=cross)
            step_ok = jnp.all(jnp.isfinite(logits[:, 0]), axis=-1)
            ok = ok & (step_ok | ~live)
            nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            nxt = jnp.where(live, nxt, tok[:, 0])
            pos = jnp.where(live, pos + 1, pos)
            return (nxt[:, None], pos, ok, caches), nxt

        ok0 = jnp.ones(live.shape, jnp.bool_)
        (tok, pos, ok, caches), toks = jax.lax.scan(
            body, (tok, pos, ok0, caches), None, length=steps)
        return toks, tok, pos, ok, caches

    return tick


# Jitted executables shared across engines (keyed by the frozen config):
# re-constructing a ServeEngine must not retrace the decode program, and
# the fused tick donates the cache + slot-state buffers so each chunk
# updates them in place instead of copying the pool.
_JIT_CACHE: dict = {}


def _cached_jit(key: tuple, builder: Callable, **jit_kw) -> Callable:
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(builder(), **jit_kw)
        _JIT_CACHE[key] = fn
    return fn


class Rejected(ValueError):
    """Typed request rejection (admission control, DESIGN.md §14).

    ``reason`` is a stable machine key: ``"prompt_overflow"`` /
    ``"decode_overflow"`` (the request cannot fit the slot cache),
    ``"queue_full"`` (bounded-queue backpressure), ``"bad_prompt"``
    (token ids outside the vocabulary — they would silently clamp through
    the embedding gather), ``"deadline"`` (already expired at submit).
    Subclasses ``ValueError`` so pre-ISSUE-7 callers keep working.
    """

    def __init__(self, reason: str, message: str):
        self.reason = reason
        super().__init__(message)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    deadline: float | None = None  # absolute engine-clock seconds
    error: str | None = None  # structured failure ("deadline_exceeded", ...)
    # lifecycle stamps, engine-clock seconds (None until reached):
    submitted_at: float | None = None  # accepted by submit()
    admitted_at: float | None = None  # just before its admitting dispatch
    first_token_at: float | None = None  # first token on the host
    first_token_returned_at: float | None = None  # end of that step()
    finished_at: float | None = None  # end of the step() it ended in


class ServeEngine:
    """Fault-tolerant continuous batching over a fixed slot pool (greedy).

    ``library``: a preloaded :class:`InterpLibrary` for interp numerics;
    ``None`` compiles the default manifest through the process session at
    construction (generation, if the disk cache is cold, happens here — not
    inside the first jitted step). Exact-numerics engines carry no library.

    When ``cfg.plan`` is a :class:`repro.plan.NumericsPlan` (per-layer
    heterogeneous numerics, DESIGN.md §16) the engine threads a *dict* of
    libraries — one per distinct plan slot, compiled at construction when
    none is passed — and the degradation ladder gains a per-layer rung: a
    corrupt slot ROM downgrades exactly the layers reading that slot
    (:meth:`_degrade_slots`), the rest stay fused, and
    ``stats["degradations"]`` becomes a per-layer-label dict (``"engine"``
    counts whole-ladder rungs).

    ``fused`` (default): each engine tick is ONE donated-buffer dispatch
    covering up to ``horizon`` decode steps (``make_engine_tick``); interp
    numerics run the library-bound fused kernels. ``fused=False`` decodes
    one dispatch per token plus a host argmax round-trip, on unfused
    numerics — the serial rung the degradation ladder falls to. Either way
    admission is one dispatch of :func:`make_engine_admit` per group of
    requests (:meth:`_admit`). ``self.stats`` counts host→device program
    dispatches and device→host transfers.

    Robustness knobs (ISSUE 7, DESIGN.md §14):

    ``max_queue``        bounded admission queue; ``submit`` raises
                         :class:`Rejected` ("queue_full") beyond it.
                         ``None`` = unbounded (legacy).
    ``deadline_s``       default per-request TTL in engine-clock seconds
                         (``Request.deadline``, absolute, overrides);
                         expired requests fail with a structured
                         ``"deadline_exceeded"`` error instead of holding
                         a slot.
    ``clock``            monotonic clock (injectable:
                         ``repro.faults.FaultClock`` drives deadline and
                         stall tests without sleeping). The engine's
                         ``engine.*`` spans (:mod:`repro.serve.spans`,
                         :meth:`spans`) and the ``Request`` stamps are
                         taken on it; the stall watchdog reads the
                         ``engine.tick`` span.
    ``watchdog_limit``   watchdog trips (non-finite tick output, stalled
                         tick) tolerated before degrading one ladder rung.
    ``max_tick_s``       stall watchdog: a tick exceeding this wall budget
                         counts as a trip (``None`` = off).
    ``verify_rom_every`` re-verify the resident ROM checksum every N ticks
                         (0 = at construction and on watchdog trips only).
    ``journal``          path (or :class:`ServeJournal`): durably journal
                         admissions and emitted tokens; see
                         :meth:`resume`.

    Sharded/AOT/async knobs (ISSUE 10, DESIGN.md §17):

    ``mesh``             a ``("data", "tp")`` serve mesh
                         (:func:`repro.launch.mesh.make_serve_mesh`): KV
                         pool sharded slot-wise over ``data`` / KV-head-wise
                         over ``tp``, weights TP-sharded + data-replicated,
                         ROM(s) replicated, slot-state batch-sharded over
                         ``data`` (the AOT fixed point). ``None`` = single
                         host (legacy).
    ``aot_buckets``      prefill bucket table (fused engines only):
                         ``True`` (default table clipped to ``cache_len``),
                         a tuple of bucket lengths, or ``None`` (no table:
                         every request is admitted at its own length and
                         programs compile on first use). With a table the
                         tick is compiled at construction; on a packable
                         config every prompt is padded to its bucket,
                         same-bucket prompts pack into one dispatch, and
                         each (bucket, pack) admission program is compiled
                         at construction too. A prompt longer than every
                         bucket is admitted at its own length
                         (``stats["aot_fallbacks"]``).
    ``max_pack``         largest packed-admission group compiled (grouping
                         uses powers of two up to ``min(max_pack, slots)``).
    ``async_host``       move detokenize + journal writes onto a background
                         worker (:class:`repro.serve.pipeline.HostPipeline`)
                         behind a bounded queue of ``pipeline_depth``
                         chunks. ``run()``/``close()`` drain it; while
                         running, ``Request.out`` trails the device by up to
                         the queue depth (read it after ``run``).

    The degradation ladder: a *fused* engine degrades to the *serial*
    per-op path with domain-guarded numerics (``"interp-guarded"`` — the
    clamp stops a recurrent poison source); a serial engine degrades to
    *exact* numerics (drops the library entirely). A resident-ROM
    integrity failure jumps straight to exact — both interp rungs gather
    the corrupt ROM, so only the table-free twin is trustworthy. Every
    transition is recorded in ``self.faults`` and counted in
    ``self.stats["degradations"]``; tokens never silently come from a
    known-bad datapath.
    """

    def __init__(self, cfg, params, slots: int, cache_len: int,
                 library: InterpLibrary | None = None, fused: bool = True,
                 horizon: int = 8, max_queue: int | None = 1024,
                 deadline_s: float | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 watchdog_limit: int = 2, max_tick_s: float | None = None,
                 verify_rom_every: int = 0,
                 journal: str | ServeJournal | None = None,
                 mesh=None, aot_buckets=None, max_pack: int = 4,
                 async_host: bool = False, pipeline_depth: int = 4):
        self.cfg, self.params = cfg, params
        self.slots, self.cache_len = slots, cache_len
        self.fused, self.horizon = bool(fused), max(1, int(horizon))
        self.max_queue = max_queue
        self.deadline_s = deadline_s
        self.clock = clock
        self.watchdog_limit = max(1, int(watchdog_limit))
        self.max_tick_s = max_tick_s
        self.verify_rom_every = max(0, int(verify_rom_every))
        # always-on span recorder on the engine clock (repro.serve.spans);
        # the newest engine's recorder is the process default
        self._spans = span_lib.SpanRecorder(clock)
        span_lib.set_default(self._spans)
        with self._spans.span("engine.init"):
            if (cfg.sliding_window is not None
                    and cache_len < cfg.sliding_window):
                # the wrapped decode slot (pos % cache) would overwrite KV
                # rows that are still inside the attention window — silent
                # context loss on every wrap; serving must retain the full
                # window
                raise ValueError(
                    f"cache_len {cache_len} < sliding_window "
                    f"{cfg.sliding_window}: a windowed engine must retain the "
                    f"full attention window")
            if not _interp(cfg):
                if library is not None:
                    raise ValueError(
                        f"library passed to ServeEngine but cfg.numerics="
                        f"{cfg.numerics!r} never consults it; drop the "
                        f"library or serve with numerics='interp'")
            elif library is None:
                # The library manifest replaces the hand-maintained warm-up
                # kind set: Explorer.compile() packs every table the interp
                # numerics can touch (activations hardcoded by MoE/SSM layers
                # and the vision-stub projector included), so a kind can't be
                # forgotten here again. To serve from a custom session (cache
                # dir, worker pool), install it with
                # repro.api.set_default_explorer() before constructing the
                # engine — or pass a compiled/loaded library. A plan engine
                # compiles one library per distinct plan slot and threads the
                # dict as a pytree (each value replicates/donates like the
                # single-library case).
                if cfg.plan is not None:
                    from repro.plan.numerics import compile_plan_libraries

                    library = compile_plan_libraries(cfg.plan)
                else:
                    library = default_explorer().compile()
            self.library = library
            self.numerics = get_numerics(
                cfg, library, fused=self.fused and _interp(cfg))
            self.caches = tf.init_cache(cfg, slots, cache_len)
            self.pos = np.zeros(slots, np.int32)  # next position per slot
            self.cur = np.full(slots, -1, np.int32)  # current token per slot
            self.req: list[Request | None] = [None] * slots
            self.queue: collections.deque[Request] = collections.deque()
            self.finished: list[Request] = []
            self.failed: list[Request] = []
            # plan engines attribute degradations per layer label ("0", "7",
            # "rest", or "engine" for whole-ladder rungs); plan-less engines
            # keep the historical scalar counter
            # decode_live_slot_steps: live slots x decode steps, per tick
            self.stats = {"dispatches": 0, "transfers": 0, "ticks": 0,
                          "decode_steps": 0, "decode_live_slot_steps": 0,
                          "rejected": 0, "expired": 0,
                          "watchdog_trips": 0,
                          "degradations": {} if cfg.plan is not None else 0,
                          "rom_verifies": 0, "rom_faults": 0,
                          "slot_failures": 0,
                          "resumed": 0, "resume_skipped_done": 0,
                          "resume_replay_steps": 0,
                          "aot_compiles": 0, "aot_hits": 0, "aot_misses": 0,
                          "aot_reshards": 0, "aot_fallbacks": 0,
                          "packed_admits": 0,
                          "packed_requests": 0, "admit_dispatches": 0,
                          "async_chunks": 0, "async_tokens": 0,
                          ATTN_FALLBACK_KEY: 0, ATTN_FOLD_KEY: 0,
                          ATTN_ABSORB_KEY: 0, CACHE_INPLACE_KEY: 0}
            self.faults: list[dict] = []  # structured fault/degradation log
            self._trips = 0  # watchdog trips since the last degradation
            # requests the running step() admitted, and those it ended:
            # stamped with the step's end when it returns
            self._firsts_now: list[Request] = []
            self._ended_now: list[Request] = []
            self.journal = (journal if isinstance(journal, (ServeJournal,
                                                            type(None)))
                            else ServeJournal(journal))
            # device-resident slot state (fused path): current token, next
            # position, liveness — donated through the tick alongside the
            # caches
            self._tok_dev = jnp.zeros((slots, 1), jnp.int32)
            self._pos_dev = jnp.zeros((slots,), jnp.int32)
            self._live_dev = jnp.zeros((slots,), jnp.bool_)
            # sharded / AOT-warmed / async serving tier (DESIGN.md §17)
            self.mesh = mesh
            self._mesh_key = aot_mod.mesh_key(mesh)
            # per-slot emitted-token counts owned by the MAIN thread:
            # retirement and chunk sizing cannot read len(Request.out) once
            # the async pipeline extends it from the worker
            self._emitted = np.zeros(slots, np.int64)
            if aot_buckets is None:
                self.aot_buckets = None
            elif aot_buckets is True:
                self.aot_buckets = aot_mod.BucketTable.for_cache(cache_len)
            elif isinstance(aot_buckets, aot_mod.BucketTable):
                self.aot_buckets = aot_mod.BucketTable.for_cache(
                    cache_len, aot_buckets.buckets)
            else:
                self.aot_buckets = aot_mod.BucketTable.for_cache(
                    cache_len, aot_buckets)
            self._pack_sizes = aot_mod.pack_sizes(max_pack, slots)
            if async_host and not self.fused:
                raise ValueError(
                    "async_host=True requires the fused engine: the serial "
                    "per-op path is the synchronous oracle/baseline")
            self.pipeline = (HostPipeline(journal=self.journal,
                                          depth=pipeline_depth, clock=clock)
                             if async_host else None)
            if mesh is not None:
                self._shard_state()
            self._build_programs()
            self._warm_aot()
            # serve-time ROM integrity: the load-time checksum catches a
            # corrupt artifact; this catches the resident copy going bad
            # afterwards
            self.verify_library()

    def spans(self) -> list:
        """The engine's finished spans still in its ring, oldest first
        (:class:`repro.serve.spans.Span`; engine-clock seconds)."""
        return self._spans.spans()

    def _shard_state(self) -> None:
        """Place params/caches/slot-state/library on the serve mesh: KV pool
        batch-sharded over ``data`` and KV-head-sharded over ``tp``, weights
        per ``SERVE_PARAM_RULES`` (TP over ``tp``, replicated over ``data``),
        the library ROM(s) and the tiny slot-state vectors replicated.
        Everything downstream — jit traces, AOT lowerings, donation — then
        carries these shardings."""
        mesh = self.mesh

        def sds(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.asarray(x).dtype), tree)

        pspecs = shlib.param_specs(sds(self.params), mesh,
                                   rules=shlib.SERVE_PARAM_RULES)
        self.params = jax.device_put(self.params, pspecs)
        cspecs = shlib.cache_specs_sharding(sds(self.caches), self.cfg, mesh)
        self.caches = jax.device_put(self.caches, cspecs)
        rep = shlib.replicated(mesh)
        # slot-state vectors go batch-over-data, matching the constraint the
        # tick/admit programs put on their outputs — warming with the same
        # placement makes steady state a sharding fixed point (zero
        # per-tick reshards in stats["aot_reshards"])
        slot_s = shlib.named_sharding(("batch",), (self.slots,), mesh)
        tok_s = shlib.named_sharding(("batch", None), (self.slots, 1), mesh)
        self._tok_dev = jax.device_put(self._tok_dev, tok_s)
        self._pos_dev = jax.device_put(self._pos_dev, slot_s)
        self._live_dev = jax.device_put(self._live_dev, slot_s)
        if self.library is not None:
            # one ROM replica per device: the fused kernels gather locally,
            # and verify_resident() checksums the (replicated) leaves as-is
            self.library = jax.device_put(self.library, rep)
            from repro.kernels.interp.ops import assert_rom_replicated
            assert_rom_replicated(*jax.tree.leaves(self.library))

    def _ctx(self):
        """Trace context for every trace/lower on this engine: the
        logical-axis rules (``constrain`` reads the thread-local rules at
        *trace* time; none without a mesh) and the fused-attention site
        counters (``stats["attn_glue_fallbacks"]``: attention sites traced
        onto the chunked glue path instead of the fused kernel;
        ``stats["attn_folded_sites"]``: flash calls traced with a group's
        query heads folded into one tile's rows;
        ``stats["attn_absorbed_sites"]``: MLA decode attentions traced in
        the absorbed-latent form; ``stats["cache_inplace_sites"]``: scanned
        decode segments traced with their cache stack carried through the
        layer loop)."""
        stack = contextlib.ExitStack()
        if self.mesh is not None:
            stack.enter_context(shlib.axis_rules(self.mesh))
        stack.enter_context(count_attention_sites(self.stats))
        return stack

    def _aot_key(self, kind: str, *extra) -> tuple:
        """Executable-cache key: the frozen (cfg [incl. plan], geometry,
        mesh) tuple plus the program-specific extras."""
        return (kind, self.cfg, self.cache_len, self.slots, *extra,
                self._mesh_key)

    def _warm_aot(self) -> None:
        """AOT warm-up (DESIGN.md §17): compile every steady-state program —
        the fused tick at each power-of-two chunk size up to ``horizon``,
        plus, on a packable config, the admission program per (bucket,
        pack-size) pair — at construction, so no request ever pays a
        compile.
        ``stats["aot_compiles"]`` counts fresh compiles (reconstructed
        engines hit the shared executable cache and count nothing)."""
        if self.aot_buckets is None:
            return
        if not self.fused:
            raise ValueError("aot_buckets requires the fused engine")
        rep = (shlib.replicated(self.mesh) if self.mesh is not None
               else None)
        sp = self._spans.span
        with sp("engine.aot"), self._ctx():
            for steps in aot_mod.tick_chunk_sizes(self.horizon):
                key = self._aot_key("tick", steps)
                if aot_mod.lookup(key) is None:
                    self.stats["aot_compiles"] += 1
                with sp("engine.aot.program", key=f"tick/{steps}"):
                    aot_mod.compile_cached(
                        key, self._tick_jit(steps),
                        (self.params, self._tok_dev, self._pos_dev,
                         self._live_dev, self.caches),
                        {"library": self.library}, self._spans)
            if not tf.packable(self.cfg):
                return
            for bucket in self.aot_buckets.buckets:
                for pk in self._pack_sizes:
                    prompts = jnp.zeros((pk, bucket), jnp.int32)
                    lens = jnp.ones((pk,), jnp.int32)
                    slots0 = jnp.arange(pk, dtype=jnp.int32)
                    if rep is not None:
                        prompts, lens, slots0 = (
                            jax.device_put(x, rep)
                            for x in (prompts, lens, slots0))
                    key = self._aot_key("admit_packed", bucket, pk)
                    if aot_mod.lookup(key) is None:
                        self.stats["aot_compiles"] += 1
                    with sp("engine.aot.program",
                            key=f"admit_packed/{bucket}/{pk}"):
                        aot_mod.compile_cached(
                            key, self._admit_jit(pk),
                            (self.params, prompts, lens, slots0,
                             self.caches, self._tok_dev, self._pos_dev,
                             self._live_dev),
                            {"library": self.library}, self._spans)

    # -- program construction (re-run on every degradation rung) ----------
    def _build_programs(self) -> None:
        # every key carries the mesh identity: a meshed engine's traces are
        # made inside its axis-rules context and must never be confused with
        # a single-host engine's traces for the same frozen cfg
        cfg, cache_len, mk = self.cfg, self.cache_len, self._mesh_key
        # prefill/decode on the engine's numerics: resume replay's teacher-
        # forced rebuild re-runs the float path admission and the tick ran
        # pre-crash (DESIGN.md §14); the serial tick decodes through _decode
        fnum = self.fused and _interp(cfg)
        self._prefill = _cached_jit(
            ("prefill", cfg, cache_len, fnum, mk),
            lambda: make_prefill(cfg, cache_len, fused=fnum))
        self._decode = _cached_jit(("decode", cfg, fnum, mk),
                                   lambda: make_serve_step(cfg, fused=fnum))
        # serial-path argmax + watchdog sentinel in one program: same
        # dispatch/transfer budget as the bare argmax it replaces
        self._argmax_ok = _cached_jit(
            ("argmax_ok",),
            lambda: (lambda logits: (
                jnp.argmax(logits[:, 0], -1).astype(jnp.int32),
                jnp.all(jnp.isfinite(logits[:, 0]), axis=-1))))
        # replay splice: donate the pool so slot insertion is in place
        self._splice = _cached_jit(
            ("splice", cfg, mk),
            lambda: (lambda pool, one, slot:
                     tf.splice_cache(cfg, pool, one, slot)),
            donate_argnums=(0,))
        # retire flips one slot's liveness (traced index: one trace total,
        # unlike the eager .at[].set which recompiles per concrete index)
        self._set_live = _cached_jit(
            ("set_live",),
            lambda: (lambda live, slot, val: live.at[slot].set(val)),
            donate_argnums=(0,))
        # resume replay: land one slot's (token, position, live) in place
        self._set_slot = _cached_jit(
            ("set_slot",),
            lambda: (lambda tok, pos, live, slot, t, p: (
                tok.at[slot, 0].set(t), pos.at[slot].set(p),
                live.at[slot].set(True))),
            donate_argnums=(0, 1, 2))

    def _tick_jit(self, steps: int) -> Callable:
        """The lazily-traced jitted tick (also what AOT warm-up lowers)."""
        return _cached_jit(("tick", self.cfg, steps, self._mesh_key),
                           lambda: make_engine_tick(self.cfg, steps),
                           donate_argnums=(1, 2, 4))

    def _admit_jit(self, pack: int) -> Callable:
        """Jitted admission for a static pack size (the prompt length is a
        shape, not a key — one jit object, one trace per length); pool +
        slot-state donated."""
        cfg, fnum = self.cfg, self.fused and _interp(self.cfg)
        return _cached_jit(
            ("admit", cfg, self.cache_len, pack, fnum, self._mesh_key),
            lambda: make_engine_admit(cfg, self.cache_len, pack, fnum),
            donate_argnums=(4, 5, 6, 7))

    def _tick_fn(self, steps: int) -> Callable:
        """Fused tick for a chunk of ``steps`` decode steps; caches and
        slot-state buffers (token/pos) are donated — decode updates the pool
        in place every tick instead of copying it. An AOT-warmed engine
        returns the precompiled executable (``stats["aot_hits"]``); a cache
        miss (post-degradation cfg, oversized chunk) falls back to the lazy
        jit and is counted."""
        jit_fn = self._tick_jit(steps)
        if self.aot_buckets is None:
            return jit_fn
        exe = aot_mod.lookup(self._aot_key("tick", steps))
        if exe is not None:
            self.stats["aot_hits"] += 1
            return self._exe_call(exe)
        self.stats["aot_misses"] += 1
        return jit_fn

    def _admit_fn(self, bucket: int, pack: int) -> Callable:
        """The warmed admission executable for a bucket, counted like
        :meth:`_tick_fn`."""
        jit_fn = self._admit_jit(pack)
        exe = aot_mod.lookup(self._aot_key("admit_packed", bucket, pack))
        if exe is not None:
            self.stats["aot_hits"] += 1
            return self._exe_call(exe)
        self.stats["aot_misses"] += 1
        return jit_fn

    def _exe_call(self, exe) -> Callable:
        """Wrap a compiled executable so mismatched input shardings get
        re-placed instead of raising (see :func:`repro.serve.aot
        .call_matched`); re-placements are counted in
        ``stats["aot_reshards"]``."""
        def call(*args, **kwargs):
            out, moved = aot_mod.call_matched(exe, args, kwargs)
            self.stats["aot_reshards"] += moved
            return out
        return call

    # -- fault handling: integrity, watchdog, degradation ladder ----------
    def _rung(self) -> str:
        """Current degradation-ladder rung. A fused engine always has a
        rung below it (the serial per-op path — the fused scan program
        itself may be the faulty component); below that, interp numerics
        can still drop to table-free exact, which is the bottom."""
        if self.fused:
            return "fused"
        return "serial" if _interp(self.cfg) else "exact"

    def _record_fault(self, reason: str, detail: str = "",
                      action: str = "", layers: tuple | None = None) -> None:
        entry = {"tick": self.stats["ticks"], "reason": reason,
                 "detail": detail, "action": action}
        if layers is not None:
            entry["layers"] = tuple(layers)
        self.faults.append(entry)

    def _count_degradation(self, label: str) -> None:
        d = self.stats["degradations"]
        if isinstance(d, dict):
            d[label] = d.get(label, 0) + 1
        else:
            self.stats["degradations"] = d + 1

    def verify_library(self) -> bool:
        """Re-checksum the resident ROM(s); on mismatch degrade — a plan
        engine checks every slot library and downgrades only the layers
        reading a corrupt one (:meth:`_degrade_slots`); a homogeneous
        engine jumps straight to exact (both interp rungs would gather the
        corrupt ROM)."""
        if self.library is None:
            return True
        with self._spans.span("engine.verify_rom"):
            return self._verify_library()

    def _verify_library(self) -> bool:
        self.stats["rom_verifies"] += 1
        if isinstance(self.library, dict):
            bad: list[tuple[str, str]] = []
            for key in sorted(self.library):
                try:
                    self.library[key].verify_resident()
                except LibraryIntegrityError as e:
                    bad.append((key, str(e)))
            if not bad:
                return True
            self.stats["rom_faults"] += len(bad)
            self._degrade_slots([k for k, _ in bad], "rom_integrity",
                                detail="; ".join(m for _, m in bad))
            return False
        try:
            self.library.verify_resident()
            return True
        except LibraryIntegrityError as e:
            self.stats["rom_faults"] += 1
            self._degrade("rom_integrity", to="exact", detail=str(e))
            return False

    def _degrade_slots(self, slot_keys: list, reason: str,
                       detail: str = "") -> None:
        """Per-layer degradation rung (plan engines, DESIGN.md §16): every
        site reading a poisoned slot library drops to exact — in the named
        layers only. The rest of the stack keeps its fused interp datapath;
        ``stats["degradations"]`` and the fault log name the layers."""
        plan = self.cfg.plan
        keys = sorted(set(slot_keys))
        layers: list = []
        for k in keys:
            for lab in plan.layers_using_slot(k):
                if lab not in layers:
                    layers.append(lab)
        layers.sort(key=str)
        self.cfg = self.cfg.replace(plan=plan.degrade_layers(layers, keys))
        self.library = {k: v for k, v in self.library.items()
                        if k not in set(keys)} or None
        for lab in layers:
            self._count_degradation(str(lab))
        self._record_fault(reason, detail=detail,
                           action=f"slots:{','.join(keys)}->exact",
                           layers=tuple(str(x) for x in layers))
        self._trips = 0
        self.numerics = get_numerics(
            self.cfg, self.library, fused=self.fused and _interp(self.cfg))
        self._build_programs()

    def _degrade(self, reason: str, to: str | None = None,
                 detail: str = "") -> None:
        """Walk one rung down the degradation ladder (or jump to ``to``).

        fused → serial flips the dispatch mode and, for interp engines,
        swaps in the domain-guarded numerics (a plan engine guards every
        interp site, :meth:`NumericsPlan.degrade_serial`); → exact drops
        the library (plan: every site to exact). The KV pool and host slot
        mirrors carry over — in-flight requests keep decoding, just on the
        safer datapath.
        """
        was = self._rung()
        if to is None:
            to = "serial" if was == "fused" else "exact"
        if to == was:
            # already at (or below) the requested rung: nothing safer to
            # fall to — log the fault and keep serving
            self._record_fault(reason, detail=detail, action=f"hold:{was}")
            self._trips = 0
            return
        plan = self.cfg.plan
        if to == "serial":
            self.fused = False
            if self.pipeline is not None:
                # the async feeder only exists for the fused tick; the
                # serial rung is the synchronous oracle — drain and drop it
                self.close()
            if plan is not None:
                self.cfg = self.cfg.replace(plan=plan.degrade_serial())
            elif _interp(self.cfg) and self.cfg.numerics != "interp-guarded":
                self.cfg = self.cfg.replace(numerics="interp-guarded")
        elif to == "exact":
            if plan is not None:
                self.cfg = self.cfg.replace(plan=plan.degrade_exact())
            elif self.cfg.numerics != "exact":
                self.cfg = self.cfg.replace(numerics="exact")
            self.library = None
        else:
            raise ValueError(f"unknown degradation rung {to!r}")
        self._count_degradation("engine")
        self._record_fault(reason, detail=detail, action=f"{was}->{to}")
        self._trips = 0
        self.numerics = get_numerics(
            self.cfg, self.library, fused=self.fused and _interp(self.cfg))
        self._build_programs()

    def _watchdog_trip(self, reason: str, detail: str = "") -> None:
        self.stats["watchdog_trips"] += 1
        self._trips += 1
        self._record_fault(reason, detail=detail, action="trip")
        # a trip is also the moment to re-check the ROM: silent corruption
        # often *presents* as a poisoned datapath
        still_ok = self.verify_library()
        if still_ok and self._trips >= self.watchdog_limit:
            self._degrade(f"repeated_{reason}")

    def _journal(self, method: str, *args, crash: str | None = None) -> None:
        """One journal write. Async engines route it through the pipeline's
        FIFO so it lands *after* every already-queued token emit (the
        single-writer ordering :meth:`resume` depends on); sync engines
        write-and-fsync inline and hit the named crashpoint."""
        if self.journal is None:
            return
        if self.pipeline is not None:
            self.pipeline.journal_call(method, *args)
            return
        getattr(self.journal, method)(*args)
        if crash is not None:
            crashpoint(crash)

    def _fail_slot(self, s: int, error: str) -> None:
        """Retire a poisoned/expired slot with a structured error."""
        r = self.req[s]
        if r is None:
            return
        r.error = error
        self.failed.append(r)
        self._ended_now.append(r)
        self.stats["slot_failures"] += 1
        self.req[s] = None
        self.cur[s] = -1
        self.pos[s] = 0
        self._emitted[s] = 0
        if self.fused:
            self._live_dev = self._set_live(self._live_dev, s, False)
        self._journal("fail", r.rid, error, crash="serve.fail.journaled")

    # -- admission control -------------------------------------------------
    def submit(self, req: Request):
        """Enqueue a request; rejects work the engine cannot serve safely.

        Typed rejections (:class:`Rejected`, a ``ValueError``):

        * cache overflow — without a sliding window, decode writes KV rows
          at absolute positions ``len(prompt) .. len(prompt)+max_new-2``;
          anything past ``cache_len - 1`` would be silently clamped by the
          dynamic-slice update (overwriting the last row again and again),
          so it is an error here rather than corruption later. Sliding-
          window engines wrap their (full-window, checked at construction)
          cache: prompts beyond the window prefill position-aligned to the
          wrap slots, and decode length is unbounded.
        * ``queue_full`` — bounded backpressure: an unbounded queue under
          sustained over-admission grows without limit while every queued
          request's deadline quietly expires.
        * ``bad_prompt`` — out-of-vocabulary token ids would clamp through
          the embedding gather and decode plausible-looking garbage.
        * ``deadline`` — already expired at submit time.
        """
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.stats["rejected"] += 1
            raise Rejected(
                "queue_full",
                f"request {req.rid}: queue full ({len(self.queue)} >= "
                f"max_queue {self.max_queue})")
        if len(req.prompt) == 0:
            self.stats["rejected"] += 1
            raise Rejected("bad_prompt", f"request {req.rid}: empty prompt")
        pmin, pmax = int(np.min(req.prompt)), int(np.max(req.prompt))
        if pmin < 0 or pmax >= self.cfg.vocab_size:
            self.stats["rejected"] += 1
            raise Rejected(
                "bad_prompt",
                f"request {req.rid}: token id {pmin if pmin < 0 else pmax} "
                f"outside vocab [0, {self.cfg.vocab_size})")
        if self.cfg.sliding_window is None:
            if len(req.prompt) > self.cache_len:
                self.stats["rejected"] += 1
                raise Rejected(
                    "prompt_overflow",
                    f"request {req.rid}: prompt length {len(req.prompt)} "
                    f"exceeds cache_len {self.cache_len}")
            if len(req.prompt) + req.max_new - 1 > self.cache_len:
                self.stats["rejected"] += 1
                raise Rejected(
                    "decode_overflow",
                    f"request {req.rid}: prompt ({len(req.prompt)}) + "
                    f"max_new ({req.max_new}) overflows cache_len "
                    f"{self.cache_len}")
        if req.deadline is None and self.deadline_s is not None:
            req.deadline = self.clock() + self.deadline_s
        if req.deadline is not None and self.clock() > req.deadline:
            self.stats["rejected"] += 1
            raise Rejected("deadline",
                           f"request {req.rid}: already past its deadline")
        self._journal("submit", req.rid, req.prompt, req.max_new,
                      req.deadline, crash="serve.submit.journaled")
        req.submitted_at = self.clock()
        self.queue.append(req)

    def _expired(self, r: Request) -> bool:
        return r.deadline is not None and self.clock() > r.deadline

    def _fail_expired_queued(self, r: Request) -> None:
        """Expired while queued: fail without burning a prefill."""
        r.error = "deadline_exceeded"
        self.failed.append(r)
        self._ended_now.append(r)
        self.stats["expired"] += 1
        self._journal("fail", r.rid, r.error)

    def _admit(self):
        """Drain the queue front into free slots in ascending order. The
        (request, slot) mapping is fixed as the queue drains, so grouping
        never reorders admissions. A request is admitted at its bucket where
        the engine pads (fused, a bucket table set, a packable config), else
        at its own prompt length; own-length requests dispatch at once, and
        same-bucket requests then pack into one dispatch per group."""
        pads = (self.fused and self.aot_buckets is not None
                and tf.packable(self.cfg))
        free = [s for s in range(self.slots) if self.req[s] is None]
        by_bucket: dict[int, list] = {}
        while free and self.queue:
            r = self.queue.popleft()
            if self._expired(r):
                self._fail_expired_queued(r)
                continue
            s = free.pop(0)
            if r.out:  # resumed mid-stream: rebuild, emit nothing
                self._admit_replay(r, s)
                continue
            b = self.aot_buckets.bucket_for(len(r.prompt)) if pads else None
            if b is None:
                if pads:  # longer than every bucket: counted
                    self.stats["aot_fallbacks"] += 1
                self._admit_group([(r, s)], len(r.prompt), bucketed=False)
                continue
            by_bucket.setdefault(b, []).append((r, s))
        for b in sorted(by_bucket):
            group = by_bucket[b]
            while group:
                pk = max(c for c in self._pack_sizes if c <= len(group))
                sub, group = group[:pk], group[pk:]
                self._admit_group(sub, b, bucketed=True)

    def _admit_group(self, sub: list, length: int, bucketed: bool) -> None:
        """One admission dispatch for ``sub`` ((request, slot), ...), every
        prompt right-padded to ``length``; a ``bucketed`` group runs the
        warmed bucket program. The first tokens come down in one transfer,
        or ride the async pipeline."""
        pk = len(sub)
        with self._spans.span("engine.prefill", bucket=length, pack=pk,
                              rids=tuple(r.rid for r, _s in sub)):
            prompts = np.zeros((pk, length), np.int32)
            lens = np.zeros(pk, np.int32)
            slot_ix = np.zeros(pk, np.int32)
            for i, (r, s) in enumerate(sub):
                n = len(r.prompt)
                prompts[i, :n] = r.prompt
                lens[i] = n
                slot_ix[i] = s
            fn = (self._admit_fn(length, pk) if bucketed
                  else self._admit_jit(pk))
            args = (jnp.asarray(prompts), jnp.asarray(lens),
                    jnp.asarray(slot_ix))
            if self.mesh is not None:
                # AOT executables pin input shardings: host-built admission
                # arrays must arrive committed-replicated like the lowering
                # saw
                rep = shlib.replicated(self.mesh)
                args = tuple(jax.device_put(a, rep) for a in args)
            t = self.clock()
            for r, _s in sub:
                r.admitted_at = t
            with self._ctx():
                (firsts, self.caches, self._tok_dev, self._pos_dev,
                 self._live_dev) = fn(
                    self.params, *args, self.caches, self._tok_dev,
                    self._pos_dev, self._live_dev, library=self.library)
            self.stats["admit_dispatches"] += 1
            if bucketed:
                self.stats["packed_admits"] += 1
                self.stats["packed_requests"] += pk
            for r, s in sub:
                self.req[s] = r
                self.pos[s] = len(r.prompt)
                self._emitted[s] = 1
            if self.pipeline is None:
                with self._spans.span("engine.sync") as sync:
                    vals = np.asarray(jax.device_get(firsts)).reshape(-1)
            else:
                # first-token download + journal emits happen on the
                # worker, in order with every other journal write
                self.pipeline.emit_admit(
                    tuple((i, r) for i, (r, _s) in enumerate(sub)), firsts)
        self._firsts_now.extend(r for r, _s in sub)
        if self.pipeline is not None:
            return
        for i, (r, s) in enumerate(sub):
            tok = int(vals[i])
            r.first_token_at = sync.span.t1
            r.out.append(tok)
            self.cur[s] = tok
            if self.journal is not None:
                self.journal.emit(r.rid, [tok])
        if self.journal is not None:
            crashpoint("serve.admit.emitted")

    def _admit_replay(self, r: Request, s: int):
        """Re-admit a journal-recovered in-flight request at its recorded
        position: prefill the prompt, then *teacher-force* the already-
        emitted tokens through the decode step to rebuild the slot's cache
        bit-identically (greedy decode is deterministic, so replaying the
        recorded tokens reproduces exactly the pre-crash state — and the
        per-slot independence the solo-oracle tests pin makes the B=1
        rebuild equal to the original pooled decode). Nothing is re-emitted
        and nothing is re-journaled."""
        r.admitted_at = self.clock()
        with self._ctx():
            _logits, cache1, _ = self._prefill(self.params, r.prompt[None, :],
                                               library=self.library)
            start = len(r.prompt)
            for i, t in enumerate(r.out[:-1]):
                tok1 = jnp.asarray([[t]], jnp.int32)
                pos1 = jnp.asarray([start + i], jnp.int32)
                _logits, cache1 = self._decode(self.params, tok1, pos1,
                                               cache1, library=self.library)
                self.stats["resume_replay_steps"] += 1
            self.caches = self._splice(self.caches, cache1, s)
        self.req[s] = r
        self.pos[s] = start + len(r.out) - 1
        self.cur[s] = r.out[-1]
        self._emitted[s] = len(r.out)
        if self.fused:
            (self._tok_dev, self._pos_dev, self._live_dev) = self._set_slot(
                self._tok_dev, self._pos_dev, self._live_dev, s,
                int(r.out[-1]), int(self.pos[s]))
        self.stats["resumed"] += 1

    def _retire(self):
        for s, r in enumerate(self.req):
            if r is None:
                continue
            # the main-thread emitted count, NOT len(r.out): the async
            # pipeline extends r.out from the worker thread
            if self._emitted[s] >= r.max_new:
                r.done = True
                self.finished.append(r)
                self._ended_now.append(r)
                self.req[s] = None
                self.cur[s] = -1
                self.pos[s] = 0
                self._emitted[s] = 0
                if self.fused:
                    self._live_dev = self._set_live(self._live_dev, s, False)
                self._journal("done", r.rid, crash="serve.retire.journaled")
            elif self._expired(r):
                self.stats["expired"] += 1
                self._fail_slot(s, "deadline_exceeded")

    def step(self, max_steps: int = 1):
        """One engine tick: admit, batch-decode every live slot, retire.

        Each slot decodes at its *own* next position (``self.pos`` is passed
        as a per-slot vector): a freshly admitted short-prompt request keeps
        writing KV/state rows contiguously after its prefill instead of at
        the batch-wide max position. Empty slots decode garbage at position 0
        that is ignored and overwritten on admission (standard slot padding).

        A fused engine batches up to ``max_steps`` decode steps into the
        tick (``run`` passes ``self.horizon``) — bounded by the smallest
        remaining budget among live slots, so no in-flight request
        overshoots its ``max_new`` mid-chunk and the freed slot admits at
        the next tick (after ``_admit`` drains the queue into free slots, a
        chunk never delays an admission that could have happened). The one
        historical edge is shared with the serial path: a request whose
        admission token already fills its budget (``max_new <= 1``) still
        decodes once before retiring. The default ``step()`` performs
        exactly one decode step either way.

        The step runs inside the ``engine.step`` span (``engine.admit``,
        ``engine.tick``, ``engine.retire`` inside it); the requests whose
        first token, or end, it produced are stamped with its end
        (``first_token_returned_at``, ``finished_at``).
        """
        with self._spans.span("engine.step") as step:
            busy = self._step(max_steps)
        t = step.span.t1
        for r in self._firsts_now:
            r.first_token_returned_at = t
        for r in self._ended_now:
            r.finished_at = t
        self._firsts_now.clear()
        self._ended_now.clear()
        return busy

    def _step(self, max_steps: int):
        if self.pipeline is not None:
            self.pipeline.check()
        if (self.verify_rom_every
                and self.stats["ticks"] % self.verify_rom_every == 0):
            self.verify_library()
        with self._spans.span("engine.admit"):
            self._admit()
        if all(r is None for r in self.req):
            if self.pipeline is not None:
                # idle: everything queued behind us is the backlog — drain
                # so callers observing Request.out see the final state
                self._drain_pipeline()
            return False
        if not self.fused:
            return self._step_serial()
        remaining = min(r.max_new - int(self._emitted[s])
                        for s, r in enumerate(self.req) if r is not None)
        steps = max(1, min(max_steps, remaining))
        # quantize to the largest power of two <= steps: retirement tails
        # then reuse log2(horizon)+1 compiled tick programs (1, 2, 4, ...)
        # instead of jitting one decode-scan per distinct tail length
        steps = 1 << (steps.bit_length() - 1)
        live = sum(r is not None for r in self.req)
        with self._spans.span("engine.tick", steps=steps, live=live) as tick:
            with self._ctx():
                (toks, self._tok_dev, self._pos_dev, ok_dev,
                 self.caches) = self._tick_fn(steps)(
                    self.params, self._tok_dev, self._pos_dev,
                    self._live_dev, self.caches, library=self.library)
            self.stats["dispatches"] += 1  # the tick program
            with self._spans.span("engine.sync"):
                if self.pipeline is not None:
                    # async host path: only the (B,) watchdog sentinel
                    # comes down synchronously (poison detection timing
                    # unchanged); the token block download + detokenize +
                    # journal emits ride the worker
                    ok = np.asarray(jax.device_get(ok_dev))
                else:
                    # ONE device->host round-trip: the (steps, B) token
                    # block and the (B,) watchdog sentinel come down
                    # together
                    out, ok = jax.device_get((toks, ok_dev))
        self.stats["transfers"] += 1
        self.stats["ticks"] += 1
        self.stats["decode_steps"] += steps
        self.stats["decode_live_slot_steps"] += live * steps
        poisoned = [s for s, r in enumerate(self.req)
                    if r is not None and not bool(ok[s])]
        if self.pipeline is not None:
            alive = tuple((s, r) for s, r in enumerate(self.req)
                          if r is not None and s not in poisoned)
            if alive:
                self.pipeline.emit_chunk(alive, toks)
            for s, _r in alive:
                self._emitted[s] += steps
                self.pos[s] += steps
        else:
            for s, r in enumerate(self.req):
                if r is not None and s not in poisoned:
                    fresh = [int(t) for t in out[:, s]]
                    r.out.extend(fresh)
                    self.cur[s] = int(out[-1, s])
                    self.pos[s] += steps
                    self._emitted[s] += steps
                    if self.journal is not None:
                        self.journal.emit(r.rid, fresh)
            if self.journal is not None:
                crashpoint("serve.tick.emitted")
        self._after_tick(poisoned, tick.span.dur)
        return True

    def _after_tick(self, poisoned: list, tick_s: float) -> None:
        """Watchdog and retirement after a tick of ``tick_s`` engine-clock
        seconds (the ``engine.tick`` span)."""
        for s in poisoned:
            # a poisoned slot is retired with a structured error — its
            # chunk of garbage tokens is never streamed or journaled
            self._fail_slot(s, "non_finite_output")
        if poisoned:
            self._watchdog_trip("non_finite_output",
                                detail=f"slots {poisoned}")
        if self.max_tick_s is not None and tick_s > self.max_tick_s:
            self._watchdog_trip("stalled_tick",
                                detail=f"{tick_s:.3f}s > {self.max_tick_s}s")
        with self._spans.span("engine.retire"):
            self._retire()

    def _step_serial(self):
        """The serial rung's per-op tick: token upload, one decode
        dispatch, a host argmax round-trip. The watchdog sentinel rides the
        argmax program: same dispatch and transfer budget as a bare
        argmax."""
        toks = jnp.asarray(np.maximum(self.cur, 0)[:, None], jnp.int32)
        pos = jnp.asarray(self.pos, jnp.int32)
        self.stats["transfers"] += 2  # token + position upload
        live = sum(r is not None for r in self.req)
        with self._spans.span("engine.tick", steps=1, live=live) as tick:
            with self._ctx():
                logits, self.caches = self._decode(
                    self.params, toks, pos, self.caches,
                    library=self.library)
            self.stats["dispatches"] += 1  # decode program
            nxt_dev, ok_dev = self._argmax_ok(logits)
            self.stats["dispatches"] += 1  # argmax+sentinel program
            with self._spans.span("engine.sync"):
                nxt, ok = jax.device_get((nxt_dev, ok_dev))
        self.stats["transfers"] += 1  # next-token (+ sentinel) download
        self.stats["ticks"] += 1
        self.stats["decode_steps"] += 1
        self.stats["decode_live_slot_steps"] += live
        poisoned = [s for s, r in enumerate(self.req)
                    if r is not None and not bool(ok[s])]
        for s, r in enumerate(self.req):
            if r is not None and s not in poisoned:
                r.out.append(int(nxt[s]))
                self.cur[s] = int(nxt[s])
                self.pos[s] += 1
                self._emitted[s] += 1
                if self.journal is not None:
                    self.journal.emit(r.rid, [int(nxt[s])])
        if self.journal is not None:
            crashpoint("serve.tick.emitted")
        self._after_tick(poisoned, tick.span.dur)
        return True

    def run(self, max_ticks: int = 10_000) -> list[Request]:
        t = 0
        while (self.queue or any(r is not None for r in self.req)) and t < max_ticks:
            self.step(self.horizon)
            t += 1
        self._drain_pipeline()
        return self.finished

    # -- async host pipeline lifecycle -------------------------------------
    def _drain_pipeline(self) -> None:
        """Block until the background worker has processed everything queued
        so far, fold its counters into ``self.stats``, and surface any
        worker exception. After this, every finished request's ``out`` holds
        its full token stream."""
        if self.pipeline is None:
            return
        self.pipeline.flush()
        got = self.pipeline.drain_stats()
        self.stats["transfers"] += got.get("transfers", 0)
        self.stats["async_chunks"] += got.get("chunks", 0)
        self.stats["async_tokens"] += got.get("tokens", 0)

    def close(self) -> None:
        """Clean shutdown of the async host pipeline (sync engines: no-op).
        The engine stays usable afterwards — it falls back to synchronous
        host bookkeeping."""
        if self.pipeline is None:
            return
        self._drain_pipeline()
        self.pipeline.close()
        self.pipeline = None

    # -- crash recovery ----------------------------------------------------
    @classmethod
    def resume(cls, journal: str, cfg, params, *, slots: int, cache_len: int,
               **kw) -> "ServeEngine":
        """Reconstruct an engine from its admission/token journal.

        Completed (``done``/``fail``) requests are *never* replayed
        (``stats["resume_skipped_done"]`` counts them; their records are
        available via :func:`repro.serve.journal.load_requests`). In-flight
        requests are re-queued with their durable token prefix and
        re-admitted through the teacher-forced rebuild
        (:meth:`_admit_replay`): nothing already journaled is re-emitted,
        and the continued greedy decode produces bitwise the token suffix
        an uninterrupted run would have (the chaos suite's recovery
        contract). The journal stays attached — the resumed engine keeps
        appending to it.
        """
        states = load_requests(journal)
        eng = cls(cfg, params, slots=slots, cache_len=cache_len,
                  journal=journal, **kw)
        for st in states.values():
            if not st.in_flight:
                eng.stats["resume_skipped_done"] += 1
                continue
            if len(st.out) >= st.max_new:
                # crashed between the last emit and the done record: the
                # request is complete — journal the terminal event now,
                # replay nothing
                req = Request(st.rid, st.prompt, st.max_new,
                              out=list(st.out), done=True,
                              deadline=st.deadline)
                eng.finished.append(req)
                eng.stats["resume_skipped_done"] += 1
                if eng.journal is not None:
                    eng.journal.done(st.rid)
                continue
            eng.queue.append(Request(st.rid, st.prompt, st.max_new,
                                     out=list(st.out),
                                     deadline=st.deadline))
        return eng
