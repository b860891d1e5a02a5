"""The serving benchmark's harness: one cell, one seed, one process.

Everything that belongs to one configuration, mix, cell or metric is a file
of its own, found by name:

- ``BENCHMARK.json``: the cells (``workloads``) and metrics;
- ``bench/configs/<config>.json``: the model as run (published keys at the
  top level, the served program's arch id, overrides and serving geometry
  under ``repro`` and ``serve``);
- ``bench/mixes/<traffic>.json``: the traffic mix (see ``traffic.py``);
- ``bench/cells/<workload>.json``: the cell's offered rate and the limits of
  its correctness comparison;
- ``bench/metrics/<metric>.py``: ``read(run) -> float | None``, one per
  metric;
- ``bench/counts/<arch>.py``, ``bench/reference/<arch>.py``: least work from
  shapes, and the plain reference, per architecture.

A run: build the weights from the seed, compile the interpolation library
(interp cells), build the ``ServeEngine`` with AOT-compiled programs for the
cell's buckets only, run every bucket x pack size x tick chunk once, serve
``lead_s`` of the cell's own traffic, then measure ``seconds`` of open-loop
traffic. After the window: the peak memory, then the correctness check
against the plain reference on a sample of finished requests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import time

import numpy as np

from bench import traffic

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- the cell

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/mixes/<traffic>.json
    params: dict  # bench/cells/<workload>.json
    end_to_end: list  # BENCHMARK.json metric entries that this cell reports
    per_layer: list
    bench_dir: pathlib.Path = BENCH  # where the cell's files were found

    @property
    def hf(self) -> dict:
        """The published model keys as run (top-level numbers, groups)."""
        return {k: v for k, v in self.config.items()
                if k not in ("repro", "serve", "source", "reduced", "assumed",
                             "departures", "deployment", "arch")}

    @property
    def arch(self) -> str:
        return self.config["arch"]

    @property
    def numerics(self) -> str:
        return self.config["repro"]["numerics"]


def _for_cell(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    bdir = root / bench["paths"][0]
    return Cell(
        name=workload, chips=int(wl["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        mix=json.loads((bdir / "mixes" / f"{wl['traffic']}.json").read_text()),
        params=json.loads((bdir / "cells" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _for_cell(m, workload)],
        bench_dir=bdir)


_MODULES: dict = {}


def module(kind: str, name: str, bench_dir: pathlib.Path = BENCH):
    """bench/<kind>/<name>.py as a module (metric readers, counts,
    references), found by name; loaded once per process."""
    path = bench_dir / kind / f"{name}.py"
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def peaks(device_kind: str, bench_dir: pathlib.Path = BENCH) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json: no peak to divide by")
    return table["devices"][device_kind]


# ------------------------------------------------------- the system under test

# published key -> served-program field, for the keys both sides have
_CFG_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
             "num_attention_heads": "n_heads", "intermediate_size": "d_ff",
             "vocab_size": "vocab_size", "rope_theta": "rope_theta",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim"}
_MLA_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim")


def model_config(cell: Cell):
    """The served program's ModelConfig for the cell, checked against the
    published keys of the configuration file."""
    from repro.configs.base import get_config

    rp = cell.config["repro"]
    cfg = get_config(rp["arch"])
    over = {k: (dataclasses.replace(getattr(cfg, k), **v)
                if isinstance(v, dict) else v)
            for k, v in rp.get("overrides", {}).items()}
    cfg = cfg.replace(numerics=rp["numerics"], **over)
    hf = cell.hf
    for k, field in _CFG_KEYS.items():
        if k in hf and float(getattr(cfg, field)) != float(hf[k]):
            raise ValueError(f"{cell.name}: {k}={hf[k]} but the served "
                             f"config has {field}={getattr(cfg, field)}")
    if cfg.mla is not None:
        for k in _MLA_KEYS:
            if getattr(cfg.mla, k) != hf[k]:
                raise ValueError(f"{cell.name}: {k}={hf[k]} but the served "
                                 f"config has {getattr(cfg.mla, k)}")
    if cfg.param_dtype != hf["torch_dtype"]:
        raise ValueError(f"{cell.name}: dtype {hf['torch_dtype']} but the "
                         f"served config has {cfg.param_dtype}")
    return cfg


def make_weights(cell: Cell, seed: int):
    """The cell's weights from the seed, in the served layout (one jitted
    program on the device); the layout is checked against the program's."""
    import jax

    from bench import weights
    from repro.models import transformer as tf

    shapes = module("reference", cell.arch, cell.bench_dir).param_shapes(
        cell.hf)
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        tf.model_shapes(model_config(cell)))
    have = jax.tree.map(lambda s: (s.shape, str(s.dtype)), shapes)
    if want != have:
        raise ValueError(f"{cell.name}: the reference's weight layout "
                         f"differs from the served program's")
    return weights.make(shapes, seed)


def build_engine(cell: Cell, params, table_dir: pathlib.Path):
    from repro.serve.engine import ServeEngine

    cfg = model_config(cell)
    sv = cell.config["serve"]
    lib = None
    if cell.numerics != "exact":
        from repro.api import Explorer, ExploreConfig

        lib = Explorer(ExploreConfig(cache_dir=str(table_dir))).compile()
    return ServeEngine(cfg, params, slots=sv["slots"],
                       cache_len=sv["cache_len"], library=lib,
                       aot_buckets=tuple(sv["buckets"]),
                       max_pack=sv["max_pack"], horizon=sv["horizon"],
                       max_queue=None)


def health(eng) -> tuple:
    """What must stay at zero: degradations, faults, failed requests,
    fused-attention refusals."""
    d = eng.stats["degradations"]
    return (sum(d.values()) if isinstance(d, dict) else d, len(eng.faults),
            len(eng.failed), eng.stats["attn_glue_fallbacks"])


def warm_up(eng, cell: Cell, vocab: int, seed: int) -> int:
    """Run every packed-admission program (bucket x pack size) and every
    tick chunk (1, 2, 4, ... horizon) once. Returns engine steps taken."""
    from repro.serve.aot import pack_sizes
    from repro.serve.engine import Request

    sv = cell.config["serve"]
    packs = pack_sizes(sv["max_pack"], sv["slots"])
    rng = np.random.default_rng([seed % (1 << 64), 3])
    max_new = 2 * sv["horizon"]  # 1 from prefill, then ticks h, h/2, ... 1
    groups, cur = [], []
    for b in sv["buckets"]:
        group = [b] * sum(packs)  # one pack of each size for this bucket
        if len(cur) + len(group) > sv["slots"]:
            groups.append(cur)
            cur = []
        cur += group
    groups.append(cur)
    steps, rid = 0, -1
    for group in groups:
        for b in group:
            eng.submit(Request(rid, rng.integers(0, vocab, b).astype(
                np.int32), max_new))
            rid -= 1
        while eng.queue or any(r is not None for r in eng.req):
            eng.step(eng.horizon)
            steps += 1
    eng.finished.clear()
    return steps


# ------------------------------------------------------------- the open loop

@dataclasses.dataclass
class Rec:
    """One request, times in seconds from the window's start."""
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    req: object = None
    submit_t: float | None = None
    admit_t: float | None = None  # start of the step that admitted it
    first_t: float | None = None
    last_t: float | None = None
    done_t: float | None = None
    n: int = 0  # tokens delivered
    failed: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    seconds: float
    recs: list
    steps: list  # dicts: t0, t1, steps, live, admitted, tokens
    setup_s: float
    peaks: dict
    counts: object  # bench/counts/<arch>.py
    trace: dict | None = None  # reduced device trace (trace.load)
    traced: tuple | None = None  # (t0, t1) of the traced part, seconds

    @property
    def hf(self) -> dict:
        return self.cell.hf

    def traced_steps(self) -> list:
        lo, hi = self.traced
        return [s for s in self.steps if s["t0"] >= lo and s["t1"] <= hi]

    def window(self) -> tuple:
        """The measured window as whole engine steps: from the end of the
        last step that ended by the nominal start (0, where none did) to
        the end of the last step, which began before the nominal end
        (``seconds``) and was waited for. The steps that end inside it are
        all the work done in it."""
        ends = [s["t1"] for s in self.steps]
        return (max((t for t in ends if t <= 0.0), default=0.0),
                max(ends + [self.seconds]))

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    def window_steps(self) -> list:
        lo, hi = self.window()
        return [s for s in self.steps if lo < s["t1"] <= hi]


class CompileCounter:
    """Counts XLA compilations (and traces) as JAX reports them."""

    def __init__(self):
        from jax import monitoring

        self.compiles = self.traces = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compiles += 1
        elif name.endswith("jaxpr_trace_duration"):
            self.traces += 1


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def drive(eng, sched: list, seconds: float, *, trace_dir=None,
          trace_s: float = 0.0):
    """Offer ``sched`` open-loop and measure ``seconds``. Arrivals ignore
    completions: every due request is submitted, then one engine step runs;
    with nothing live the loop sleeps until the next arrival. Returns
    (recs, steps, traced (t0, t1) or None, tainted_at or None)."""
    import jax

    from repro.serve.engine import Request

    recs = [Rec(a.rid, a.due, a.prompt, a.max_new) for a in sched]
    recs.sort(key=lambda r: r.due)
    active: list[Rec] = []
    steps: list[dict] = []
    # a fault before the window (a fused-attention refusal is counted when
    # the programs are traced, at construction) taints every request
    tainted = 0.0 if any(health(eng)) else None
    lead = -recs[0].due if recs and recs[0].due < 0 else 0.0
    t_zero = time.perf_counter() + lead  # the window opens at t = 0
    nxt, traced, tracing = 0, None, False
    spans = trace_dir is not None
    trace_from = seconds - trace_s
    win_span = None

    def now() -> float:
        return time.perf_counter() - t_zero

    while True:
        t = now()
        if spans and not tracing and traced is None and t >= trace_from:
            jax.profiler.start_trace(str(trace_dir), profiler_options=_opts())
            win_span = jax.profiler.TraceAnnotation("traced_window")
            win_span.__enter__()
            tracing, traced = True, [now(), None]
            t = traced[0]
        if t >= seconds:
            break
        with _span("submit", tracing):
            while nxt < len(recs) and recs[nxt].due <= t:
                r = recs[nxt]
                r.req = Request(r.rid, r.prompt, r.max_new)
                eng.submit(r.req)
                r.submit_t = now()
                active.append(r)
                nxt += 1
        if eng.queue or any(q is not None for q in eng.req):
            before = eng.stats["decode_steps"]
            t0 = now()
            with _span("step", tracing):
                eng.step(eng.horizon)
            t1 = now()
            with _span("record", tracing):
                n_steps = eng.stats["decode_steps"] - before
                live, admitted, still, delivered = [], [], [], 0
                if tainted is None and any(health(eng)):
                    tainted = t1
                for r in active:
                    n = len(r.req.out)
                    g = n - r.n
                    if g > 0:
                        # (start position, decode tokens) of the slot; the
                        # first token of an admitted request is prefill's
                        if r.n == 0:
                            r.admit_t, r.first_t = t0, t1
                            admitted.append(r.prompt_len)
                            if g > 1:
                                live.append((r.prompt_len, g - 1))
                        else:
                            live.append((r.prompt_len + r.n - 1, g))
                        r.n, r.last_t = n, t1
                        delivered += g
                        if tainted is not None:
                            r.failed = True
                    if r.req.error is not None:
                        r.failed = True
                    elif r.req.done:
                        r.done_t = t1
                    else:
                        still.append(r)
                active = still
                steps.append({"t0": t0, "t1": t1, "steps": n_steps,
                              "live": live, "admitted": admitted,
                              "tokens": delivered})
        else:
            wake = recs[nxt].due if nxt < len(recs) else seconds
            with _span("sleep", tracing):
                time.sleep(max(0.0, min(wake, seconds) - now()))
    if tracing:
        traced[1] = now()
        win_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return recs, steps, (tuple(traced) if traced else None), tainted


def _opts():
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0  # Python frames would slow the host loop
    o.host_tracer_level = 2
    return o


def write_record(path: pathlib.Path, recs, steps, setup_s: float,
                 seconds: float) -> None:
    """The open loop as it ran: every engine step and every request's
    times (seconds from the window's start), for looking at a run after."""
    keys = ("rid", "due", "max_new", "submit_t", "admit_t", "first_t",
            "last_t", "done_t", "n", "failed")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({
        "seconds": seconds, "setup_s": setup_s, "steps": steps,
        "recs": [dict({k: getattr(r, k) for k in keys},
                      prompt_len=r.prompt_len) for r in recs]}))


# ------------------------------------------------------------------ a run

def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: pathlib.Path = ROOT,
        record: pathlib.Path | None = None) -> dict:
    """One run of one cell; returns the result line (a dict). ``record``:
    where to write the open loop's steps and requests as JSON."""
    import jax

    from bench import correctness
    from bench import trace as trace_mod

    cell = load_cell(workload, root)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    pk = peaks(dev[0].device_kind, cell.bench_dir)
    counter = CompileCounter()
    log(f"cell {cell.name}: config {cell.config['repro']['arch']} "
        f"{cell.config['repro'].get('overrides', {})}, numerics "
        f"{cell.numerics}, mix {cell.mix.get('name', '?')}, rate "
        f"{cell.params['rate']} req/s, seed {seed}, {seconds} s")
    params = make_weights(cell, seed)
    jax.block_until_ready(params)
    log(f"weights: {time.perf_counter() - t_start:.3f} s since start")
    eng = build_engine(cell, params, root / "artifacts" / "bench_tables")
    log(f"engine: {eng.stats['aot_compiles']} programs compiled, "
        f"{time.perf_counter() - t_start:.3f} s since start")
    vocab = cell.hf["vocab_size"]
    n_warm = warm_up(eng, cell, vocab, seed)
    sched = traffic.arrivals(cell.mix, cell.params["rate"], seconds, seed,
                             vocab)
    stats0 = dict(eng.stats)
    c0, tr0 = counter.compiles, counter.traces
    lead = max(0.0, -min(a.due for a in sched))
    setup_s = time.perf_counter() - t_start + lead
    tdir = root / "artifacts" / "bench_trace" if trace else None
    if tdir is not None:
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
    recs, steps, traced, tainted = drive(
        eng, sched, seconds, trace_dir=tdir,
        trace_s=min(seconds, float(cell.mix.get("trace_s", seconds))))
    in_window_compiles = counter.compiles - c0
    if record is not None:
        write_record(record, recs, steps, setup_s, seconds)
    delta = {k: eng.stats[k] - stats0[k] for k in
             ("aot_misses", "aot_fallbacks", "attn_glue_fallbacks")}
    late = [r.submit_t - r.due for r in recs
            if r.submit_t is not None and 0 <= r.due < seconds]
    late_p95 = f"{np.percentile(late, 95):.6f}" if late else "n/a"
    log(f"warm-up: {n_warm} engine steps; set-up {setup_s:.3f} s "
        f"(lead-in {lead:.3f} s of the same traffic)")
    log(f"window: {len(steps)} engine steps; generator lateness p95 "
        f"{late_p95} s; "
        f"compilations in window {in_window_compiles} "
        f"(traces {counter.traces - tr0}); aot_misses {delta['aot_misses']}, "
        f"aot_fallbacks {delta['aot_fallbacks']}, attn_glue_fallbacks "
        f"{delta['attn_glue_fallbacks']}; engine health {health(eng)}"
        f"{'' if tainted is None else f', fault at t={tainted:.3f} s'}")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in dev[:cell.chips])
    device["memory_peak_bytes"] = int(mem)
    runrec = Run(cell, seconds, recs, steps, setup_s, pk,
                 module("counts", cell.arch, cell.bench_dir))
    attempted = [r for r in recs if 0 <= r.due < seconds]
    result = {"correct": False, "attempted": len(attempted),
              "failed": sum(r.failed for r in attempted)}
    if trace:
        runrec.traced = traced
        runrec.trace = trace_mod.load(trace_mod.find_xplane(str(tdir)))
        d0 = runrec.trace["devices"][0]
        lo, hi = trace_mod.window(runrec.trace)
        busy = [trace_mod.busy_ns(d, lo, hi) for d in
                runrec.trace["devices"][:cell.chips]]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        st = trace_mod.self_times(d0, lo, hi)
        breakdown = {
            "device_ops": [[k, v / 1e9] for k, v in sorted(
                st.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": trace_mod.idle_gaps(d0, runrec.trace["host"], lo,
                                             hi)}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = module("metrics", m["name"], cell.bench_dir).read(runrec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = breakdown
    # the program's state goes before the reference runs
    finished = [r for r in recs if r.done_t is not None and not r.failed]
    del eng, params
    gc.collect()
    compared = correctness.check(cell, finished, seed)
    result["correct"] = (all(c["value"] is not None
                             and c["value"] <= c["limit"]
                             for c in compared.values())
                         and bool(finished) and tainted is None)
    for k, c in compared.items():
        log(f"compared {k}: {c['value']!r} (limit {c['limit']!r})")
    log(f"correct: {result['correct']} ({len(finished)} requests finished, "
        f"fault {'none' if tainted is None else 'yes'})")
    result["compared"] = compared
    return result
