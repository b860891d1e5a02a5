"""What the serving engine records about itself, as the per-layer metrics
read it.

The engine (``repro.serve.engine``) stamps each ``Request`` on its own
clock (``submitted_at``, ``admitted_at``, ``first_token_at``,
``first_token_returned_at``, ``finished_at``) and records ``engine.*``
spans (``repro.serve.spans``): in a ring reachable from the process's
default recorder, and, while a profile runs, on the host plane of the
trace. A run's ``recs[i].req`` is the engine's own ``Request``.

A program that records none of this (an older checkout) reads as None
everywhere here: nothing raises.
"""
from __future__ import annotations

from bench import stats
from bench import trace

STEP, SYNC = "engine.step", "engine.sync"
# the intervals clipped to [lo, hi), merged where they touch
union = trace._union


def gaps_s(run, start: str, end: str) -> list[float]:
    """``req.<end> - req.<start>`` (seconds) over the requests due in the
    window [0, seconds) that carry both stamps."""
    out = []
    for r in run.recs:
        if not 0.0 <= r.due < run.seconds or r.req is None:
            continue
        a, b = getattr(r.req, start, None), getattr(r.req, end, None)
        if a is not None and b is not None:
            out.append(b - a)
    return out


def p95_ms(run, start: str, end: str) -> float | None:
    v = stats.p95(gaps_s(run, start, end))
    return None if v is None else 1e3 * v


def aot_span():
    """The ``engine.aot`` span of the newest engine's construction, or None
    (no such engine, no AOT warm-up, or a program without spans)."""
    try:
        from repro.serve import spans
    except ImportError:
        return None
    rec = spans.default()
    init, aot = rec.last("engine.init"), rec.last("engine.aot")
    if init is None or aot is None or aot.parent != init.id:
        return None
    return aot


def host_spans(path: str, names=(STEP, SYNC)) -> dict:
    """{name: [(start_ns, end_ns), ...]} of the named spans on the host
    plane of the ``.xplane.pb`` at ``path`` (the profiler's clock, the
    clock of the device planes)."""
    import jax

    out: dict = {n: [] for n in names}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns, e.start_ns
                                        + e.duration_ns))
    return out


def minus(a: list, b: list) -> list[tuple[float, float]]:
    """Merged intervals ``a`` without merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def host_idle_ns(dev: dict, host: dict, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which no operation runs on the device ``dev`` (a
    device of ``trace.load``) and the host is inside ``engine.step`` but
    not inside ``engine.sync``: the engine's own host work holding the
    chip back."""
    busy = union(((o[0], o[0] + o[1]) for o in dev["ops"]), lo, hi)
    idle = minus([(lo, hi)], busy)
    work = minus(union(host[STEP], lo, hi), union(host[SYNC], lo, hi))
    return length(minus(idle, minus(idle, work)))  # idle and work
