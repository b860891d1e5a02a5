"""Reduce a profiler trace to what the per-layer metrics read.

``load(path)`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps, per
TPU device, the ``XLA Ops`` events (start, duration, instruction name, and
for a Pallas kernel its operand types) and the ``XLA Modules`` events (one
per program run), plus the host spans the harness annotated. The result is
plain JSON-able data; ``tests/bench/data`` holds a small one recorded on a
TPU v5e.

The ``XLA Ops`` line nests: a ``while`` (a scan) spans the ops of its body.
Busy time is the union of all intervals; a breakdown uses self time.
"""
from __future__ import annotations

import collections
import glob
import os
import re

HOST_SPANS = ("traced_window", "submit", "step", "record", "sleep")
_INSTR = re.compile(r"%?([\w.\-]+) = ")
_TYPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def _op_key(full: str) -> tuple[str, list | None]:
    """(instruction name without its .N suffix, operand types of a Pallas
    custom call or None)."""
    m = _INSTR.match(full)
    name = m.group(1) if m else full.split(" ", 1)[0]
    name = re.sub(r"\.\d+$", "", name)
    if 'custom_call_target="tpu_custom_call"' not in full:
        return name, None
    lo = full.find("custom-call(")
    hi = full.find("), custom_call_target")
    operands = [[t, [int(x) for x in dims.split(",") if x]]
                for t, dims in _TYPE.findall(full[lo:hi])]
    return name, operands


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def load(path: str) -> dict:
    """The reduced trace: {"devices": [{"ops": [[start_ns, dur_ns, name,
    operands|None], ...], "modules": [[start_ns, dur_ns, name], ...]}],
    "host": [[start_ns, dur_ns, name], ...]}."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    keys: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        full = e.name
                        k = keys.get(full)
                        if k is None:
                            k = keys[full] = _op_key(full)
                        dev["ops"].append([e.start_ns, e.duration_ns, k[0],
                                           k[1]])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        dev["modules"].append([e.start_ns, e.duration_ns,
                                               e.name.split("(")[0]])
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.start_ns, e.duration_ns, e.name])
    devices.sort(key=lambda d: d["name"])
    host.sort()
    return {"devices": devices, "host": host}


def window(tr: dict) -> tuple[float, float]:
    """(start_ns, end_ns) of the traced window (the harness's span)."""
    spans = [h for h in tr["host"] if h[2] == "traced_window"]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} traced_window spans in the trace")
    s, d, _ = spans[0]
    return s, s + d


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: dict, lo: float, hi: float) -> float:
    """Time in [lo, hi) during which some operation ran on the device."""
    return sum(e - s for s, e in
               _union(((o[0], o[0] + o[1]) for o in dev["ops"]), lo, hi))


def op_time_ns(dev: dict, match, lo: float, hi: float) -> float:
    """Summed duration of the ops for which ``match(name, operands)`` holds
    and which start inside [lo, hi)."""
    return sum(o[1] for o in dev["ops"]
               if lo <= o[0] < hi and match(o[2], o[3]))


def module_time_ns(dev: dict, prefix: str, lo: float, hi: float) -> float:
    """Summed device time of the runs of programs named ``prefix``*."""
    return sum(m[1] for m in dev["modules"]
               if lo <= m[0] < hi and m[2].startswith(prefix))


def label(name: str, operands) -> str:
    """A readable op label: the instruction name, and for a Pallas call (all
    named alike in the trace) its operand count and first operand's type."""
    if operands is None:
        return name
    t, shape = operands[0] if operands else ("", [])
    return f"{name}[{len(operands)} operands, {t} rank {len(shape)}]"


def self_times(dev: dict, lo: float, hi: float) -> dict[str, float]:
    """Self time (ns) per op label: duration minus the time of the ops
    nested inside it."""
    ops = sorted((o for o in dev["ops"] if lo <= o[0] < hi),
                 key=lambda o: (o[0], -o[1]))
    total: dict = collections.Counter()
    stack: list[list] = []  # [end, name, child_ns]
    for s, d, name, operands in ops:
        name = label(name, operands)
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            total[nm] -= child
        if stack:
            stack[-1][2] += d
        total[name] += d
        stack.append([s + d, name, 0.0])
    for end, nm, child in stack:
        total[nm] -= child
    return dict(total)


def idle_gaps(dev: dict, host: list, lo: float, hi: float,
              top: int = 10) -> list[list]:
    """The longest gaps between device work in [lo, hi), each labelled with
    the innermost harness span open on the host at the gap's middle."""
    busy = _union(((o[0], o[0] + o[1]) for o in dev["ops"]), lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    spans = [h for h in host if h[2] != "traced_window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        what = "none"
        for hs, hd, name in spans:
            if hs <= mid < hs + hd:
                what = name  # later (inner) spans start later
        out.append([what, (e - s) / 1e9])
    return out
