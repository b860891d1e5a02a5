"""Async host pipeline: detokenize/journal bookkeeping off the tick path.

The synchronous engine interleaves device work with host work every tick:
dispatch the fused tick, download the (steps, B) token block, extend each
request's output list, journal (fsync) the fresh tokens — the device idles
through all of that Python. :class:`HostPipeline` moves everything after
the dispatch onto one background worker thread fed through a *bounded*
queue:

  * the main thread keeps only the (B,) watchdog-sentinel download per tick
    (poison detection timing is unchanged from DESIGN.md §14) and hands the
    device-resident token block to the worker;
  * the worker downloads the block, extends ``Request.out``, and performs
    **all** journal writes — admission records, token emits, done/fail
    marks — in queue order. One writer thread means the journal's
    append-then-fsync ordering is exactly the synchronous engine's, so
    :meth:`ServeEngine.resume` replays an async engine's journal
    unchanged;
  * the bounded queue is backpressure: if the host falls behind, the main
    thread blocks on ``put`` instead of buffering unboundedly;
  * worker exceptions are captured and re-raised on the main thread at the
    next ``check()``/``flush()`` — a failed fsync fails the engine, not a
    daemon thread's stderr.

Shutdown: ``flush()`` drains (blocks until every queued item is processed),
``close()`` drains then joins the thread. Stats are accumulated worker-side
and folded into the engine's counters at ``drain_stats()`` — no cross-
thread mutation of shared dicts.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable

import jax
import numpy as np


class HostPipeline:
    """One background worker consuming (chunk | admit | journal) items."""

    def __init__(self, journal=None, depth: int = 4,
                 clock: Callable[[], float] = time.monotonic):
        self.journal = journal
        self.clock = clock  # stamps Request.first_token_at on the worker
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self._lock = threading.Lock()
        self._stats = {"transfers": 0, "chunks": 0, "tokens": 0}
        self._exc: BaseException | None = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._worker, name="serve-host-pipeline", daemon=True)
        self._thread.start()

    # -- producer side (engine main thread) --------------------------------
    def emit_chunk(self, items, toks) -> None:
        """``items``: ((slot, Request), ...) for the healthy slots of one
        tick; ``toks``: the device-resident (steps, B) token block. The
        worker downloads, detokenizes into each request and journals."""
        self._put(("chunk", tuple(items), toks))

    def emit_admit(self, items, firsts) -> None:
        """``items``: ((row, Request), ...) of one admission dispatch;
        ``firsts``: device-resident first-token vector (or scalar)."""
        self._put(("admit", tuple(items), firsts))

    def journal_call(self, method: str, *args) -> None:
        """Route a journal write (submit/done/fail) through the worker so it
        lands *after* every token emit already queued."""
        if self.journal is not None:
            self._put(("journal", method, args))

    def flush(self) -> None:
        """Block until the queue is fully processed; surface worker errors."""
        self._q.join()
        self.check()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(("stop",))
        self._thread.join(timeout=60.0)
        self.check()

    def check(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def drain_stats(self) -> dict:
        """Return-and-zero the worker-side counters (fold into engine
        stats on the main thread)."""
        with self._lock:
            out, self._stats = self._stats, {k: 0 for k in self._stats}
        return out

    @property
    def depth(self) -> int:
        return self._q.qsize()

    def _put(self, item) -> None:
        self.check()
        if self._closed:
            raise RuntimeError("HostPipeline is closed")
        self._q.put(item)  # blocks when full: bounded backpressure

    # -- worker side --------------------------------------------------------
    def _bump(self, **deltas) -> None:
        with self._lock:
            for k, v in deltas.items():
                self._stats[k] += v

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            try:
                kind = item[0]
                if kind == "stop":
                    return
                if self._exc is not None:
                    continue  # poisoned: drain without side effects
                if kind == "chunk":
                    _, items, toks = item
                    block = np.asarray(jax.device_get(toks))
                    self._bump(transfers=1, chunks=1,
                               tokens=block.shape[0] * len(items))
                    for slot, req in items:
                        fresh = [int(t) for t in block[:, slot]]
                        req.out.extend(fresh)
                        if self.journal is not None:
                            self.journal.emit(req.rid, fresh)
                elif kind == "admit":
                    _, items, firsts = item
                    vals = np.asarray(jax.device_get(firsts)).reshape(-1)
                    t = self.clock()
                    self._bump(transfers=1, tokens=len(items))
                    for row, req in items:
                        tok = int(vals[row])
                        req.first_token_at = t
                        req.out.append(tok)
                        if self.journal is not None:
                            self.journal.emit(req.rid, [tok])
                elif kind == "journal":
                    _, method, args = item
                    if self.journal is not None:
                        getattr(self.journal, method)(*args)
            except BaseException as e:  # noqa: BLE001 — surfaced to main
                self._exc = e
            finally:
                self._q.task_done()
