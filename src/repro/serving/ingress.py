"""Async double-buffered ingress for the streaming KWS server.

The fused tick (`repro.serving.serve_loop._fused_tick`) is one device
program, but the live `step_batch` path around it is synchronous: build
the slab, dispatch, then BLOCK on the device-to-host score fetch before
the next tick may even be assembled. On an async-dispatch backend the
device is idle while the host stages the next slab and the host is idle
while the device computes — which is exactly the live-vs-scan
throughput gap `BENCH_serve.json` measures (the `lax.scan` replay never
returns to the host between ticks).

This module closes that gap without touching the tick itself:

  * `TickHandle` — the deferred result of one dispatched tick: the one
    lane-dense buffer the tick program packs its (scores, top) into
    (`pack_result`). The server starts that buffer's copy to the host
    at dispatch and hands the handle back at once; the scores
    materialize on first `result()`. The packed buffer is a fresh
    output of the tick, never a `ServerState` leaf, so later ticks
    that donate the state leave it alone — a handle fetched two ticks
    late reads exactly what a synchronous fetch would have.
  * `PipelinedIngress` — preallocated ping-pong host staging. `stage()`
    hands out a (slab, mask) buffer pair to assemble the next tick into
    while the previous tick is still in flight; `commit()` dispatches
    it via `StreamingKWSServer.step_batch_async`. A buffer is reused
    only after the tick that consumed it has been forced to completion
    (the `depth`-deep FIFO), so host writes can never race the device's
    read of a staged slab. `window=K` coalesces K committed ticks into
    one `run_batch_async` scan dispatch — the fixed per-dispatch host
    cost amortizes K-fold at (K-1) ticks of added latency.
  * `TickCoalescer` — micro-batched arrival merging: per-stream frames
    arriving within one 16 ms window coalesce into a single staged
    tick, flushed when every open stream has submitted, when the window
    deadline passes (`poll`), or when a stream submits a second frame
    (which by definition belongs to the next tick).

The pipelined path is BIT-identical to the synchronous `step_batch`
sequence: it dispatches the same jitted program on the same operands in
the same order — only the host-side fetch moves later in time
(tests/test_serve_async.py proves it for every classifier backend,
cascaded and sharded included).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.metrics import span

__all__ = [
    "pack_result",
    "unpack_result",
    "TickHandle",
    "PipelinedIngress",
    "TickCoalescer",
    "CoalescedTick",
]


def pack_result(scores, top):
    """A tick's (scores (..., N, K) float32, top (..., N) int32) as one
    lane-dense int32 buffer (..., K + 1, N), under the ``kws_pack``
    scope: rows 0..K-1 hold the scores transposed, their float32 bits
    unchanged (bitcast), row K holds top. With the stream axis minor, a
    TPU's (8, 128) tiles carry 13 rows of N streams instead of 12 of
    128 lanes per stream, and the host fetches one buffer, not two.
    Integers all the way: no float path can flush top's small values."""
    with jax.named_scope("kws_pack"):
        bits = jax.lax.bitcast_convert_type(
            jnp.swapaxes(scores, -1, -2), jnp.int32
        )
        return jnp.concatenate(
            [bits, jnp.expand_dims(top.astype(jnp.int32), -2)], axis=-2
        )


def unpack_result(packed: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(scores (..., N, K) float32, top (..., N) int32) as views of a
    host `pack_result` buffer: the very bits the tick computed."""
    k = packed.shape[-2] - 1
    scores = np.swapaxes(packed[..., :k, :].view(np.float32), -1, -2)
    return scores, packed[..., k, :]


class TickHandle:
    """Deferred result of one asynchronously dispatched serving tick.

    Holds the tick's packed result (`pack_result`) — a fresh output of
    the tick program, never one of the `ServerState` buffers the NEXT
    tick donates, so it needs no copy of its own to outlive them. The
    server has started its copy to the host at dispatch. `result()`
    blocks until the tick has executed, materializes the buffer once as
    an owned host array, returns (scores, top) as views of it, and
    caches them; the device array is dropped at that point so
    steady-state serving holds at most `depth` tick outputs.

    `meta` is caller-owned freight (e.g. a submit timestamp or the
    {stream_id: slot} map of a coalesced tick); `done_at` records the
    host clock at the EARLIEST moment the tick was observed complete —
    the first `ready() == True` poll, or the end of the first
    `result()` when nobody polled — for SLO-style latency accounting.
    (It used to be stamped only inside `result()`, so a consumer that
    polled `ready()` and fetched later recorded the fetch time, not
    the completion time, inflating its submit-to-scores latency.)

    The first `result()` runs under the ``kws.handle.fetch`` span
    (children ``kws.handle.wait``, ``kws.handle.d2h``) tagged with
    `tick`, the server's dispatch number, and observed into
    `fetch_hist` when given (the server's ``kws_serve_tick_fetch_ms``);
    its one device-to-host transfer increments `transfers` when given
    (``kws_serve_d2h_transfers_total``). `dispatched_at` is the end of
    the dispatch span.
    """

    __slots__ = ("_packed", "_host", "meta", "tick", "dispatched_at",
                 "done_at", "_fetch_hist", "_transfers", "_clock")

    def __init__(self, packed, meta: Any = None,
                 tick: Optional[int] = None,
                 dispatched_at: Optional[float] = None, fetch_hist=None,
                 transfers=None,
                 clock: Callable[[], float] = time.perf_counter):
        self._packed = packed
        self._host: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.meta = meta
        self.tick = tick
        self.dispatched_at = dispatched_at
        self.done_at: Optional[float] = None
        self._fetch_hist = fetch_hist
        self._transfers = transfers
        self._clock = clock

    def ready(self) -> bool:
        """True when the tick has finished executing (non-blocking).
        The first True poll stamps `done_at`."""
        if self._host is not None:
            return True
        try:
            ok = bool(self._packed.is_ready())
        except AttributeError:  # non-jax array stand-ins
            ok = True
        if ok and self.done_at is None:
            self.done_at = self._clock()
        return ok

    def result(self) -> Tuple[np.ndarray, np.ndarray]:
        """(scores (N, K), top (N,)) — (W, N, K), (W, N) for a window —
        as views of one owned host array; blocks until the tick has
        executed. Idempotent — later calls return the cached views, so
        fetching a handle after further ticks (or slot resets) ran is
        always safe."""
        if self._host is None:
            with span("kws.handle.fetch", self._fetch_hist, self._clock,
                      tick=self.tick) as sp:
                with span("kws.handle.wait", tick=self.tick):
                    jax.block_until_ready(self._packed)
                with span("kws.handle.d2h", tick=self.tick):
                    self._host = unpack_result(np.array(self._packed))
                    if self._transfers is not None:
                        self._transfers.inc()
            self._packed = None
            if self.done_at is None:
                self.done_at = sp.end
        return self._host

    @property
    def scores(self) -> np.ndarray:
        return self.result()[0]

    @property
    def top(self) -> np.ndarray:
        return self.result()[1]


class PipelinedIngress:
    """Double-buffered slab staging over the server's async dispatch.

    `depth` preallocated (slab, mask) host buffer pairs cycle
    round-robin; at most `depth` dispatches are in flight. `stage()`
    returns the next pair for the caller to assemble a tick into —
    forcing the dispatch that consumed this buffer `depth` cycles ago
    to completion first, which both bounds the pipeline and guarantees
    the buffer being handed out is no longer being read by the device.
    `commit()` dispatches without blocking. Completed handles
    accumulate in FIFO order; collect them with `retired()` or force
    everything with `drain()`.

    depth=1 degrades to the synchronous cadence (every dispatch
    completes before the next is staged); depth=2 is classic double
    buffering — host staging of tick N+1 overlaps device execution of
    tick N.

    `window` is the throughput/latency knob: with window=1 (default)
    every `commit()` dispatches one fused tick via `step_batch_async`
    and `handle.meta` is that tick's meta. With window=K, K
    consecutively committed ticks coalesce into ONE device dispatch
    (`run_batch_async`: a length-K scan of the same fused tick body,
    bit-identical to K sequential ticks) — amortizing the fixed
    per-dispatch host cost K-fold, which is what closes the
    live-vs-scan throughput gap on a dispatch-bound host. The window's
    handle materializes (K, N, C) scores / (K, N) tops, `handle.meta`
    is the list of the K per-tick metas in commit order, and a tick's
    scores arrive only when its window flushes — at a 16 ms tick
    cadence that bounds added latency at (K-1) ticks, so keep K small
    (2-8) for live serving. `commit()` returns the handle on the
    window-filling commit and None otherwise; `flush()` force-
    dispatches a partial window (scan length = ticks staged so far).
    """

    def __init__(self, server, dim: int, depth: int = 2,
                 window: int = 1):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        server._is_raw(int(dim))  # canonical kind validation, up front
        self.server = server
        self.dim = int(dim)
        self.depth = depth
        self.window = window
        n = server.max_streams
        self._slabs = [
            np.zeros((window, n, self.dim), np.float32)
            for _ in range(depth)
        ]
        self._masks = [
            np.zeros((window, n), bool) for _ in range(depth)
        ]
        # (buffer index, handle, traces) in dispatch order; len <= depth
        self._fifo: collections.deque = collections.deque()
        self._retired: List[TickHandle] = []
        self._cursor = 0
        self._fill = 0  # ticks staged+committed into the cursor buffer
        self._metas: List[Any] = []
        self._staged = False
        # observability rides the server's registry: one TickTrace per
        # STAGED tick (stage -> commit -> dispatch -> retire marks, from
        # span boundaries; a window of K ticks shares the dispatch/retire
        # timestamps of its one device call), plus in-flight / pending-
        # window gauges.
        # All host clock reads around the existing calls — operands and
        # dispatch order are untouched, so the pipelined path stays
        # bit-identical with metrics on.
        self.metrics = getattr(server, "metrics", None)
        self._seq = 0
        self._cur_trace = None
        self._traces: List[Any] = []  # committed, awaiting dispatch
        if self.metrics is not None:
            self._m_in_flight = self.metrics.gauge(
                "kws_ingress_in_flight",
                "device dispatches in flight (<= depth)",
            )
            self._m_pending = self.metrics.gauge(
                "kws_ingress_pending_ticks",
                "ticks committed into the current window, undispatched",
            )
            self._m_dispatches = self.metrics.counter(
                "kws_ingress_dispatches_total",
                "device dispatches issued by the pipelined ingress",
            )

    @property
    def in_flight(self) -> int:
        return len(self._fifo)

    @property
    def pending_ticks(self) -> int:
        """Ticks committed into the current window but not dispatched."""
        return self._fill

    def stage(self) -> Tuple[np.ndarray, np.ndarray]:
        """Next (slab, mask) staging pair, mask cleared. Blocks only
        when the pipeline is full (forces the oldest in-flight
        dispatch)."""
        if self._staged:
            raise RuntimeError("stage() called again before commit()")
        k = self.server.dispatch_seq
        with span("kws.ingress.stage", clock=self.server._clock,
                  tick=k) as sp:
            n = self.server.max_streams
            if n != self._slabs[0].shape[1]:
                # The server was resized (autoscaler / shard-loss
                # recovery): the preallocated buffers are the wrong
                # capacity. Reallocating is only safe with the pipeline
                # empty — in-flight dispatches and half-filled windows
                # still hold old-capacity slabs — so callers drain()
                # around a resize and the next stage() picks up the new
                # capacity here.
                if self._fifo or self._fill:
                    raise RuntimeError(
                        "server capacity changed mid-pipeline: drain() "
                        "the ingress before staging into the resized "
                        "server"
                    )
                self._slabs = [
                    np.zeros((self.window, n, self.dim), np.float32)
                    for _ in range(self.depth)
                ]
                self._masks = [
                    np.zeros((self.window, n), bool)
                    for _ in range(self.depth)
                ]
            i = self._cursor
            if self._fill == 0 and self._fifo and self._fifo[0][0] == i:
                # about to write row 0 of buffer i: the dispatch that
                # consumed it is the FIFO front — buffers cycle
                # round-robin and retire in dispatch order
                with span("kws.ingress.reuse_wait", tick=k):
                    while self._fifo and self._fifo[0][0] == i:
                        self._retire(*self._fifo.popleft()[1:])
            mask = self._masks[i][self._fill]
            mask[:] = False
        self._staged = True
        if self.metrics is not None:
            tr = self.metrics.trace(("tick", self._seq))
            self._seq += 1
            tr.mark("stage", sp.end)
            self._cur_trace = tr
        return self._slabs[i][self._fill], mask

    def commit(self, meta: Any = None) -> Optional[TickHandle]:
        """Commit the staged tick; dispatches (non-blocking) when the
        window is full. Returns the window's handle on the dispatching
        commit, None while the window is still filling."""
        if not self._staged:
            raise RuntimeError("commit() without a prior stage()")
        self._staged = False
        with span("kws.ingress.commit", clock=self.server._clock,
                  tick=self.server.dispatch_seq) as sp:
            self._metas.append(meta)
            if self._cur_trace is not None:
                self._cur_trace.mark("commit", sp.start)
                self._traces.append(self._cur_trace)
                self._cur_trace = None
                self._m_pending.set(self._fill + 1)
            self._fill += 1
            if self._fill == self.window:
                return self._dispatch()
        return None

    def flush(self) -> Optional[TickHandle]:
        """Dispatch the partially filled window now (no-op when empty).
        A partial window scans only the ticks actually staged — never
        padded no-op ticks — so the state trajectory stays identical."""
        if self._staged:
            raise RuntimeError("flush() with a stage() pending commit()")
        if self._fill == 0:
            return None
        return self._dispatch()

    def _dispatch(self) -> TickHandle:
        i, k = self._cursor, self._fill
        if self.window == 1:
            handle = self.server.step_batch_async(
                self._slabs[i][0], self._masks[i][0]
            )
            handle.meta = self._metas[0]
        else:
            handle = self.server.run_batch_async(
                self._slabs[i][:k], self._masks[i][:k]
            )
            handle.meta = list(self._metas)
        traces, self._traces = self._traces, []
        # one device call serves the whole window: its ticks share the
        # dispatch timestamp (and, at retire, done_at)
        for tr in traces:
            tr.mark("dispatch", handle.dispatched_at)
        if self.metrics is not None:
            self._m_dispatches.inc()
            self._m_in_flight.set(len(self._fifo) + 1)
            self._m_pending.set(0)
        self._fifo.append((i, handle, traces))
        self._cursor = (i + 1) % self.depth
        self._fill = 0
        self._metas = []
        return handle

    def _retire(self, h: TickHandle, traces) -> None:
        """Force one in-flight dispatch to completion and collect it."""
        h.result()
        for tr in traces:
            tr.mark("retire", h.done_at)
        if self.metrics is not None:
            self._m_in_flight.set(len(self._fifo))
        self._retired.append(h)

    def retired(self) -> List[TickHandle]:
        """Handles forced to completion so far, in dispatch order
        (clears the internal list)."""
        out, self._retired = self._retired, []
        return out

    def drain(self) -> List[TickHandle]:
        """Flush the pending window, force every in-flight dispatch,
        and return ALL completed handles (previously retired +
        just-drained), in dispatch order."""
        self.flush()
        while self._fifo:
            self._retire(*self._fifo.popleft()[1:])
        return self.retired()


@dataclasses.dataclass
class CoalescedTick:
    """Meta freight of one coalesced tick's handle: which streams
    submitted (and the slot each occupied AT DISPATCH TIME — the
    mapping to index the handle's score rows with, immune to later
    close/reopen), plus the window's host timestamps."""

    sids: Dict[int, int]
    staged_at: float
    flushed_at: Optional[float] = None


class TickCoalescer:
    """Merge sub-window per-stream arrivals into single dispatched ticks.

    Live traffic rarely arrives slab-shaped: each stream's 16 ms hop
    lands on its own schedule. Dispatching a full-slab tick per arrival
    wastes the batch; waiting for stragglers forever stalls it. The
    coalescer stages arrivals into one pending tick and flushes it when

      * every open stream has submitted (the tick is full),
      * the window deadline (`window_ms` after the first arrival)
        passes — checked by `poll()`, or
      * a stream submits a SECOND frame (which belongs to the next
        tick: the pending one flushes first, then the new frame opens
        the next window).

    Flushing dispatches through a per-kind `PipelinedIngress`, so
    coalescing composes with double buffering: the flushed tick's
    handle materializes while the next window fills. Completed handles
    (meta = `CoalescedTick`) are collected via `retired()` / `drain()`.

    `clock` is injectable for deterministic tests; `now` may also be
    passed explicitly to `add`/`poll`/`flush`.
    """

    def __init__(self, server, window_ms: float = 16.0, depth: int = 2,
                 clock: Callable[[], float] = time.monotonic):
        if window_ms <= 0:
            raise ValueError(f"window_ms must be > 0, got {window_ms}")
        self.server = server
        self.window_s = window_ms * 1e-3
        self.depth = depth
        self.clock = clock
        self._ingress: Dict[int, PipelinedIngress] = {}
        self._pending = None  # (ingress, slab, mask, CoalescedTick, deadline)
        self._retired: List[TickHandle] = []
        # per-reason flush counters on the server's registry: "full"
        # (every open stream submitted), "deadline" (window_ms passed),
        # "second_frame" (a stream's next-tick frame forced the flush),
        # "manual" (caller flush()/drain())
        self.metrics = getattr(server, "metrics", None)

    @property
    def pending_streams(self) -> int:
        """Streams staged in the currently open window (0 = no window)."""
        return 0 if self._pending is None else len(self._pending[3].sids)

    def add(self, stream_id: int, frame, now: Optional[float] = None
            ) -> List[TickHandle]:
        """Stage one stream's frame; returns any handles this call
        retired (a second-frame or tick-full flush may complete older
        ticks)."""
        now = self.clock() if now is None else now
        if stream_id not in self.server.active:
            raise ValueError(f"stream {stream_id} not open")
        frame = np.asarray(frame, np.float32)
        dim = int(frame.shape[-1])
        self.server._is_raw(dim)  # canonical kind/width validation
        if self._pending is not None and self._pending[0].dim != dim:
            raise ValueError(
                "all frames in one tick must be the same kind; pending "
                f"window holds dim {self._pending[0].dim}, got {dim} "
                "(flush() the window before switching kinds)"
            )
        if self._pending is not None and stream_id in self._pending[3].sids:
            # a stream's second frame belongs to the NEXT tick
            self._flush("second_frame", now)
        if self._pending is None:
            ing = self._ingress.get(dim)
            if ing is None:
                ing = PipelinedIngress(self.server, dim, depth=self.depth)
                self._ingress[dim] = ing
            slab, mask = ing.stage()
            meta = CoalescedTick(sids={}, staged_at=now)
            self._pending = (ing, slab, mask, meta, now + self.window_s)
        ing, slab, mask, meta, _deadline = self._pending
        slot = self.server.active[stream_id]
        slab[slot] = frame
        mask[slot] = True
        meta.sids[stream_id] = slot
        if len(meta.sids) >= len(self.server.active):
            self._flush("full", now)
        return self.retired()

    def poll(self, now: Optional[float] = None) -> List[TickHandle]:
        """Flush the pending window iff its deadline has passed; returns
        handles retired so far either way."""
        now = self.clock() if now is None else now
        if self._pending is not None and now >= self._pending[4]:
            self._flush("deadline", now)
        return self.retired()

    def flush(self, now: Optional[float] = None) -> Optional[TickHandle]:
        """Dispatch the pending window as one tick (no-op when empty)."""
        return self._flush("manual", now)

    def _flush(self, reason: str, now: Optional[float] = None
               ) -> Optional[TickHandle]:
        if self._pending is None:
            return None
        now = self.clock() if now is None else now
        ing, _slab, _mask, meta, _deadline = self._pending
        self._pending = None
        meta.flushed_at = now
        handle = ing.commit(meta=meta)
        if self.metrics is not None:
            self.metrics.counter(
                "kws_coalescer_flushes_total",
                "coalesced-tick flushes by trigger",
                reason=reason,
            ).inc()
        self._retired.extend(ing.retired())
        return handle

    def retired(self) -> List[TickHandle]:
        """Completed handles collected so far (clears the list)."""
        for ing in self._ingress.values():
            self._retired.extend(ing.retired())
        out, self._retired = self._retired, []
        return out

    def drain(self) -> List[TickHandle]:
        """Flush the pending window and force every in-flight tick."""
        self.flush()
        for ing in self._ingress.values():
            self._retired.extend(ing.drain())
        return self.retired()
