"""Admission batching: coalesce concurrent small requests into micro-batches.

The serving tier's queueing half (docs/SERVING.md). Single-row requests
each paying a full dispatch would serialise the device behind per-call
latency; instead, submitters enqueue and a single dispatcher thread
admits work in micro-batches:

- a batch CLOSES when either (a) `max_wait_ms` has elapsed since its
  OLDEST admitted request (the latency budget a request can pay waiting
  for company — default ~1 ms; the deadline is PINNED to that oldest
  request when its window opens and never re-armed by later arrivals,
  so a steady trickle cannot stretch a batch past the head request's
  budget — the fake-clock regression test in tests/test_serve.py), or
  (b) the batch reaches `max_batch` rows (the largest pre-traced
  bucket);
- the dispatcher never sleeps: it parks on a Condition and wakes on
  submit, so an idle server burns nothing and a lone request under no
  load waits only the max-wait admission window;
- EXPRESS LANE (ISSUE 12): when the queue is empty AND no batch is
  mid-dispatch, a single-row request skips the admission window
  entirely — `express()` dispatches it synchronously on the CALLER's
  thread against the pre-traced [1, F] bucket, so an idle server's
  single-row latency is dispatch time, not `max_wait_ms` + dispatch.
  Under load the lane closes (queue non-empty, or the dispatch gate
  held) and requests coalesce exactly as before, so the saturated-
  regime tail cannot regress; the gate also means an express dispatch
  and a batch dispatch never overlap on the device;
- requests are never split across batches and never reordered within
  one — each remembers its row span, so the dispatcher's response
  scatter is positional and a request's rows can neither drop nor
  duplicate (tests/test_serve.py drives this with concurrent
  submitters).

HOT-LOOP MODULE (the ddtlint serve-blocking-io rule): no `time.sleep`,
no synchronous file I/O anywhere in here — a blocked dispatcher thread
stalls EVERY in-flight request's latency, not just its own. The
express lane raises the stakes: the SAME dispatch callable now also
runs on HTTP handler threads, so blocking I/O in the dispatch path
taxes the express path's whole point.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
import uuid


class ShuttingDown(RuntimeError):
    """Raised to waiters whose request cannot be served because the
    batcher is closing."""


#: Trace-id mint (ISSUE 17): a random process prefix + a monotonic
#: sequence — unique enough to join client logs against serve_trace
#: records, and O(1) per request (no per-request entropy syscall in the
#: hot path; the tracing-overhead A/B in scripts/serve_smoke.py holds
#: the default-on path to 1.1x of --no-request-traces).
_TRACE_PREFIX = uuid.uuid4().hex[:12]
_TRACE_SEQ = itertools.count(1)


def _gen_trace_id() -> str:
    return f"{_TRACE_PREFIX}-{next(_TRACE_SEQ):08x}"


def trace_breakdown(req: "PendingRequest") -> "dict | None":
    """The ONE shape home for a completed request's timing breakdown
    (response `X-DDT-Timing` header, the per-model trace ring, and the
    flushed `serve_trace` event all render this dict — they cannot
    drift). Segments, all in ms on the batcher's injected clock:

    - handler_ms — accept -> admit: submit()/express() entry to queue
      append (express: to gate acquisition), i.e. handler-side overhead;
    - queue_ms   — admit -> gate: queue + admission-window wait until
      the batch holding this request acquired the dispatch gate
      (~0 on the express lane — that is the lane's point);
    - gate_ms    — gate -> device: batch assembly under the gate
      (width checks, per-request transform, concat);
    - device_ms  — the device call (score_binned);
    - wake_ms    — device done -> result publication;
    - total_ms   — accept -> publication (the client-observed span
      minus transport).

    Returns None for an untraced or still-pending request."""
    m = req.marks
    if m is None or "wake" not in m:
        return None
    acc = m["accept"]
    adm = m.get("admit", acc)
    gate = m.get("gate", adm)
    dev = m.get("device", gate)
    done = m.get("done", dev)
    wake = m["wake"]
    return {
        "handler_ms": round((adm - acc) * 1e3, 3),
        "queue_ms": round((gate - adm) * 1e3, 3),
        "gate_ms": round((dev - gate) * 1e3, 3),
        "device_ms": round((done - dev) * 1e3, 3),
        "wake_ms": round((wake - done) * 1e3, 3),
        "total_ms": round((wake - acc) * 1e3, 3),
    }


class PendingRequest:
    """One submitted request: rows in, scores (or an exception) out.

    `result()` blocks the SUBMITTER only; the dispatcher thread signals
    the event after the scatter. Latency accounting: `t_submit` is
    stamped at enqueue, the engine stamps completion — the span covers
    queue wait + admission window + dispatch, which is what a caller
    experiences. `model_token` is stamped by the dispatcher with the
    content digest of the model that actually scored this request —
    reading the engine's current token around submit/result instead is
    a race against hot swap (a swap landing in between attributes the
    response to the wrong version; scripts/serve_smoke.py catches it).
    `express` marks a request the express lane dispatched synchronously
    (never queued) — the engine's stats read it for the two-regime
    telemetry. `trace_id`/`marks` carry the ISSUE 17 request trace:
    the id round-trips client -> response header, and `marks` (None
    when tracing is off) accumulates clock marks through the batcher's
    injected clock seam — trace_breakdown() renders them."""

    __slots__ = ("rows", "n", "t_submit", "model_token", "express",
                 "trace_id", "marks", "_event", "_result", "_error")

    def __init__(self, rows, n: int):
        self.rows = rows
        self.n = n
        self.t_submit = time.perf_counter()
        self.model_token = None
        self.express = False
        self.trace_id = None
        self.marks = None
        self._event = threading.Event()
        self._result = None
        self._error = None

    def set_result(self, scores) -> None:
        self._result = scores
        self._event.set()

    def set_error(self, err: BaseException) -> None:
        self._error = err
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        if not self._event.wait(timeout):
            raise TimeoutError("serve request timed out")
        if self._error is not None:
            raise self._error
        return self._result

    def exception(self) -> "BaseException | None":
        """The delivered error without raising it (None while pending or
        on success) — the fleet's express path inspects this to turn an
        evicted-mid-express race into a reload-and-requeue instead of a
        client-visible failure (ddt_tpu/serve/fleet.py)."""
        return self._error


class MicroBatcher:
    """The admission queue + dispatcher thread.

    `dispatch(batch: list[PendingRequest], queue_depth: int)` is called
    on the dispatcher thread with the admitted batch (total rows <=
    max_batch unless a single over-sized request exceeds it alone —
    those dispatch solo) and the queue depth observed at close time
    (the engine's backlog telemetry). The dispatch callable OWNS
    result/error delivery for every request it receives; if it raises,
    the batcher fails the batch's requests with the exception so no
    submitter hangs."""

    def __init__(self, dispatch, max_wait_ms: float = 1.0,
                 max_batch: int = 256, clock=None, cv=None,
                 own_thread: bool = True, request_traces: bool = True):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {max_wait_ms}")
        self._dispatch = dispatch
        # Per-request trace accumulation (ISSUE 17): on by default; the
        # CLI's --no-request-traces turns it off (a client-supplied
        # trace id is still echoed — only the timing marks and ring
        # entries are skipped).
        self.request_traces = bool(request_traces)
        self.max_wait_s = max_wait_ms / 1e3
        self.max_batch = int(max_batch)
        # Injectable clock (tests drive the admission-deadline math with
        # a fake clock; production always runs perf_counter). Used for
        # t_submit stamps and deadline arithmetic only — the Condition
        # waits themselves are real time.
        self._clock = clock if clock is not None else time.perf_counter
        self._q: collections.deque[PendingRequest] = collections.deque()
        self._cv = threading.Condition()
        if cv is not None:
            # DRIVEN mode (ddt_tpu/serve/fleet.py): the fleet engine
            # shares ONE Condition across every model's batcher so its
            # single dispatcher thread can park on all queues at once;
            # submit()/express() notify through it and the *_locked
            # driver surface below is called with it held.
            self._cv = cv
        # Held around EVERY dispatch (batch loop and express lane): an
        # express dispatch and a batch dispatch never overlap on the
        # device, and the express lane only opens when nothing is
        # mid-flight (its tail-latency-never-regresses contract).
        self._gate = threading.Lock()
        self._closed = False
        self._thread = None
        if own_thread:
            self._thread = threading.Thread(
                target=self._loop, name="ddt-serve-batcher", daemon=True)
            self._thread.start()

    def submit(self, rows, n: int,
               trace_id: "str | None" = None) -> PendingRequest:
        """Enqueue one request (`rows` is the request's row block, `n`
        its row count). Returns immediately; wait on the PendingRequest.
        `trace_id` is the client-supplied id (X-DDT-Trace-Id) — honored
        verbatim, else one is minted when tracing is on."""
        req = PendingRequest(rows, n)
        t = self._clock()
        req.t_submit = t
        if self.request_traces:
            req.trace_id = trace_id if trace_id else _gen_trace_id()
            # The clock rides along so the dispatch body (engine.py's
            # dispatch_batch) stamps gate/device/wake marks on the SAME
            # timebase — the clock= seam is the whole breakdown's clock.
            req.marks = {"_clock": self._clock, "accept": t}
        elif trace_id is not None:
            req.trace_id = trace_id
        with self._cv:
            if self._closed:
                raise ShuttingDown("serve batcher is shut down")
            self._q.append(req)
            if req.marks is not None:
                req.marks["admit"] = self._clock()
            self._cv.notify_all()
        return req

    def express(self, rows, n: int,
                trace_id: "str | None" = None) -> "PendingRequest | None":
        """Express lane: dispatch ONE request synchronously on the
        calling thread, bypassing the admission window — but only when
        the lane is open (queue empty, dispatch gate free). Returns the
        completed PendingRequest, or None when the lane is closed and
        the caller should `submit()` into the queue like everyone else.

        Fairness: the lane is only entered from an EMPTY queue, so no
        queued request is ever overtaken; a batch admitted while the
        express dispatch runs blocks on the gate for at most one
        single-row pre-traced dispatch — and under load the queue is
        never empty, so the lane stays shut and the coalesced path is
        untouched (the two-regime contract of docs/SERVING.md "Express
        lane")."""
        with self._cv:
            if self._closed:
                raise ShuttingDown("serve batcher is shut down")
            if self._q:
                return None                  # load: coalesce as before
            if not self._gate.acquire(blocking=False):
                return None                  # a dispatch is in flight
        # The try/finally opens IMMEDIATELY on the held path: any raise
        # between a successful acquire and the release (even from
        # PendingRequest construction) would otherwise leak the gate and
        # close the lane — and stall the dispatcher loop — forever (the
        # ddtlint lock-release rule pins this shape).
        try:
            req = PendingRequest(rows, n)
            t = self._clock()
            req.t_submit = t
            req.express = True
            if self.request_traces:
                req.trace_id = trace_id if trace_id else _gen_trace_id()
                # "admit" on the express lane is gate acquisition — the
                # queue was skipped, so queue_ms in the breakdown is the
                # lane's ~0 signature.
                req.marks = {"_clock": self._clock, "accept": t,
                             "admit": t}
            elif trace_id is not None:
                req.trace_id = trace_id
            try:
                self._dispatch([req], 0)
            # Same error contract as the dispatcher loop: a scoring
            # failure reaches THIS request's waiter, never the caller's
            # stack mid-flight.
            except Exception as e:  # ddtlint: disable=broad-except
                if not req.done():
                    req.set_error(e)
        finally:
            self._gate.release()
        return req

    def backlog_rows(self) -> int:
        """Live queued-row count (the /metrics and /healthz live-backlog
        gauge — ISSUE 17): takes the Condition briefly, reads, releases.
        Strictly read-only; never signals the dispatcher."""
        with self._cv:
            return self.backlog_rows_locked()

    def close(self, timeout: float = 5.0) -> None:
        """Stop admitting, drain what is queued, join the dispatcher
        (driven batchers have no thread of their own — the fleet loop
        observes `_closed` and drains)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)

    # ------------------------------------------------------------------ #
    # fleet-driver surface (ddt_tpu/serve/fleet.py)
    # ------------------------------------------------------------------ #
    # The *_locked methods are called by the fleet's single dispatcher
    # thread WITH the shared Condition held (the cv= injected at
    # construction); they never take locks themselves.

    def backlog_rows_locked(self) -> int:
        return sum(r.n for r in self._q)

    def head_deadline_locked(self) -> "float | None":
        """Admission deadline of the OLDEST queued request (the same
        pinned-to-the-head-never-re-armed deadline `_loop` uses), or
        None on an empty queue."""
        if not self._q:
            return None
        return self._q[0].t_submit + self.max_wait_s

    def ready_locked(self, now: float) -> bool:
        """True when a batch should close NOW: the head request's
        window expired, or the row budget is already full."""
        if not self._q:
            return False
        if self._q[0].t_submit + self.max_wait_s <= now:
            return True
        return self.backlog_rows_locked() >= self.max_batch

    def admit_locked(self) -> "tuple[list[PendingRequest], int]":
        """Pop the next micro-batch for the external driver (same FIFO
        never-split-never-reordered admission as the owned loop)."""
        return self._admit_locked()

    def fail_pending_locked(self, err: BaseException) -> int:
        """Fail every queued request with `err` (the fleet control
        plane's remove path); returns how many waiters were failed."""
        n = 0
        while self._q:
            self._q.popleft().set_error(err)
            n += 1
        return n

    def dispatch_under_gate(self, fn, batch, depth: int) -> None:
        """Run one admitted batch through `fn(batch, depth)` under the
        dispatch gate — the fleet driver's batch seam. Same contracts
        as `_loop`: the gate means this never overlaps an express
        dispatch on the same model, and a raising `fn` fails the
        batch's waiters instead of killing the driver thread."""
        try:
            with self._gate:
                fn(batch, depth)
        except Exception as e:  # ddtlint: disable=broad-except
            for req in batch:
                if not req.done():
                    req.set_error(e)

    # ------------------------------------------------------------------ #
    # dispatcher thread
    # ------------------------------------------------------------------ #

    def _admit_locked(self) -> "tuple[list[PendingRequest], int]":
        """Pop the next micro-batch (called with the lock held, queue
        non-empty). Requests are admitted FIFO until the row budget is
        hit; an over-budget FIRST request dispatches alone (large
        requests degrade to solo batches rather than erroring)."""
        batch: list[PendingRequest] = []
        rows = 0
        while self._q:
            nxt = self._q[0]
            if batch and rows + nxt.n > self.max_batch:
                break
            batch.append(self._q.popleft())
            rows += nxt.n
            if rows >= self.max_batch:
                break
        return batch, len(self._q)

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._closed:
                    self._cv.wait()
                if not self._q:
                    return                       # closed and drained
                # Admission window: wait for company until the OLDEST
                # queued request's budget expires or the row budget
                # fills. The deadline is computed ONCE from that head
                # request and never touched inside the wake loop — a
                # steady trickle of arrivals re-notifies the Condition
                # but cannot re-arm the window past the head's budget
                # (the fake-clock regression test pins this).
                # cv.wait(timeout) parks the thread — no sleep-polling
                # (the serve-blocking-io contract).
                deadline = self._q[0].t_submit + self.max_wait_s
                while (not self._closed
                       and sum(r.n for r in self._q) < self.max_batch):
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        break
                    self._cv.wait(remaining)
                    if not self._q:              # spurious wake post-drain
                        break
                if not self._q:
                    continue
                batch, depth = self._admit_locked()
            try:
                with self._gate:
                    self._dispatch(batch, depth)
            # The dispatcher thread must survive any scoring failure:
            # deliver it to the batch's waiters and keep serving — dying
            # here would hang every future submitter.
            except Exception as e:  # ddtlint: disable=broad-except
                for req in batch:
                    if not req.done():
                        req.set_error(e)
