"""Request-coalescing micro-batcher for the top-N serving hot path.

TPU-native replacement for the reference's per-request thread-fanned
partition scans (app/oryx-app-serving/.../als/model/ALSServingModel.java:
261-276 fans one top-N over LSH partitions with an executor PER REQUEST):
on an accelerator the economical unit is one big batched matmul, so
concurrent HTTP requests are gathered for a sub-millisecond window (or
until ``max_batch``) and answered with ONE ``top_n_batch`` device call.
Under the reference LoadBenchmark's concurrency this turns N matmul
launches + N host-device round-trips into one of each.

Coalescing applies when the request has no score-rewriting rescorer
(``rescore`` hooks change scores, which a shared scan cannot honor);
host-side ``allowed`` filters and per-query known-item exclusions ride
along — ``top_n_batch`` masks exclusions on device and falls back per
query if a filter exhausts its candidates.

Pure asyncio: submissions happen on the event loop; the batched device
call runs in the default executor so the loop never blocks on the chip.
"""

from __future__ import annotations

import asyncio
import collections
import statistics
import weakref

import numpy as np

from oryx_tpu.api.serving import OverloadedException
from oryx_tpu.common import blackbox
from oryx_tpu.common import devicephase
from oryx_tpu.common import faults
from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import resilience
from oryx_tpu.common import spans

log = spans.get_logger(__name__)

_BATCH_SIZE = metrics_mod.default_registry().histogram(
    "oryx_coalescer_batch_size",
    "Real (pre-padding) request count per coalesced device call",
    buckets=metrics_mod.POW2_BUCKETS,
)
_QUEUE_DEPTH = metrics_mod.default_registry().gauge(
    "oryx_coalescer_queue_depth",
    "Requests waiting for a coalesced flush",
)
_DEADLINE_FLUSHES = metrics_mod.default_registry().counter(
    "oryx_coalescer_deadline_flushes_total",
    "Flushes forced past the inflight cap by the queue-wait deadline",
)
_FLUSH_OPENED = metrics_mod.default_registry().counter(
    "oryx_coalescer_flush_opened_total",
    "Coalesced flushes by what opened them: the window's timer, a full "
    "batch, the gate's timer at the predicted end of the device's work less "
    "the host stage (anticipated), the reported end of it (device_free), a "
    "call's completion that found the gate open (completion: all that a call "
    "which reports no device phase, or whose scan is not worth a wait, ever "
    "takes behind a busy chip), the queue-wait deadline",
    labelnames=("by",),
)
#: its children, resolved once: a flush is on every request's path
_OPENED_BY = {by: _FLUSH_OPENED.labels(by) for by in (
    "window", "full", "anticipated", "device_free", "completion", "deadline")}
_PAD_WASTE = metrics_mod.default_registry().counter(
    "oryx_coalescer_pad_waste_rows_total",
    "Padding rows added to reach power-of-two batch shapes",
)
_SHED = metrics_mod.default_registry().counter(
    "oryx_shed_requests_total",
    "Requests refused up front (503 + Retry-After) because the coalescer "
    "queue exceeded oryx.serving.compute.max-queue-depth",
)
_DEGRADED = metrics_mod.default_registry().counter(
    "oryx_breaker_degraded_requests_total",
    "Requests served WITHOUT coalescing because the device-call circuit "
    "breaker was open (per-request fallback scans on the current model)",
)
_DEADLINE_DROPS = metrics_mod.default_registry().counter(
    "oryx_coalescer_deadline_dropped_total",
    "Queued requests whose per-request deadline expired before dispatch "
    "(answered 504 without spending a device call on them)",
)


def floor_pow2(n: int) -> int:
    """Largest power of two ≤ max(1, n) — the coalescer's batch-cap floor,
    shared with the batch warmer so both always agree on real flush sizes."""
    return 1 << max(0, max(1, n).bit_length() - 1)


def _pad_pow2(n: int) -> int:
    """The batch a flush of ``n`` requests is padded to."""
    return 1 << max(0, n - 1).bit_length()


def pow2_buckets(max_batch: int) -> list[int]:
    """Ascending pow2 batch buckets ``[1, 2, ..., floor_pow2(max_batch)]``.

    THE bucket enumeration of the serving hot path: the coalescer pads every
    flush up to one of these sizes (``_execute``), and the warmup subsystem
    precompiles exactly this ladder (smallest first, so a starting replica
    turns ready incrementally) — keeping both ends in one function means a
    cap change can never warm sizes that are not flushed, or flush sizes
    that were not warmed."""
    return [1 << i for i in range(floor_pow2(max_batch).bit_length())]


class _Pending:
    __slots__ = ("vec", "want", "how_many", "offset", "allowed", "excluded",
                 "future", "enq_t", "wait_span", "deadline")

    def __init__(self, vec, how_many, offset, allowed, excluded, future,
                 enq_t: float = 0.0, wait_span=None, deadline=None):
        self.vec = vec
        self.want = how_many + offset
        self.how_many = how_many
        self.offset = offset
        self.allowed = allowed
        self.excluded = excluded
        self.future = future
        self.enq_t = enq_t
        # queue-wait span: opened at enqueue as a child of the request's
        # ingress span (contextvars do NOT cross the executor hop, so the
        # span object itself is the carrier), closed at dispatch
        self.wait_span = wait_span
        # the request's Deadline, captured at enqueue for the same reason:
        # the executor-side dispatch checks it before spending device time
        self.deadline = deadline


#: readings an estimate is made of: enough to ride out a flush the machine
#: froze under, few enough to follow a scan that changed
_RECENT = 8
#: the gate is worth its aim where a flush opened at its slot would leave its
#: programs this share of a scan, or more, in the device's queue
_WORTH = 1 / 3
#: a timer may fire a clock's resolution before it is due
_EARLY = 1e-6


class _Flush:
    """One dispatched flush's device phase, as its call reports it
    (common/devicephase.py). ``enqueued`` and ``device_done`` run on the
    executor thread and only stamp their instant on the loop's clock: the
    loop reads the stamps when it next decides something. ``device_done``
    wakes it only while requests wait at the gate behind this very flush
    (``TopNCoalescer._held``) — a wakeup hands the interpreter to the loop
    in the middle of the flush's own path, so a report nobody waits on must
    cost the flush's answers nothing. Only the first of each counts: a
    query that falls back to the widening scan inside a flush launches
    programs of its own, after the flush's device phase.

    ``prev`` is the flush opened before this one, if its device phase was
    still running then: this flush's programs start when they are enqueued
    or when ``prev``'s are done, whichever is later (``start_t``)."""

    __slots__ = ("coal", "loop", "gen", "width", "prev", "opened_t", "enq_t",
                 "start_t", "done_t")

    def __init__(self, coal, loop, gen: int, width: int, prev, opened_t: float):
        self.coal = coal
        self.loop = loop
        self.gen = gen  # of the coalescer's estimates: a model since replaced
        self.width = width  # padded batch: the scan's time depends on it
        self.prev = prev
        self.opened_t = opened_t
        self.enq_t = self.start_t = self.done_t = None

    def enqueued(self) -> None:
        if self.enq_t is None:
            self.enq_t = self.loop.time()

    def device_done(self) -> None:
        if self.done_t is not None or self.enq_t is None:
            return
        now = self.loop.time()
        prev = self.prev
        if prev is None:
            self.start_t = self.enq_t
        elif prev.done_t is not None:
            self.start_t = max(self.enq_t, prev.done_t)
        # else the flush before has not said so yet (its thread was not
        # scheduled): this one gives no reading of its own scan
        self.done_t = now
        coal = self.coal
        if coal._held and coal._last is self:
            self.loop.call_soon_threadsafe(coal._kick, self.loop, "device_free")


class TopNCoalescer:
    """Gathers concurrent top-N requests into one batched device call.

    Batch-while-busy: with the chip free a request flushes after at most
    ``window_ms``; while a flush has the chip new arrivals accumulate, and
    the next flush opens **when the chip will be free by the time its host
    stage is over** (and one of ``max_inflight`` slots is). Under
    closed-loop clients (each awaiting its response before sending the next
    request) this makes the batch size converge on arrival-rate ×
    device-latency automatically — a fixed window would degenerate to
    one-request batches the moment latency exceeds it, paying a full device
    round-trip per request.

    The device phase is REPORTED by the call (``common/devicephase.py``:
    *enqueued* at the launch of its last program, *device done* when it has
    their results) as two stamps the loop reads when it next decides — the
    executor thread wakes the loop for *device done* only while requests
    wait at the gate — and the estimates come from the flushes themselves,
    read at each call's completion off the last few of the model object
    being served. The host stage ``h``: opened → enqueued, a late timer
    included, the median. The scan ``S`` by padded batch width: *device done
    − the previous flush's device done*, off the flushes whose programs sat
    behind another's — that is the scan plus whatever the two reports' lags
    differ by, so the LEAST of them; a width whose scan has not shown yet is
    scheduled by the slots, which make its programs sit. The ``lag`` of a
    report behind the device (the launch onto an idle device, the
    notification, the results' copies back): what a program that found the
    device idle took beyond ``S``, the median.

    **Whether there is anything to wait for** is read off the same three:
    by the slots alone, two flushes deep, a flush opens at the completion
    of the one before the one that has the chip and its programs sit
    ``S − h − lag`` in the device's queue. Where that is under a third of
    the scan (``_WORTH``: a scan of 3 ms behind a host stage of 1.5 and a
    lag of 1.5) an aim that errs by a fraction of a host stage costs more than
    the wait it takes away, and the gate stands open: the slots alone
    schedule the model, as they do one that reports nothing. Where it is
    more (a scan of 7 ms), behind a flush in its device phase the next may
    open at *predicted device-free − h* — its handoff, assembly, upload and
    dispatch then run under the scan and its programs reach the device as
    it frees, instead of a whole scan early with the batch closed to
    everything that arrives meanwhile — and no later than the *device done*
    report itself, which is all the first flushes of a model have. At the
    gate whatever is pending flushes at once; with nothing pending the next
    arrival arms the window as on a free chip.

    ``max_inflight`` caps the calls between dispatch and completion: at 2 a
    flush's rescore, id lists and wakeups run under the next flush's scan.
    A call that reports no device phase (a model without the hooks, a fake)
    is scheduled by the slots alone: it flushes after the window while one
    is free, and a completion flushes whatever queued behind it.

    ``deadline_ms`` bounds the queue wait behind in-flight batches (the p99
    failure mode: with every inflight slot busy, arrivals used to wait an
    unbounded number of device round-trips). When the OLDEST pending request
    has waited past the deadline, a flush dispatches anyway — exceeding
    ``max_inflight`` by AT MOST one call, ever: while that over-cap call is
    out, further expired waiters re-arm and wait for a completion instead of
    stacking device calls. 0 disables.

    One instance per serving app; requests against different model objects
    (a MODEL handoff mid-flight) are grouped by model identity at flush."""

    def __init__(self, window_ms: float = 1.0, max_batch: int = 256,
                 max_inflight: int = 2, deadline_ms: float = 250.0,
                 max_queue_depth: int = 0, breaker=None):
        self.window_s = window_ms / 1000.0
        # floor to a power of two: batches pad up to a pow2 for stable jit
        # signatures, and padding must never exceed the configured cap
        # (the operator tuned it to bound device memory)
        self.max_batch = floor_pow2(max_batch)
        self.max_inflight = max(1, max_inflight)
        self.deadline_s = max(0.0, deadline_ms) / 1000.0
        # load shed past this queue depth (0 = unbounded); the Retry-After
        # hint is roughly one device round-trip — the queue-wait deadline
        self.max_queue_depth = max(0, max_queue_depth)
        # device-call circuit breaker (common/resilience.py); None = always
        # coalesce. Callers consult admit() BEFORE routing a request here.
        self.breaker = breaker
        self._pending: list[tuple[object, _Pending]] = []
        self._flusher: asyncio.TimerHandle | None = None
        self._deadline_timer: asyncio.TimerHandle | None = None
        self._inflight = 0
        self.deadline_flushes = 0  # tests/test_batcher.py reads it
        # the gate (class docstring). ``_last``: the flush opened last, until
        # its call is over — what the next flush's programs would queue
        # behind while it has not stamped its device phase over; ``_held``:
        # requests wait at the gate behind it (its executor thread reads
        # this to know whether the loop is to be woken for its reports)
        self._last: _Flush | None = None
        self._held = False
        self._gate_timer: asyncio.TimerHandle | None = None
        # the estimates, of the model whose flushes they were read off
        self._model: weakref.ref | None = None
        self._gen = 0
        self._host: collections.deque[float] = collections.deque(maxlen=_RECENT)
        self._scan: dict[int, collections.deque[float]] = {}
        self._lag: collections.deque[float] = collections.deque(maxlen=_RECENT)
        # what the gate reads of them, worked out once a call (_release): it
        # is consulted at every arrival. h, lag, and S by width
        self._h = self._lag_s = 0.0
        self._s: dict[int, float] = {}

    def admit(self) -> bool:
        """Breaker admission for the coalesced path: False while the
        device-call breaker is open (callers degrade to per-request scans
        on the current model instead of erroring); half-open admits the
        breaker's probe quota so a recovered device closes it again."""
        if self.breaker is None or self.breaker.allow():
            return True
        _DEGRADED.inc()
        return False

    async def top_n(self, model, query_vec, how_many: int, offset: int = 0,
                    allowed=None, excluded=None) -> list:
        """Coalesced equivalent of ``model.top_n(...)`` (no rescore)."""
        loop = asyncio.get_running_loop()
        if self.max_queue_depth and len(self._pending) >= self.max_queue_depth:
            # shed NOW, before queueing: a 503 in microseconds beats a 200
            # after a timeout-sized queue wait, and the client's retry lands
            # on a drained queue (or another replica)
            _SHED.inc()
            # one throttled flight-recorder event per shed burst (the
            # ``suppressed`` count carries the storm's size) — an overload
            # must be reconstructable from a dead replica's bundle without
            # letting the storm itself evict every other event
            blackbox.record_event(
                "shed", severity="warning", throttle_sec=1.0,
                queue_depth=len(self._pending),
                max_queue_depth=self.max_queue_depth,
            )
            raise OverloadedException(
                f"coalescer queue depth {len(self._pending)} >= "
                f"{self.max_queue_depth}",
                retry_after_sec=max(1.0, self.deadline_s),
            )
        fut = loop.create_future()
        wait_span = spans.start_span(
            "coalescer.queue_wait",
            attributes={"route": "coalescer.queue_wait"},
        )
        self._pending.append((model, _Pending(
            np.asarray(query_vec, dtype=np.float32), how_many, offset,
            allowed,
            excluded if excluded is not None and len(excluded) else None,
            fut, loop.time(), wait_span, resilience.current_deadline(),
        )))
        self._maybe_flush(loop)
        return await fut

    def _maybe_flush(self, loop) -> None:
        _QUEUE_DEPTH.set(len(self._pending))
        if not self._pending:
            self._held = False
            return
        if not self._may_open(loop):
            # a completion, the device's report or the gate's timer will
            # re-trigger; the deadline timer bounds the wait if the
            # in-flight call is slow or wedged
            self._arm_deadline(loop)
            return
        if len(self._pending) >= self.max_batch:
            self._flush(loop, "full")
        elif self._flusher is None:
            self._flusher = loop.call_later(
                self.window_s, self._flush, loop, "window")

    # -- the gate: when the next flush may open (class docstring) -----------

    def _may_open(self, loop) -> bool:
        """May a flush open now? Where it is the gate that says no, requests
        are ``_held`` and the gate's timer is set."""
        if self._inflight >= self.max_inflight:
            self._held = False  # only a completion helps: it re-triggers
            return False
        # before the stamps are read: a report that lands after this line
        # sees it and wakes the loop, one that landed before it is read here
        self._held = True
        at = self._opens_at()
        if at is not None and at <= loop.time() + _EARLY:
            self._held = False
            return True
        self._arm_gate(loop, at)
        return False

    def _device_busy(self) -> bool:
        """Is the flush opened last still in its device phase, as far as
        it has said?"""
        return self._last is not None and self._last.done_t is None

    def _opens_at(self) -> "float | None":
        """From when the next flush may open behind the one that has the
        chip: 0.0 where none has it or there is nothing worth waiting for,
        None where only that flush's *device done* report can say."""
        if not self._device_busy():
            return 0.0
        last = self._last
        if not self._host:
            # no call of this model is over yet: one that has reported its
            # launch will report its end; one that has not may never
            reports = last.enq_t is not None and last.gen == self._gen
            return None if reports else 0.0
        scan = self._s.get(last.width)
        if scan is None:
            # a width's scan shows when a flush of it sits behind another
            # (_release): until the slots have made one, they schedule it
            return 0.0
        if not self._worth_an_aim(scan):
            return 0.0
        free = self._free_at(last)
        # its report comes ``lag`` after the device is free: the next
        # flush's programs are due THEN, not at the report
        return None if free is None else free - self._lag_s - self._h

    def _worth_an_aim(self, scan: float) -> bool:
        """Would a flush opened at its slot leave its programs a share of
        ``scan`` worth taking in the device's queue (class docstring)?"""
        return scan - self._h - self._lag_s >= _WORTH * scan

    def _follow(self, model) -> None:
        """The estimates are of ONE model object's flushes: another's scan
        is another size, and it may report nothing."""
        if self._model is not None and self._model() is model:
            return
        self._model = weakref.ref(model)
        self._gen += 1
        self._host.clear()
        self._scan.clear()
        self._lag.clear()
        self._s.clear()
        self._h = self._lag_s = 0.0

    def _free_at(self, flush: _Flush) -> "float | None":
        """When ``flush``'s call will report its device phase over: as it
        did, else as the estimates predict (None where its width has no
        scan yet). A program that finds the device idle is reported done
        the lag and its scan after it was enqueued; one that queued, a scan
        after the call before it."""
        if flush.done_t is not None:
            return flush.done_t
        scan = self._s.get(flush.width)
        if scan is None:
            return None
        start = flush.opened_t + self._h if flush.enq_t is None else flush.enq_t
        start += self._lag_s
        if flush.prev is not None:
            before = self._free_at(flush.prev)
            if before is None:
                return None
            start = max(start, before)
        return start + scan

    def _believed(self, loop, flush: _Flush, by: str) -> tuple:
        """What the gate believed of the device when ``flush`` was opened,
        for its call span — entered only where that span is recorded
        (docs/observability.md "Request tracing"). ``(now, attributes)``:
        the loop's clock at the span's start, which the offsets count from."""
        now = loop.time()
        told = {"gate.h_ms": _ms(self._h), "gate.lag_ms": _ms(self._lag_s)}
        scan = self._s.get(flush.width)
        if scan is not None:
            told["gate.scan_ms"] = _ms(scan)
        told["gate.engaged"] = scan is not None and self._worth_an_aim(scan)
        if flush.prev is not None:
            # the aim: when the device was to be free of the flush before —
            # its report was expected ``lag`` after that (_opens_at)
            free = self._free_at(flush.prev)
            if free is not None:
                told["gate.free_in_ms"] = _ms(free - self._lag_s - now)
        if by == "anticipated":
            # how late the loop ran the gate's timer (_arm_gate)
            told["gate.late_ms"] = _ms(now - flush.opened_t)
        return now, told

    def _arm_gate(self, loop, at: "float | None") -> None:
        """The gate's timer for ``at`` (None: only a report opens it). A
        timer on a busy loop (and a selector that sleeps in whole
        milliseconds) fires late, and that is host stage like the rest: the
        flush it opens counts as opened when it was due, so the estimate
        allows for it."""
        if self._gate_timer is not None:
            self._gate_timer.cancel()
            self._gate_timer = None
        if at is not None:
            self._gate_timer = loop.call_at(at, self._kick, loop,
                                            "anticipated", at)

    def _kick(self, loop, by: str, opened_t: "float | None" = None) -> None:
        """What kept requests waiting may be over: the gate's timer, the
        report they were held for, a completion (``by``, the label of the
        flush this opens). They have waited already: no window on top."""
        if not self._pending:
            self._held = False
        elif self._may_open(loop):
            self._flush(loop, by, opened_t=opened_t)
        else:
            self._arm_deadline(loop)

    def _arm_deadline(self, loop) -> None:
        if self.deadline_s <= 0 or self._deadline_timer is not None:
            return
        oldest = self._pending[0][1].enq_t
        # floor the re-arm delay: an ALREADY-expired waiter (over-cap slot
        # spent, device wedged) would otherwise re-arm at 0 and busy-spin
        # the event loop until a device call completes
        delay = max(oldest + self.deadline_s - loop.time(),
                    self.deadline_s / 8.0, 0.001)
        self._deadline_timer = loop.call_later(
            delay, lambda: self._deadline_fire(loop)
        )

    def _deadline_fire(self, loop) -> None:
        self._deadline_timer = None
        if not self._pending:
            return
        # the entry this timer was armed for may have flushed already: only
        # force past the inflight cap for a waiter that actually expired
        oldest = self._pending[0][1].enq_t
        if loop.time() - oldest + 1e-4 < self.deadline_s:
            self._arm_deadline(loop)
            return
        if self._inflight > self.max_inflight:
            # the single over-cap slot is already spent (a previous forced
            # call hasn't completed): never stack further device calls —
            # re-arm and wait for a completion to drain the queue
            self._arm_deadline(loop)
            return
        if self._inflight == self.max_inflight:
            self.deadline_flushes += 1
            _DEADLINE_FLUSHES.inc()
        # past the gate in any case, and past the cap by that one call
        self._flush(loop, "deadline", force=True)
        if self._pending:
            self._arm_deadline(loop)

    def _flush(self, loop, by: str, force: bool = False,
               opened_t: "float | None" = None) -> None:
        """Dispatch what is pending, as far as slots and the gate allow;
        ``by`` says what opened the flush (the counter's label),
        ``opened_t`` when, where that was not now (``_arm_gate``)."""
        if self._flusher is not None:
            self._flusher.cancel()
            self._flusher = None
        self._arm_gate(loop, None)  # set anew behind the flush opened here
        if not force and self._inflight >= self.max_inflight:
            return  # raced with a slower flush path; completion re-triggers
        batch = self._pending[:self.max_batch]
        self._pending = self._pending[self.max_batch:]
        if not batch:
            return
        by_model: dict[int, tuple[object, list[_Pending]]] = {}
        for model, p in batch:
            by_model.setdefault(id(model), (model, []))[1].append(p)
        # a flush spanning several model objects (MODEL handoff mid-flight)
        # must still honor max_inflight and the gate: dispatch while both
        # allow (force grants exactly one call past them — the deadline
        # escape hatch) and push the rest back to the queue front for the
        # next completion
        groups = list(by_model.values())
        while groups:
            self._follow(groups[0][0])
            if not (force or self._may_open(loop)):
                break
            force = False
            model, group = groups.pop(0)
            self._inflight += 1
            _BATCH_SIZE.observe(len(group))
            _OPENED_BY[by].inc()
            # queue wait ends at dispatch, and the device-call span OPENS
            # here (not in the executor): the executor-scheduling handoff is
            # part of what the request waits for, so it must be inside a
            # span — otherwise the trace shows an unattributable gap. The
            # call span opens BEFORE the wait spans close so a scheduling
            # pause between the two timestamps reads as span overlap, never
            # as an unattributed hole in the trace.
            now = loop.time()
            flush = _Flush(self, loop, self._gen, _pad_pow2(len(group)),
                           self._last if self._device_busy() else None,
                           now if opened_t is None else opened_t)
            opened_t = None
            self._last = flush
            waits = [p.wait_span.context for p in group]
            # parent = the first waiter; links = the OTHER waiters (linking
            # the parent too would double-count that request in the fan-in)
            call_span = spans.start_span(
                "coalescer.device_call",
                parent=waits[0],
                links=[c for c in waits[1:] if c is not None],
                attributes={
                    "route": "coalescer.device_call",
                    "batch.size": len(group),
                    "opened_by": by,
                    "queue_wait_max_ms": round(
                        (now - min(p.enq_t for p in group)) * 1000.0, 3
                    ),
                },
            )
            # the flush's stages (children of the call span) sit in the FIRST
            # waiter's trace only; ``call`` — the call span's id, on it and
            # on every stage — is what joins them to the other waiters
            call_span.set_attribute("call", call_span.span_id)
            believed = (None if call_span is spans.NOOP_SPAN
                        else self._believed(loop, flush, by))
            handoff = spans.start_span(
                "coalescer.handoff", parent=call_span,
                attributes={"call": call_span.span_id},
            )
            for p in group:
                p.wait_span.set_attribute(
                    "queue_wait_ms", round((now - p.enq_t) * 1000.0, 3)
                )
                spans.finish_span(p.wait_span)
            try:
                loop.run_in_executor(None, self._execute, loop, model, group,
                                     call_span, handoff, flush, believed)
            except Exception as e:  # noqa: BLE001 — executor/loop torn down
                # dispatch itself failed (executor shut down mid-close): the
                # slot was taken but _execute will never run, so _done will
                # never release it — undo the increment HERE and fail the
                # group's futures instead of leaving them (and every later
                # pending request behind the leaked slot) to hang until
                # client timeout
                self._release(loop, flush)
                spans.finish_span(handoff)
                call_span.record_exception(e)
                spans.finish_span(call_span)
                log.exception(
                    "coalesced dispatch failed before execution; failing "
                    "its %d request(s)", len(group),
                )
                for p in group:
                    _set_exception(p.future, e)
        for model, group in reversed(groups):
            self._pending[:0] = [(model, p) for p in group]
        self._maybe_flush(loop)

    def _release(self, loop, flush: _Flush) -> None:
        """``flush``'s call is over: its slot, its place in the gate, and
        what it read of the host stage, the scan and the lag."""
        self._inflight -= 1
        if flush.gen == self._gen and flush.enq_t is not None:
            self._host.append(flush.enq_t - flush.opened_t)
            self._h = statistics.median(self._host)
            scans = self._scan.get(flush.width)
            if flush.start_t is None:
                pass  # the flush before never said when it was done
            elif flush.start_t > flush.enq_t:
                # its programs sat behind the flush before: done − that
                # flush's done is the scan, plus whatever the two reports'
                # lags differ by — the LEAST of the recent is the scan
                if scans is None:
                    scans = self._scan[flush.width] = collections.deque(
                        maxlen=_RECENT)
                scans.append(flush.done_t - flush.start_t)
                self._s[flush.width] = min(scans)
            elif scans:
                # they found the device idle: what the report took beyond
                # the scan is the lag. Alone, such a flush cannot tell the
                # two apart: a width's scan shows only once a flush of it
                # has sat in the device's queue, as the slots make it
                self._lag.append(flush.done_t - flush.enq_t - min(scans))
                self._lag_s = statistics.median(self._lag)
        if flush.done_t is None:
            # it reported no device phase (a call without the hooks, one
            # that failed or had nothing to scan): whatever it had on the
            # device is over with the call
            flush.done_t = loop.time()
        flush.prev = None  # read for the last time: the chain stays short
        if flush is self._last:
            self._last = None

    def _done(self, loop, flush: _Flush) -> None:
        self._release(loop, flush)
        # whatever queued behind the finished call has waited a device
        # round-trip already: it flushes NOW if the gate is open — re-arming
        # the window here would idle the device for window_ms per cycle
        self._kick(loop, "completion")

    def _execute(self, loop, model, group: list[_Pending], call_span,
                 handoff, flush: _Flush, believed=None) -> None:
        """Executor thread: ONE batched device call for the whole group.

        The device call is a FAN-IN: ``call_span`` (opened at dispatch on
        the loop) is parented into the first waiter's trace and *linked* to
        every waiter's queue-wait span, so each participating trace can
        find the shared call — and its batch-size/pad-waste attributes —
        that answered it.

        Its stages are its children, end to end with no hole between them:
        ``coalescer.handoff`` (``handoff``, opened on the loop with the call
        span, closed by this function's first line), ``coalescer.assemble``
        (here, up to the call of ``top_n_batch``), the model's ``topn.*``,
        and ``coalescer.wakeup`` — from the call span's close until the
        LAST waiter's future has been resolved on the loop.

        Resilience (docs/robustness.md): requests whose per-request
        Deadline expired while queued are answered 504 here WITHOUT
        spending device time on them; a failed batch reports to the
        device-call circuit breaker and each of its requests retries as an
        uncoalesced per-request scan (degraded mode) before any client
        sees an error."""
        spans.finish_span(handoff)
        span_finished = False
        try:
            with spans.activate(call_span):
                with spans.stage("coalescer.assemble"):
                    live: list[_Pending] = []
                    for p in group:
                        if p.deadline is not None and p.deadline.expired():
                            _DEADLINE_DROPS.inc()
                            loop.call_soon_threadsafe(
                                _set_exception, p.future,
                                resilience.DeadlineExceeded(
                                    "deadline expired in the coalescer queue"
                                ),
                            )
                        else:
                            live.append(p)
                    group = live
                    if not group:
                        # ``finally`` closes the call span, frees the slot
                        return
                    qs = np.stack([p.vec for p in group])
                    want = max(p.want for p in group)
                    alloweds = (
                        [p.allowed for p in group]
                        if any(p.allowed is not None for p in group)
                        else None
                    )
                    # ids, or a model's own codes for them (an array):
                    # None where a request leaves nothing out
                    excluded = (
                        [p.excluded for p in group]
                        if any(p.excluded is not None for p in group)
                        else None
                    )
                    # pad the batch to a power of two: coalesced batch sizes
                    # vary per flush, and every distinct size would otherwise
                    # be a fresh XLA trace/compile of the batched top-N
                    # program — seconds of compile on the hot path
                    n_real = len(group)
                    n_pad = _pad_pow2(n_real)
                    call_span.set_attribute("batch.padded", n_pad)
                    call_span.set_attribute("pad.waste_rows", n_pad - n_real)
                    if n_pad > n_real:
                        _PAD_WASTE.inc(n_pad - n_real)
                        qs = np.concatenate(
                            [qs, np.repeat(qs[:1], n_pad - n_real, axis=0)]
                        )
                        if alloweds is not None:
                            alloweds = alloweds + [None] * (n_pad - n_real)
                        if excluded is not None:
                            excluded = (list(excluded)
                                        + [None] * (n_pad - n_real))
                faults.maybe_fail("serving.device_call")
                with devicephase.reporting(flush):
                    results = model.top_n_batch(qs, want, alloweds, excluded)
                if believed is not None:
                    _tell_device_phase(call_span, flush, believed)
            if self.breaker is not None:
                self.breaker.record_success()
            # trace completeness: the call span must land in the ring
            # BEFORE any waiter's future resolves — a client that has its
            # response may immediately fetch GET /trace?trace_id=, and a
            # trace missing its device call there is a torn read (the
            # sanitized suite widened this executor-side race enough to
            # observe it)
            span_finished = True
            spans.finish_span(call_span)
            wakeup = spans.start_span(
                "coalescer.wakeup", parent=call_span,
                attributes={"call": call_span.span_id},
            )
            last = len(group) - 1
            for n, (p, res) in enumerate(zip(group, results)):
                out = res[p.offset:p.offset + p.how_many]
                if n == last:
                    loop.call_soon_threadsafe(_set_last_result, p.future, out,
                                              wakeup)
                else:
                    loop.call_soon_threadsafe(_set_result, p.future, out)
        except Exception as e:  # noqa: BLE001 — fail the batch, not the loop
            if self.breaker is not None:
                self.breaker.record_failure()
            call_span.record_exception(e)
            if not span_finished:
                span_finished = True
                spans.finish_span(call_span)  # same ordering on the error path
            log.exception(
                "coalesced top-N batch failed; retrying its %d request(s) "
                "individually", len(group),
            )
            self._fallback_individually(loop, model, group, e)
        finally:
            if not span_finished:
                spans.finish_span(call_span)
            loop.call_soon_threadsafe(self._done, loop, flush)

    def _fallback_individually(self, loop, model, group: list[_Pending],
                               batch_exc: BaseException) -> None:
        """Degraded completion of a failed batch: each request re-runs as an
        uncoalesced per-request scan on the same model (the path an open
        breaker routes NEW requests to), so one bad batched program — or an
        injected device fault — costs latency, not errors. A request whose
        fallback also fails gets the ORIGINAL batch exception: that is the
        failure that actually broke it."""
        direct = getattr(model, "top_n", None)
        for p in group:
            if p.deadline is not None and p.deadline.expired():
                loop.call_soon_threadsafe(
                    _set_exception, p.future,
                    resilience.DeadlineExceeded(
                        "deadline expired during degraded retry"
                    ),
                )
                continue
            if direct is None:
                loop.call_soon_threadsafe(_set_exception, p.future, batch_exc)
                continue
            try:
                res = direct(p.vec, p.how_many, p.offset, p.allowed, None,
                             excluded=p.excluded)
            except Exception:  # noqa: BLE001 — the batch exception is the story
                log.exception("degraded per-request fallback also failed")
                loop.call_soon_threadsafe(_set_exception, p.future, batch_exc)
            else:
                _DEGRADED.inc()
                loop.call_soon_threadsafe(_set_result, p.future, res)


def _ms(seconds: float) -> float:
    return round(seconds * 1000.0, 3)


def _tell_device_phase(call_span, flush: _Flush, believed: tuple) -> None:
    """On a recorded call span, once, at the call's end: where the call
    reported its device phase (offsets from the span's start; absent where
    it reported nothing) and what the gate believed at the open
    (``TopNCoalescer._believed``)."""
    opened, told = believed
    if flush.enq_t is not None:
        told["enqueued_ms"] = _ms(flush.enq_t - opened)
    if flush.done_t is not None:
        told["device_done_ms"] = _ms(flush.done_t - opened)
    for key, value in told.items():
        call_span.set_attribute(key, value)


def _set_result(future: asyncio.Future, value) -> None:
    if not future.done():
        future.set_result(value)


def _set_last_result(future: asyncio.Future, value, wakeup_span) -> None:
    """The flush's last waiter: its result, then the end of the flush's
    ``coalescer.wakeup`` — every waiter's future is resolved by now."""
    _set_result(future, value)
    spans.finish_span(wakeup_span)


def _set_exception(future: asyncio.Future, exc: BaseException) -> None:
    if not future.done():
        future.set_exception(exc)
