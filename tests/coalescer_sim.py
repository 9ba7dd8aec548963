"""The REAL ``TopNCoalescer`` under a virtual clock, against a fake device.

The coalescer's schedule is a matter of milliseconds between an event loop
and executor threads, which no wall-clock test on a shared CPU can assert.
Here the loop's clock is virtual: it moves only when the loop has nothing
ready AND every executor job is asleep on that same clock
(:meth:`VirtualLoop.sleep_until`), and then jumps to whichever is due first,
a timer of the loop or a sleeper. The coalescer, its timers, its
``run_in_executor`` and its ``call_soon_threadsafe`` are the real ones.

:class:`FifoDevice` is the chip: programs run one at a time in the order
they were enqueued. :class:`SimModel` is a serving model whose batched call
takes a host stage, a scan on that device and post-scan host work, and
reports its device phase (``common/devicephase.py``) as the real model does
— or does not, which is how the parent's schedule is reproduced.
"""

import asyncio
import heapq
import itertools
import math
import random
import selectors
import statistics
import threading

import numpy as np

from oryx_tpu.common import devicephase

_REAL_WAIT_S = 30.0  # a thread that sleeps past this in real time is a bug


class _VirtualSelector(selectors.DefaultSelector):
    """The loop's idle wait: instead of sleeping, let the clock jump."""

    loop: "VirtualLoop"

    def select(self, timeout=None):
        loop = self.loop
        if timeout and loop.tick_s:
            # epoll sleeps in whole milliseconds, rounded up: a timer fires
            # up to one late unless something else wakes the loop first
            timeout = math.ceil(timeout / loop.tick_s - 1e-9) * loop.tick_s
        target = None if timeout is None else loop.now + timeout
        while True:
            with loop.cv:
                # everything the executor threads will post at this instant
                # has been posted once all of them are asleep or finished
                assert loop.cv.wait_for(lambda: loop.active == 0,
                                        timeout=_REAL_WAIT_S), "a job hangs"
            events = super().select(0)
            if events or (timeout is not None and timeout <= 0):
                return events
            with loop.cv:
                wake = loop.sleepers[0][0] if loop.sleepers else None
                if wake is not None and (target is None or wake <= target):
                    _, _, event = heapq.heappop(loop.sleepers)
                    loop.now = max(loop.now, wake)
                    loop.active += 1
                    event.set()
                    continue
            if target is None:
                # nothing is due on the virtual clock: whatever comes next
                # is real (a socket, a thread the test itself started)
                return super().select(0.05)
            loop.now = target
            return events


class VirtualLoop(asyncio.SelectorEventLoop):
    """``tick_s``: the selector's sleep granularity (0: timers fire on
    time; 0.001 is Linux's epoll, as asyncio uses it)."""

    def __init__(self, tick_s: float = 0.0):
        selector = _VirtualSelector()
        selector.loop = self
        self.tick_s = tick_s
        self.now = 0.0
        self.cv = threading.Condition()
        self.active = 0  # executor jobs that are running, not asleep
        self.sleepers: list = []  # heap of (due, n, threading.Event)
        self._n = itertools.count()
        super().__init__(selector)

    def time(self) -> float:
        return self.now

    def run_in_executor(self, executor, func, *args):
        with self.cv:
            self.active += 1

        def job():
            try:
                return func(*args)
            finally:
                with self.cv:
                    self.active -= 1
                    self.cv.notify_all()

        return super().run_in_executor(executor, job)

    def sleep_until(self, due: float) -> None:
        """An executor job's ``time.sleep`` on the loop's clock."""
        event = threading.Event()
        with self.cv:
            heapq.heappush(self.sleepers, (due, next(self._n), event))
            self.active -= 1
            self.cv.notify_all()
        assert event.wait(_REAL_WAIT_S), "the virtual clock stopped"

    def sleep(self, seconds: float) -> None:
        self.sleep_until(self.now + seconds)

    def run(self, coro):
        """``asyncio.run`` for this loop."""
        try:
            return self.run_until_complete(coro)
        finally:
            self.run_until_complete(self.shutdown_default_executor())
            self.close()


class FifoDevice:
    """One chip: programs run one at a time, in the order enqueued.
    ``scan_s(width)`` is a program's time by its padded batch. ``launch_s``
    is what a program takes to reach a device that is idle (hidden behind
    the program before it where one is running)."""

    def __init__(self, loop: VirtualLoop, scan_s, launch_s: float = 0.0):
        self.loop = loop
        self.scan_s = scan_s
        self.launch_s = launch_s
        self.free_t = 0.0
        self.waits: list[float] = []  # each program: enqueued → started
        self.gaps: list[float] = []  # the device idle before each program
        self.runs: list[tuple[float, float]] = []  # each program: start, end
        self._lock = threading.Lock()

    def enqueue(self, width: int) -> float:
        """Launch a program now; returns when it will be done."""
        with self._lock:
            now = self.loop.time()
            start = max(now + self.launch_s, self.free_t)
            self.waits.append(start - now)
            self.gaps.append(start - self.free_t)
            self.free_t = start + self.scan_s(width)
            self.runs.append((start, self.free_t))
            return self.free_t


class SimModel:
    """A serving model on a :class:`FifoDevice`: ``host_s`` from the call to
    the launch (``host_s()`` where it varies), the scan, ``lag_s`` from the
    device being done to the host knowing it, ``post_s`` from there to the
    return. The answer to query ``q`` is ``[("i<q[0]>", 0.0)]``: each
    request can check it got its own. ``calls`` keeps each batched call's
    ``(instant it was made, padded batch)``."""

    def __init__(self, loop: VirtualLoop, device: FifoDevice, host_s,
                 post_s: float, reports: bool = True, lag_s: float = 0.0):
        self.loop = loop
        self.device = device
        self.host_s = host_s if callable(host_s) else (lambda: host_s)
        self.post_s = post_s
        self.lag_s = lag_s if callable(lag_s) else (lambda: lag_s)
        self.reports = reports
        self.calls: list[tuple[float, int]] = []
        self.in_flight = self.most_in_flight = 0
        self._lock = threading.Lock()

    def top_n_batch(self, qs, how_many, alloweds=None, excluded=None):
        with self._lock:
            self.calls.append((self.loop.time(), len(qs)))
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
        try:
            self.loop.sleep(self.host_s())
            done = self.device.enqueue(len(qs))
            if self.reports:
                devicephase.enqueued()
            self.loop.sleep_until(done + self.lag_s())
            if self.reports:
                devicephase.device_done()
            self.loop.sleep(self.post_s)
            return [[(f"i{int(q[0])}", 0.0)] * how_many for q in qs]
        finally:
            with self._lock:
                self.in_flight -= 1


async def poisson(coal, model, rate: float, seconds: float, seed: int):
    """Open loop: independent requests at ``rate`` a second for ``seconds``
    of the virtual clock. Returns each request's latency in seconds.
    Arrivals come from a thread, as requests come from a socket: they wake
    the loop when they are due, whatever its timers' granularity."""
    loop = asyncio.get_running_loop()
    rng = random.Random(seed)
    latencies: list[float] = []
    tasks: list[asyncio.Task] = []

    async def one(n: int):
        t0 = loop.time()
        res = await coal.top_n(model, np.array([float(n), 0.0]), 1)
        assert res == [(f"i{n}", 0.0)]
        latencies.append(loop.time() - t0)

    def arrive():
        tasks.append(asyncio.create_task(one(len(tasks))))

    def feed():
        due = 0.0
        while True:
            due += rng.expovariate(rate)
            if due >= seconds:
                return
            loop.sleep_until(due)
            loop.call_soon_threadsafe(arrive)

    await loop.run_in_executor(None, feed)
    await asyncio.sleep(0)  # the last arrival's task exists now
    await asyncio.gather(*tasks)
    return latencies


def p50_p95_ms(latencies) -> tuple[float, float]:
    cuts = statistics.quantiles(latencies, n=20)
    return statistics.median(latencies) * 1e3, cuts[18] * 1e3
