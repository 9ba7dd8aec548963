"""Load generator: one child process of the benchmark. Never imports jax.

Run as ``python3 loadgen.py <spec.json> <out.json>``. The spec gives the
port, the loop (``open`` or ``closed``), the moment the window starts on
the system-wide monotonic clock, and this child's share of the traffic.

Open loop: requests are sent when they are DUE whether or not earlier ones
have been answered, each is timed from its due time, and how late the child
sent it is recorded, so a starved generator is not read as a fast server.
Closed loop: each of the child's clients sends its next request when the
last answer has arrived, until the window closes.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


def traceparent(index: int) -> str:
    """A W3C traceparent that carries the request's index as its trace id,
    so the program's spans can be matched to the client's record."""
    return f"00-{index + 1:032x}-{index + 1:016x}-01"


def index_of_trace(trace_id: str) -> int:
    return int(trace_id, 16) - 1


async def _get(sess, base: str, path: str, index: int, timeout_s: float):
    """(status, body, done) of one GET; status 0 = no answer."""
    import aiohttp

    try:
        async with sess.get(
            base + path, headers={"traceparent": traceparent(index)},
            timeout=aiohttp.ClientTimeout(total=timeout_s),
        ) as resp:
            body = await resp.read()
            return resp.status, body, time.monotonic()
    except Exception:  # noqa: BLE001 — refused, reset, timed out: no answer
        return 0, b"", None


async def _sleep_until(due: float) -> None:
    """Sleep, never spin: a generator that burns a core while it waits takes
    that core from the server it measures. The loop's timer wakes some
    tenths of a millisecond late; the record says how late."""
    delay = due - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


def _keeps(spec: dict, index: int) -> bool:
    every = spec.get("keep_every", 0)
    return bool(every) and index % every == spec.get("keep_phase", 0) % every


class GcWatch:
    """The longest garbage collection of this process, with the moment it
    ended and its generation."""

    def __init__(self):
        import gc

        self.max, self.at, self.gen, self._t = 0.0, None, None, 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.monotonic()
        if phase == "start":
            self._t = now
        elif now - self._t > self.max:
            self.max, self.at = now - self._t, now
            self.gen = info.get("generation")

    def stop(self) -> None:
        import gc

        gc.callbacks.remove(self._on_gc)

    def as_dict(self, t_start: float) -> dict:
        return {"gc_max_ms": self.max * 1e3, "gc_generation": self.gen,
                "gc_at_s": None if self.at is None else self.at - t_start}


class Stalls(GcWatch):
    """What held this process up: the longest garbage collection and the
    longest overshoot of a 5 ms ticker, each with the moment it ended."""

    def __init__(self):
        super().__init__()
        self.tick_max, self.tick_at = 0.0, None

    async def tick(self):
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(0.005)
            over = time.monotonic() - t0 - 0.005
            if over > self.tick_max:
                self.tick_max, self.tick_at = over, time.monotonic()

    def as_dict(self, t_start):
        return dict(super().as_dict(t_start),
                    tick_max_ms=self.tick_max * 1e3,
                    tick_at_s=(None if self.tick_at is None
                               else self.tick_at - t_start))


async def drive(spec: dict) -> dict:
    import aiohttp

    base = f"http://127.0.0.1:{spec['port']}"
    timeout_s = spec["timeout_s"]
    rec = {"index": [], "due": [], "sent": [], "done": [], "status": [],
           "bodies": {}}

    def record(index, due, sent, status, body, done):
        rec["index"].append(index)
        rec["due"].append(due)
        rec["sent"].append(sent)
        rec["done"].append(done)
        rec["status"].append(status)
        if status == 200 and _keeps(spec, index):
            rec["bodies"][str(index)] = body.decode("utf-8", "replace")

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as sess:
        # open the connections the window will use before it starts
        warm = [_get(sess, base, p, -1, timeout_s) for p in spec["warm_paths"]]
        rec["warm_status"] = [s for s, _, _ in await asyncio.gather(*warm)]
        # the parent answers "ready" with the window's start, one clock for
        # all children (the monotonic clock is the machine's, not a process's)
        print("ready", flush=True)
        t_start = float(await asyncio.get_running_loop().run_in_executor(
            None, sys.stdin.readline))
        stalls = Stalls()
        ticker = asyncio.create_task(stalls.tick())

        async def one(index, path, due):
            sent = time.monotonic()
            status, body, done = await _get(sess, base, path, index, timeout_s)
            record(index, due, sent, status, body, done)

        if spec["loop"] == "open":
            tasks = []
            for index, rel, path in spec["schedule"]:
                await _sleep_until(t_start + rel)
                tasks.append(asyncio.create_task(
                    one(index, path, t_start + rel)))
            if tasks:
                await asyncio.gather(*tasks)
        else:
            t_end = t_start + spec["seconds"]
            paths, stride = spec["paths"], spec["stride"]
            counter = {"k": 0}

            async def client():
                while time.monotonic() < t_end:
                    k = counter["k"]
                    counter["k"] += 1
                    # a closed client's request is due when it is sent
                    await one(spec["child"] + stride * k,
                              paths[k % len(paths)], time.monotonic())

            await _sleep_until(t_start)
            await asyncio.gather(*[client() for _ in range(spec["clients"])])
        ticker.cancel()
        rec["stalls"] = stalls.as_dict(t_start)
    return rec


def main(argv) -> int:
    with open(argv[1], encoding="utf-8") as f:
        spec = json.load(f)
    rec = asyncio.run(drive(spec))
    with open(argv[2] + ".tmp", "w", encoding="utf-8") as f:
        json.dump(rec, f)
    import os

    os.replace(argv[2] + ".tmp", argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
