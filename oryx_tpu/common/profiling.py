"""Device-performance attribution: XLA cost accounting, memory telemetry,
and the shared on-demand profiler session.

The repo could already attribute a slow request to queue vs device vs
transport time (PRs 2-3), but a live process still could not answer "how
fast is the device running relative to the hardware" — the 0.15% MFU
finding and the 58 s-pack-vs-6 s-compute split each required a hand-run
bench to discover. This module makes the roofline position a scrape-time
fact on every replica and trainer, three ways:

  * **Cost accounting** (:class:`CostRegistry`): compiled executables
    register their ``cost_analysis()`` FLOPs/bytes per signature (the
    ``compilecache.aot_compile(cost_key=...)`` route, used by the serving
    warm ladder and the lazy per-signature registration in the ALS batched
    top-N; the trainer registers its half-iteration cost analytically from
    the packed layout). At execution time call sites multiply calls ×
    per-call cost into ``oryx_device_flops_total`` /
    ``oryx_device_bytes_total{program}``, and scrape-time gauges divide the
    windowed rate by the configured peaks (``oryx.profiling.peak-tflops`` /
    ``peak-hbm-gbps``) into ``oryx_device_mfu`` and
    ``oryx_device_hbm_bandwidth_fraction`` — ``GET /metrics`` on a live
    replica reports its roofline position continuously.
  * **Memory telemetry**: scrape-time gauges over ``device.memory_stats()``
    (bytes in use / peak / limit per device) plus host RSS via the existing
    ``executils`` helper — the measurement side of reference-scale memory
    parity. :func:`memory_snapshot` returns the same numbers as a stable
    dict the benches embed in ``BENCH_*.json`` payloads
    (``trace_summary --history`` reads them back).
  * **On-demand profiling** (:class:`ProfileSession`): ONE
    ``jax.profiler.start_trace``/``stop_trace`` capture may be in flight
    per process (jax raises on a second start). The session serializes
    owners behind a lock with a duration bound — a capture past its bound
    is force-stopped by the next starter instead of wedging profiling
    forever. ``POST /debug/profile`` on the serving console and the
    ``StepTracer`` step captures both go through it.

Import cost: metrics families only — jax is imported lazily so transport
and tooling processes that never touch a device pay nothing.
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time
from collections import deque

from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import spans

log = spans.get_logger(__name__)

_FLOPS = metrics_mod.default_registry().counter(
    "oryx_device_flops_total",
    "Device FLOPs attributed via per-program cost accounting "
    "(calls x compiled cost_analysis, or an analytic model where noted)",
    ("program",),
)
_BYTES = metrics_mod.default_registry().counter(
    "oryx_device_bytes_total",
    "Device bytes accessed (HBM traffic proxy) attributed per program",
    ("program",),
)
_CALLS = metrics_mod.default_registry().counter(
    "oryx_device_calls_total",
    "Device-program executions recorded by the cost-accounting layer "
    "(counted even for signatures whose cost is not registered yet)",
    ("program",),
)
_MFU = metrics_mod.default_registry().gauge(
    "oryx_device_mfu",
    "Model FLOP utilization over the sliding window: attributed FLOP/s "
    "divided by oryx.profiling.peak-tflops (0 when no peak is known)",
)
_FLOPS_RATE = metrics_mod.default_registry().gauge(
    "oryx_device_flops_per_second",
    "Attributed device FLOP/s over the sliding window",
)
_HBM_FRACTION = metrics_mod.default_registry().gauge(
    "oryx_device_hbm_bandwidth_fraction",
    "Achieved HBM bandwidth over the sliding window as a fraction of "
    "oryx.profiling.peak-hbm-gbps (0 when no peak is known)",
)
_BYTES_RATE = metrics_mod.default_registry().gauge(
    "oryx_device_bytes_per_second",
    "Attributed device bytes/s over the sliding window",
)
_HOST_RSS = metrics_mod.default_registry().gauge(
    "oryx_host_rss_bytes",
    "Current resident-set bytes of this process (can go down)",
)
_HOST_PEAK_RSS = metrics_mod.default_registry().gauge(
    "oryx_host_peak_rss_bytes",
    "Peak resident-set bytes of this process since start",
)
_DEV_IN_USE = metrics_mod.default_registry().gauge(
    "oryx_device_memory_bytes_in_use",
    "Device memory currently allocated, per local device "
    "(0 where the backend reports no memory_stats, e.g. CPU)",
    ("device",),
)
_DEV_PEAK = metrics_mod.default_registry().gauge(
    "oryx_device_memory_peak_bytes",
    "Peak device memory allocated since process start, per local device",
    ("device",),
)
_DEV_LIMIT = metrics_mod.default_registry().gauge(
    "oryx_device_memory_limit_bytes",
    "Usable device memory limit, per local device",
    ("device",),
)
_ARENA_BYTES = metrics_mod.default_registry().gauge(
    "oryx_factor_arena_bytes",
    "Host bytes allocated by factor-arena slabs across live vector stores "
    "(models/als/vectors.py: one contiguous (N, k) float32 slab per store)",
)
_ARENA_FILL = metrics_mod.default_registry().gauge(
    "oryx_factor_arena_fill_fraction",
    "Live rows / allocated rows across factor arenas (doubling growth and "
    "tombstones make this < 1; GC compaction pulls it back up)",
)
_QUANT_BYTES = metrics_mod.default_registry().gauge(
    "oryx_device_quantized_factor_bytes",
    "Device bytes held by quantized factor snapshots "
    "(oryx.serving.device-dtype = int8: int8 slab + per-row f32 scales; "
    "5.08e9 at 20M x 250f, which one v5e chip holds)",
)

#: Known per-chip peaks by device-kind prefix: (f32 matmul FLOP/s, HBM B/s).
#: Used when ``oryx.profiling.peak-tflops``/``peak-hbm-gbps`` are 0 — the
#: same v5e figures the batch bench's MFU model uses.
_KNOWN_PEAKS = {
    "TPU v5 lite": (4.925e13, 8.19e11),
    "TPU v5e": (4.925e13, 8.19e11),
}


class CostRegistry:
    """Per-program device cost table + windowed FLOP/byte rate tracker.

    ``register``/``register_compiled`` store (flops, bytes) per program
    signature; ``record`` multiplies calls × cost into the process counters
    and a bounded sample window the scrape-time rate gauges read. One lock,
    critical sections of a few arithmetic ops — safe from coalescer
    executor threads and the trainer loop concurrently."""

    def __init__(self, window_sec: float = 60.0):
        self._lock = threading.Lock()
        self._costs: dict[str, tuple[float, float]] = {}
        self._flops_total = 0.0
        self._bytes_total = 0.0
        # (monotonic t, flops delta, bytes delta) per record; pruned past
        # the window on every append and every rate read
        self._events: deque = deque()
        self._window = max(1.0, float(window_sec))
        self._created = time.monotonic()
        # one-scrape memo: four gauges read rates() back to back per scrape;
        # summing the window once per scrape instead of once per gauge
        self._rates_at = float("-inf")
        self._rates_val = (0.0, 0.0)

    def set_window(self, window_sec: float) -> None:
        with self._lock:
            self._window = max(1.0, float(window_sec))

    def register(self, key: str, flops: float, bytes_accessed: float) -> None:
        """Store per-call cost for ``key`` (overwrites: a new model
        generation's re-registration supersedes the old shapes)."""
        with self._lock:
            self._costs[str(key)] = (max(0.0, float(flops)),
                                     max(0.0, float(bytes_accessed)))

    def register_compiled(self, key: str, compiled) -> bool:
        """Pull ``cost_analysis()`` FLOPs / bytes-accessed off a compiled
        executable. False when the executable exposes no usable cost
        analysis — never raises."""
        try:
            ca = compiled.cost_analysis()
            flops = float(ca.get("flops", 0.0))
            bytes_ = float(ca.get("bytes accessed", 0.0))
        except Exception:  # noqa: BLE001 — accounting must never break a compile
            log.debug("cost_analysis unavailable for %s", key, exc_info=True)
            return False
        if flops <= 0.0 and bytes_ <= 0.0:
            return False
        self.register(key, flops, bytes_)
        return True

    def known(self, key: str) -> bool:
        with self._lock:
            return key in self._costs

    def cost(self, key: str) -> "tuple[float, float] | None":
        with self._lock:
            return self._costs.get(key)

    def record(self, key: str, calls: int = 1) -> None:
        """Attribute ``calls`` executions of ``key``: counters += calls ×
        per-call cost. Signatures with no registered cost still count calls
        (the gap is visible as calls-without-flops, not silently zero)."""
        if calls <= 0 or not metrics_mod.default_registry().enabled:
            return
        _maybe_wire_jax()
        _CALLS.labels(key).inc(calls)
        with self._lock:
            cost = self._costs.get(key)
            if cost is None:
                return
            df, db = cost[0] * calls, cost[1] * calls
            self._flops_total += df
            self._bytes_total += db
            now = time.monotonic()
            self._events.append((now, df, db))
            self._prune(now)
        _FLOPS.labels(key).inc(df)
        _BYTES.labels(key).inc(db)

    def _prune(self, now: float) -> None:
        horizon = now - self._window  # analyze: ignore[lock-discipline] -- _prune runs only under self._lock, taken by its callers
        ev = self._events
        while ev and ev[0][0] < horizon:
            ev.popleft()

    def rates(self) -> tuple[float, float]:
        """(FLOP/s, bytes/s) over the sliding window. The denominator is
        the full window (clamped to process-registry age), so an idle
        process decays to 0 instead of freezing at its last busy rate.
        Results are memoized for 50 ms: the four scrape-time gauges (MFU,
        FLOP/s, bandwidth fraction, bytes/s) each call this back to back
        within one scrape, and only the first should pay the window sum."""
        now = time.monotonic()
        with self._lock:
            if now - self._rates_at < 0.05:
                return self._rates_val
            self._prune(now)
            span = max(1.0, min(self._window, now - self._created))
            df = sum(e[1] for e in self._events)
            db = sum(e[2] for e in self._events)
            self._rates_val = (df / span, db / span)
            self._rates_at = now
            return self._rates_val

    def totals(self) -> tuple[float, float]:
        with self._lock:
            return self._flops_total, self._bytes_total

    def reset(self) -> None:
        with self._lock:
            self._costs.clear()
            self._events.clear()
            self._flops_total = 0.0
            self._bytes_total = 0.0
            self._created = time.monotonic()
            self._rates_at = float("-inf")
            self._rates_val = (0.0, 0.0)


_COSTS = CostRegistry()

# configured peaks (FLOP/s, bytes/s); plain float writes/reads are atomic
# under the GIL — written by configure(), read by the gauge callbacks
_peak_flops_per_s = 0.0
_peak_bytes_per_s = 0.0


def costs() -> CostRegistry:
    """The process-wide cost registry every call site records into."""
    return _COSTS


def peak_flops_per_s() -> float:
    return _peak_flops_per_s


def peak_bytes_per_s() -> float:
    return _peak_bytes_per_s


def _auto_peaks() -> tuple[float, float]:
    """Per-chip peaks from the local device kind, for the known table.
    Only consulted when jax is ALREADY imported — profiling.configure must
    never be the thing that initializes a backend (a JAX-free parent that
    only supervises children would take the chip from them)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0.0, 0.0
    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — no usable backend: peaks stay unknown
        return 0.0, 0.0
    for prefix, peaks in _KNOWN_PEAKS.items():
        if kind.startswith(prefix):
            return peaks
    return 0.0, 0.0


_MFU.set_function(
    lambda: _COSTS.rates()[0] / _peak_flops_per_s if _peak_flops_per_s else 0.0
)
_FLOPS_RATE.set_function(lambda: _COSTS.rates()[0])
_HBM_FRACTION.set_function(
    lambda: _COSTS.rates()[1] / _peak_bytes_per_s if _peak_bytes_per_s else 0.0
)
_BYTES_RATE.set_function(lambda: _COSTS.rates()[1])


def _host_rss() -> float:
    from oryx_tpu.common import executils

    return float(executils.get_used_memory())


def host_peak_rss_bytes() -> int:
    """Peak RSS of this process (ru_maxrss is KiB on Linux, bytes on mac)."""
    import resource

    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(peak if sys.platform == "darwin" else peak * 1024)


_HOST_RSS.set_function(_host_rss)
_HOST_PEAK_RSS.set_function(lambda: float(host_peak_rss_bytes()))


# -- factor-arena / quantized-snapshot telemetry ----------------------------
# WEAK sets: a retired store or snapshot must never be pinned by its gauge
# (the exact hazard the load-fraction gauge's weakref solves). Providers
# expose arena_nbytes()/arena_fill() and quantized_nbytes() respectively.
import weakref as _weakref  # noqa: E402 — stdlib, kept near its single use

_ARENAS: "_weakref.WeakSet" = _weakref.WeakSet()
_QUANT_PROVIDERS: "_weakref.WeakSet" = _weakref.WeakSet()


def register_arena(store) -> None:
    """Track a live factor arena for the scrape-time byte/fill gauges."""
    _ARENAS.add(store)


def register_quantized(provider) -> None:
    """Track a live quantized device snapshot (``quantized_nbytes()``): the
    int8 rows and their scales, 5.08 GB of one chip at 20M x 250f."""
    _QUANT_PROVIDERS.add(provider)


def _arena_bytes() -> float:
    return float(sum(s.arena_nbytes() for s in list(_ARENAS)))


def _arena_fill() -> float:
    sized = [(s.arena_nbytes(), s.arena_fill()) for s in list(_ARENAS)]
    sized = [(b, f) for b, f in sized if b > 0]
    if not sized:
        return 0.0
    total = sum(b for b, _ in sized)
    return sum(b * f for b, f in sized) / total  # byte-weighted fill


def _quantized_bytes() -> float:
    return float(sum(p.quantized_nbytes() for p in list(_QUANT_PROVIDERS)))


_ARENA_BYTES.set_function(_arena_bytes)
_ARENA_FILL.set_function(_arena_fill)
_QUANT_BYTES.set_function(_quantized_bytes)


def _device_stat_fn(device, stat: str):
    def fn() -> float:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — a scrape must never 500
            return 0.0
        if not stats:
            return 0.0  # backends without memory stats (CPU) read 0
        return float(stats.get(stat, 0.0))

    return fn


_devices_wired = False
_wire_lock = threading.Lock()
# whether each peak still wants auto-detection (no explicit config value);
# True until configure() says otherwise so un-configured processes
# (direct als_train callers) still auto-detect on their first record()
_want_auto_flops = True
_want_auto_bytes = True
# jax-dependent wiring done (or attempted once with jax importable) —
# the fast-path flag _maybe_wire_jax checks per record()
_jax_wired = False


def _wire_jax_locked() -> None:
    """The jax-dependent half of :func:`configure`: resolve wanted auto
    peaks from the device kind and mint one memory-gauge child per local
    device. Caller holds ``_wire_lock`` and has checked jax is imported."""
    global _devices_wired, _peak_flops_per_s, _peak_bytes_per_s
    if _want_auto_flops or _want_auto_bytes:
        auto_f, auto_b = _auto_peaks()
        if _want_auto_flops:
            _peak_flops_per_s = auto_f
        if _want_auto_bytes:
            _peak_bytes_per_s = auto_b
    if _devices_wired:
        return
    jax = sys.modules.get("jax")
    try:
        devices = jax.devices()
    except Exception:  # noqa: BLE001 — no usable backend
        return
    if devices:
        # the oryx_build_info satellite (common/metrics.py): backend and
        # device kind become known exactly here, the first moment a live
        # backend exists in this process
        metrics_mod.set_build_info(devices[0].platform,
                                   devices[0].device_kind)
    for d in devices:
        label = f"{d.platform}:{d.id}"
        _DEV_IN_USE.labels(label).set_function(
            _device_stat_fn(d, "bytes_in_use"))
        _DEV_PEAK.labels(label).set_function(
            _device_stat_fn(d, "peak_bytes_in_use"))
        _DEV_LIMIT.labels(label).set_function(
            _device_stat_fn(d, "bytes_limit"))
    _devices_wired = True


def _maybe_wire_jax() -> None:
    """Late completion of configure()'s jax-dependent wiring. Layers
    construct (and call configure) before their model class ever imports
    jax — lambda_rt loads the model via classutils AFTER layer init — so
    peak auto-detection and the device-memory gauges arm on the first
    execution-site record() once jax has appeared. One attempt per process
    with jax importable: by the time anything records device work, the
    backend either initializes or never will."""
    global _jax_wired
    if _jax_wired or sys.modules.get("jax") is None:
        return
    with _wire_lock:
        if _jax_wired:
            return
        _jax_wired = True
        _wire_jax_locked()


def configure(config) -> None:
    """Apply ``oryx.profiling.*``: roofline peaks for the MFU/bandwidth
    gauges (0 = auto-detect from the device kind where known), the rate
    window, and the per-device memory gauges. Safe to call repeatedly —
    every layer entry point calls it like ``metrics.configure``. When jax
    is not imported yet the jax-dependent wiring completes lazily on the
    first :meth:`CostRegistry.record` (see :func:`_maybe_wire_jax`)."""
    global _peak_flops_per_s, _peak_bytes_per_s
    global _want_auto_flops, _want_auto_bytes, _jax_wired
    tflops = config.get_float("oryx.profiling.peak-tflops", 0.0)
    gbps = config.get_float("oryx.profiling.peak-hbm-gbps", 0.0)
    _COSTS.set_window(config.get_float("oryx.profiling.window-sec", 60.0))
    with _wire_lock:
        _want_auto_flops = tflops <= 0
        _want_auto_bytes = gbps <= 0
        _peak_flops_per_s = tflops * 1e12 if tflops > 0 else 0.0
        _peak_bytes_per_s = gbps * 1e9 if gbps > 0 else 0.0
        _jax_wired = sys.modules.get("jax") is not None
        if _jax_wired:
            _wire_jax_locked()


def memory_snapshot() -> dict:
    """Host RSS + per-device memory as a JSON-able dict with STABLE keys —
    what ``bench.py``/``bench_batch.py`` embed in BENCH payloads and
    ``trace_summary --history`` renders round over round."""
    from oryx_tpu.common import executils

    out: dict = {
        "host_rss_bytes": int(executils.get_used_memory()),
        "host_peak_rss_bytes": host_peak_rss_bytes(),
        "host_peak_rss_mb": host_peak_rss_bytes() // (1024 * 1024),
        "devices": {},
    }
    jax = sys.modules.get("jax")
    if jax is None:
        return out
    try:
        devices = jax.devices()
    except Exception:  # noqa: BLE001 — snapshot works without a backend
        return out
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001
            stats = {}
        out["devices"][f"{d.platform}:{d.id}"] = {
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
            "limit_bytes": int(stats.get("bytes_limit", 0)),
        }
    return out


# ---------------------------------------------------------------------------
# On-demand profiler session
# ---------------------------------------------------------------------------


class ProfileBusyError(RuntimeError):
    """A capture is already in flight (jax allows exactly one per process)."""


class ProfileSession:
    """One-at-a-time ``jax.profiler`` capture with ownership + a duration
    bound. ``start`` raises :class:`ProfileBusyError` while another owner's
    capture is within its bound; a capture PAST its bound is force-stopped
    by the next starter (a crashed owner must not wedge profiling for the
    process lifetime). ``stop(owner=...)`` only stops the matching owner's
    capture, so a late or duplicate stop can never cut someone else's
    capture short."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dir: "str | None" = None
        self._owner: "str | None" = None
        self._deadline = 0.0

    def busy(self) -> bool:
        with self._lock:
            return self._dir is not None

    def owner(self) -> "str | None":
        with self._lock:
            return self._owner

    def start(self, log_dir: str, owner: str = "",
              max_seconds: "float | None" = None) -> str:
        """Begin a capture into ``log_dir``; returns the directory. Raises
        :class:`ProfileBusyError` when an in-bound capture is running."""
        import jax

        with self._lock:
            if self._dir is not None:
                if max_seconds is None or time.monotonic() < self._deadline:
                    raise ProfileBusyError(
                        f"profiler capture already in flight "
                        f"(owner={self._owner!r}, dir={self._dir})"
                    )
                # previous capture outlived its bound: reclaim the profiler
                log.warning(
                    "force-stopping overdue profiler capture "
                    "(owner=%r, dir=%s)", self._owner, self._dir,
                )
                self._stop_locked()
            jax.profiler.start_trace(log_dir)
            self._dir = log_dir
            self._owner = owner
            self._deadline = (
                time.monotonic() + max_seconds
                if max_seconds is not None else float("inf")
            )
            return log_dir

    def stop(self, owner: "str | None" = None) -> "str | None":
        """Stop the active capture (any owner when ``owner`` is None) and
        return its directory; None when there is nothing of ours to stop."""
        with self._lock:
            if self._dir is None:
                return None
            if owner is not None and owner != self._owner:
                return None
            return self._stop_locked()

    def _stop_locked(self) -> "str | None":
        d = self._dir  # analyze: ignore[lock-discipline] -- _stop_locked runs only under self._lock, taken by its callers
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — never leave the session wedged
            log.exception("failed to stop profiler trace (dir=%s)", d)
        finally:
            self._dir = None
            self._owner = None  # analyze: ignore[lock-discipline] -- under self._lock (see above)
            self._deadline = 0.0  # analyze: ignore[lock-discipline] -- under self._lock (see above)
        return d

    def capture(self, log_dir: str, seconds: float,
                owner: str = "capture") -> str:
        """Blocking timed capture (run via ``asyncio.to_thread`` from async
        handlers): start, sleep ``seconds``, stop. Returns the trace dir."""
        d = self.start(log_dir, owner=owner, max_seconds=seconds + 30.0)
        try:
            time.sleep(max(0.0, seconds))
        finally:
            self.stop(owner=owner)
        return d


_SESSION = ProfileSession()


def profile_session() -> ProfileSession:
    """The process-wide session /debug/profile and StepTracer share."""
    return _SESSION


def capture_dir(base: "str | None" = None) -> str:
    """A fresh UNIQUE directory for one capture: a timestamped mkdtemp
    subdir under ``base`` (``oryx.profiling.profile-dir``) or a temp dir
    when unset. mkdtemp's suffix keeps two captures starting within the
    same wall-clock second from sharing (and mixing traces in) one dir."""
    if base:
        os.makedirs(base, exist_ok=True)
        return tempfile.mkdtemp(
            prefix=time.strftime("profile-%Y%m%d-%H%M%S-"), dir=base)
    return tempfile.mkdtemp(prefix="oryx-profile-")


def timed_capture(base: "str | None", seconds: float,
                  owner: str = "capture") -> str:
    """Blocking one-shot: mint a fresh capture dir and run a timed capture
    through the shared session. This is the complete worker-thread body
    behind ``POST /debug/profile`` — directory creation AND the capture both
    block, so the whole thing must run off the event loop in one hop."""
    d = capture_dir(base)
    try:
        return _SESSION.capture(d, seconds, owner=owner)
    except ProfileBusyError:
        # we minted the dir before losing the session race; don't leave an
        # empty orphan behind every raced 409
        try:
            os.rmdir(d)
        except OSError:
            pass
        raise
