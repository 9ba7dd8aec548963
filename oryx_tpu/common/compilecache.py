"""Compile-lifecycle subsystem: persistent XLA cache, compile accounting,
and bucket-warmup state.

The serving tier's worst tail-latency source is the XLA compile: every new
pow2 batch bucket and every model-generation swap (new array shapes) used to
pay a synchronous multi-second compile inside a request (BENCH_r05's HTTP
p99 of 2259 ms vs p50 269 ms "still includes first-compiles of new batch
sizes inside the timed window"). This module converts those request-path
compiles into startup/background cost, three ways:

  * **Persistent compilation cache**: :func:`configure` points jax's disk
    cache at a directory so process restarts and horizontal serving
    replicas deserialize XLA binaries instead of recompiling them. The
    directory is placed from outside first: ``JAX_COMPILATION_CACHE_DIR``
    if set (jax's own reading of it stands and this module sets none),
    else ``oryx.compile.cache-dir``, else a fixed ``<checkout>/.jax_cache``
    — fixed because the path is part of the cache key, so a directory
    that moves never hits. ``min-entry-size-bytes`` /
    ``min-compile-time-sec`` bound what gets written (jax's own defaults
    skip sub-second compiles, which is exactly the wrong default for a
    serving tier that wants EVERY bucket binary on disk).
  * **Compile accounting**: a ``jax.monitoring`` listener counts every XLA
    backend compile into ``oryx_jit_compiles_total`` (and persistent-cache
    hits into ``oryx_compile_cache_hits_total`` with the saved seconds in
    ``oryx_compile_cache_saved_seconds_total``), so "zero compiles in the
    warm window" is an asserted number in bench/tests, not a hope. A
    process-local monotonic count (:func:`compiles_total`) backs the same
    assertion even when the metrics registry is disabled or reset.
  * **Warmup state**: the serving batch warmer reports its bucket ladder
    progress here; ``GET /readyz`` gates readiness on
    ``oryx.compile.ready-warm-fraction`` of buckets being compiled so a
    load balancer never routes into a cold replica. Progress is exported as
    ``oryx_warmup_buckets_{done,total}`` gauges and per-bucket
    ``oryx_warmup_seconds`` observations.

:func:`aot_compile` is the sanctioned route for ahead-of-time compiles
(``jitted.lower(shapes).compile()``): it seeds both the in-process lowering
cache and the persistent cache without occupying the request path. The
``compile-on-hot-path`` analyze checker flags ``jax.jit``/``.lower(``
reachable from request handlers that does NOT go through this module.
"""

from __future__ import annotations

import os
import threading

from oryx_tpu.common import metrics as metrics_mod
from oryx_tpu.common import spans

log = spans.get_logger(__name__)

_JIT_COMPILES = metrics_mod.default_registry().counter(
    "oryx_jit_compiles_total",
    "XLA backend compiles (persistent-cache hits included; in-memory jit "
    "dispatch cache hits fire nothing)",
)
_CACHE_HITS = metrics_mod.default_registry().counter(
    "oryx_compile_cache_hits_total",
    "XLA compiles served from the persistent compilation cache",
)
_CACHE_SAVED = metrics_mod.default_registry().counter(
    "oryx_compile_cache_saved_seconds_total",
    "Compile seconds avoided via persistent compilation-cache hits",
)
_WARMUP_SECONDS = metrics_mod.default_registry().histogram(
    "oryx_warmup_seconds",
    "Warmup durations: one observation per bucket and one per model ladder",
    ("scope",),
    buckets=metrics_mod.STEP_BUCKETS,
)

# jax.monitoring event names. backend_compile fires for every
# compile_or_get_cached call that missed the in-memory dispatch cache; the
# cache_* pair fires only on persistent-cache hits.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

_install_lock = threading.Lock()
_installed = False
# monotonic for the life of the process, immune to registry reset/disable —
# bench and tests diff these around a timed window. Incremented under a
# lock: `n += 1` is load/add/store and concurrent compiles (warmer thread +
# coalescer executor threads) could drop a count, letting a window with one
# real compile read as a zero delta
_count_lock = threading.Lock()
_compile_events = 0
_cache_hit_events = 0


def _on_event(event: str, duration: float, **_kw) -> None:
    global _compile_events, _cache_hit_events
    if event == _COMPILE_EVENT:
        with _count_lock:
            _compile_events += 1
        _JIT_COMPILES.inc()
    elif event == _CACHE_HIT_EVENT:
        with _count_lock:
            _cache_hit_events += 1
        _CACHE_HITS.inc()
    elif event == _CACHE_SAVED_EVENT:
        _CACHE_SAVED.inc(max(0.0, duration))


def install_compile_listener() -> None:
    """Register the jax.monitoring duration listener once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_event)
        _installed = True


def compiles_total() -> int:
    """XLA backend compiles observed so far in THIS process (monotonic)."""
    return _compile_events


def cache_hits_total() -> int:
    """Persistent-cache hits observed so far in this process (monotonic)."""
    return _cache_hit_events


#: Where the cache lives when nothing outside the program places it: one
#: fixed directory beside the package, shared by every entry point run from
#: this checkout (listed in .gitignore).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def cache_dir() -> "str | None":
    """The persistent cache directory jax is using in this process."""
    import jax

    return jax.config.jax_compilation_cache_dir


def configure(config) -> None:
    """Install the compile listener and enable jax's persistent compilation
    cache, with the ``oryx.compile.*`` thresholds.

    THE one place a cache directory is chosen — ``chip_smoke.py``, the
    benches and every layer come through here, and none builds a path of
    its own. ``JAX_COMPILATION_CACHE_DIR``, when set, wins and this code
    sets no directory (jax has already read it); otherwise
    ``oryx.compile.cache-dir``; otherwise :data:`DEFAULT_CACHE_DIR`. Call
    before the process's first compile: jax initializes its cache once.

    Safe to call repeatedly (every layer entry point calls it, like
    ``metrics.configure``); config errors degrade to a warning — a broken
    cache dir must never stop a layer from serving."""
    install_compile_listener()
    import jax

    cdir = None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cdir = (config.get_string("oryx.compile.cache-dir", None)
                or DEFAULT_CACHE_DIR)
    try:
        if cdir:
            os.makedirs(cdir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cdir)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes",
            config.get_int("oryx.compile.min-entry-size-bytes", 0),
        )
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            config.get_float("oryx.compile.min-compile-time-sec", 0.0),
        )
        log.info("persistent compilation cache at %s", cache_dir())
    except Exception:  # noqa: BLE001 — cache is an optimization, not a dep
        log.warning("could not enable persistent compilation cache at %s",
                    cdir, exc_info=True)


def aot_compile(jitted, *args, cost_key: "str | None" = None, **kwargs):
    """Ahead-of-time ``jitted.lower(*args).compile()`` — THE sanctioned way
    to compile off the request path (analyze: compile-on-hot-path).

    Array arguments may be real arrays or ``jax.ShapeDtypeStruct``s; only
    shapes/dtypes matter. Seeds the in-process lowering cache and, when
    enabled, the persistent compilation cache, so the first on-path dispatch
    of the same signature pays a cache read instead of an XLA compile.
    Returns the compiled executable, or None when lowering/compiling fails
    (the caller's execution-warm fallback still covers the signature).

    ``cost_key`` additionally registers the executable's ``cost_analysis()``
    FLOPs/bytes under that program signature in the process cost registry
    (common/profiling.py) — execution sites then attribute device work by
    recording calls against the same key."""
    lower = getattr(jitted, "lower", None)
    if lower is None:
        return None
    try:
        compiled = lower(*args, **kwargs).compile()
    except Exception:  # noqa: BLE001 — warm path must never take a layer down
        log.warning("AOT compile failed", exc_info=True)
        return None
    if cost_key:
        from oryx_tpu.common import profiling

        profiling.costs().register_compiled(cost_key, compiled)
    return compiled


class WarmupState:
    """Progress of the serving tier's bucket-warmup ladder.

    ``arm()`` is called at layer start when warmup is configured: an armed
    state is NOT ready until a full ladder completes (otherwise the window
    between "model loaded" and "warmer picked it up" would flap /readyz).
    ``begin(total)`` starts a cycle, ``bucket_done()`` ticks it, and
    ``finish()`` marks the sticky completed bit once a cycle fully warms.
    Completion is sticky by design: a later model-generation swap re-runs
    the ladder off-path against the STAGED model while the already-warm old
    generation keeps serving, so readiness must not drop mid-swap."""

    def __init__(self):
        self._lock = threading.Lock()
        self.done = 0
        self.total = 0
        self._armed = False
        self._completed_once = False

    def reset(self) -> None:
        with self._lock:
            self.done = 0
            self.total = 0
            self._armed = False
            self._completed_once = False

    def arm(self) -> None:
        with self._lock:
            self._armed = True

    def begin(self, total: int) -> None:
        with self._lock:
            self.done = 0
            self.total = max(0, total)

    def bucket_done(self) -> None:
        with self._lock:
            self.done += 1

    def finish(self) -> None:
        with self._lock:
            if self.total and self.done >= self.total:
                self._completed_once = True

    def mark_trivial(self) -> None:
        """The served model has no batched path to warm (wordcount-style
        apps): warmup is trivially complete — never hold readiness."""
        with self._lock:
            self._completed_once = True

    def snapshot(self) -> dict:
        with self._lock:
            return {"done": self.done, "total": self.total}

    def warm_fraction(self) -> float:
        with self._lock:
            if self.total <= 0:
                return 1.0
            return self.done / self.total

    def ready(self, min_fraction: float) -> bool:
        """Readiness contribution for /readyz: unarmed states never gate
        (warmup not configured); armed states need ``min_fraction`` of the
        current ladder — or one fully completed ladder, ever."""
        with self._lock:
            if self._completed_once or not self._armed:
                return True
            if self.total <= 0:
                return False  # armed but the ladder has not started yet
            return (self.done / self.total) >= min_fraction


_WARMUP = WarmupState()


def warmup_state() -> WarmupState:
    """The process-wide warmup state the serving layer and /readyz share."""
    return _WARMUP


def observe_warmup(scope: str, seconds: float) -> None:
    """Record one warmup duration (``scope`` is ``bucket`` or ``model``)."""
    _WARMUP_SECONDS.labels(scope).observe(seconds)


_WARM_DONE = metrics_mod.default_registry().gauge(
    "oryx_warmup_buckets_done",
    "Batch buckets compiled in the current warmup cycle",
)
_WARM_TOTAL = metrics_mod.default_registry().gauge(
    "oryx_warmup_buckets_total",
    "Batch buckets the current warmup cycle will compile",
)
# scrape-time callbacks over the module singleton (it lives for the process,
# so no weakref dance is needed here)
_WARM_DONE.set_function(lambda: warmup_state().snapshot()["done"])
_WARM_TOTAL.set_function(lambda: warmup_state().snapshot()["total"])
