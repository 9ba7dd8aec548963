"""How a device call tells whoever scheduled it where its device phase lies.

The coalescer (serving/batcher.py) decides when to open its next flush from
when the chip will be free, and only the code that launches the programs and
waits for them knows that: ``models/als/serving.py:_dispatch`` calls
:func:`enqueued` once its last program is launched, ``_download`` calls
:func:`device_done` once it has their results on the host. The scheduler hands its reporter over with :func:`reporting` around
the call — a context variable of the calling thread, so the model's
signatures carry nothing, tracing may be off, and a call nobody scheduled (a
direct ``top_n``, the warm ladder) reports to no one.
"""

from __future__ import annotations

import contextvars

_REPORTER: "contextvars.ContextVar[object | None]" = contextvars.ContextVar(
    "oryx_device_phase", default=None
)


class reporting:
    """Hand ``reporter`` (``enqueued()``, ``device_done()``) to the device
    calls this thread makes inside the ``with``."""

    __slots__ = ("_reporter", "_token")

    def __init__(self, reporter):
        self._reporter = reporter

    def __enter__(self):
        self._token = _REPORTER.set(self._reporter)
        return self._reporter

    def __exit__(self, *exc) -> None:
        _REPORTER.reset(self._token)


def enqueued() -> None:
    """The call's last program has been launched."""
    reporter = _REPORTER.get()
    if reporter is not None:
        reporter.enqueued()


def device_done() -> None:
    """The call's results are ready: the device has run its programs."""
    reporter = _REPORTER.get()
    if reporter is not None:
        reporter.device_done()
