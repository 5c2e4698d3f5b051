"""Scoped signal-handler installation (trainer preemption path)."""

from __future__ import annotations

import contextlib
import signal
import threading


@contextlib.contextmanager
def installed_signal_handler(signum: int, handler):
    """Install ``handler`` for ``signum`` — main thread only
    (``signal.signal``'s requirement; other threads no-op and yield
    False) — and restore the previous disposition on exit, so embedding
    the caller in a larger process (pytest, a notebook) doesn't
    permanently hijack its signals.

    Restore detail: a previous handler installed by non-Python code
    reads back as ``None``, which ``signal.signal`` refuses to accept —
    restore ``SIG_DFL`` in that case rather than raising TypeError out
    of the ``finally`` (which would mask the in-flight exit path).
    """
    install = threading.current_thread() is threading.main_thread()
    prev = signal.signal(signum, handler) if install else None
    try:
        yield install
    finally:
        if install:
            signal.signal(signum,
                          prev if prev is not None else signal.SIG_DFL)


class SigtermFlag:
    """Truthy once SIGTERM has been delivered.  The handler only flips
    this flag — the cooperative-interruption contract (see TrainLoop:
    raising from a handler after the step donated its input state leaves
    deleted buffers) shared by run_training, tools/faultline.py, and the
    injected-preemption fault (resilience/faults.py)."""

    __slots__ = ("_seen",)

    def __init__(self):
        self._seen = False

    def __bool__(self) -> bool:
        return self._seen

    def __call__(self) -> bool:
        return self._seen


@contextlib.contextmanager
def sigterm_flag():
    """Install a flag-setting SIGTERM handler for the enclosed block and
    yield the flag (poll it at safe boundaries; never raise from it)."""
    flag = SigtermFlag()

    def _handler(signum, frame):
        flag._seen = True

    with installed_signal_handler(signal.SIGTERM, _handler):
        yield flag
