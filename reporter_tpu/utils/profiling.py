"""Device profiling hooks (SURVEY.md §5 "Tracing / profiling").

The reference has no tracing; Valhalla only has timing logs. The TPU
build's device side is opaque without XLA-level traces, so this wraps
jax.profiler with a uniform entry point:

    from reporter_tpu.utils.profiling import device_trace
    with device_trace("/tmp/xplane"):          # no-op when dir is falsy
        matcher.match_many(traces)

The dump is an XPlane/perfetto trace directory loadable in TensorBoard's
profile plugin or ui.perfetto.dev. `REPORTER_TPU_TRACE_DIR` turns every
`device_trace(None)` call site on without code changes — the service and
stream workers wrap their match calls with it. Named host spans inside
a capture come from ``utils/tracing.py``: every recorder ``span()``
lands on the profiler's host plane as ``rtpu.<name>``.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator

from reporter_tpu.utils import locks

# jax.profiler.trace is a PROCESS-GLOBAL singleton (start_trace raises if
# one is already active). The serving scheduler overlaps match_many calls
# from several worker threads, so concurrent device_trace entries are
# normal — only the first concurrent entrant starts a capture; the rest
# run untraced (their device work still lands in the active capture,
# which is what an XPlane trace of overlapped batches should show).
_trace_lock = locks.named_lock("profiling.trace")
_trace_active = False


@contextlib.contextmanager
def device_trace(trace_dir: "str | None" = None) -> Iterator[None]:
    """Context manager: capture a jax.profiler trace into ``trace_dir``.

    Falsy ``trace_dir`` falls back to $REPORTER_TPU_TRACE_DIR; if that is
    unset too, the context is a no-op (zero overhead in production).
    Re-entrant across threads: nested/concurrent entries while a capture
    is active are no-ops instead of profiler errors.
    """
    global _trace_active
    target = trace_dir or os.environ.get("REPORTER_TPU_TRACE_DIR", "")
    if not target:
        yield
        return
    with _trace_lock:
        if _trace_active:
            owner = False
        else:
            _trace_active = owner = True
    if not owner:
        yield
        return
    import jax

    try:
        with jax.profiler.trace(target):
            yield
    finally:
        with _trace_lock:
            _trace_active = False
