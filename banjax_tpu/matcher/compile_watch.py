"""Process-wide count of XLA program builds.

Every first use of a (rows, L_p) bucket builds a device program: tens of
seconds of Mosaic on a cold chip, about a second when JAX's persistent
compile cache has it.  Either way the batch that paid for it says nothing
about how fast the device is, so the callers that learn from batch
latency — the breaker's latency budget (runner.note_device_outcome) and
the pipeline's adaptive batch sizer — compare this counter before and
after a batch and leave a batch that built a program out of their
samples.  Hard device errors are counted as before.

JAX reports one `backend_compile_duration` event per program it compiles
or loads from the persistent cache; the listener below counts them.
"""

from __future__ import annotations

import threading

from jax import monitoring

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_lock = threading.Lock()
_builds = 0


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    global _builds
    if event == _BUILD_EVENT:
        with _lock:  # programs compile on several threads at once
            _builds += 1


monitoring.register_event_duration_secs_listener(_on_duration)


def count() -> int:
    """Programs built (compiled or cache-loaded) by this process so far."""
    return _builds
