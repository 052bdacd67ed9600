"""Named host spans on the profiler's timeline, without importing JAX.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` once JAX is
loaded in this process, and one shared no-op context before that, so the
engine and the runner never pull JAX into a process that does not use it
(process-backend children, the DES).  A ``TraceAnnotation`` lands on the
same profile as the device's operations, which lets idle device time be
charged to what the host was doing.  Spans are named
``revati.<layer>.<phase>``; ``meta`` goes into the profile as the span's
arguments (an engine step carries its index into ``step_log``).
"""

from __future__ import annotations

import sys
from contextlib import nullcontext

_NULL = nullcontext()


def span(name: str, **meta):
    annotation = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                         None)
    return _NULL if annotation is None else annotation(name, **meta)
