"""Named host spans of the serving engine, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``apex.<phase>``:
a profiler trace records it on the host plane, on the same clock as the
device planes, so a trace of a live server puts each stretch of device
idle time down to what the engine thread was doing.  Where no trace is
being taken a span costs one enter and one exit (about a microsecond).

The engine thread opens ``apex.step`` per iteration and, inside it,
``apex.admit``, ``apex.rebalance``, ``apex.tier_move``, ``apex.schedule``,
``apex.dispatch``, ``apex.host_wait``, ``apex.sync``, ``apex.host_token``,
``apex.finish_chunks`` and ``apex.retire``; a host-attention job opens
``apex.host_job`` with ``apex.host_job.device_wait``, ``.copy`` and
``.attention`` on the thread that runs it.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "apex."


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The span ``apex.<name>``; ``meta`` rides along as trace metadata."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def step_span(step_num: int) -> jax.profiler.StepTraceAnnotation:
    """The span ``apex.step`` of one engine iteration."""
    return jax.profiler.StepTraceAnnotation(PREFIX + "step",
                                            step_num=step_num)


def spanned(name: str):
    """Decorator: the function runs inside the span ``apex.<name>``."""
    return functools.partial(jax.profiler.annotate_function,
                             name=PREFIX + name)
