"""Spans of the program, on the profiler's clock.

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation("repro.<name>",
**stats)``.  With no profiler running it costs a fraction of a
microsecond.  Under the profiler it lands on the host plane, whose clock
the device planes share, with ``stats`` as the event's stats; stats known
only at the end go in through the annotation's ``set_metadata``::

    with span("retire") as s:
        ...
        s.set_metadata(finished=n)
"""
from __future__ import annotations

import jax


def span(name: str, **stats):
    return jax.profiler.TraceAnnotation(f"repro.{name}", **stats)
