"""Named host spans of a plan, written to the JAX profiler's own trace.

``span(name)`` opens ``jax.profiler.TraceAnnotation("scope:" + name)``.
Under ``jax.profiler.trace`` (or ``start_trace``/``stop_trace``) each span
lands on the host plane of the same trace as the device's operations, on
the same clock, so a reader can put every device gap down to the host
work around it. With no profiler running a span costs one TraceMe check,
so spans are always in place: there is no switch.

Rules for placing a span:

* only in host code, never inside a jitted function (it would run once,
  while tracing, and time nothing);
* never inside a loop over tenants, partitions, columns or candidates;
* at most one opening of each name per plan, whatever the number of
  tenants or partitions.

The spans a plan opens, and what each covers, are listed in
``docs/engine.md`` ("Tracing a plan").
"""

from __future__ import annotations

import jax

PREFIX = "scope:"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``scope:<name>``, used as ``with span("gpart"): ...``.

    Keyword arguments (numbers or strings) go with the span as metadata:
    the trace's event keeps the name and carries each as a stat. A count
    known only at the end is set before the span closes, through
    ``with span(name) as sp: ...; sp.set_metadata(key=value)``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
