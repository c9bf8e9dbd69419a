"""Observability: statistics polling/exposition, deny-event pipeline, and
raw-frame parsing (the host-side analogue of the XDP header parse)."""

from contextlib import nullcontext

#: what a span site enters when tracing is off
_NO_SPAN = nullcontext()


def program_span(tracer, name: str, seq=None):
    """``tracer``'s program span (``telemetry.SpanTracer.span``), or a
    no-op where there is no tracer (a daemon run without ``--trace``)."""
    return _NO_SPAN if tracer is None else tracer.span(name, seq)
