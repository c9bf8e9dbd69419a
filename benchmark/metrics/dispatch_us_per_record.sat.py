"""Dispatch: host microseconds per record in the daemon's h2d and
dispatch spans (its SpanTracer, on in the traced run), across the
window."""


def read(run):
    a, b = run.tracer_t0, run.tracer_t1
    if not a or not b:
        return None
    n = b["dispatch"][0] - a["dispatch"][0]
    if n <= 0:
        return None
    us = sum(b[s][1] - a[s][1] for s in ("h2d", "dispatch"))
    return us / n
