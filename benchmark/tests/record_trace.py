"""Record the small profiler trace that ``test_trace.py`` reduces.

    python benchmark/tests/record_trace.py OUT_DIR

Run on the chip.  Four rounds of: a device computation inside a
``bench.loop_pass`` span, then either a 30 ms host wait inside a
``bench.verdict_sink`` span and a 5 ms wait outside any span (rounds 0
and 2), or a 40 ms wait outside any span (rounds 1 and 3).  The device
is idle across each wait, so the trace's idle gaps carry known names.
Prints the trace's planes and lines; copy the ``.xplane.pb`` to
``benchmark/tests/data/small.xplane.pb``.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.default_backend() != "tpu":
        print("record_trace: not on a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench.loop_pass"):
            f(x).block_until_ready()
        if i % 2 == 0:
            with jax.profiler.TraceAnnotation("bench.verdict_sink"):
                time.sleep(0.03)
            time.sleep(0.005)
        else:
            time.sleep(0.04)
    with jax.profiler.TraceAnnotation("bench.loop_pass"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark import trace
    from jax.profiler import ProfileData

    path = trace.find_xplane(out)
    print("xplane", path, os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print("plane", plane.name, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
