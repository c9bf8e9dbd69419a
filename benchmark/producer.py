"""Producer process: pushes pre-packed records into the daemon's ingest
ring, as a NIC-facing shim would.  It imports
no JAX (the ring needs only numpy), so it never touches the chip.

    python benchmark/producer.py --ring PATH --pool POOL.npz --log LOG.npz

Commands arrive on stdin, one line each:

- ``warm``: push every pool record once, as fast as the ring admits.
- ``go {"t_start": .., "t1": ..}``: from ``t_start`` until ``t1``
  (``time.monotonic()``, which the daemon's process shares) keep the
  ring full with records in pool order, cycling.  Then write the log and
  exit.

The log holds one row per push: ring sequence number, pool index,
commit time, and this process's cumulative ring-blocked microseconds
after the push.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from infw.ring import IngestRing  # noqa: E402

PUSH_TIMEOUT_S = 120.0


class Log:
    def __init__(self) -> None:
        self.rows = []

    def push(self, ring, wire, v4_only, pool_idx) -> None:
        seq = ring.push(wire, v4_only=v4_only, timeout=PUSH_TIMEOUT_S)
        t = time.monotonic()
        blocked = ring.counter_values()["ring_blocked_us_total"]
        self.rows.append((seq, pool_idx, t, blocked))

    def save(self, path: str) -> None:
        a = np.asarray(self.rows, np.float64).reshape(-1, 4)
        np.savez(path, seq=a[:, 0].astype(np.int64),
                 pool=a[:, 1].astype(np.int64), commit=a[:, 2],
                 blocked_us=a[:, 3])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--ring", required=True)
    p.add_argument("--pool", required=True)
    p.add_argument("--log", required=True)
    args = p.parse_args(argv)

    z = np.load(args.pool)
    v4_only = z["v4_only"]
    wires = [z[f"wire_{i}"] for i in range(len(v4_only))]
    n_pool = len(wires)
    ring = IngestRing.attach(args.ring, timeout=60.0)
    log = Log()
    try:
        if sys.stdin.readline().strip() != "warm":
            raise SystemExit("producer: expected 'warm'")
        for i in range(n_pool):
            log.push(ring, wires[i], bool(v4_only[i]), i)
        line = sys.stdin.readline()
        cmd, _, body = line.partition(" ")
        if cmd != "go":
            raise SystemExit(f"producer: expected 'go', got {line!r}")
        go = json.loads(body)
        t_start, t1 = go["t_start"], go["t1"]
        while time.monotonic() < t_start:
            time.sleep(max(0.0, t_start - time.monotonic()))
        k = 0
        while time.monotonic() < t1:
            i = k % n_pool
            log.push(ring, wires[i], bool(v4_only[i]), i)
            k += 1
    finally:
        log.save(args.log)
        ring.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
