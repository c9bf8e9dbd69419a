#!/usr/bin/env python
"""Gather microbenchmark (slim): cost of take(tbl(N,W)u32, idx(B,)) per
row vs row width, plus a Pallas in-VMEM gather attempt.  Uses the
bench's chained two-point-slope methodology."""
import sys
import time

from _common import jax_setup, setup_repo_path

setup_repo_path()

import numpy as np
import jax
import jax.numpy as jnp

B = 1 << 20


def slope(step, idx0, label):
    @jax.jit
    def loop(k, idx):
        def body(i, idx):
            return step(idx ^ i.astype(jnp.int32))
        return jax.lax.fori_loop(0, k, body, idx)

    loop(1, idx0).block_until_ready()
    idx_host = np.asarray(idx0)
    salt = [0]

    def best_of(k, attempts=3):
        best = float("inf")
        for _ in range(attempts):
            # fresh input CONTENT per attempt, so no layer between
            # host and device can replay a cached execution of the same
            # (k, idx0)
            salt[0] += 1
            idx = jax.device_put(idx_host ^ np.int32(salt[0]))
            idx.block_until_ready()
            t0 = time.perf_counter()
            loop(k, idx).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    k1, k2 = 3, 23
    b1 = best_of(k1)
    while True:
        b2 = best_of(k2)
        if b2 - b1 >= 0.5 or k2 >= 2000:
            break
        k2 *= 3
        b1 = best_of(k1)
    dt = (b2 - b1) / (k2 - k1)
    print(f"{label}: {dt/B*1e9:6.2f} ns/row ({B*1e-6/dt:6.1f} M rows/s)",
          file=sys.stderr, flush=True)
    return dt


def main():
    jax_setup()
    rng = np.random.default_rng(7)
    N = 65536
    idx0 = jax.device_put(rng.integers(0, N, B, dtype=np.int64).astype(np.int32))

    for W in (8, 32, 64, 128, 256):
        tbl = jax.device_put(
            rng.integers(0, 2**31, (N, W), dtype=np.int64).astype(np.uint32))

        def step(idx, tbl=tbl):
            rows = jnp.take(tbl, jnp.clip(idx, 0, N - 1), axis=0)
            s = jnp.sum(rows.astype(jnp.uint32), axis=1)
            return (idx + s.astype(jnp.int32)) % N

        slope(step, idx0, f"xla take N=65536 W={W} ({W*4}B)")

    print("=== Pallas in-VMEM gather attempt ===", file=sys.stderr, flush=True)
    try:
        from jax.experimental import pallas as pl

        N2 = 4096
        tblv = jax.device_put(
            rng.integers(0, 2**31, (N2, 128), dtype=np.int64).astype(np.uint32))
        idxs = jax.device_put(
            rng.integers(0, N2, B, dtype=np.int64).astype(np.int32))
        BB = 1024

        def kern(idx_ref, tbl_ref, out_ref):
            rows = jnp.take(tbl_ref[:], idx_ref[:], axis=0)
            out_ref[:] = jnp.sum(rows.astype(jnp.uint32), axis=1, keepdims=True)

        @jax.jit
        def pstep(idx):
            s = pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((B, 1), jnp.uint32),
                grid=(B // BB,),
                in_specs=[
                    pl.BlockSpec((BB,), lambda i: (i,)),
                    pl.BlockSpec((N2, 128), lambda i: (0, 0)),
                ],
                out_specs=pl.BlockSpec((BB, 1), lambda i: (i, 0)),
            )(jnp.clip(idx, 0, N2 - 1), tblv)
            return (idx + s[:, 0].astype(jnp.int32)) % N2

        slope(pstep, idxs, "pallas vmem take N=4096 row=512B")
    except Exception as e:
        print(f"pallas gather FAILED: {type(e).__name__}: {str(e)[:600]}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
