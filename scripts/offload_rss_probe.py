#!/usr/bin/env python
"""Diagnose the flux-offload host OOM (r04: bench pid killed at 130 GB
RSS during the warmup image).

Streams a block-sized buffer to the device N times with the same
backpressure discipline as ``diffusion/offload.py`` (block on a consumer,
delete the device array) and prints host RSS growth per variant:

    variant none     — stream + delete, no extra hygiene (offload.py today)
    variant gc       — + gc.collect() every K transfers
    variant refresh  — + drop python refs immediately

If RSS grows linearly under 'none' but not 'gc', the transfer client
frees its host copy only at gc time → offload.py needs periodic collection.
"""

from __future__ import annotations

import argparse
import gc
import os
import resource
import sys
import time


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def cur_rss_gb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1e6
    return 0.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=["none", "gc", "refresh"],
                    default="none")
    ap.add_argument("--mb", type=int, default=512, help="buffer size")
    ap.add_argument("--n", type=int, default=40, help="transfers")
    ap.add_argument("--gc-every", type=int, default=4)
    cli = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    print(f"platform={dev.platform} kind={dev.device_kind}", flush=True)
    host = np.random.default_rng(0).standard_normal(
        (cli.mb, 1024, 256), dtype=np.float32)          # mb MB
    consume = jax.jit(lambda a: jnp.sum(a))

    base = cur_rss_gb()
    print(f"baseline rss={base:.2f} GB", flush=True)
    t0 = time.time()
    for i in range(cli.n):
        arr = jax.device_put(host, dev)
        out = consume(arr)
        jax.block_until_ready(out)                       # backpressure
        arr.delete()
        if cli.variant == "refresh":
            del arr, out
        if cli.variant == "gc" and (i + 1) % cli.gc_every == 0:
            gc.collect()
        if (i + 1) % 5 == 0:
            print(f"i={i+1:3d} rss={cur_rss_gb():.2f} GB "
                  f"(+{cur_rss_gb() - base:.2f}) "
                  f"{(i+1) * cli.mb / 1024 / (time.time() - t0):.2f} GB/s",
                  flush=True)
    gc.collect()
    print(f"final rss={cur_rss_gb():.2f} GB (peak {rss_gb():.2f}) "
          f"streamed {cli.n * cli.mb / 1024:.1f} GB", flush=True)


if __name__ == "__main__":
    main()
