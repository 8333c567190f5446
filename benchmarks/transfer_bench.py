"""KV transfer plane microbenchmark: host TCP path vs device pull path.

Measures end-to-end GB/s of shipping KV pages between a sender and a
receiver in one process (loopback worst case for the device plane — on a
real pod the pull rides ICI/DCN). Mirrors the reference's motivation for
NIXL over host staging (block/transfer.rs strategies): the host path pays
device→host, TCP, host→device; the device path pays none of them.

Usage:  python -m benchmarks.transfer_bench [--mb 64] [--iters 5]
Prints one JSON document with GB/s for both strategies.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np


async def _bench(mb: int, iters: int) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.disagg.device_transfer import DevicePlane
    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer

    # KV-page-shaped payload: [L, Hkv, n, ps, D] bf16, ~mb MB per k/v pair
    elems = mb * (1 << 20) // 2 // 2  # /2 dtype bytes, /2 for k+v
    n_pages = max(1, elems // (8 * 64 * 128))
    shape = (1, 8, n_pages, 64, 128)
    k_dev = jnp.ones(shape, jnp.bfloat16)
    v_dev = jnp.zeros(shape, jnp.bfloat16)
    k_host = np.asarray(k_dev)
    v_host = np.asarray(v_dev)
    nbytes = 2 * k_host.nbytes
    page_ids = list(range(n_pages))

    landed: dict = {}

    async def write_fn(ids, kk, vv):
        landed["np"] = (kk.shape, vv.shape)

    async def device_write_fn(ids, kk, vv):
        kk.block_until_ready()
        landed["dev"] = (kk.shape, vv.shape)

    server = KvTransferServer(write_fn, device_write_fn=device_write_fn)
    await server.start()
    client = KvTransferClient()
    out = {"payload_mb": round(nbytes / (1 << 20), 1), "pages": n_pages}
    try:
        # host strategies, most- to least-preferred: shm is the same-host
        # fast path; bulk is THE remote path (side blocking socket,
        # threads both ends); inline is the legacy single-connection
        # asyncio framing. Each is isolated by suppressing the faster
        # ones on the shared client.
        import dynamo_tpu.disagg.transfer as _tr

        shm_ok = client._shm_pool is not None
        # below the bulk threshold the "bulk" row would silently measure
        # the inline path — skip it instead of lying
        bulk_ok = nbytes >= _tr._BULK_MIN
        host_strategies = [("host_shm", shm_ok), ("host_bulk", bulk_ok),
                           ("host_inline", True), ("device", True)]
        for strategy, available in host_strategies:
            if not available:
                out[strategy] = None
                continue
            # plane isolation for the host variants
            client._shm_bad.clear()
            client._bulk_bad.clear()
            if strategy in ("host_bulk", "host_inline"):
                client._shm_bad[server.address] = 1 << 30
            if strategy == "host_inline":
                client._bulk_bad[server.address] = 1 << 30
            times = []
            for i in range(iters + 1):
                rid = f"{strategy}-{i}"
                server.expect(rid)
                t0 = time.perf_counter()
                if strategy.startswith("host"):
                    ok = await client.write(
                        *server.address, rid, page_ids,
                        np.asarray(k_dev), np.asarray(v_dev), 0,
                    )
                else:
                    plane = DevicePlane.get()
                    if plane is None:
                        out["device"] = None
                        break
                    ok = await client.send(
                        *server.address, rid, page_ids, k_dev, v_dev, 0
                    )
                dt = time.perf_counter() - t0
                assert ok
                if i > 0:  # first iter warms connections/compiles
                    times.append(dt)
            if times:
                best = min(times)
                out[strategy] = {
                    "gb_s": round(nbytes / best / (1 << 30), 3),
                    "ms": round(best * 1e3, 2),
                }
        out["planes_landed"] = dict(server.transfers)
    finally:
        client.close()
        await server.stop()
    host_best = next(
        (
            out[s]["gb_s"]
            for s in ("host_shm", "host_bulk", "host_inline")
            if isinstance(out.get(s), dict)
        ),
        None,
    )
    if host_best and isinstance(out.get("device"), dict):
        out["device_speedup"] = round(out["device"]["gb_s"] / host_best, 2)
    return out


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="KV transfer plane microbench")
    p.add_argument("--mb", type=int, default=64, help="payload size, MB")
    p.add_argument("--iters", type=int, default=5)
    args = p.parse_args(argv)

    import os

    # Sender and receiver share this process, so the device plane is safe
    # on every backend (the CPU cross-PROCESS abort doesn't apply).
    os.environ.setdefault("DYN_KV_TRANSFER", "device")
    import jax

    out = asyncio.run(_bench(args.mb, args.iters))
    out["platform"] = jax.default_backend()
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
