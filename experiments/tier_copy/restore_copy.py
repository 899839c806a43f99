"""Host-side legs of a host-tier KV restore, timed one by one at the
stablelm-3b-pp2 cell's staging shape, (16, 128, 16, 32, 80) bf16 a pool.

    python experiments/tier_copy/restore_copy.py [--reps 5] [--pages 100]

(a) the padded host copy ``np.asarray(staging)[:, take]`` of a buffer that
    came from a device-to-host copy; (b) the same into a preallocated,
    already-touched output; (c) ``jax.device_put`` of the device-to-host
    buffer itself; (d) the same from a fresh numpy buffer; then the
    device-to-host copy, the ``pinned_host`` round trip, and the restore
    that copies the staged buffer as it is and pads on the device.
Prints one JSON line (seconds per repetition and GB/s at the median) and
writes it to ``chiprun_out/restore_copy.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

SHAPE = (16, 128, 16, 32, 80)


def timed(fn, reps, setup=lambda: None):
    out = []
    for _ in range(reps):
        arg = setup()
        t0 = time.perf_counter()
        fn(arg)
        out.append(time.perf_counter() - t0)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--pages", type=int, default=100)
    a = ap.parse_args()
    dev = jax.devices()[0]
    W, n = SHAPE[1], a.pages
    src = jax.random.normal(jax.random.PRNGKey(0), SHAPE, jnp.bfloat16)
    src.block_until_ready()
    nbytes = src.nbytes
    take = np.minimum(np.arange(W), n - 1)
    res: dict[str, list[float]] = {}

    res["d2h"] = timed(lambda _: np.asarray(src + 0), a.reps)
    d2h = lambda: np.asarray(jax.block_until_ready(src + 0))   # noqa: E731
    res["a_pad_from_d2h"] = timed(lambda b: b[:, :n][:, take], a.reps, d2h)
    out = np.empty(SHAPE, src.dtype)
    out.fill(0)

    def into(b):
        for j, t in enumerate(take):
            out[:, j] = b[:, t]
    res["b_pad_into_touched"] = timed(into, a.reps, d2h)
    res["b_take_into_touched"] = timed(
        lambda b: np.take(b[:, :n], take, axis=1, out=out), a.reps, d2h)
    put = lambda b: jax.device_put(b, dev).block_until_ready()  # noqa: E731
    res["c_put_from_d2h"] = timed(put, a.reps, d2h)
    res["d_put_from_fresh"] = timed(put, a.reps, lambda: np.array(d2h()))
    res["put_from_touched"] = timed(put, a.reps, lambda: out)
    res["first_touch"] = timed(lambda _: np.ones(SHAPE, src.dtype), a.reps)
    try:
        pinned = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
        hold = [None]

        def to_pinned(_):
            hold[0] = jax.device_put(src + 0, pinned).block_until_ready()
        res["pinned_d2h"] = timed(to_pinned, a.reps)
        on_dev = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")
        res["pinned_h2d"] = timed(
            lambda b: jax.device_put(b, on_dev).block_until_ready(), a.reps,
            lambda: hold[0])
    except Exception as e:          # noqa: BLE001 - report, keep the rest
        res["pinned_error"] = [repr(e)[:300]]

    pool = jnp.zeros((16, 2 * W) + SHAPE[2:], src.dtype)
    ids = jnp.asarray(np.minimum(np.arange(W), n - 1) + 3, jnp.int32)

    @jax.jit
    def pad_scatter(pool, staged, ids, n):
        t = jnp.minimum(jnp.arange(staged.shape[1]), n - 1)
        return pool.at[:, ids].set(jnp.take(staged, t, axis=1))

    def restore(b):
        s = jax.device_put(b, dev)
        pad_scatter(pool, s, ids, n).block_until_ready()
    restore(d2h())                   # compile
    res["restore_put_pad_on_device"] = timed(restore, a.reps, d2h)

    line = {"device": dev.device_kind, "shape": list(SHAPE),
            "pages": n, "pool_bytes": nbytes}
    for k, v in res.items():
        if isinstance(v[0], float):
            line[k] = {"s": v, "gbps_median": nbytes / statistics.median(v)
                       / 1e9}
        else:
            line[k] = v
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/restore_copy.json", "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
