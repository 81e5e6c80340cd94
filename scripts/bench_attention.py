"""Forward and backward of ``dptpu.ops.attention`` at the token cells'
shapes on the chip: the scan against the Pallas kernels at several block
sizes, each pass alone (median of fenced calls) and ``causal_attention``
whole with its gradient as the program on the chip gets it; Trinity-Mini's
shape with its window of 2,048 and without (``gaps`` 0.0 five times: the
kernels' ``out``, ``lse``, ``dq``, ``dk``, ``dv`` are the scan's bit for
bit). Refuses to run off the chip.
``python scripts/bench_attention.py [joyai|lfm2|trinity] ...``
"""

import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dptpu.ops import attention, attention_kernel

SHAPES = {  # rows, key/value heads, group, tokens, qk head, v head
    "joyai": (1, 32, 1, 8192, 192, 128),
    "lfm2": (2, 8, 4, 8192, 64, 64),
    "trinity": (1, 4, 8, 8192, 128, 128),
}
BLOCKS = {
    "joyai": [(512, 512), (1024, 512), (512, 1024), (1024, 1024)],
    "lfm2": [(512, 512), (256, 512), (512, 1024)],
    "trinity": [(512, 512)],
}
# the windows a shape is timed under, None the causal call
WINDOWS = {"trinity": (None, 2048)}


def timed(fn, *args, calls=8):
    jax.block_until_ready(fn(*args))  # compiles
    times = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return 1e3 * statistics.median(times)


def gap(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def bench(name, window, label):
    """One shape under one window: the row of numbers for ``label``."""
    b, h, g, s, d, dv = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, h, s * g, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h, s, dv), jnp.bfloat16)
    do = jax.random.normal(keys[3], (b, h, s * g, dv), jnp.bfloat16)
    scale, block = d ** -0.5, attention.DEFAULT_BLOCK
    scan_fwd = jax.jit(lambda q, k, v: attention._forward(
        q, k, v, block, g, scale, window))
    scan_bwd = jax.jit(lambda *a: attention._backward(
        *a, block, g, scale, window))
    out, lse = scan_fwd(q, k, v)
    grads = scan_bwd(q, k, v, out, lse, do)
    row = {"scan": {"forward_ms": timed(scan_fwd, q, k, v),
                    "backward_ms": timed(scan_bwd, q, k, v, out, lse,
                                         do)}}
    print(label, "scan", row["scan"], flush=True)
    for bq, bkv in BLOCKS[name]:
        kw = dict(block=block, groups=g, scale=scale, window=window,
                  block_q=bq, block_kv=bkv)
        fwd = jax.jit(functools.partial(attention_kernel.forward, **kw))
        bwd = jax.jit(functools.partial(attention_kernel.backward, **kw))
        try:
            got_out, got_lse = fwd(q, k, v)
            got = bwd(q, k, v, out, lse, do)
            row[f"{bq}x{bkv}"] = {
                "forward_ms": timed(fwd, q, k, v),
                "backward_ms": timed(bwd, q, k, v, out, lse, do),
                "gaps": [gap(got_out, out), gap(got_lse, lse)]
                + [gap(x, y) for x, y in zip(got, grads)]}
        except Exception as e:  # a size the chip's compiler refuses
            row[f"{bq}x{bkv}"] = {"error": str(e)[:400]}
        print(label, f"{bq}x{bkv}", row[f"{bq}x{bkv}"], flush=True)

    # the whole call as a model makes it, on the chip's own choice
    qm = jax.random.normal(keys[0], (b, s, h * g, d), jnp.bfloat16)
    km = jax.random.normal(keys[1], (b, s, h, d), jnp.bfloat16)
    vm = jax.random.normal(keys[2], (b, s, h, dv), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(attention.causal_attention(
            q, k, v, scale=scale, window=window).astype(jnp.float32) ** 2)

    whole = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    row["causal_attention_grad_ms"] = timed(whole, qm, km, vm)
    print(label, "whole", row["causal_attention_grad_ms"], flush=True)
    return row


def main(names):
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs the chip, found {device.platform}")
    results = {"device": device.device_kind}
    for name in names:
        for window in WINDOWS.get(name, (None,)):
            label = name if window is None else f"{name}-window-{window}"
            results[label] = bench(name, window, label)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_attention.json", "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main(sys.argv[1:] or list(SHAPES))
