"""Forward and backward of ``dptpu.ops.attention`` at the two token cells'
shapes on the chip: the scan against the Pallas kernels at several block
sizes, each pass alone (median of fenced calls) and ``causal_attention``
whole with its gradient as the program on the chip gets it. Refuses to
run off the chip. ``python scripts/bench_attention.py [joyai|lfm2] ...``
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
}
BLOCKS = {
    "joyai": [(512, 512), (1024, 512), (512, 1024), (1024, 1024)],
    "lfm2": [(512, 512), (256, 512), (512, 1024)],
}


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


def main(names):
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs the chip, found {device.platform}")
    results = {"device": device.device_kind}
    for name in names:
        b, h, g, s, d, dv = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(keys[0], (b, h, s * g, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, h, s, dv), jnp.bfloat16)
        do = jax.random.normal(keys[3], (b, h, s * g, dv), jnp.bfloat16)
        scale, block = d ** -0.5, attention.DEFAULT_BLOCK
        scan_fwd = jax.jit(lambda q, k, v: attention._forward(
            q, k, v, block, g, scale))
        scan_bwd = jax.jit(lambda *a: attention._backward(
            *a, block, g, scale))
        out, lse = scan_fwd(q, k, v)
        grads = scan_bwd(q, k, v, out, lse, do)
        row = {"scan": {"forward_ms": timed(scan_fwd, q, k, v),
                        "backward_ms": timed(scan_bwd, q, k, v, out, lse,
                                             do)}}
        print(name, "scan", row["scan"], flush=True)
        for bq, bkv in BLOCKS[name]:
            kw = dict(block=block, groups=g, scale=scale, block_q=bq,
                      block_kv=bkv)
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
            print(name, f"{bq}x{bkv}", row[f"{bq}x{bkv}"], flush=True)

        # the whole call as a model makes it, on the chip's own choice
        qm = jax.random.normal(keys[0], (b, s, h * g, d), jnp.bfloat16)
        km = jax.random.normal(keys[1], (b, s, h, d), jnp.bfloat16)
        vm = jax.random.normal(keys[2], (b, s, h, dv), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(attention.causal_attention(
                q, k, v, scale=scale).astype(jnp.float32) ** 2)

        whole = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        row["causal_attention_grad_ms"] = timed(whole, qm, km, vm)
        print(name, "whole", row["causal_attention_grad_ms"], flush=True)
        results[name] = row
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_attention.json", "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main(sys.argv[1:] or list(SHAPES))
