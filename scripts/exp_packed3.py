#!/usr/bin/env python3
"""Experiment 3: 1-D flat packing + same-shape conv-kernel stacking.

1-D f32 leaves (BN scale/bias/stats, fc bias) go into one flat vector;
>=2-D leaves are grouped by shape and stacked along a new leading dim
(leading-dim slices are layout-preserving, unlike flattening, which
forced a layout change per kernel — exp_packed2 measured that at +13 ms).
Boundary tensor count drops ~430 -> ~40. Interleaved A/B vs stock.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax import tree_util as jtu

    from dptpu.models import create_model
    from dptpu.ops.loss import cross_entropy_loss
    from dptpu.ops.metrics import topk_correct_fraction
    from dptpu.ops.schedules import make_step_decay_schedule
    from dptpu.train import create_train_state, make_optimizer, make_train_step

    per_chip_batch = 128
    model = create_model("resnet50", dtype=jnp.bfloat16)
    tx = make_optimizer(0.9, 1e-4)
    state = create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, 224, 224, 3)
    )
    lr_schedule = make_step_decay_schedule(0.1, 100)
    rng = np.random.RandomState(0)
    batch = jax.device_put({
        "images": rng.randint(0, 256, (per_chip_batch, 224, 224, 3)).astype(np.uint8),
        "labels": rng.randint(0, 1000, (per_chip_batch,)).astype(np.int32),
    })
    stock_step = make_train_step(None, jnp.bfloat16, lr_schedule=lr_schedule)

    # ---- packer: flat 1-D + shape-stacked ND ----
    def make_packer(template):
        leaves, treedef = jtu.tree_flatten(template)
        small = [i for i, l in enumerate(leaves)
                 if l.ndim <= 1 and l.dtype == jnp.float32]
        big = [i for i in range(len(leaves)) if i not in small]
        sizes = {i: int(leaves[i].size) for i in small}
        offs, off = {}, 0
        for i in small:
            offs[i] = off
            off += sizes[i]
        total = off
        groups = {}  # shape -> [leaf indices]
        for i in big:
            groups.setdefault((leaves[i].shape, str(leaves[i].dtype)), []).append(i)
        gkeys = sorted(groups, key=str)

        def pack(tree):
            ls = jtu.tree_leaves(tree)
            flat = (jnp.concatenate([ls[i].reshape(-1) for i in small])
                    if total else jnp.zeros((0,), jnp.float32))
            stacks = [jnp.stack([ls[i] for i in groups[k]]) for k in gkeys]
            return {"flat": flat, "stacks": stacks}

        def unpack(packed):
            out = [None] * len(jtu.tree_leaves(template))
            for i in small:
                out[i] = jax.lax.dynamic_slice(
                    packed["flat"], (offs[i],), (sizes[i],)
                ).reshape(leaves[i].shape)
            for k, st in zip(gkeys, packed["stacks"]):
                for j, i in enumerate(groups[k]):
                    out[i] = st[j]
            return treedef.unflatten(out)

        n_tensors = 1 + len(gkeys)
        return pack, unpack, n_tensors

    pack_p, unpack_p, np_ = make_packer(state.params)
    pack_s, unpack_s, ns_ = make_packer(state.batch_stats)
    print(f"params -> {np_} tensors, stats -> {ns_} tensors")
    momentum, weight_decay = 0.9, 1e-4

    def pack_state(st):
        return dict(step=st.step, p=pack_p(st.params),
                    s=pack_s(st.batch_stats),
                    b=pack_p(st.opt_state[1].trace))

    def packed_step(carry, batch):
        images = batch["images"]
        mean = jnp.asarray([0.485, 0.456, 0.406], jnp.float32) * 255.0
        std = jnp.asarray([0.229, 0.224, 0.225], jnp.float32) * 255.0
        images = ((images.astype(jnp.float32) - mean) / std).astype(jnp.bfloat16)
        labels = batch["labels"]

        def loss_fn(p):
            params = unpack_p(p)
            stats = unpack_s(carry["s"])
            out, mutated = model.apply(
                {"params": params, "batch_stats": stats},
                images, train=True, mutable=["batch_stats"],
            )
            return cross_entropy_loss(out, labels), (out, mutated["batch_stats"])

        (loss, (logits, new_stats)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(carry["p"])
        top1, top5 = topk_correct_fraction(logits, labels, (1, 5))
        lr = lr_schedule(carry["step"])
        upd = lambda b_, g_, p_: momentum * b_ + g_ + weight_decay * p_
        new_b = jtu.tree_map(upd, carry["b"], g, carry["p"])
        new_p = jtu.tree_map(lambda p_, b_: p_ - lr * b_, carry["p"], new_b)
        metrics = {"loss": loss, "top1": top1 * 100.0, "top5": top5 * 100.0,
                   "lr": jnp.asarray(lr, jnp.float32)}
        return dict(step=carry["step"] + 1, p=new_p, s=pack_s(new_stats),
                    b=new_b), metrics

    packed_jit = jax.jit(packed_step, donate_argnums=0)
    fresh = lambda t: jtu.tree_map(jnp.copy, t)

    st, carry = fresh(state), pack_state(fresh(state))
    sl, pl = [], []
    for _ in range(3):
        st, m1 = stock_step(st, batch)
        carry, m2 = packed_jit(carry, batch)
        sl.append(float(m1["loss"])); pl.append(float(m2["loss"]))
    print("stock  losses:", sl)
    print("packed losses:", pl)

    import collections, re
    text = packed_jit.lower(pack_state(fresh(state)), batch).compile().as_text()
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    ops = collections.Counter()
    for line in lines[start:]:
        m = re.match(r"\s*(?:ROOT )?%?[\w.-]+ = \S+?\[[\d,]*\][^ ]* ([\w-]+)", line)
        if m:
            ops[m.group(1)] += 1
    print("packed entry:", dict(ops.most_common(8)))

    def timer(fn, st0):
        holder = {"st": st0}
        def window(iters):
            s = holder["st"]
            t0 = time.perf_counter()
            for _ in range(iters):
                s, m = fn(s, batch)
            float(m["loss"])
            holder["st"] = s
            return time.perf_counter() - t0
        return window

    wa, wb = timer(stock_step, fresh(state)), timer(packed_jit, pack_state(fresh(state)))
    wa(5); wb(5)
    ra, rb = [], []
    for rep in range(3):
        ts = wa(20); tl = wa(120); ra.append((tl - ts) / 100.0)
        ts = wb(20); tl = wb(120); rb.append((tl - ts) / 100.0)
    print("stock  ms/step:", [f"{t*1e3:.2f}" for t in ra], f"median {np.median(ra)*1e3:.2f}")
    print("packed ms/step:", [f"{t*1e3:.2f}" for t in rb], f"median {np.median(rb)*1e3:.2f}")


if __name__ == "__main__":
    main()
