"""Worker for the 2-host full-fit() integration test.

Each instance is one JAX process with 2 fake CPU chips; together a 4-chip
pod. Runs the COMPLETE fit() path — CLI-parsed config, rendezvous,
hierarchical mesh, per-host sharded train loader, full-val-on-every-host
validation with the count divisor, chief-only checkpointing — on
synthetic data, and prints per-epoch metrics for cross-rank comparison.

Usage: python _multihost_fit_worker.py <port> <rank> <outdir> [world_size]
"""

import os
import sys


def main():
    port, rank, outdir = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    world = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    os.chdir(outdir)
    rankdir = os.path.join(outdir, f"rank{rank}")
    os.makedirs(rankdir, exist_ok=True)
    os.chdir(rankdir)

    from dptpu.config import parse_config
    from dptpu.train import fit

    # capture the mesh fit() ACTUALLY builds (dptpu/train/plan.py makes
    # it) so the host-major hierarchical ordering is asserted
    # end-to-end, not on a replica
    from dptpu.train import plan as fit_mod

    real_make_mesh = fit_mod.make_mesh
    captured = {}

    def capturing_make_mesh(*a, **k):
        captured["mesh"] = real_make_mesh(*a, **k)
        return captured["mesh"]

    fit_mod.make_mesh = capturing_make_mesh

    cfg = parse_config(
        [
            "synthetic:128", "-a", "resnet18", "-b", "16", "--epochs", "2",
            "--lr", "0.01", "-j", "2",
            "--dist-url", f"tcp://127.0.0.1:{port}",
            "--world-size", str(world), "--rank", str(rank),
        ],
        variant="ddp",
    )
    result = fit(cfg, image_size=32, verbose=False)
    mesh = captured.get("mesh")
    if mesh is not None:
        flat = list(mesh.devices.reshape(-1))
        procs = [d.process_index for d in flat]
        host_major = procs == sorted(procs) and len(set(procs)) == world
        print(f"RANK{rank} MESH host_major={host_major} procs={procs}",
              flush=True)
    for h in result["history"]:
        print(
            f"RANK{rank} EPOCH{h['epoch']} "
            f"loss={h['train_loss']:.6f} top1={h['train_top1']:.4f} "
            f"vloss={h['val_loss']:.6f} vcount={h['val_count']:.1f}",
            flush=True,
        )
    print(f"RANK{rank} CKPT {os.path.exists('checkpoint.pth.tar')}", flush=True)


if __name__ == "__main__":
    main()
