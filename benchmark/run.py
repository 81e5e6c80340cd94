#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Loads, warms up, measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON
object as the last line of standard output. Off the chip, or on fewer
chips than the cell asks for, it prints no result and exits non-zero.
See benchmark/README.md.
"""

import time

STARTED_WALL = time.time()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def require_chips(chips: int) -> None:
    """The cell's chips, on a TPU, or no run: never a quiet CPU result."""
    import jax

    platform = jax.default_backend()
    count = jax.device_count()
    if platform != "tpu" or count != chips:
        print(f"benchmark: this cell runs on {chips} TPU chip(s); jax "
              f"reports platform={platform!r} with {count} device(s). "
              f"Refusing to run.", file=sys.stderr)
        raise SystemExit(3)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    # the compile cache: where the machine says, else at one fixed path in
    # the checkout (the program's own default), so only a checkout's first
    # run of a cell compiles
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from benchmark.lib import cells, drive

    cell = cells.load_cell(args.workload)
    try:
        import dptpu  # noqa: F401
    except ImportError as exc:
        print(f"benchmark: the dptpu package is not in this checkout "
              f"({exc})", file=sys.stderr)
        return 2
    require_chips(cell.chips)
    result = drive.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            STARTED_WALL)
    drive.print_numbers(result["numbers"])
    print(drive.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
