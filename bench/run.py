"""Run one benchmark cell once and print its result record.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

With ``--trace 0`` the record carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Exits non-zero, printing no record, on anything but a TPU or on
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(here), "src"))
    import harness
    import repro  # noqa: F401  the system under test, from the checkout

    cell = harness.load_cell(args.workload)
    import jax
    harness.use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r} "
              f"devices", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness.peaks(devices[0].device_kind)    # an unknown chip stops here
    rec, run = harness.run_once(cell, args.seed, args.seconds,
                                bool(args.trace), T_START)
    harness.report(rec, run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
