import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before ANY other import: jax locks the
# device count on first init.  The dry-run (and only the dry-run) builds
# the production 16x16 / 2x16x16 meshes out of 512 host devices.

"""Multi-pod dry-run: .lower().compile() every (architecture x input
shape x mesh) cell on the production mesh, record memory/cost analysis +
collective-bytes for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --cells all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --cells stablelm-3b/train_4k
Results are cached per cell in results/dryrun/<cell>__<mesh>.json, so the
run is resumable.
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.launch import cells as cells_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes_from_hlo, roofline_report

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def run_cell(arch: str, shape: str, multi_pod: bool, level_only: bool = False):
    mesh = make_production_mesh(multi_pod=multi_pod)
    kw = {"level_only": True} if level_only else {}
    cell = cells_mod.build_cell(arch, shape, mesh, **kw)
    if cell is None:
        return {"cell": f"{arch}/{shape}", "skipped": True,
                "reason": "long_500k on pure full-attention arch "
                          "(DESIGN.md §Arch-applicability)"}
    t0 = time.time()
    jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings)
    lowered = jitted.lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(mem)    # proves it fits (per-device argument/output/temp bytes)
    print({k: v for k, v in cost.items()
           if k in ("flops", "bytes accessed")})  # FLOPs/bytes for §Roofline
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    out = {
        "cell": cell.label,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)},
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collectives": coll,
        "meta": cell.meta,
    }
    out["roofline"] = roofline_report(out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="all",
                    help="'all', 'bfs', or comma-sep arch/shape ids")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.cells == "all":
        todo = cells_mod.all_cells() + cells_mod.bfs_cells()
    elif args.cells == "bfs":
        todo = cells_mod.bfs_cells()
    else:
        todo = [tuple(c.split("/", 1)) for c in args.cells.split(",")]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(RESULTS, exist_ok=True)
    failures = []
    for arch, shape in todo:
        is_bfs = arch.startswith("bfs")
        for mp in meshes:
            tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
            path = os.path.join(RESULTS, tag + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[cached] {tag}")
                continue
            try:
                # BFS: single-pod run also lowers the level-step (roofline)
                out = run_cell(arch, shape, mp)
                if is_bfs and not mp:
                    lvl = run_cell(arch, shape, mp, level_only=True)
                    out["level_step"] = lvl
                with open(path, "w") as f:
                    json.dump(out, f, indent=1)
                r = out.get("roofline", {})
                print(f"[ok] {tag}: compile={out.get('compile_s')}s "
                      f"flops={out.get('flops', 0):.3g} "
                      f"coll={out.get('collectives', {}).get('total_bytes', 0):.3g}B "
                      f"bound={r.get('dominant', '?')}")
            except Exception as e:
                failures.append((tag, str(e)))
                print(f"[FAIL] {tag}: {e}")
                traceback.print_exc(limit=4)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e.splitlines()[0][:200] if e else "")
        sys.exit(1)
    print("\nDRY-RUN COMPLETE: all cells lowered + compiled.")


if __name__ == "__main__":
    main()
