import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes, with ShapeDtypeStruct stand-ins (no device
allocation), and record memory/cost/collective analysis for §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.archs.base import get_arch  # noqa: E402
from repro.distributed.meshinfo import MeshInfo  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline.collectives import collective_bytes_from_hlo  # noqa: E402


def dryrun_cell(arch_name: str, shape: str, *, multi_pod: bool, out_dir: str | None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mi = MeshInfo(mesh=mesh)
    arch = get_arch(arch_name)
    cell = arch.make_cell(shape, mi)

    in_shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        cell.in_specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            cell.fn,
            in_shardings=in_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    record = {
        "cell": cell.name,
        "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "note": cell.note,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {
            "flops": cost.get("flops") if cost else None,
            "bytes_accessed": cost.get("bytes accessed") if cost else None,
            "transcendentals": cost.get("transcendentals") if cost else None,
        },
        "collectives": coll,
    }
    print(f"=== {cell.name} @ {record['mesh']} ===")
    print("memory_analysis:", mem)
    print(
        "cost_analysis: flops={flops} bytes={bytes_accessed}".format(**record["cost"])
    )
    print("collective_bytes:", json.dumps(coll["per_op_bytes"], indent=None))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{cell.name.replace(':', '_')}_{record['mesh'].replace('x', '-')}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(record, f, indent=2)
    return record


ALL_CELLS = None  # filled lazily from the registry


def all_cells():
    from repro.configs import ASSIGNED

    cells = []
    for a in ASSIGNED + ("airship-sift1m",):
        arch = get_arch(a)
        for s in arch.shape_names():
            cells.append((a, s))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    targets = all_cells() if args.all else [(args.arch, args.shape)]
    failures = []
    for arch_name, shape in targets:
        for mp in meshes:
            try:
                dryrun_cell(arch_name, shape, multi_pod=mp, out_dir=args.out)
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                failures.append((arch_name, shape, mp, repr(e)))
                print(f"FAILED {arch_name}:{shape} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
