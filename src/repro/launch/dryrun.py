"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analyses.

  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-20b \
      --shape train_4k --mesh both --out experiments/dryrun

Run as a script, it forces 512 host devices before jax is imported (the
device count locks at first init).  Importing the module sets nothing.
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ARCH_IDS, all_cells, get_arch
from repro.configs.common import shardings
from repro.launch.mesh import make_production_mesh

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

# effective wire-byte multiplier per collective kind (ring algorithms)
_WIRE_FACTOR = {
    "all-reduce": 2.0,       # reduce-scatter + all-gather phases
    "all-gather": 1.0,       # result bytes received
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-device collective bytes by op kind, parsed from optimized HLO.
    Shapes in the post-SPMD module are per-device (local) shapes; '-done' ops
    are skipped so async pairs count once."""
    by_kind: dict = {}
    count = 0
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done." in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_type, kind = m.group(1), m.group(2)
        b = _shape_bytes(result_type)
        wire = b * _WIRE_FACTOR[kind]
        by_kind.setdefault(kind, dict(ops=0, result_bytes=0, wire_bytes=0.0))
        by_kind[kind]["ops"] += 1
        by_kind[kind]["result_bytes"] += b
        by_kind[kind]["wire_bytes"] += wire
        count += 1
    total_wire = sum(k["wire_bytes"] for k in by_kind.values())
    return {"ops": count, "by_kind": by_kind, "wire_bytes": total_wire}


def run_cell(
    arch_id: str,
    shape: str,
    multi_pod: bool,
    verbose: bool = True,
    hlo_path: str | None = None,
) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = get_arch(arch_id)
    t0 = time.time()
    cell = arch.build_cell(shape, mesh)
    in_sh = shardings(mesh, cell.in_specs)
    out_sh = (
        shardings(mesh, cell.out_specs) if cell.out_specs is not None else None
    )
    with mesh:
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            if hasattr(ma, k):
                mem[k] = int(getattr(ma, k))
        if mem:
            mem["peak_bytes_per_device"] = (
                mem.get("argument_size_in_bytes", 0)
                + mem.get("output_size_in_bytes", 0)
                + mem.get("temp_size_in_bytes", 0)
                - mem.get("alias_size_in_bytes", 0)
            )
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = repr(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in ca:
                cost[k] = float(ca[k])
    except Exception as e:
        cost["error"] = repr(e)

    hlo_text = compiled.as_text()
    coll = collective_stats(hlo_text)
    if hlo_path is not None:
        import gzip

        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo_text)

    record = {
        "cell": cell.name,
        "arch": arch_id,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost": cost,
        "collectives": coll,
        "meta": cell.meta,
    }
    if verbose:
        print(
            f"[dryrun] {cell.name} mesh={record['mesh']}: "
            f"compile {t_compile:.1f}s, "
            f"flops/dev {cost.get('flops', float('nan')):.3e}, "
            f"bytes/dev {cost.get('bytes accessed', float('nan')):.3e}, "
            f"wire/dev {coll['wire_bytes']:.3e} ({coll['ops']} collectives)"
        )
        if "peak_bytes_per_device" in mem:
            print(
                f"         args {mem['argument_size_in_bytes']/2**30:.2f} GiB"
                f" + temp {mem['temp_size_in_bytes']/2**30:.2f} GiB"
                f" + out {mem['output_size_in_bytes']/2**30:.2f} GiB per device"
            )
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch != "all":
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape != "all":
        cells = [c for c in cells if c[1] == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch_id, shape in cells:
        for multi in meshes:
            tag = f"{arch_id}__{shape}__{'multi' if multi else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[dryrun] skip (exists): {tag}")
                continue
            try:
                rec = run_cell(
                    arch_id, shape, multi,
                    hlo_path=os.path.join(args.out, tag + ".hlo.txt.gz"),
                )
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception:
                failures.append(tag)
                traceback.print_exc()
                with open(path + ".failed", "w") as f:
                    f.write(traceback.format_exc())
    if failures:
        print(f"[dryrun] FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all requested cells compiled OK")


if __name__ == "__main__":
    main()
