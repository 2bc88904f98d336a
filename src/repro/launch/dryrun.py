import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:  jax.jit(step, in_shardings, out_shardings).lower(*specs)
                .compile()  on the 16x16 single-pod mesh and the 2x16x16
multi-pod mesh, then record memory_analysis / cost_analysis / parsed
collective traffic into a JSON results file consumed by EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --cell train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import time
import traceback

import jax

from repro.analysis.roofline import V5E, analyze_compiled
from repro.configs import ASSIGNED, get_arch
from repro.configs.base import TransformerConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SkippedCell, build_cell


def _lower_compile(prog, mesh):
    from repro.distributed.sharding import to_named
    with mesh:
        jitted = jax.jit(
            prog.fn,
            in_shardings=to_named(prog.in_specs, mesh),
            out_shardings=(to_named(prog.out_specs, mesh)
                           if prog.out_specs is not None else None),
            donate_argnums=prog.donate or (),
        )
        lowered = jitted.lower(*prog.args)
        return lowered.compile()


def _probe_terms(compiled):
    from repro.analysis.hlo import collective_summary
    ca = compiled.cost_analysis()
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            float(collective_summary(compiled.as_text())
                  ["total_traffic_bytes"]))


def run_cell(arch_name: str, cell_name: str, *, multi_pod: bool = False,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    t0 = time.perf_counter()
    prog = build_cell(arch_name, cell_name, mesh)

    compiled = _lower_compile(prog, mesh)
    t_compile = time.perf_counter() - t0
    t_lower = 0.0

    # the production meshes model v5e pods (16x16 chips per pod)
    roof = analyze_compiled(arch_name, cell_name, mesh_name, chips,
                            compiled, prog.model_flops, device_kind=V5E)

    # --- scan-cost correction (LM cells): XLA cost_analysis counts a
    # while-loop body once, so a scanned L-layer program under-reports by
    # ~L. Probe with 1- and 2-layer UNROLLED variants; the delta is one
    # layer's true (flops, bytes, collective) cost.
    arch_cfg = get_arch(arch_name).config
    if isinstance(arch_cfg, TransformerConfig) and arch_cfg.n_layers > 2:
        p1 = build_cell(arch_name, cell_name, mesh, layer_mode="unroll",
                        n_layers_override=1)
        p2 = build_cell(arch_name, cell_name, mesh, layer_mode="unroll",
                        n_layers_override=2)
        f1, b1, c1 = _probe_terms(_lower_compile(p1, mesh))
        f2, b2, c2 = _probe_terms(_lower_compile(p2, mesh))
        L = arch_cfg.n_layers
        roof.hlo_flops = f1 + (L - 1) * max(f2 - f1, 0.0)
        roof.hlo_bytes = b1 + (L - 1) * max(b2 - b1, 0.0)
        roof.collective_bytes = c1 + (L - 1) * max(c2 - c1, 0.0)
        roof.collectives["scan_corrected"] = True

    rec = roof.to_dict()
    rec.update({"step": prog.step_name, "lower_s": t_lower,
                "compile_s": t_compile, "status": "ok"})
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception:
        pass
    if verbose:
        gb = rec.get("memory_analysis", {})
        arg_gb = gb.get("argument_size_in_bytes", 0) / 2**30
        tmp_gb = gb.get("temp_size_in_bytes", 0) / 2**30
        print(f"[{mesh_name}] {arch_name}/{cell_name} ({prog.step_name}) "
              f"OK  lower {t_lower:.1f}s compile {t_compile:.1f}s | "
              f"args {arg_gb:.2f} GiB temp {tmp_gb:.2f} GiB (per dev) | "
              f"bottleneck={rec['bottleneck']} "
              f"t=({rec['t_compute']:.2e},{rec['t_memory']:.2e},"
              f"{rec['t_collective']:.2e})s mfu_bound={rec['mfu_bound']:.3f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    records = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    targets = []
    if args.all:
        for a in ASSIGNED:
            for c in get_arch(a).shapes:
                targets.append((a, c.name))
    else:
        arch = args.arch
        cells = ([args.cell] if args.cell
                 else [c.name for c in get_arch(arch).shapes])
        targets = [(arch, c) for c in cells]

    for multi_pod in meshes:
        for a, c in targets:
            try:
                records.append(run_cell(a, c, multi_pod=multi_pod))
            except SkippedCell as e:
                print(f"[{'2x16x16' if multi_pod else '16x16'}] SKIP {e}")
                records.append({"arch": a, "cell": c, "status": "skip",
                                "mesh": "2x16x16" if multi_pod else "16x16",
                                "reason": str(e)})
            except Exception as e:
                traceback.print_exc()
                records.append({"arch": a, "cell": c, "status": "error",
                                "mesh": "2x16x16" if multi_pod else "16x16",
                                "error": f"{type(e).__name__}: {e}"})

    if args.out:
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        key = lambda r: (r["arch"], r["cell"], r.get("mesh"))
        merged = {key(r): r for r in existing}
        for r in records:
            merged[key(r)] = r
        with open(args.out, "w") as f:
            json.dump(list(merged.values()), f, indent=1)
        print(f"wrote {len(merged)} records -> {args.out}")
    n_err = sum(1 for r in records if r.get("status") == "error")
    print(f"done: {len(records)} cells, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
