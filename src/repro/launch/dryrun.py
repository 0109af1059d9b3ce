import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # 512 host placeholders, never a TPU

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST precede any jax import: jax locks the device count
at first init, and the production meshes need 512 host placeholder devices.
(Smoke tests and benches never import this module — they see 1 device.)

Usage:
    python -m repro.launch.dryrun --arch granite-34b --shape train_4k
    python -m repro.launch.dryrun --arch granite-34b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # driver: subprocess per cell
    python -m repro.launch.dryrun --report         # render EXPERIMENTS tables

Each cell writes reports/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, collective-byte breakdown and the three
roofline terms; the sweep is resumable (existing JSONs are skipped).
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPORT_DIR = Path(__file__).resolve().parents[3] / "reports" / "dryrun"


def _compile_spec(spec):
    import jax

    t0 = time.time()
    jitted = jax.jit(spec.fn, donate_argnums=spec.donate_argnums)
    lowered = jitted.lower(*spec.args)
    t_lower = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1
    cost_list = compiled.cost_analysis()
    cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
    return lowered, compiled, dict(cost), t_lower, t_compile


def run_cell(arch_id: str, shape: str, multi_pod: bool,
             rules_name: str = "default", arch_obj=None) -> dict:
    from repro.configs import get_arch
    from repro.dist.tuned import get_rules
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import analyze, collective_bytes_from_hlo

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    rules = get_rules(rules_name, mesh)
    arch = arch_obj if arch_obj is not None else get_arch(arch_id)
    cell = arch.cells()[shape]

    spec = arch.build(shape, mesh, rules)
    with mesh:
        lowered, compiled, cost, t_lower, t_compile = _compile_spec(spec)
        spmd_hlo = compiled.as_text()  # post-partitioning: collectives visible

        flops_pd = float(cost.get("flops", 0.0))
        bytes_pd = float(cost.get("bytes accessed", 0.0))
        col_pd, col_by_op = collective_bytes_from_hlo(spmd_hlo)
        probes = None

        # lax.scan bodies are cost-counted once; extrapolate per-layer cost
        # from two UNROLLED probe compiles (exact for identical layers).
        # Probes run on the single-pod mesh only: the multi-pod pass proves
        # the 'pod' axis shards; the roofline table is single-pod (§Roofline).
        if hasattr(arch, "cost_probe_configs") and not multi_pod:
            probe_cfgs, n_layers = arch.cost_probe_configs(shape)
            vals = []
            for l, cfg_l in probe_cfgs:
                spec_l = arch.build(shape, mesh, rules, cfg=cfg_l)
                _, comp_l, cost_l, _, _ = _compile_spec(spec_l)
                cb_l, _ = collective_bytes_from_hlo(comp_l.as_text())
                vals.append((l, float(cost_l.get("flops", 0.0)),
                             float(cost_l.get("bytes accessed", 0.0)), cb_l))
            (l2, f2, b2, c2), (l4, f4, b4, c4) = vals
            dl = l4 - l2
            flops_pd = f2 + (n_layers - l2) * (f4 - f2) / dl
            bytes_pd = b2 + (n_layers - l2) * (b4 - b2) / dl
            col_pd = c2 + (n_layers - l2) * (c4 - c2) / dl
            probes = {"l2": [f2, b2, c2], "l4": [f4, b4, c4],
                      "n_layers": n_layers}

        # fori_loop corrections (MMR) — analytic, per device
        if hasattr(arch, "cost_corrections"):
            ef, eb = arch.cost_corrections(shape, chips)
            flops_pd += ef
            bytes_pd += eb

    mem = compiled.memory_analysis()
    mem_stats = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_stats[k] = float(v)

    model_flops = arch.model_flops(shape)

    rep = analyze(
        arch_id, shape, mesh_name, chips, cost, spmd_hlo,
        model_flops=model_flops, memory_stats=mem_stats,
        flops_override=flops_pd, bytes_override=bytes_pd,
        collective_override=col_pd, collective_by_op=col_by_op,
    )
    out = rep.to_dict()
    out.update({
        "rules": rules_name,
        "skip_reason": cell.skip_reason,
        "beyond_assignment": cell.beyond_assignment,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "probes": probes,
        "cost_raw": {k: float(v) for k, v in cost.items()
                     if isinstance(v, (int, float))},
    })
    return out


def cell_list(include_beyond: bool = True):
    from repro.configs import ASSIGNED, get_arch

    assigned, beyond = [], []
    arch_ids = ASSIGNED + ["flexvec"]
    for aid in arch_ids:
        arch = get_arch(aid)
        for shape, cell in arch.cells().items():
            if cell.beyond_assignment or cell.skip_reason or aid == "flexvec":
                if include_beyond and (not cell.skip_reason or cell.beyond_assignment):
                    beyond.append((aid, shape))
                continue
            assigned.append((aid, shape))
    return assigned + beyond


def drive_all(multi_pod_too: bool = True, rules_name: str = "default",
              timeout: int = 7200) -> None:
    """Subprocess per cell: crash isolation + fresh memory + resumability."""
    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    cells = cell_list()
    meshes = [False, True] if multi_pod_too else [False]
    todo = []
    for aid, shape in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            suffix = "" if rules_name == "default" else f"__{rules_name}"
            path = REPORT_DIR / f"{aid}__{shape}__{mesh_name}{suffix}.json"
            if path.exists():
                continue
            todo.append((aid, shape, mp, path))
    print(f"[dryrun] {len(todo)} cells to run", flush=True)
    for i, (aid, shape, mp, path) in enumerate(todo):
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", aid, "--shape", shape, "--rules", rules_name,
               "--out", str(path)]
        if mp:
            cmd.append("--multi-pod")
        print(f"[dryrun {i+1}/{len(todo)}] {aid}/{shape} "
              f"mesh={'2x16x16' if mp else '16x16'}", flush=True)
        t = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
        if r.returncode != 0:
            err = {"arch": aid, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "error": r.stderr[-4000:]}
            path.write_text(json.dumps(err, indent=2))
            print(f"  FAILED in {time.time()-t:.0f}s: {r.stderr.splitlines()[-1] if r.stderr else '?'}",
                  flush=True)
        else:
            print(f"  ok in {time.time()-t:.0f}s", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rules", default="default")
    ap.add_argument("--out")
    args = ap.parse_args()

    if args.all:
        drive_all(rules_name=args.rules)
        return

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    out = run_cell(args.arch, args.shape, args.multi_pod, args.rules)
    text = json.dumps(out, indent=2, default=str)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)


if __name__ == "__main__":
    main()
