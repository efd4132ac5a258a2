import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell on the production mesh, record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --list
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-20b \
        --shape train_4k --mesh single --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --driver --out results/dryrun
        (driver: one subprocess per remaining cell; resumable)

The very first lines above set the 512-device host platform BEFORE any jax
import — jax locks the device count on first init.  Nothing else in the
repo sets this flag (tests and benchmarks see 1 device).
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def _cell_key(arch: str, shape: str, mesh_kind: str) -> str:
    return f"{arch}__{shape}__{mesh_kind}".replace("/", "_")


def list_cells():
    from repro.configs import all_cells

    rows = []
    for c in all_cells():
        rows.append((c.arch, c.shape, c.family, c.kind, c.skip_reason))
    return rows


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str) -> dict:
    import jax

    from repro.configs import get_cell
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import collective_bytes, model_flops, roofline_terms

    multi_pod = mesh_kind == "multi"
    cell = get_cell(arch, shape)
    rec: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "family": cell.family, "kind": cell.kind,
        "n_devices": 512 if multi_pod else 256,
        "time": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    if cell.skip_reason is not None:
        rec["status"] = "skipped"
        rec["skip_reason"] = cell.skip_reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    if multi_pod:
        n_mesh_devices = 512
    else:
        n_mesh_devices = 256
    t0 = time.time()

    def to_shardings(tree):
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )

    with jax.set_mesh(mesh):
        if cell.make_mesh_step is not None:
            step, args = cell.make_mesh_step(mesh, multi_pod)
            lowered = step.lower(*args)
        else:
            args = cell.input_specs()
            in_shardings = to_shardings(cell.in_shardings(multi_pod))
            kwargs = {}
            if cell.out_shardings is not None:
                kwargs["out_shardings"] = to_shardings(
                    cell.out_shardings(multi_pod)
                )
            step = jax.jit(cell.step_fn, in_shardings=in_shardings, **kwargs)
            lowered = step.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    # ---- memory analysis (proves it fits) ----
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(ma, "generated_code_size_in_bytes", 0)
            ),
        }
        ma_total = (
            rec["memory_analysis"]["argument_bytes"]
            + rec["memory_analysis"]["output_bytes"]
            + rec["memory_analysis"]["temp_bytes"]
        )
        rec["memory_analysis"]["total_bytes"] = ma_total
        rec["bytes_per_device"] = ma_total  # partitioned module = per-device
    except Exception as e:  # pragma: no cover
        rec["memory_analysis_error"] = repr(e)

    # ---- cost analysis (FLOPs / bytes for the roofline) ----
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed", 0.0))
        rec["cost_analysis"] = {
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
    except Exception as e:  # pragma: no cover
        rec["cost_analysis_error"] = repr(e)
        flops, bytes_accessed = 0.0, 0.0

    # ---- collective bytes from the partitioned HLO ----
    try:
        hlo = compiled.as_text()
        cb = collective_bytes(hlo)
        rec["collective_bytes"] = cb
        rec["hlo_collective_counts"] = {
            k: hlo.count(f" {k}(") for k in
            ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
        }
    except Exception as e:  # pragma: no cover
        rec["collective_error"] = repr(e)
        cb = {"total": 0}

    # ---- two-point loop-analysis correction (LM cells) ----
    # XLA's cost analysis counts a lax.scan body ONCE; the layer stack runs
    # n_layers times.  We compile two small UNROLLED variants (L=2, L=4,
    # inner attention un-chunked) of the same cell and extrapolate:
    #   body = (m4 - m2) / 2 ;  outside = m2 - 2*body ;
    #   corrected_L = outside + L * body
    # Validated against a fully-unrolled granite-20b compile (ratio within
    # a few %).  GNN/recsys/index cells have no layer scan → no correction.
    corrected = None
    if cell.family == "lm" and cell.make_for_cfg is not None:
        import dataclasses as _dc

        from repro.configs.common import LM_SHAPES

        seq = LM_SHAPES[cell.shape]["seq"]
        probe_metrics = {}
        for l_probe in (2, 4):
            vcfg = _dc.replace(
                cell.model_cfg, n_layers=l_probe, scan_unroll=l_probe,
                kv_chunk=max(seq, cell.model_cfg.kv_chunk),
            )
            vstep, vspecs, vshard, _, _, vouts = cell.make_for_cfg(vcfg)
            vkwargs = {}
            if vouts is not None:
                vkwargs["out_shardings"] = to_shardings(vouts(multi_pod))
            with jax.set_mesh(mesh):
                vlow = jax.jit(
                    vstep, in_shardings=to_shardings(vshard(multi_pod)),
                    **vkwargs,
                ).lower(*vspecs())
                vcomp = vlow.compile()
            vca = vcomp.cost_analysis()
            if isinstance(vca, (list, tuple)):
                vca = vca[0]
            vcb = collective_bytes(vcomp.as_text())
            probe_metrics[l_probe] = {
                "flops": float(vca.get("flops", 0.0)),
                "bytes": float(vca.get("bytes accessed", 0.0)),
                "coll": float(vcb.get("total", 0)),
            }
        l_full = cell.model_cfg.n_layers
        corrected = {}
        for name, key in (("flops", "flops"), ("bytes", "bytes"),
                          ("coll", "coll")):
            m2 = probe_metrics[2][key]
            m4 = probe_metrics[4][key]
            body = (m4 - m2) / 2.0
            outside = m2 - 2.0 * body
            corrected[name] = max(outside + l_full * body, 0.0)
        rec["analysis_correction"] = {
            "probe_L2": probe_metrics[2], "probe_L4": probe_metrics[4],
            "corrected": corrected,
        }
        flops = max(flops, corrected["flops"])
        bytes_accessed = max(bytes_accessed, corrected["bytes"])
        cb = dict(cb)
        cb["total"] = max(float(cb.get("total", 0)), corrected["coll"])

    # ---- roofline ----
    terms = roofline_terms(
        flops_per_device=flops,
        bytes_per_device=bytes_accessed,
        collective_bytes_per_device=float(cb.get("total", 0)),
    )
    rec["roofline"] = terms
    mf = model_flops(cell)
    if mf is not None:
        rec["model_flops_global"] = mf
        hlo_flops_global = flops * n_mesh_devices
        rec["model_to_hlo_flops"] = (
            mf / hlo_flops_global if hlo_flops_global else None
        )
    rec["status"] = "ok"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--driver", action="store_true",
                    help="subprocess per remaining cell (resumable)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()

    if args.list:
        for arch, shape, family, kind, skip in list_cells():
            flag = f"SKIP({skip})" if skip else ""
            print(f"{arch:28s} {shape:16s} {family:8s} {kind:8s} {flag}")
        return

    os.makedirs(args.out, exist_ok=True)

    if args.driver:
        from repro.configs import all_cells

        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        todo = []
        for c in all_cells():
            for mk in meshes:
                key = _cell_key(c.arch, c.shape, mk)
                path = os.path.join(args.out, key + ".json")
                if os.path.exists(path) and not args.force:
                    continue
                todo.append((c.arch, c.shape, mk))
        print(f"driver: {len(todo)} cells to run")
        for i, (arch, shape, mk) in enumerate(todo):
            print(f"[{i + 1}/{len(todo)}] {arch}/{shape} mesh={mk}",
                  flush=True)
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--mesh", mk,
                "--out", args.out,
            ]
            try:
                proc = subprocess.run(
                    cmd, timeout=args.timeout, capture_output=True, text=True
                )
                if proc.returncode != 0:
                    key = _cell_key(arch, shape, mk)
                    with open(os.path.join(args.out, key + ".json"), "w") as fh:
                        json.dump({
                            "arch": arch, "shape": shape, "mesh": mk,
                            "status": "error",
                            "stderr": proc.stderr[-4000:],
                        }, fh, indent=2)
                    print(f"   ERROR (recorded): {proc.stderr[-400:]}")
                else:
                    print("   ok")
            except subprocess.TimeoutExpired:
                key = _cell_key(arch, shape, mk)
                with open(os.path.join(args.out, key + ".json"), "w") as fh:
                    json.dump({
                        "arch": arch, "shape": shape, "mesh": mk,
                        "status": "timeout",
                    }, fh, indent=2)
                print("   TIMEOUT (recorded)")
        return

    assert args.arch and args.shape, "--arch and --shape required"
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        key = _cell_key(args.arch, args.shape, mk)
        path = os.path.join(args.out, key + ".json")
        if os.path.exists(path) and not args.force:
            print(f"skip existing {path}")
            continue
        try:
            rec = run_cell(args.arch, args.shape, mk, args.out)
        except Exception:
            rec = {
                "arch": args.arch, "shape": args.shape, "mesh": mk,
                "status": "error", "traceback": traceback.format_exc(),
            }
        with open(path, "w") as fh:
            json.dump(rec, fh, indent=2)
        status = rec.get("status")
        print(f"{key}: {status}")
        if status == "ok":
            r = rec["roofline"]
            print(
                f"  compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                f"collective={r['collective_s']:.3e}s dominant={r['dominant']}"
            )
        elif status == "error":
            print(rec.get("traceback", "")[-2000:])
            sys.exit(1)


if __name__ == "__main__":
    main()
