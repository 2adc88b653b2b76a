import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede any jax import: jax locks the device count on first init,
# and 512 fake CPU devices stand in for the production meshes (this tool
# never opens an accelerator; its children inherit both variables).
"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes with ShapeDtypeStruct stand-ins (no allocation).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all        # full matrix
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both

Per cell this prints ``compiled.memory_analysis()`` (proves it fits) and
``compiled.cost_analysis()`` (FLOPs/bytes for §Roofline), parses collective
bytes from the HLO, and writes a JSON artifact under artifacts/dryrun/.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import traceback

from repro import hw
from repro.telemetry import now


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    train:        {"state": TrainState, "batch": {...}}
    prefill:      {"params": params, "batch": {...}}
    decode/chunk: {"params": params, "seq_state": SeqState,
                   "tokens": (b, T), "positions": (b, T)} — the one
                  chunk-oriented serve step (decode is T=1, a prefill
                  chunk is T=shape.chunk)
    """
    from repro import train_lib
    from repro.configs.registry import get_arch, get_shape
    from repro.models import build_model
    from repro.optim import AdamW

    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    model = build_model(cfg)
    if shape.kind == "train":
        state = train_lib.abstract_state(model, AdamW())
        return {"state": state, "batch": model.batch_specs(shape)}
    if shape.kind == "prefill":
        return {"params": train_lib.abstract_params(model),
                "batch": model.batch_specs(shape)}
    bspecs = model.batch_specs(shape)
    return {"params": train_lib.abstract_params(model),
            "seq_state": model.seq_state_specs(shape),
            "tokens": bspecs["tokens"], "positions": bspecs["positions"]}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None,
             save_hlo: str | None = None) -> dict:
    import jax
    from jax.sharding import PartitionSpec as P

    from repro import train_lib
    from repro.configs.registry import get_arch, get_shape
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.parallel.spec import make_parallel_config
    from repro.parallel.axes import Resolver

    t0 = now()
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = dict(overrides or {})
    moments_dtype = overrides.pop("moments_dtype", "float32")
    moe_group = overrides.pop("moe_group", None)
    pcfg = make_parallel_config(cfg, shape, dict(mesh.shape),
                                overrides=overrides or None)
    model = build_model(cfg, moe_group=moe_group)
    resolver = Resolver(mesh, pcfg)
    specs = input_specs(arch, shape_name)
    named = lambda t: train_lib.to_named(t, mesh)

    if shape.kind == "train":
        opt = AdamW(moments_dtype=moments_dtype)
        step = train_lib.make_train_step(model, opt, pcfg, mesh)
        sspec = train_lib.state_pspecs(model, pcfg, mesh)
        bspec = train_lib.batch_pspecs(specs["batch"], resolver)
        # rebuild the abstract state with THIS optimizer (moments dtype!)
        state = train_lib.abstract_state(model, opt)
        jitted = jax.jit(step,
                         in_shardings=(named(sspec), named(bspec)),
                         out_shardings=(named(sspec), None),
                         donate_argnums=(0,))
        args = (state, specs["batch"])
    elif shape.kind == "prefill":
        step = train_lib.make_prefill_step(model, pcfg, mesh)
        pspec = train_lib.param_pspecs(model, pcfg, mesh)
        bspec = train_lib.batch_pspecs(specs["batch"], resolver)
        cspec = train_lib.cache_pspecs(model, shape, resolver)
        jitted = jax.jit(step,
                         in_shardings=(named(pspec), named(bspec)),
                         out_shardings=(named(cspec), None))
        args = (specs["params"], specs["batch"])
    else:   # decode / chunk: one chunk of the serve step
        step = train_lib.make_serve_step(model, pcfg, mesh)
        pspec = train_lib.param_pspecs(model, pcfg, mesh)
        cspec = train_lib.seq_state_pspecs(model, shape, resolver)
        tspec = train_lib.batch_pspecs(
            {"tokens": specs["tokens"],
             "positions": specs["positions"]}, resolver)
        jitted = jax.jit(step,
                         in_shardings=(named(pspec), named(cspec),
                                       named(tspec["tokens"]),
                                       named(tspec["positions"])),
                         out_shardings=(named(cspec), None),
                         donate_argnums=(1,))
        args = (specs["params"], specs["seq_state"], specs["tokens"],
                specs["positions"])

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = now() - t0
        compiled = lowered.compile()
        t_compile = now() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_info = {}
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            mem_info[field] = int(getattr(mem, field, 0) or 0)
    print("memory_analysis:", mem_info)

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):     # older jax: one dict per device
        cost = cost[0] if cost else {}
    cost_info = {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float)) and k in
                 ("flops", "bytes accessed", "transcendentals",
                  "utilization operand 0 {}", "bytes accessed output {}")}
    print("cost_analysis:", {k: v for k, v in cost_info.items()})

    hlo = compiled.as_text()
    if save_hlo:
        import zstandard
        with open(save_hlo, "wb") as f:
            f.write(zstandard.compress(hlo.encode()))
    hstats = analyze_hlo(hlo, chips_per_pod=256)
    print("hlo_analysis: flops=%.3e bytes=%.3e coll=%.3e cross_pod=%.3e" % (
        hstats["flops"], hstats["bytes"], hstats["collective_total_bytes"],
        hstats["cross_pod_bytes"]))

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "device_kind": hw.DRYRUN_DEVICE_KIND,
        "n_devices": int(len(mesh.devices.flat)),
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "parallel": dataclasses.asdict(pcfg),
        "memory": mem_info,
        # raw XLA numbers (while-bodies counted once — see hlo_cost.py)
        "xla_flops_raw": cost_info.get("flops"),
        "xla_bytes_raw": cost_info.get("bytes accessed"),
        # trip-count-corrected per-chip numbers (roofline inputs)
        "hlo": hstats,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    return result


CELLS_ENV = "REPRO_DRYRUN_CELL"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="both")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--override", default="",
                    help="';'-separated k=json ParallelConfig overrides, "
                         "e.g. 'seq_shard=true;batch_axes=[\"pod\",\"data\"]'")
    ap.add_argument("--tag", default="", help="artifact suffix (perf loop)")
    ap.add_argument("--save-hlo", action="store_true",
                    help="also write the compiled HLO (zstd) next to the "
                         "JSON artifact")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override.split(";"):
        if "=" in kv:
            k, v = kv.split("=", 1)
            v = json.loads(v)
            overrides[k] = tuple(v) if isinstance(v, list) else v

    os.makedirs(args.out, exist_ok=True)
    pods = {"on": [True], "off": [False], "both": [False, True]}[
        args.multi_pod]

    if args.all:
        from repro.configs.registry import dryrun_cells
        cells = dryrun_cells()
        failures = 0
        for arch, shape in cells:
            for mp in pods:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--multi-pod", "on" if mp else "off",
                       "--out", args.out]
                if args.override:
                    cmd += ["--override", args.override, "--tag", args.tag]
                print(f"=== {arch} x {shape} x "
                      f"{'2x16x16' if mp else '16x16'} ===", flush=True)
                rc = subprocess.run(cmd).returncode
                failures += rc != 0
        print(f"dry-run matrix done, failures={failures}")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    mesh_tag = {True: "2x16x16", False: "16x16"}
    for mp in pods:
        name = f"{args.arch}__{args.shape}__{mesh_tag[mp]}"
        if args.tag:
            name += f"__{args.tag}"
        path = os.path.join(args.out, name + ".json")
        try:
            res = run_cell(args.arch, args.shape, mp, overrides or None,
                           save_hlo=(os.path.join(args.out, name + ".hlo.zst")
                                     if args.save_hlo else None))
        except Exception as e:
            traceback.print_exc()
            res = {"arch": args.arch, "shape": args.shape,
                   "mesh": mesh_tag[mp], "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(("OK   " if res["ok"] else "FAIL ") + name, flush=True)
        if not res["ok"]:
            sys.exit(1)


if __name__ == "__main__":
    main()
