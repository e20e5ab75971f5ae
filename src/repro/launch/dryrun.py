import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and extract the roofline terms from the compiled artifact.

For each cell this driver:
  1. builds the production mesh — (16,16) single-pod or (2,16,16) multi-pod;
  2. builds sharded ShapeDtypeStructs for params / optimizer state / inputs /
     KV caches (zero allocation — a 34B-param train state stays symbolic);
  3. jits the right program (train_step / prefill / decode), ``.lower()``s
     and ``.compile()``s it;
  4. records ``memory_analysis()`` (proves the per-device footprint fits),
     ``cost_analysis()`` (FLOPs / bytes for the roofline), and the
     collective schedule parsed from the optimized HLO;
  5. appends one JSON line to the results file.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all            # every cell, subprocess each
  python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import json
import re
import subprocess
import sys
import time

HW = {  # TPU v5e per chip (assignment constants)
    "peak_flops_bf16": 197e12,
    "hbm_bw": 819e9,
    "ici_bw": 50e9,  # per link; we take the single-link figure (DESIGN.md §8)
}

def count_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts from the symbolic defs."""
    from repro.models.model import build_model
    from repro.models.params import param_count

    model = build_model(cfg)
    total = param_count(model.param_defs)
    active = total
    if cfg.num_experts:
        # replace per-layer expert params with top-k worth of experts
        from repro.models.params import param_count as pc

        expert_per_layer = 3 * cfg.d_model * cfg.moe_d_ff * cfg.num_experts
        active_expert = 3 * cfg.d_model * cfg.moe_d_ff * cfg.num_experts_per_tok
        active = total - cfg.num_layers * (expert_per_layer - active_expert)
    return total, active


def model_flops(cfg, shape) -> float:
    """6*N*D (train) / 2*N*D (inference) with N = active params."""
    _, active = count_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else (shape.seq_len if shape.kind == "prefill" else 1))
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * active * tokens


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_path: str | None) -> dict:
    import jax

    from repro.configs import applicable_shapes, get_arch, get_shape, shape_skip_reason
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import build_model
    from repro.models.params import param_structs
    from repro.optim import AdamWConfig, cosine_schedule
    from repro.sharding.specs import decode_rules, infer_rules, train_rules
    from repro.training.train_step import make_train_state_defs, make_train_step

    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    skip = shape_skip_reason(cfg, shape_name)
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "kind": shape.kind,
    }
    if skip:
        record.update(status="skipped", reason=skip)
        if out_path:
            with open(out_path, "a") as f:
                f.write(json.dumps(record) + "\n")
        return record

    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    if shape.kind == "decode":
        rules = decode_rules(mesh, kv_heads=cfg.num_kv_heads or None, batch=shape.global_batch)
    elif shape.kind == "prefill":
        rules = infer_rules(mesh, kv_heads=cfg.num_kv_heads or None)
    else:
        rules = train_rules(mesh)
    model = build_model(cfg, rules)

    with mesh:
        if shape.kind == "train":
            defs = make_train_state_defs(model)
            state_structs = param_structs(defs, mesh, rules)
            batch_structs = param_structs(model.input_defs(shape), mesh, rules)
            step = make_train_step(model, AdamWConfig(), cosine_schedule(3e-4, 100, 10000))
            lowered = jax.jit(step, donate_argnums=0).lower(state_structs, batch_structs)
        elif shape.kind == "prefill":
            p_structs = param_structs(model.param_defs, mesh, rules)
            in_structs = param_structs(model.input_defs(shape), mesh, rules)
            lowered = jax.jit(model.prefill_fn).lower(p_structs, in_structs)
        else:  # decode
            p_structs = param_structs(model.param_defs, mesh, rules)
            in_structs = param_structs(model.input_defs(shape), mesh, rules)
            cache_structs = param_structs(model.cache_defs(shape), mesh, rules)
            lowered = jax.jit(model.decode_fn, donate_argnums=2).lower(p_structs, in_structs, cache_structs)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    from repro.launch.hlo_analysis import analyze

    ca = compiled.cost_analysis() or {}
    raw_flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))
    summary = analyze(compiled.as_text())  # loop-aware (trip-count-scaled)
    flops = summary.flops
    bytes_accessed = summary.bytes
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }
    # CPU backend's peak stat can be unreliable; the conservative footprint
    # is arguments (resident params/opt/caches) + temp arena + outputs.
    footprint = mem["argument_bytes"] + mem["temp_bytes"] + mem["output_bytes"]
    # Per-device param residency (from the sharded struct shapes).
    import numpy as _np

    def _dev_bytes(struct):
        shard = struct.sharding.shard_shape(struct.shape)
        return int(_np.prod(shard)) * struct.dtype.itemsize

    if shape.kind == "train":
        p_structs_for_count = param_structs(model.param_defs, mesh, rules)
    else:
        p_structs_for_count = p_structs
    params_dev = sum(_dev_bytes(s) for s in jax.tree.leaves(p_structs_for_count))
    # TPU estimate for inference programs: XLA:CPU materializes every scan-xs
    # layer slice (~2x params of dead temp); XLA:TPU windows into the stacked
    # buffer instead. Documented in EXPERIMENTS.md §Dry-run.
    if shape.kind in ("prefill", "decode") and cfg.num_layers > 1:
        tpu_est = footprint - int(2 * params_dev * (1 - 1.0 / cfg.num_layers))
        tpu_est = max(tpu_est, mem["argument_bytes"] + mem["output_bytes"])  # floor: live buffers
    else:
        tpu_est = footprint

    mf = model_flops(cfg, shape)
    total_params, active_params = count_params(cfg)
    compute_s = flops / HW["peak_flops_bf16"]
    memory_s = bytes_accessed / HW["hbm_bw"]
    collective_s = summary.collective_bytes / HW["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    record.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops_per_device=flops,
        bytes_per_device=bytes_accessed,
        raw_cost_analysis={"flops": raw_flops, "bytes": raw_bytes,
                           "note": "while-bodies counted once by XLA; see corrected fields"},
        collectives={
            "total_bytes": summary.collective_bytes,
            **summary.collective_detail,
        },
        while_trips=summary.while_trips,
        memory=mem,
        hbm_per_device_gb=round(footprint / 2**30, 3),
        fits_16gb=footprint < 16 * 2**30,
        params_bytes_per_device=params_dev,
        hbm_tpu_estimate_gb=round(tpu_est / 2**30, 3),
        fits_16gb_tpu_est=tpu_est < 16 * 2**30,
        params_total=total_params,
        params_active=active_params,
        model_flops_global=mf,
        model_flops_per_device=mf / n_chips,
        useful_flops_ratio=(mf / n_chips) / flops if flops else 0.0,
        roofline={
            **{k: round(v, 6) for k, v in terms.items()},
            "dominant": dominant,
            "bound_s": round(max(terms.values()), 6),
        },
    )
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(record) + "\n")
    return record


def all_cells(multi_pod: bool):
    from repro.configs import ARCHS, applicable_shapes

    for arch, cfg in ARCHS.items():
        for shape_name in ["train_4k", "prefill_32k", "decode_32k", "long_500k"]:
            yield arch, shape_name


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    if args.all:
        done = set()
        if args.skip_done and os.path.exists(args.out):
            with open(args.out) as f:
                for line in f:
                    try:
                        r = json.loads(line)
                        done.add((r["arch"], r["shape"], r["mesh"]))
                    except json.JSONDecodeError:
                        continue
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for arch, shape_name in all_cells(args.multi_pod):
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                if (arch, shape_name, mesh_name) in done:
                    print(f"[skip-done] {arch} {shape_name} {mesh_name}", flush=True)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch, "--shape", shape_name, "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                print(f"[cell] {arch} {shape_name} {mesh_name}", flush=True)
                t0 = time.perf_counter()
                try:
                    proc = subprocess.run(cmd, timeout=args.timeout, capture_output=True, text=True)
                    if proc.returncode != 0:
                        err = (proc.stderr or "").strip().splitlines()
                        msg = err[-1] if err else f"exit {proc.returncode}"
                        with open(args.out, "a") as f:
                            f.write(json.dumps({"arch": arch, "shape": shape_name, "mesh": mesh_name, "status": "error", "reason": msg[-500:]}) + "\n")
                        print(f"  ERROR: {msg[-200:]}", flush=True)
                except subprocess.TimeoutExpired:
                    with open(args.out, "a") as f:
                        f.write(json.dumps({"arch": arch, "shape": shape_name, "mesh": mesh_name, "status": "timeout"}) + "\n")
                    print("  TIMEOUT", flush=True)
                print(f"  done in {time.perf_counter()-t0:.0f}s", flush=True)
        return

    record = run_cell(args.arch, args.shape, args.multi_pod, args.out)
    print(json.dumps(record, indent=2))


if __name__ == "__main__":
    main()
