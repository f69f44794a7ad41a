import os
# while-loop LICM on the CPU placeholder backend hoists per-layer converts /
# repartitions of scan-stacked buffers OUT of the loop, materializing whole
# [L, ...] copies (observed: +2.5× peak memory).  The TPU backend schedules
# these in-loop; disabling the pass makes the CPU memory analysis faithful.
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_disable_hlo_passes=while-loop-invariant-code-motion"
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be imported/run before any other jax-touching import — the two lines
above pin 512 placeholder host devices before jax locks the device count.

Usage (one cell per process; the sweep driver is benchmarks/dryrun_sweep.py):

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch qwen3-1.7b --shape train_4k [--multi-pod] \
        [--out results.jsonl] [--fsdp/--no-fsdp] [--policy fp32|bf16|q8]

Emits one JSON record: compile status, memory_analysis, cost_analysis,
per-kind collective bytes, the three roofline terms, MODEL_FLOPS ratio.
"""
import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fsdp: bool = True, policy: str = "", extra: str = "",
             overrides: str = "") -> dict:
    """``overrides``: comma-separated knobs for §Perf hillclimbing, e.g.
    ``parallelism=fsdp_only,attn_chunk=1024,seq_parallel=1,
    capacity_factor=1.0,residual_budget=2e9,remat=none``."""
    import jax
    from repro.configs import get_config, shapes_for
    from repro.launch import hlo_analysis as HA
    from repro.launch import hlo_static as HS
    from repro.launch import steps as S
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = {s.name: s for s in shapes_for(arch)}.get(shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "fsdp": fsdp, "policy": policy or None, "extra": extra or None}
    if shape is None:
        rec["status"] = "skipped"
        rec["reason"] = ("long_500k needs sub-quadratic attention; "
                         "this is a pure full-attention arch (see DESIGN.md)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    opts = S.default_train_options(cfg)
    if policy:
        opts = S.TrainOptions(**{**opts.__dict__, "opt_state_policy": policy})
    if not fsdp:
        opts = S.TrainOptions(**{**opts.__dict__, "fsdp": False})

    # §Perf knobs
    cfg_over, opt_over = {}, {}
    for kv in (overrides.split(",") if overrides else []):
        k, v = kv.split("=")
        if k in ("parallelism", "opt_state_policy", "grad_accum_dtype"):
            opt_over[k] = v
        elif k in ("microbatch",):
            opt_over[k] = int(v)
        elif k == "residual_budget":
            opt_over[k] = float(v)
        elif k in ("attn_chunk", "loss_chunk", "prefill_chunk"):
            cfg_over[k] = int(v)
        elif k == "seq_parallel":
            cfg_over[k] = bool(int(v))
        elif k == "remat":
            cfg_over[k] = v
        elif k == "capacity_factor":
            cfg_over["moe"] = {**cfg.moe, "capacity_factor": float(v)}
        elif k == "window":
            cfg_over[k] = int(v) if int(v) > 0 else None
        elif k == "moe_sharding":
            cfg_over[k] = v
        else:
            raise KeyError(f"unknown override {k}")
    if cfg_over:
        cfg = cfg.replace(**cfg_over)
    if opt_over:
        opts = S.TrainOptions(**{**opts.__dict__, **opt_over})
    if overrides:
        rec["extra"] = ((extra + ";") if extra else "") + overrides

    t0 = time.time()
    jax.set_mesh(mesh)
    with mesh:
        jitted, args = S.build_jitted(cfg, shape, mesh, opts)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    # static analysis with while-trip multipliers (cost_analysis counts scan
    # bodies once — undercounting by ~n_layers; see hlo_static docstring)
    st = HS.analyze(hlo)
    coll = {"per_kind": st["collective_bytes"],
            "counts": st["collective_counts"],
            "total": st["collective_total"]}
    terms = HA.roofline_terms(
        {"flops": st["flops"], "bytes accessed": st["hbm_bytes"]},
        coll, n_chips)
    n_total = S.est_param_count(cfg)
    n_active = HA.active_param_count(cfg, n_total)
    mflops = HA.model_flops(cfg, shape, n_active)
    hlo_flops_total = terms["hlo_flops_per_chip"] * n_chips

    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
                          + (getattr(mem, "temp_size_in_bytes", 0) or 0),
            # The CPU placeholder backend has no native bf16: every bf16 dot
            # and its activation chain is upcast to f32, inflating temp by
            # up to 2× vs the TPU compile.  Arguments (params/opt/caches)
            # keep their true dtypes.  tpu_adjusted halves temps — an
            # *upper bound* on the TPU-side peak is peak_bytes, a best
            # estimate is tpu_adjusted_bytes.
            "tpu_adjusted_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
                          + (getattr(mem, "temp_size_in_bytes", 0) or 0) // 2,
        },
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals")
                 if k in cost},
        "collectives": coll,
        "roofline": terms,
        "model_flops_total": mflops,
        "hlo_flops_total": hlo_flops_total,
        "useful_flops_ratio": (mflops / hlo_flops_total
                               if hlo_flops_total else None),
        "params_total": n_total,
        "params_active": n_active,
    })
    rec["dominant"] = HA.dominant_term(terms)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", dest="fsdp", action="store_false")
    ap.add_argument("--policy", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--extra", default="", help="free-form tag for §Perf runs")
    ap.add_argument("--overrides", default="",
                    help="comma-separated cfg/opts knobs (see run_cell)")
    args = ap.parse_args()

    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod,
                       fsdp=args.fsdp, policy=args.policy, extra=args.extra,
                       overrides=args.overrides)
    except Exception as exc:  # noqa: BLE001 — record the failure, don't die
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "error": repr(exc),
               "trace": traceback.format_exc()[-2000:]}
    line = json.dumps(rec)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line[:600] if rec.get("status") == "ok" else line[:3000])
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
