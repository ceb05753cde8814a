import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
  1. FULL lowering (scan-stacked layers) on the production mesh —
     ``.lower().compile()`` must succeed; records memory_analysis()
     (per-device bytes) and the compile itself proves the sharding story.
  2. Two REDUCED-DEPTH unrolled lowerings (1 and 2 scan units, full width)
     whose cost_analysis()/HLO-collective deltas give exact per-unit
     FLOPs/bytes/collective bytes; extrapolated to full depth
     (lax.scan bodies are counted once by XLA cost analysis — verified).
  3. Roofline terms + bottleneck via repro.launch.roofline.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --arch gp-exact-2m          # paper cells
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, SHAPES, get_config, runnable_shapes
from repro.configs.base import ShapeConfig
from repro.distributed.sharding import (
    cache_shardings,
    p_batch,
    params_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    CellAnalysis,
    extrapolate,
    model_flops_estimate,
    parse_collective_bytes,
)
from repro.models import (
    batch_shardings,
    build_model,
    input_specs,
    make_serve_step,
    make_train_step,
)
from repro.models.model import make_prefill_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/artifacts/dryrun")

GP_ARCHS = ["gp-exact-2m", "gp-exact-8m"]


# --------------------------------------------------------------------------
# depth-reduction helpers for the FLOPs extrapolation
# --------------------------------------------------------------------------

def reduced_depth_cfg(cfg, n_units: int):
    """Full-width config with n scanned units; returns (cfg_small, units_total)."""
    if cfg.family == "hybrid":
        P = cfg.shared_attn_period
        G = cfg.num_layers // P
        tail = cfg.num_layers - G * P
        return dataclasses.replace(cfg, num_layers=n_units * P + tail), G
    if cfg.family == "encdec":
        return (
            dataclasses.replace(cfg, num_layers=n_units, encoder_layers=n_units),
            cfg.num_layers,
        )
    if cfg.family == "moe" and cfg.first_dense_layers:
        return (
            dataclasses.replace(cfg, num_layers=cfg.first_dense_layers + n_units),
            cfg.num_layers - cfg.first_dense_layers,
        )
    return dataclasses.replace(cfg, num_layers=n_units), cfg.num_layers


# --------------------------------------------------------------------------
# lowering one cell
# --------------------------------------------------------------------------

def _shape_struct_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _eval_shapes(cfg, shape, *, use_scan):
    """Abstract (params, opt/cache, batch) trees + their sharding specs."""
    bundle = build_model(cfg)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    max_seq = max(shape.seq_len + 1, 8)

    params_s = jax.eval_shape(lambda k: bundle.init(k, max_seq=max_seq), key)
    p_specs = params_shardings(params_s, bundle.stacked_paths)

    if shape.kind == "train":
        step, init_opt = make_train_step(bundle, use_scan=use_scan)
        opt_s = jax.eval_shape(init_opt, params_s)
        o_specs = type(opt_s)(
            jax.sharding.PartitionSpec(),
            params_shardings(opt_s.mu, bundle.stacked_paths),
            params_shardings(opt_s.nu, bundle.stacked_paths),
        )
        batch_s = input_specs(cfg, shape)
        b_specs = batch_shardings(cfg, shape)
        args = (params_s, opt_s, batch_s)
        shardings = (p_specs, o_specs, b_specs)
        out_shardings = (p_specs, o_specs, jax.sharding.PartitionSpec())
        return bundle, step, args, shardings, out_shardings, (0, 1)

    if shape.kind == "prefill":
        step = make_prefill_step(bundle, cache_len=shape.seq_len, use_scan=use_scan)
        batch_s = input_specs(cfg, shape)
        b_specs = batch_shardings(cfg, shape)
        cache_s = jax.eval_shape(
            lambda: bundle.init_cache(None, shape.global_batch, shape.seq_len)
        )
        c_specs = cache_shardings(cache_s)
        args = (params_s, batch_s)
        shardings = (p_specs, b_specs)
        tok_out = jax.sharding.PartitionSpec(*b_specs["tokens"][:1])
        out_shardings = (tok_out, c_specs)
        return bundle, step, args, shardings, out_shardings, ()

    # decode
    step = make_serve_step(bundle, use_scan=use_scan)
    batch_s = input_specs(cfg, shape)
    b_specs = batch_shardings(cfg, shape)
    cache_s = jax.eval_shape(
        lambda: bundle.init_cache(None, shape.global_batch, shape.seq_len)
    )
    c_specs = cache_shardings(cache_s)
    args = (params_s, batch_s["token"], cache_s, batch_s["pos"])
    shardings = (p_specs, b_specs["token"], c_specs, b_specs["pos"])
    out_shardings = (b_specs["token"], c_specs)
    return bundle, step, args, shardings, out_shardings, (2,)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool, use_scan=True, cfg=None):
    """Lower + compile; returns (compiled, lowered, elapsed)."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        bundle, step, args, shardings, out_shardings, donate = _eval_shapes(
            cfg, shape, use_scan=use_scan
        )
        lowered = jax.jit(
            step,
            in_shardings=shardings,
            out_shardings=out_shardings,
            donate_argnums=donate,
        ).lower(*args)
        compiled = lowered.compile()
    return compiled, lowered, time.time() - t0


def _cost_numbers(compiled):
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = parse_collective_bytes(compiled.as_text())
    return flops, byts, coll


OPT_FIELDS = {
    "chunked": {"chunked_attention": True},
    "sp": {"use_sp": True},
    "bf16grad": {"grad_reduce_dtype": "bfloat16"},
}


def apply_opts(cfg, opts: str):
    for o in filter(None, (opts or "").split(",")):
        cfg = dataclasses.replace(cfg, **OPT_FIELDS[o])
    return cfg


def analyze_cell(arch: str, shape_name: str, *, multi_pod: bool, opts: str = "") -> dict:
    """Full pipeline for one cell → result dict (written to artifacts)."""
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = apply_opts(get_config(arch), opts)
    shape = SHAPES[shape_name]
    out: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "opts": opts}

    # 1. full compile (the pass/fail gate) + memory analysis
    compiled, lowered, dt = lower_cell(arch, shape_name, multi_pod=multi_pod, cfg=cfg)
    ma = compiled.memory_analysis()
    per_dev_mem = int(
        getattr(ma, "argument_size_in_bytes", 0)
        + getattr(ma, "temp_size_in_bytes", 0)
        + getattr(ma, "output_size_in_bytes", 0)
        - getattr(ma, "alias_size_in_bytes", 0)
    )
    out.update(
        compile_seconds=round(dt, 1),
        per_device_bytes=per_dev_mem,
        per_device_gib=round(per_dev_mem / 2**30, 3),
        memory_analysis=str(ma),
    )

    # raw (scan-counted-once) numbers for the record
    raw_flops, raw_bytes, raw_coll = _cost_numbers(compiled)
    out.update(raw_flops=raw_flops, raw_bytes=raw_bytes, raw_collectives=raw_coll)

    # 2. unrolled L=1 / L=2 lowerings → per-unit deltas
    cfg1, units = reduced_depth_cfg(cfg, 1)
    cfg2, _ = reduced_depth_cfg(cfg, 2)  # opts inherited via cfg
    c1, _, _ = lower_cell(arch, shape_name, multi_pod=multi_pod, use_scan=False, cfg=cfg1)
    c2, _, _ = lower_cell(arch, shape_name, multi_pod=multi_pod, use_scan=False, cfg=cfg2)
    f1, b1, coll1 = _cost_numbers(c1)
    f2, b2, coll2 = _cost_numbers(c2)

    flops = extrapolate(f1, f2, units)
    byts = extrapolate(b1, b2, units)
    coll = extrapolate(coll1["total"], coll2["total"], units)
    coll_breakdown = {
        k: extrapolate(coll1.get(k, 0), coll2.get(k, 0), units)
        for k in set(coll1) | set(coll2)
        if k != "total"
    }

    n_chips = 512 if multi_pod else 256
    analysis = CellAnalysis(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll,
        collective_breakdown=coll_breakdown,
        per_device_memory=per_dev_mem,
        model_flops=model_flops_estimate(cfg, shape) / n_chips,
    )
    out["analysis"] = analysis.to_dict()
    out["extrapolation"] = {
        "units": units,
        "f1": f1,
        "f2": f2,
        "b1": b1,
        "b2": b2,
        "coll1": coll1["total"],
        "coll2": coll2["total"],
    }
    return out


# --------------------------------------------------------------------------
# GP cells (the paper's own technique at pod scale)
# --------------------------------------------------------------------------

def gp_cell(arch: str, *, multi_pod: bool, opts: str = "") -> dict:
    """Distributed BBMM exact-GP MLL training step, n row-sharded.

    opts: "bf16" computes kernel tiles in bf16 (f32 accumulate) and gathers
    M in bf16 — the beyond-paper §Perf variant."""
    from repro.core import AddedDiagOperator, BBMMSettings, ShardedKernelOperator, marginal_log_likelihood
    from repro.gp.kernels import RBFKernel
    from repro.launch.roofline import PEAK_FLOPS, PEAK_FLOPS_F32

    bf16 = "bf16" in (opts or "")
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if arch == "gp-exact-2m":
        n, d = 2_097_152, 8
    else:  # gp-exact-8m
        n, d = 8_388_608, 8
    t, p = 10, 20  # paper defaults

    def make_mll(max_iters):
        def mll(params, X, y, key):
            kern = RBFKernel(
                lengthscale=jnp.exp(params["log_ell"]),
                outputscale=jnp.exp(params["log_out"]),
            )
            op = AddedDiagOperator(
                ShardedKernelOperator(
                    kernel=kern, X=X, data_axes=axes, chunk=8192,
                    compute_dtype="bfloat16" if bf16 else "float32",
                ),
                jnp.exp(params["log_noise"]),
            )
            s = BBMMSettings(num_probes=t, max_cg_iters=max_iters, precond_rank=0)
            return marginal_log_likelihood(op, y, key, s)

        return mll

    params = {
        "log_ell": jax.ShapeDtypeStruct((), jnp.float32),
        "log_out": jax.ShapeDtypeStruct((), jnp.float32),
        "log_noise": jax.ShapeDtypeStruct((), jnp.float32),
    }
    from jax.sharding import PartitionSpec as P

    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_spec = {k: P() for k in params}

    def lower_with(iters):
        def step(params, X, y, key):
            loss, g = jax.value_and_grad(lambda q: -make_mll(iters)(q, X, y, key))(params)
            new = jax.tree.map(lambda a, b: a - 0.1 * b, params, g)
            return new, loss

        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(p_spec, P(), P(axes), P()),
                out_shardings=(p_spec, P()),
            ).lower(params, X, y, key)
            return lowered.compile()

    out = {"arch": arch, "shape": "mll_step", "mesh": mesh_name, "opts": opts}
    t0 = time.time()
    compiled = lower_with(p)
    ma = compiled.memory_analysis()
    per_dev_mem = int(
        getattr(ma, "argument_size_in_bytes", 0)
        + getattr(ma, "temp_size_in_bytes", 0)
        + getattr(ma, "output_size_in_bytes", 0)
    )
    out.update(
        compile_seconds=round(time.time() - t0, 1),
        per_device_bytes=per_dev_mem,
        per_device_gib=round(per_dev_mem / 2**30, 3),
        memory_analysis=str(ma),
    )
    raw_flops, raw_bytes, raw_coll = _cost_numbers(compiled)
    out.update(raw_flops=raw_flops, raw_bytes=raw_bytes, raw_collectives=raw_coll)

    # GP roofline terms are ANALYTIC — unlike the LM cells, this step nests
    # two scans (CG iters × column chunks) whose bodies XLA counts once, so
    # HLO extrapolation along one axis cannot recover the product; the
    # BBMM loop is simple enough to count exactly instead (raw HLO numbers
    # above remain the cross-check).
    cols = t + 1  # probe block + y
    n_loc = n / n_chips
    iters_fwd = p
    matmul_passes = iters_fwd + 2  # + backward: one vjp matmul + precond work
    # per device per matmul pass: distance tile (2·n_loc·n·d) + kernel→M
    # contraction (2·n_loc·n·cols) + exp etc (~6 flops/entry)
    flops = matmul_passes * (2.0 * n_loc * n * (d + cols) + 6.0 * n_loc * n)
    # fused-tile HBM traffic per pass: read X (n·d) + gathered M (n·cols)
    # + write/read local rows — O(n), NOT O(n²) (the BBMM insight)
    byts = matmul_passes * 4.0 * (n * d + 2.0 * n * cols + 2.0 * n_loc * cols)
    # collectives per pass: all-gather of M (received bytes per device);
    # bf16 halves the payload
    elt = 2.0 if bf16 else 4.0
    coll = matmul_passes * elt * n * cols
    model_flops = matmul_passes * 2.0 * n_loc * n * (d + cols)

    analysis = CellAnalysis(
        arch=arch,
        shape="mll_step",
        mesh=mesh_name,
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll,
        collective_breakdown={"all-gather": coll},
        per_device_memory=per_dev_mem,
        model_flops=model_flops,
        peak_flops=PEAK_FLOPS if bf16 else PEAK_FLOPS_F32,
    )
    out["analysis"] = analysis.to_dict()
    out["method"] = "analytic (nested-scan HLO counts once; see source)"
    return out


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def run_one(arch, shape_name, multi_pod, outdir, opts=""):
    tag = f"{arch}_{shape_name}_{'2x16x16' if multi_pod else '16x16'}"
    if opts:
        tag += "_" + opts.replace(",", "+")
    path = os.path.join(outdir, tag + ".json")
    try:
        if arch in GP_ARCHS:
            result = gp_cell(arch, multi_pod=multi_pod, opts=opts)
        else:
            result = analyze_cell(arch, shape_name, multi_pod=multi_pod, opts=opts)
        result["status"] = "ok"
    except Exception as e:  # noqa
        result = {
            "arch": arch,
            "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2, default=str)
    status = result["status"]
    mem = result.get("per_device_gib", "-")
    print(f"[{status}] {tag}  mem/dev={mem} GiB  ({result.get('compile_seconds', '-')}s)", flush=True)
    if status == "ok":
        a = result["analysis"]
        print(
            f"    t_comp={a['t_compute']:.4f}s t_mem={a['t_memory']:.4f}s "
            f"t_coll={a['t_collective']:.4f}s  bottleneck={a['bottleneck']} "
            f"useful={a['useful_ratio']:.2f}",
            flush=True,
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gp", action="store_true", help="run the GP paper cells")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--opt", default="", help="comma list: chunked,sp,bf16grad")
    args = ap.parse_args()

    if args.all:
        for arch in ALL_ARCHS:
            cfg = get_config(arch)
            for shape in runnable_shapes(cfg):
                for mp in (False, True):
                    run_one(arch, shape.name, mp, args.out)
        for arch in GP_ARCHS:
            for mp in (False, True):
                run_one(arch, "mll_step", mp, args.out)
        return
    if args.gp:
        for arch in GP_ARCHS:
            for mp in (False, True):
                run_one(arch, "mll_step", mp, args.out)
        return
    assert args.arch, "--arch required (or --all)"
    if args.arch in GP_ARCHS:
        run_one(args.arch, "mll_step", args.multi_pod, args.out, opts=args.opt)
        return
    shapes = [args.shape] if args.shape else [s.name for s in runnable_shapes(get_config(args.arch))]
    for s in shapes:
        run_one(args.arch, s, args.multi_pod, args.out, opts=args.opt)


if __name__ == "__main__":
    main()
