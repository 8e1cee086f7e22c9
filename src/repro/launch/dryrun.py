"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production mesh, extract memory/cost/collective analysis, write JSON records.

MUST be run as a module entry point (python -m repro.launch.dryrun ...);
the XLA device-count override below happens before ANY other import.
"""
# --- these two lines MUST come before any other import (jax locks device
# --- count on first init) -------------------------------------------------
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, list_archs            # noqa: E402
from repro.distributed import sharding as shd                # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.shapes import (SHAPES, batch_axes, cell_applicable,  # noqa: E402
                                 input_specs, ruleset_name)
from repro.launch.steps import (make_decode_step, make_prefill_step,   # noqa: E402
                                make_train_step)
from repro.models import abstract_params                     # noqa: E402
from repro.models.transformer import cache_axes              # noqa: E402
from repro.optim import adamw                                # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")

# hardware constants (given): TPU v5e-class chip
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link; 4 links usable per chip
ICI_LINKS = 4

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\w+\[[^\]]*\]\S*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str):
    """Sum result-operand sizes of every collective op in the compiled HLO.
    '-start' variants counted once ('-done' carries no shape work)."""
    per_op = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, op = m.group(1), m.group(2)
        if m.group(0).find(op + "-done(") >= 0:
            continue
        per_op[op] = per_op.get(op, 0) + _shape_bytes(shape_txt)
    per_op["total"] = sum(v for k, v in per_op.items())
    return per_op


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N_active*tokens (train) / 2*N_active*tokens (fwd)."""
    total, active = cfg.param_counts()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * active * tokens
    return 2.0 * active * shape.batch        # one token per sequence


def _compile_step(cfg, shape, mesh, rules):
    """Build + jit + lower + compile the step for one cell. Returns
    (compiled, lower_s, compile_s)."""
    t0 = time.time()
    params_sds, param_axes = abstract_params(cfg)
    param_sh = shd.make_shardings(param_axes, mesh, rules, params_sds)
    inputs = input_specs(cfg, shape)
    in_axes = batch_axes(cfg, shape)
    input_sh = shd.make_shardings(in_axes, mesh, rules, inputs)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_sds = adamw.abstract_state(params_sds)
        opt_axes = adamw.state_axes(param_axes)
        opt_sh = shd.make_shardings(opt_axes, mesh, rules, opt_sds)
        step = make_train_step(cfg, opt_cfg)
        jitted = jax.jit(step,
                         in_shardings=(param_sh, opt_sh, input_sh["batch"]),
                         out_shardings=(param_sh, opt_sh, None),
                         donate_argnums=(0, 1))
        args = (params_sds, opt_sds, inputs["batch"])
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, s_max=shape.seq)
        cache_sds = jax.eval_shape(
            lambda: __import__("repro.models", fromlist=["init_caches"])
            .init_caches(cfg, shape.batch, shape.seq, jnp.bfloat16))
        cache_sh = shd.make_shardings(cache_axes(cfg), mesh, rules, cache_sds)
        jitted = jax.jit(step,
                         in_shardings=(param_sh, input_sh["batch"]),
                         out_shardings=(repl, cache_sh))
        args = (params_sds, inputs["batch"])
    else:
        step = make_decode_step(cfg)
        cache_sh = shd.make_shardings(cache_axes(cfg), mesh, rules,
                                      inputs["caches"])
        logits_spec = shd.make_specs({"x": ("batch", "vocab")}, mesh, rules,
                                     {"x": jax.ShapeDtypeStruct(
                                         (shape.batch, cfg.vocab_size),
                                         jnp.float32)})["x"]
        jitted = jax.jit(step,
                         in_shardings=(param_sh, cache_sh,
                                       input_sh["tokens"], repl),
                         out_shardings=(NamedSharding(mesh, logits_spec),
                                        cache_sh),
                         donate_argnums=(1,))
        args = (params_sds, inputs["caches"], inputs["tokens"], inputs["pos"])

    with jax.set_mesh(mesh), shd.use_rules(rules):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def _cost_terms(compiled):
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]),
            "coll_by_op": coll}


def roofline_terms_extrapolated(arch: str, shape, mesh, rules,
                                cfg_overrides=None):
    """XLA's HLO cost analysis counts loop bodies ONCE (trip counts are not
    modelled), so a rolled scan-over-layers under-reports FLOPs. We therefore
    compile two short UNROLLED variants (depth = period and 2*period, inner
    scans at trip count 1) and extrapolate linearly:

        total(L groups) = once + L * per_group
        once + per_group  = cost(depth=period, unrolled)
        once + 2*per_group = cost(depth=2*period, unrolled)

    Exact for everything linear in depth; chunked-linear algorithms (loss
    chunking, flash attention, mamba scan) are trip-1-exact because their
    total work is chunk-size-invariant. (rwkv6's intra-chunk term is
    quadratic in chunk size: trip-1 overstates it — noted in EXPERIMENTS.)
    """
    cfg0 = get_config(arch)
    S = shape.seq
    # chunk policy for the exact-count compiles: every inner scan is unrolled,
    # so cap trip counts at <=4 bodies (1-core compile-time budget) while
    # keeping chunks as close to production as possible. Total FLOPs of the
    # loss / flash / mamba scans are chunk-size invariant; rwkv's intra-chunk
    # term grows with chunk and is an upper bound — noted in EXPERIMENTS.md.
    scan_chunk = max(256 if "mamba" in cfg0.block_pattern else 64, S // 4)
    mk = lambda groups: cfg0.scaled(
        dtype="bfloat16", param_dtype="bfloat16",
        n_layers=cfg0.period * groups, unroll_inner=True,
        scan_chunk=min(scan_chunk, S), loss_chunk=S,
        attn_q_chunk=max(512, S // 2), attn_kv_chunk=max(1024, S // 2),
        **(cfg_overrides or {}))
    c1, *_ = _compile_step(mk(1), shape, mesh, rules)
    c2, *_ = _compile_step(mk(2), shape, mesh, rules)
    t1, t2 = _cost_terms(c1), _cost_terms(c2)
    n = cfg0.n_groups
    out = {}
    for k in ("flops", "bytes", "coll"):
        per_group = t2[k] - t1[k]
        out[k] = t1[k] + (n - 1) * per_group
        out[k + "_per_group"] = per_group
        out[k + "_once"] = t1[k] - per_group
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               rules_override=None, save_hlo: bool = False,
               extrapolate: bool = True, cfg_overrides=None):
    """Returns the JSON record for one cell. ``cfg_overrides`` is the perf-
    iteration hook (EXPERIMENTS.md §Perf): dataclass field overrides applied
    to both the full compile and the roofline extrapolation compiles."""
    shape = SHAPES[shape_name]
    # loss_chunk=seq: sequence-chunked loss only helps when activations are
    # replicated along S; under the production seq/act_embed sharding the
    # un-chunked loss is sharded anyway, and the chunk reshape would CROSS
    # shard boundaries (all-gathering a global-batch f32 cotangent).
    kw = dict(dtype="bfloat16", param_dtype="bfloat16", loss_chunk=shape.seq)
    kw.update(cfg_overrides or {})          # overrides win
    cfg = get_config(arch).scaled(**kw)
    skip = cell_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"cell": f"{arch}__{shape_name}", "arch": arch, "shape": shape_name,
           "mesh": mesh_name, "kind": shape.kind}
    if skip:
        rec.update(status="skipped", reason=skip)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rules = (rules_override or shd.RULESETS[ruleset_name(shape)])(mesh, cfg)

    compiled, t_lower, t_compile = _compile_step(cfg, shape, mesh, rules)
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    raw_flops_dev = float(cost.get("flops", 0.0))
    raw_bytes_dev = float(cost.get("bytes accessed", 0.0))
    if extrapolate and not multi_pod:
        ext = roofline_terms_extrapolated(arch, shape, mesh, rules,
                                          cfg_overrides=cfg_overrides)
        flops_dev, bytes_dev = ext["flops"], ext["bytes"]
        coll_total = ext["coll"]
    else:
        ext = None
        flops_dev, bytes_dev, coll_total = (raw_flops_dev, raw_bytes_dev,
                                            float(coll["total"]))
    model_flops = _model_flops(cfg, shape)
    total_p, active_p = cfg.param_counts()

    bytes_per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rec.update(
        status="ok",
        devices=int(n_dev),
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops=flops_dev,                       # per-device (SPMD program)
        bytes_accessed=bytes_dev,              # per-device
        collective_bytes=coll_total,           # per-device program
        collectives=coll,                      # raw (rolled-scan) breakdown
        raw_flops=raw_flops_dev,               # uncorrected cost_analysis
        raw_bytes_accessed=raw_bytes_dev,
        extrapolation=ext,
        memory={
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        bytes_per_device=int(bytes_per_device),
        model_flops_global=model_flops,
        model_flops_per_device=model_flops / n_dev,
        params_total=int(total_p),
        params_active=int(active_p),
        roofline={
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_total / (ICI_BW * ICI_LINKS),
            "useful_flops_ratio": (model_flops / n_dev) / max(flops_dev, 1.0),
        },
    )
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: rec["roofline"][k])
    rec["roofline"]["dominant"] = dom
    if save_hlo:
        hlo_path = os.path.join(ARTIFACT_DIR,
                                f"{arch}__{shape_name}__{mesh_name}.hlo")
        with open(hlo_path, "w") as f:
            f.write(hlo)
        rec["hlo_path"] = hlo_path
    return rec


def run_cells(cells, out_dir: str, save_hlo: bool = False):
    os.makedirs(out_dir, exist_ok=True)
    records = []
    for arch, shape_name, multi_pod in cells:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        tag = f"{arch}__{shape_name}__{mesh_name}"
        path = os.path.join(out_dir, tag + ".json")
        try:
            rec = lower_cell(arch, shape_name, multi_pod, save_hlo=save_hlo)
        except Exception as e:  # a failing cell is a bug in the system
            rec = {"cell": f"{arch}__{shape_name}", "mesh": mesh_name,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f"compile={rec['compile_s']:.0f}s dom={r['dominant']} "
                     f"comp={r['compute_s']*1e3:.1f}ms mem={r['memory_s']*1e3:.1f}ms "
                     f"coll={r['collective_s']*1e3:.1f}ms "
                     f"useful={r['useful_flops_ratio']:.2f} "
                     f"hbm={rec['bytes_per_device']/2**30:.2f}GiB")
        elif status == "error":
            extra = rec["error"][:160]
        else:
            extra = "SKIP: " + rec["reason"][:120]
        print(f"[dryrun] {tag}: {status} {extra}", flush=True)
        records.append(rec)
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None],
                    help="shape name (default: all)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out-dir", default=ARTIFACT_DIR)
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = [(a, s, mp) for a in archs for s in shapes for mp in pods]
    records = run_cells(cells, args.out_dir, save_hlo=args.save_hlo)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    main()
