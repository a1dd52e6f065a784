import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ^ MUST precede any jax import: the dry-run builds the 256/512-chip
# production mesh out of placeholder CPU host devices (this file only —
# smoke tests and benches see the real single CPU device), even on a host
# that has an accelerator.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the full sharded step function
(train_step = fwd + bwd + AdamW update; serve_step = decode + cache update
+ argmax), lowers it against allocation-free ShapeDtypeStructs carrying the
production NamedShardings, compiles, and records:

  * compiled.memory_analysis()   — per-device bytes (proves it fits)
  * compiled.cost_analysis()     — per-device HLO FLOPs / bytes accessed
  * collective schedule          — op-type totals parsed from the partitioned
                                   HLO text (all-gather / all-reduce /
                                   reduce-scatter / all-to-all / permute)

Results land in experiments/dryrun/<mesh>/<arch>__<shape>[__variant].json and
feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch yi_6b --shape train_4k
  python -m repro.launch.dryrun --arch yi_6b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all            # every cell, both meshes
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, cell_applicable, get_config, input_specs, list_archs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.convert import pick_dims
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim.adamw import AdamWConfig
from repro.runtime import serve_loop, train_loop

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"= (\(?[^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_collectives(hlo_text: str):
    """Per-device collective bytes by op type, from the RESULT shapes of every
    collective in the partitioned HLO (post-optimization text prints operands
    by name only, so result shapes are the reliable source; for reduce-scatter
    this undercounts by ~group_size — noted in EXPERIMENTS.md)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        op = m.group(2)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(m.group(1)):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        rec = out.setdefault(op, {"bytes": 0, "count": 0})
        rec["bytes"] += nbytes
        rec["count"] += 1
    return out


# ---------------------------------------------------------------------------

def build_cfg(arch: str, shape: ShapeConfig, plan: shd.MeshPlan,
              elitekv: bool = True, cache_ratio: float = 0.25,
              moe_impl: str = "ep", overrides=None,
              unroll: bool = True) -> ModelConfig:
    cfg = get_config(arch)
    cfg = shd.pad_cfg_for_tp(cfg, plan.tp)
    # XLA cost analysis counts while-loop bodies ONCE, so attention q-chunk
    # loops are python-unrolled for truthful FLOPs; the layer scan stays a
    # scan (realistic memory) and its per-layer cost is recovered via the
    # unroll=1 vs unroll=2 delta (see lower_cell).  The mamba chunk scan is
    # NOT unrolled: its inner-loop flops are elementwise (no GEMMs), <1% of
    # the block — the undercount is negligible and unrolling explodes the HLO.
    cfg = dataclasses.replace(
        cfg, dtype=jnp.bfloat16,
        scan_layers=True, attn_chunk_unroll=unroll, ssm_unroll=False,
        ssm_chunk=128)
    if elitekv and cfg.n_attn_layers > 0:
        ek = pick_dims(cfg, cache_ratio, align=128)
        cfg = dataclasses.replace(cfg, elitekv=ek)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def _sds(tree, shardings):
    """ShapeDtypeStructs with attached shardings (no allocation)."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
        tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               elitekv: bool = True, cache_ratio: float = 0.25,
               moe_impl: str = "ep", moment_dtype: str | None = None,
               seq_parallel: bool = True, param_dtype: str = "float32",
               overrides=None, unroll: bool = True, return_artifacts: bool = False,
               decode_fsdp: bool | None = None, decode_seq_tp: bool = True,
               opt_chunk: int = 0, loss_chunk: int = 0):
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = shd.plan_for_mesh(mesh, seq_parallel=seq_parallel)
    if shape.kind == "decode":
        if decode_fsdp is None:
            # §Perf: inference keeps weights replicated across data (no
            # per-step ZeRO-3 all-gathers) whenever the bf16 weights fit the
            # TP shards (~≤8 GiB/dev); the 100B+ MoE giants keep FSDP
            decode_fsdp = get_config(arch).param_count() * 2 / plan.tp > 8e9
        if not decode_fsdp:
            plan = shd.plan_for_mesh(mesh, fsdp=False, seq_parallel=seq_parallel)
    cfg = build_cfg(arch, shape, plan, elitekv=elitekv,
                    cache_ratio=cache_ratio, overrides=overrides, unroll=unroll)
    if loss_chunk:
        cfg = dataclasses.replace(cfg, loss_chunk=loss_chunk)
    ok, reason = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "x".join(map(str, mesh.devices.shape)),
                "skipped": True, "reason": reason}

    P_ = jax.sharding.PartitionSpec
    extra = {}

    def build_and_compile(cfg):
        nonlocal extra
        key = jax.random.PRNGKey(0)
        pshapes, bshapes = jax.eval_shape(lambda k: lm.init(k, cfg), key)
        if param_dtype != "float32":
            pshapes = _cast_tree(pshapes, jnp.dtype(param_dtype))
        pspecs = shd.param_pspecs(pshapes, cfg, plan)
        pshard = jax.tree.map(plan.named, pspecs, is_leaf=lambda x: isinstance(x, P_))
        bshard = jax.tree.map(lambda s: plan.named(P_(*([None] * s.ndim))), bshapes)
        params_in = _sds(pshapes, pshard)
        buffers_in = _sds(bshapes, bshard)

        ispecs = input_specs(cfg, shape, dtype=jnp.bfloat16)
        in_pspecs = shd.input_pspecs(cfg, shape, plan)
        batch_in = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                            sharding=plan.named(in_pspecs[k]))
                    for k, v in ispecs.items()}

        t0 = time.time()
        if shape.kind == "train":
            # default moments: int8 for the ≥100B MoE giants, else fp32
            md = moment_dtype or ("int8" if cfg.param_count() > 5e10 else "float32")
            tc = train_loop.TrainConfig(
                optimizer=AdamWConfig(moment_dtype=md, update_chunk=opt_chunk),
                moe_impl=moe_impl if cfg.n_experts else "ragged")
            constrain = shd.make_constrain(plan, cfg, shape.seq_len, shape.global_batch)
            step = train_loop.make_train_step(cfg, tc, mesh=mesh, constrain=constrain,
                                              data_axes=plan.dp_axes)
            oshapes = jax.eval_shape(lambda p: train_loop.init_opt_state(p, tc), pshapes)
            ospecs = shd.opt_pspecs(oshapes, pshapes, cfg, plan, md)
            oshard = jax.tree.map(plan.named, ospecs, is_leaf=lambda x: isinstance(x, P_))
            opt_in = _sds(oshapes, oshard)
            fn = jax.jit(step, donate_argnums=(0, 2))
            lowered = fn.lower(params_in, buffers_in, opt_in, batch_in)
            extra = {"moment_dtype": md}
        elif shape.kind == "prefill":
            params_in = _sds(_cast_tree(pshapes, jnp.bfloat16), pshard)
            cshapes = jax.eval_shape(
                lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len, jnp.bfloat16))
            cspecs = shd.cache_pspecs(cshapes, cfg, plan, shape.global_batch)
            cache_in = _sds(cshapes, jax.tree.map(plan.named, cspecs,
                                                  is_leaf=lambda x: isinstance(x, P_)))
            constrain = shd.make_constrain(plan, cfg, shape.seq_len, shape.global_batch)
            step = serve_loop.make_prefill_step(
                cfg, mesh=mesh, constrain=constrain,
                moe_impl=moe_impl if cfg.n_experts else "ragged", data_axes=plan.dp_axes)
            fn = jax.jit(step, donate_argnums=(3,))
            lowered = fn.lower(params_in, buffers_in, batch_in, cache_in)
        else:  # decode
            params_in = _sds(_cast_tree(pshapes, jnp.bfloat16), pshard)
            cshapes = jax.eval_shape(
                lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len, jnp.bfloat16))
            cspecs = shd.cache_pspecs(cshapes, cfg, plan, shape.global_batch,
                                      seq_over_tp=decode_seq_tp)
            cache_in = _sds(cshapes, jax.tree.map(plan.named, cspecs,
                                                  is_leaf=lambda x: isinstance(x, P_)))
            constrain = shd.make_constrain(plan, cfg, shape.seq_len,
                                           shape.global_batch, decode=True,
                                           seq_over_tp=decode_seq_tp)
            step = serve_loop.make_decode_step(
                cfg, mesh=mesh, constrain=constrain,
                moe_impl=moe_impl if cfg.n_experts else "ragged", data_axes=plan.dp_axes)
            tok_in = list(batch_in.values())[0]
            fn = jax.jit(step, donate_argnums=(3,))
            lowered = fn.lower(params_in, buffers_in, tok_in, cache_in)
        t_lower = time.time() - t0
        t0 = time.time()
        # dump post-SPMD-partitioning HLO: the CPU backend upcasts bf16 GEMMs
        # to f32 (convert_convert fusions), inflating *optimized-text* byte
        # counts ~2×; the post-SPMD dump still carries true bf16 shapes.
        import shutil
        import tempfile
        dump = tempfile.mkdtemp(prefix="spmd_dump_")
        try:
            compiled = lowered.compile(compiler_options={
                "xla_dump_to": dump,
                "xla_dump_hlo_pass_re": "spmd-partitioning"})
            spmd_files = sorted(Path(dump).glob("*after_spmd-partitioning*.txt"))
            spmd_text = spmd_files[-1].read_text() if spmd_files else None
        finally:
            shutil.rmtree(dump, ignore_errors=True)
        return compiled, spmd_text, t_lower, time.time() - t0

    # --- pass 1: flop/collective probe (attention chunks unrolled) ---
    compiled, spmd_text, t_lower, t_compile = build_and_compile(cfg)
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(spmd_text or compiled.as_text())
    # --- memory pass: the PRODUCTION lowering (inner chunk loops as scans —
    # the unrolled probe inflates temp memory because buffer assignment does
    # not reuse across unrolled chunk blocks) ---
    if cfg.attn_chunk_unroll:
        cfg_mem = dataclasses.replace(cfg, attn_chunk_unroll=False)
        compiled_mem, _, _, t_cm = build_and_compile(cfg_mem)
        ma = compiled_mem.memory_analysis()
        t_compile += t_cm
    else:
        ma = compiled.memory_analysis()

    # --- pass 2: unroll=2 — the delta is exactly one layer-scan body;
    #     total = base + (n_super - 1) · delta  (XLA counts loop bodies once) ---
    n_super = cfg.num_layers // cfg.block_period
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    if n_super > 1:
        cfg2 = dataclasses.replace(cfg, scan_unroll=2)
        compiled2, spmd_text2, _, t_c2 = build_and_compile(cfg2)
        ca2 = compiled2.cost_analysis() or {}
        colls2 = parse_collectives(spmd_text2 or compiled2.as_text())
        mult = n_super - 1
        dflops = max(0.0, float(ca2.get("flops", 0.0)) - flops)
        dbytes = max(0.0, float(ca2.get("bytes accessed", 0.0)) - bytes_acc)
        flops += mult * dflops
        bytes_acc += mult * dbytes
        merged = {}
        for op in set(colls) | set(colls2):
            b1 = colls.get(op, {"bytes": 0, "count": 0})
            b2 = colls2.get(op, {"bytes": 0, "count": 0})
            merged[op] = {
                "bytes": b1["bytes"] + mult * max(0, b2["bytes"] - b1["bytes"]),
                "count": b1["count"] + mult * max(0, b2["count"] - b1["count"]),
            }
        colls = merged
        t_compile += t_c2
    ca = dict(ca, flops=flops)
    ca["bytes accessed"] = bytes_acc

    n_chips = int(np.prod(mesh.devices.shape))
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "kind": shape.kind, "skipped": False,
        "chips": n_chips,
        "elitekv": dataclasses.asdict(cfg.elitekv),
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "tokens_per_step": tokens,
        "cache_floats_per_token": (
            cfg.elitekv.cache_per_token_per_layer(cfg.n_kv_heads, cfg.head_dim)
            * cfg.n_attn_layers),
        "flops_per_device": float(ca.get("flops", -1)),
        "bytes_accessed_per_device": float(ca.get("bytes accessed", -1)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
        },
        "collectives": colls,
        "collective_bytes_per_device": sum(v["bytes"] for v in colls.values()),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        **extra,
    }
    if return_artifacts:
        return result, compiled, cfg
    return result


def run_cell(args) -> dict:
    res = lower_cell(args.arch, args.shape, args.multi_pod,
                     elitekv=not args.no_elitekv, cache_ratio=args.cache_ratio,
                     moment_dtype=args.moment_dtype or None,
                     seq_parallel=not args.no_seq_parallel,
                     param_dtype=args.param_dtype,
                     decode_fsdp=args.decode_fsdp or None,
                     decode_seq_tp=not args.no_decode_seq_tp,
                     opt_chunk=args.opt_chunk, loss_chunk=args.loss_chunk)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    out_dir = Path(args.out) / mesh_tag
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{args.arch}__{args.shape}"
    if args.variant:
        tag += f"__{args.variant}"
    path = out_dir / f"{tag}.json"
    path.write_text(json.dumps(res, indent=1))
    print(json.dumps(res, indent=1))
    if not res.get("skipped"):
        gb = res["memory"]["peak_estimate_bytes"] / 2**30
        print(f"[dryrun] {tag} mesh={mesh_tag}: peak/device ≈ {gb:.2f} GiB, "
              f"flops/dev {res['flops_per_device']:.3e}, "
              f"coll/dev {res['collective_bytes_per_device']/2**20:.1f} MiB, "
              f"compile {res['compile_s']}s", file=sys.stderr)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(list_archs()), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-elitekv", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--cache-ratio", type=float, default=0.25)
    ap.add_argument("--moment-dtype", default="")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--variant", default="")
    ap.add_argument("--decode-fsdp", action="store_true",
                    help="re-enable ZeRO-3 weight gathers at decode (baseline)")
    ap.add_argument("--no-decode-seq-tp", action="store_true",
                    help="disable context-parallel decode cache (baseline)")
    ap.add_argument("--opt-chunk", type=int, default=0)
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        import subprocess
        archs = [a for a in list_archs() if not a.startswith("llama2_13b")]
        for mp in (False, True):
            for arch in archs:
                for shape in SHAPES:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--out", args.out]
                    if mp:
                        cmd.append("--multi-pod")
                    print(">>", " ".join(cmd), flush=True)
                    subprocess.run(cmd, check=False)
        return
    assert args.arch and args.shape, "--arch/--shape or --all"
    run_cell(args)


if __name__ == "__main__":
    main()
