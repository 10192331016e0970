"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record the compiled artifact's roofline inputs (deliverables e and g).

For each cell this produces artifacts/dryrun/<arch>__<shape>__<mesh>.json:
  memory_analysis   — per-device argument/output/temp/peak bytes (fit proof)
  cost_analysis     — per-device HLO FLOPs + bytes accessed
  collectives       — per-device operand bytes by collective op, parsed from
                      the post-SPMD compiled HLO text
  model_flops       — 6*N_active*D (train) / 2*N_active*D (inference)
  timings           — lower/compile wall seconds

Usage:
  python -m repro.launch.dryrun --arch llama3_8b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both     # every runnable cell
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (tests may shrink the fake-device pool; must happen before jax imports)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

import argparse
import functools
import json
import re
import time

import jax
import jax.numpy as jnp

from ..configs.base import SHAPES, ArchConfig, ShapeSpec, all_cells, get, shape_applicable
from ..models import decode_step, forward, logits_fn
from ..roofline import analytic
from ..roofline import hlo as hlo_walk
from ..train.train_step import train_step
from . import specs as S
from .mesh import make_production_mesh

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_TY_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def _type_bytes(match) -> int:
    dt, dims = match.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def parse_collective_bytes(hlo_text: str, n_devices: int) -> dict:
    """Per-device collective traffic from the post-SPMD compiled HLO.

    Post-optimization HLO prints operands as name references, so sizes come
    from each collective's RESULT type (tuple members summed for -start
    forms — the (operand, result) alias pair is halved).  "wire bytes" uses
    the standard ring-algorithm per-chip traffic:
        all-reduce        2 R (g-1)/g      (R = result bytes, g = group)
        all-gather          R (g-1)/g      (R = gathered result)
        reduce-scatter      R (g-1)        (R = scattered result)
        all-to-all          R (g-1)/g
        collective-permute  R
    """
    res_bytes = {k: 0 for k in _COLLECTIVES}
    wire = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        m = re.search(r"=\s+(\(?[^()]*(?:\([^)]*\))?[^()=]*?)\s+([a-z\-]+)\(", ls)
        if m is None:
            continue
        op = m.group(2)
        base = op[:-6] if op.endswith("-start") else op
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        restypes = m.group(1)
        R = sum(_type_bytes(t) for t in _TY_RE.finditer(restypes))
        if op.endswith("-start") and restypes.startswith("("):
            R //= 2  # (operand, result) alias tuple
        g = max(_group_size(ls, n_devices), 1)
        res_bytes[base] += R
        counts[base] += 1
        if base == "all-reduce":
            wire[base] += 2.0 * R * (g - 1) / g
        elif base in ("all-gather", "all-to-all"):
            wire[base] += R * (g - 1) / g
        elif base == "reduce-scatter":
            wire[base] += R * (g - 1)
        else:  # collective-permute
            wire[base] += float(R)
    return {"result_bytes": res_bytes, "wire_bytes": wire, "counts": counts,
            "total_wire_bytes": sum(wire.values()),
            "total_bytes": sum(res_bytes.values())}


def count_params(cfg: ArchConfig) -> dict:
    sds = jax.eval_shape(
        functools.partial(__import__("repro.models", fromlist=["init_params"])
                          .init_params, cfg), jax.random.PRNGKey(0))
    import math
    total = sum(math.prod(x.shape) for x in jax.tree.leaves(sds))
    routed = 0
    if cfg.n_experts:
        per_layer = 3 * cfg.n_experts * cfg.d_model * cfg.moe_d_ff
        routed = per_layer * cfg.n_layers
    active = total - routed
    if cfg.n_experts:
        active += routed * cfg.experts_per_token // cfg.n_experts
    return {"total": int(total), "active": int(active)}


def model_flops(cfg: ArchConfig, shape: ShapeSpec, n_active: int) -> float:
    """Matmul-only convention: 6*N*D train, 2*N*D inference forward,
    2*N*B decode (one token per sequence)."""
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch


def build_step(cfg: ArchConfig, shape: ShapeSpec, mesh):
    """Returns (jitted fn, abstract args tuple)."""
    if shape.kind == "train":
        state_sds, state_specs = S.abstract_train_state(cfg, mesh)
        batch = S.batch_specs(cfg, shape, mesh, with_labels=True)
        ocfg = S.opt_config_for(cfg)
        n_data = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                n_data *= mesh.shape[ax]
        from ..models import param_pspecs
        fn = jax.jit(
            functools.partial(train_step, cfg=cfg, opt_cfg=ocfg,
                              dispatch_groups=n_data,
                              microbatches=cfg.train_microbatches,
                              param_specs=param_pspecs(cfg)),
            donate_argnums=(0,))
        return fn, (state_sds, batch)

    params_sds, _ = S.abstract_params(cfg, mesh)
    if shape.kind == "prefill":
        batch = S.batch_specs(cfg, shape, mesh, with_labels=False)

        def prefill(params, batch):
            h, _ = forward(params, cfg, batch)
            logits = logits_fn(params["embed"], h[:, -1:])
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return jax.jit(prefill), (params_sds, batch)

    cache_sds, _ = S.abstract_cache(cfg, shape, mesh)
    tokens, pos = S.decode_inputs(cfg, shape, mesh)

    n_data = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            n_data *= mesh.shape[ax]

    def serve(params, cache, tokens, pos):
        # dispatch_groups=1 at decode: sharding the handful of decode tokens
        # over data would re-claim the axis expert-FF shards need (measured
        # 4.9 -> 243 GB regression; §Perf cell-3 iter-2, refuted).
        h, cache = decode_step(params, cfg, cache, tokens, pos,
                               dispatch_groups=1)
        logits = logits_fn(params["embed"], h)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return jax.jit(serve, donate_argnums=(1,)), (params_sds, cache_sds,
                                                 tokens, pos)


# --- §Perf hillclimb variants: named (rule overrides, config replaces) ----
# Each entry is one hypothesis from EXPERIMENTS.md §Perf; "baseline" == {}.
VARIANTS: dict = {
    "baseline": ({}, {}),
    # dense-TP cells: drop tensor parallelism, ZeRO-3 everything over BOTH
    # axes; fewer microbatches cut the per-step param re-gather count.
    "fsdp_pure": ({"embed_fsdp": ("data", "model"), "ff": None,
                   "heads": None, "vocab": None, "expert": None},
                  {"fsdp": True, "train_microbatches": 2}),
    "fsdp_mb1": ({"embed_fsdp": ("data", "model"), "ff": None,
                  "heads": None, "vocab": None, "expert": None},
                 {"fsdp": True, "train_microbatches": 1}),
    # MoE train: keep EP over model, shard expert-FF over data (EP^2) so
    # routed weights never re-gather; dense params stay ZeRO over data.
    "moe_ep2": ({"moe_ff": "data"}, {"train_microbatches": 2}),
    "moe_ep2_mb1": ({"moe_ff": "data"}, {"train_microbatches": 1}),
    # decode: no ZeRO re-gather at inference — experts sharded E x F.
    "decode_ep2": ({"embed_fsdp": None, "moe_ff": "data"}, {"fsdp": False}),
    # capacity-factor ablation (compute waste vs drop rate)
    "cf10": ({}, {"capacity_factor": 1.0}),
}


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             save_hlo: bool = False, variant: str = "baseline") -> dict:
    cfg = get(arch)
    rule_over, cfg_over = VARIANTS[variant]
    if cfg_over:
        cfg = cfg.replace(**cfg_over)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    tag = "" if variant == "baseline" else f"@{variant}"
    rec = {"arch": arch + tag, "shape": shape_name, "mesh": mesh_kind,
           "family": cfg.family, "variant": variant}
    if not ok:
        rec["skipped"] = reason
        _write(out_dir, rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    S.rules_for(cfg, shape, mesh, extra=rule_over)
    params = count_params(cfg)
    rec["params"] = params
    rec["model_flops"] = model_flops(cfg, shape, params["active"])
    rec["n_devices"] = mesh.size
    cc = analytic.cell_cost(cfg, shape)
    rec["analytic"] = {"flops_computed": cc.flops_computed,
                       "flops_useful": cc.flops_useful,
                       "hbm_bytes": cc.hbm_bytes,
                       "params_bytes": cc.params_bytes}

    with jax.set_mesh(mesh):
        fn, args = build_step(cfg, shape, mesh)
        t0 = time.time()
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        if hasattr(mem, "peak_memory_in_bytes"):
            rec["memory"]["peak_memory_in_bytes"] = int(mem.peak_memory_in_bytes)
        cost = compiled.cost_analysis()
        rec["cost_xla_flat"] = {k: float(cost[k]) for k in
                                ("flops", "bytes accessed", "transcendentals")
                                if k in cost}
        hlo = compiled.as_text()
        rec["collectives_flat"] = parse_collective_bytes(hlo, mesh.size)
        rec["collectives"] = hlo_walk.collective_summary(hlo, mesh.size)
        rec["hlo_lines"] = hlo.count("\n")
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            hname = (arch + tag).replace('@', '_AT_')
            with open(f"{out_dir}/{hname}__{shape_name}__{mesh_kind}.hlo", "w") as f:
                f.write(hlo)
        del hlo
    rec["timings"] = {"lower_s": t1 - t0, "compile_s": t2 - t1}
    _write(out_dir, rec)
    return rec


def _write(out_dir: str, rec: dict):
    os.makedirs(out_dir, exist_ok=True)
    name = rec['arch'].replace('@', '_AT_')
    path = f"{out_dir}/{name}__{rec['shape']}__{rec['mesh']}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] wrote {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for cfg, shape, ok, _ in all_cells():
            for mk in meshes:
                name = cfg.name.replace("-", "_").replace(".", "_")
                run_cell(name, shape.name, mk, args.out, args.save_hlo)
    else:
        for mk in meshes:
            rec = run_cell(args.arch, args.shape, mk, args.out, args.save_hlo,
                           variant=args.variant)
            if "skipped" in rec:
                print(f"[dryrun] SKIP {args.arch} x {args.shape}: {rec['skipped']}")
            else:
                print(json.dumps({k: rec[k] for k in
                                  ("memory", "cost_xla_flat", "timings")},
                                 indent=1))
                print("collective wire bytes/device (trip-weighted):",
                      rec["collectives"]["total_wire_bytes"])


if __name__ == "__main__":
    main()
