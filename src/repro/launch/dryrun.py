"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16,16) or (2,16,16), the
architecture config, the sharding policy, and AOT-compiles the real step
function against ShapeDtypeStruct inputs — no arrays are allocated. The
compiled artifact yields:

  * memory_analysis()  — per-device argument/output/temp bytes (fits-HBM
    proof against the 16 GiB v5e budget),
  * cost_analysis()    — XLA FLOPs / bytes (scan bodies counted once —
    see hlo_analysis for the trip-corrected whole-step view),
  * as_text()          — post-SPMD HLO, parsed for per-device collective
    bytes (trip-count corrected).

Results are dumped as JSON under experiments/dryrun/ for the roofline
stage. Usage:

    python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--jobs-file f.txt]
"""
import argparse
import dataclasses
import json
import os
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SHAPES, ArchConfig, Shape, get_config, list_archs
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    microbatches_for,
)
from repro.models import lm
from repro.optim import adafactor, adamw
from repro.runtime.sharding import (
    auto_parallelism,
    batch_specs,
    cache_specs,
    param_count,
    param_specs,
    shardings,
)

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
HBM_BYTES = 16 * 2 ** 30  # v5e


def skip_reason(cfg: ArchConfig, shape: Shape) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention arch: 512k decode is quadratic-cost; "
                "skipped per assignment (noted in DESIGN.md)")
    return None


def build_cell(arch_id: str, shape_name: str, multi_pod: bool):
    """Returns (jitted, example_args, meta) ready to lower."""
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    par = auto_parallelism(cfg, mesh, shape)
    sds_params = jax.eval_shape(
        lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(0)
    )
    pspecs = param_specs(sds_params, par)
    pshard = shardings(pspecs, mesh)
    batch = input_specs(cfg, shape)
    bshard = shardings(batch_specs(batch, par), mesh)

    if shape.kind == "train":
        n_params = param_count(cfg)
        big = n_params > 60e9
        # bf16 moments whenever state is ZeRO-tight: always under the
        # TP-off policy (the policy's fit estimate assumes 8 B/param) and
        # for >60B models; smaller f32-moment configs keep headroom anyway
        bf16_moments = big or par.tp_axis is None
        if n_params > 300e9:
            # the 1T config: factored second moment + bf16 first moment is
            # what fits the 16 GiB budget (see EXPERIMENTS.md memory table)
            opt = adafactor(moment_dtype=jnp.bfloat16)
        else:
            opt = adamw(moment_dtype=jnp.bfloat16 if bf16_moments
                        else jnp.float32)
        sds_opt = jax.eval_shape(opt.init, sds_params)
        ospecs = param_specs(sds_opt, par)   # name-based rules match m/v
        oshard = shardings(ospecs, mesh)
        mb = microbatches_for(cfg, shape, par)
        step = make_train_step(
            cfg, par, opt, num_microbatches=mb,
            accum_dtype=jnp.bfloat16 if big else jnp.float32,
            grad_shardings=pshard,
        )
        state_shape = {"params": sds_params, "opt": sds_opt}
        state_shard = {"params": pshard, "opt": oshard}
        jitted = jax.jit(
            step,
            in_shardings=(state_shard, bshard),
            out_shardings=(state_shard, None),
            donate_argnums=(0,),
        )
        return jitted, (state_shape, batch), {
            "microbatches": mb, "par": par, "mesh": mesh, "cfg": cfg,
        }

    # serving shapes
    B = shape.global_batch
    max_len = shape.seq_len + (1 if shape.kind == "decode" else 0)
    sds_cache = jax.eval_shape(lambda: lm.init_cache(cfg, B, max_len))
    cspecs = cache_specs(sds_cache, par, cfg, B)
    cshard = shardings(cspecs, mesh)
    if shape.kind == "prefill":
        step = make_prefill_step(cfg, par)
    else:
        step = make_serve_step(cfg, par)
    jitted = jax.jit(
        step,
        in_shardings=(pshard, cshard, bshard),
        out_shardings=(None, cshard),
        donate_argnums=(1,),
    )
    return jitted, (sds_params, sds_cache, batch), {
        "par": par, "mesh": mesh, "cfg": cfg,
    }


def run_cell(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "params": param_count(cfg),
    }
    reason = skip_reason(cfg, shape)
    if reason:
        rec["skipped"] = reason
        return rec
    t0 = time.time()
    jitted, args, meta = build_cell(arch_id, shape_name, multi_pod)
    with meta["mesh"]:
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        ma = compiled.memory_analysis()
        if ma is not None:
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
            live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            rec["memory"]["live_bytes"] = int(live)
            rec["memory"]["fits_16g"] = bool(live < HBM_BYTES)
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        rec["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(
                sum(v for k, v in ca.items() if k.startswith("bytes accessed"))
            ),
        }
        hlo = compiled.as_text()
        stats = analyze_collectives(hlo)
        rec["collectives"] = {
            "bytes_by_kind": stats.bytes_by_kind,
            "count_by_kind": stats.count_by_kind,
            "total_bytes": stats.total_bytes,
        }
        rec["hlo_chars"] = len(hlo)
    if "microbatches" in meta:
        rec["microbatches"] = meta["microbatches"]
    par = meta["par"]
    rec["policy"] = {
        "fsdp_axes": list(par.fsdp_axes),
        "ep_axes": list(par.ep_axes),
        "tp_axis": par.tp_axis,
    }
    return rec


def main() -> None:
    # the production meshes need 512 host devices; set before the first
    # jax call initializes the CPU backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                path = out_dir / f"{tag}.json"
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape, mp)
                    status = ("SKIP" if "skipped" in rec else "OK")
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    status = "FAIL"
                    failures += 1
                rec["wall_s"] = round(time.time() - t0, 2)
                path.write_text(json.dumps(rec, indent=2))
                extra = ""
                if status == "OK" and "memory" in rec:
                    gb = rec["memory"]["live_bytes"] / 2 ** 30
                    extra = (f" live={gb:.2f}GiB coll="
                             f"{rec['collectives']['total_bytes']/1e9:.2f}GB")
                print(f"[{status}] {tag} ({rec['wall_s']}s){extra}",
                      flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")
    print("dry-run sweep complete")


if __name__ == "__main__":
    main()
