"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell: build the step function
(train_step for train shapes, prefill/serve_step otherwise), lower with
ShapeDtypeStruct inputs (no allocation), ``.compile()`` on the production
mesh, and record ``memory_analysis()`` / ``cost_analysis()`` / the HLO
collective summary to a JSON cache consumed by the roofline report.

A CPU-only tool: the production mesh is 256-512 virtual host devices.  The
environment lines below pin the CPU backend and the device count before
anything imports jax (the count locks at first init); other XLA flags
are kept.  Smoke tests / benches never import this module.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --sweep            # all cells, subprocess each
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"
os.environ["XLA_FLAGS"] = " ".join(
    [
        flag
        for flag in os.environ.get("XLA_FLAGS", "").split()
        if not flag.startswith(_DEVICE_COUNT_FLAG)
    ]
    + [f"{_DEVICE_COUNT_FLAG}=512"]
)

import argparse
import dataclasses
import gzip
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

from repro.launch import profile as _profile

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"
HLO_DIR = Path(__file__).resolve().parents[3] / "results" / "hlo"


def _cell_path(arch: str, shape: str, mesh: str, variant: str = "base") -> Path:
    safe = arch.replace("/", "_").replace(".", "_")
    return RESULTS_DIR / f"{safe}__{shape}__{mesh}__{variant}.json"


def run_cell(
    arch_name: str,
    shape_name: str,
    mesh_kind: str,
    *,
    photonic: bool = False,
    photonic_scope: str = "weights",
    photonic_org: str = "SMWA",  # str | OrgSpec; validated by DPUConfig
    save_hlo: bool = False,
    overrides: dict | None = None,
    variant: str = "base",
    zero1: bool = True,
    skip_main: bool = False,  # annotate mode: only re-run the (cheap) ladder
    dp_shardmap: bool = False,  # shard_map-pinned DP step (runtime/dp_step)
    dp_compress: bool = False,  # int8-compressed gradient all-reduce
) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.dpu import DPUConfig
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh, require_devices
    from repro.models import registry
    from repro.models.common import axes_tree, init_tree
    from repro.optim import adamw
    from repro.runtime import sharding as shd

    arch = registry.get(arch_name)
    shape = registry.SHAPES[shape_name]
    multi = mesh_kind == "multi"
    require_devices(512 if multi else 256)
    mesh = make_production_mesh(multi_pod=multi)
    model_axis = mesh.shape["model"]

    cfg = arch.config.pad_for_mesh(model_axis)
    if photonic:
        cfg = dataclasses.replace(
            cfg,
            photonic=DPUConfig(
                organization=photonic_org, bits=4, datarate_gs=5.0
            ),
            photonic_backend="ref",
            photonic_scope=photonic_scope,
        )
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    # Engine-routed photonic GEMMs: constructing the engine here validates
    # the operating point + site policy before any lowering work.
    from repro.models.common import engine_from_model_config

    eng = engine_from_model_config(cfg)

    defs = arch.param_defs(cfg)
    param_sds = jax.eval_shape(
        lambda k: init_tree(defs, k, cfg.param_dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )

    out: dict = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_kind,
        "variant": variant,
        "kind": shape.kind,
        "padded_heads": cfg.padded_heads,
        "padded_vocab": cfg.padded_vocab,
        "num_kv_heads_effective": cfg.num_kv_heads,
        "param_count": sum(
            int(jnp.prod(jnp.array(l.shape))) for l in jax.tree.leaves(param_sds)
        ),
        "photonic_engine": None if eng is None else eng.describe().to_dict(),
    }

    def build(bcfg):
        """(jitted step fn, SDS args) for this cell at config `bcfg`."""
        bdefs = arch.param_defs(bcfg)
        baxes = axes_tree(bdefs)
        bsds = jax.eval_shape(
            lambda k: init_tree(bdefs, k, bcfg.param_dtype),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        p_sh = shd.tree_shardings(mesh, bsds, baxes)
        if shape.kind == "train" and dp_shardmap:
            from repro.compat import NamedSharding, PartitionSpec
            from repro.runtime.dp_step import make_dp_train_step

            opt_cfg = adamw.AdamWConfig()
            opt_sds = jax.eval_shape(adamw.init, bsds)
            batch_sds, _ = arch.train_batch_spec(bcfg, shape)
            step = make_dp_train_step(
                lambda p, b: arch.loss(p, b, bcfg), opt_cfg, mesh,
                compress_grads=dp_compress,
            )
            repl = NamedSharding(mesh, PartitionSpec())
            bsh = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
            jitted = jax.jit(
                step,
                in_shardings=(
                    jax.tree.map(lambda _: repl, bsds),
                    jax.tree.map(lambda _: repl, opt_sds),
                    jax.tree.map(lambda _: bsh, batch_sds),
                ),
                donate_argnums=(0, 1),
            )
            return jitted, (bsds, opt_sds, batch_sds)
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            opt_sds = jax.eval_shape(adamw.init, bsds)
            # ZeRO-1 by default: moments shard over (pod, data) in addition
            # to the param's own TP axes — see EXPERIMENTS.md §Perf.
            dp_degree = 1
            for ax in ("pod", "data"):
                dp_degree *= mesh.shape.get(ax, 1)
            moment_axes = shd.zero1_axes(baxes, bsds, dp_degree) if zero1 else baxes
            opt_sh = shd.tree_shardings(
                mesh, opt_sds, adamw.opt_state_axes(moment_axes)
            )
            batch_sds, batch_axes = arch.train_batch_spec(bcfg, shape)
            batch_sh = shd.tree_shardings(mesh, batch_sds, batch_axes)

            moment_sh_p = shd.tree_shardings(mesh, bsds, moment_axes)

            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(arch.loss)(params, batch, bcfg)
                if zero1:
                    # ZeRO-1: slice grads+params to the moment sharding so the
                    # f32 update math runs at 1/dp size; params re-gather via
                    # the jit out_sharding.
                    grads = jax.lax.with_sharding_constraint(grads, moment_sh_p)
                    params = jax.lax.with_sharding_constraint(params, moment_sh_p)
                params, opt_state, metrics = adamw.update(
                    opt_cfg, params, grads, opt_state
                )
                return params, opt_state, loss, metrics["grad_norm"]

            jitted = jax.jit(
                train_step,
                in_shardings=(p_sh, opt_sh, batch_sh),
                out_shardings=(p_sh, opt_sh, None, None),
                donate_argnums=(0, 1),
            )
            args = (bsds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            batch_sds, batch_axes = arch.prefill_batch_spec(bcfg, shape)
            batch_sh = shd.tree_shardings(mesh, batch_sds, batch_axes)

            def prefill_step(params, batch):
                return arch.prefill(params, batch, bcfg, shape.seq_len)

            jitted = jax.jit(prefill_step, in_shardings=(p_sh, batch_sh))
            args = (bsds, batch_sds)
        else:  # decode
            (tok_sds, tok_axes), (cache_sds, cache_axes) = arch.decode_specs(
                bcfg, shape
            )
            tok_sh = shd.tree_shardings(mesh, tok_sds, tok_axes)
            cache_sh = shd.tree_shardings(mesh, cache_sds, cache_axes)

            def serve_step(params, token, cache):
                return arch.decode(params, token, cache, bcfg)

            jitted = jax.jit(
                serve_step,
                in_shardings=(p_sh, tok_sh, cache_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(2,),
            )
            args = (bsds, tok_sds, cache_sds)
        return jitted, args

    if not skip_main:
        with shd.use_rules(mesh, cfg.logical_rules):
            jitted, args = build(cfg)
            t0 = time.time()
            lowered = jitted.lower(*args)
            out["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            out["compile_s"] = round(time.time() - t0, 2)

        out["sharding_fallbacks"] = [
            {
                "shape": list(s),
                "logical": n,
                "mesh_axis": str(a),
                "dim": d,
                "axis_size": z,
            }
            for (s, n, a, d, z) in shd.fallback_log()
        ]

        ma = compiled.memory_analysis()
        for field in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            out[field] = getattr(ma, field, None)

        ca = (compiled.cost_analysis() or {})
        out["hlo_flops_per_device"] = ca.get("flops")
        out["hlo_bytes_per_device"] = ca.get("bytes accessed")

        hlo = compiled.as_text()
        out["hlo_chars"] = len(hlo)
        out.update(hlo_analysis.collective_summary(hlo))

    # ---- layer-ladder cost analysis (exact FLOPs/bytes; see Arch.ladder) ----
    try:
        ladder_steps = {}
        flops_total = 0.0
        bytes_total = 0.0
        dot_total = 0.0
        for step_name, ov, coeff in arch.ladder(cfg):
            lcfg = dataclasses.replace(cfg, **ov)
            with shd.use_rules(mesh, lcfg.logical_rules):
                lj, largs = build(lcfg)
                lcomp = lj.lower(*largs).compile()
            lca = (lcomp.cost_analysis() or {})
            dot_b = hlo_analysis.matmul_traffic_bytes(lcomp.as_text())
            ladder_steps[step_name] = {
                "coeff": coeff,
                "flops": lca.get("flops"),
                "bytes": lca.get("bytes accessed"),
                "dot_bytes": dot_b,
            }
            flops_total += coeff * (lca.get("flops") or 0.0)
            bytes_total += coeff * (
                lca.get("bytes") or lca.get("bytes accessed") or 0.0
            )
            dot_total += coeff * dot_b
        out["ladder"] = ladder_steps
        out["flops_per_device_exact"] = flops_total
        out["bytes_per_device_exact"] = bytes_total
        # fusion-optimal HBM traffic: dot operands/outputs + step args once
        out["dot_bytes_ladder_only"] = dot_total
        out["dot_bytes_per_device_exact"] = dot_total + (
            out.get("argument_size_in_bytes") or 0.0
        )
    except Exception:
        out["ladder_error"] = traceback.format_exc()[-3000:]
    if save_hlo and not skip_main:
        HLO_DIR.mkdir(parents=True, exist_ok=True)
        p = HLO_DIR / (
            _cell_path(arch_name, shape_name, mesh_kind, variant).stem + ".hlo.gz"
        )
        with gzip.open(p, "wt") as f:
            f.write(hlo)
        out["hlo_path"] = str(p)
    out["ok"] = True
    return out


# ---------------------------------------------------------------------------
# Sweep driver — one subprocess per cell (isolation + JSON cache)
# ---------------------------------------------------------------------------
def all_cells():
    from repro.models import registry

    cells = []
    for arch_name in registry.names():
        arch = registry.get(arch_name)
        for shape_name in registry.SHAPES:
            skipped = shape_name in arch.skip_shapes
            for mesh_kind in ("single", "multi"):
                cells.append((arch_name, shape_name, mesh_kind, skipped))
    return cells


def sweep(save_hlo: bool, timeout_s: int = 3600, force: bool = False):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    cells = all_cells()
    todo = []
    for arch, shp, mesh, skipped in cells:
        path = _cell_path(arch, shp, mesh)
        if skipped:
            path.write_text(
                json.dumps(
                    {
                        "arch": arch, "shape": shp, "mesh": mesh, "ok": True,
                        "skipped": True,
                        "reason": "shape inapplicable to arch (DESIGN.md §6)",
                    },
                    indent=1,
                )
            )
            continue
        if path.exists() and not force:
            try:
                if json.loads(path.read_text()).get("ok"):
                    continue
            except Exception:
                pass
        todo.append((arch, shp, mesh))

    print(f"[sweep] {len(todo)} cells to run ({len(cells)} total)", flush=True)
    for i, (arch, shp, mesh) in enumerate(todo):
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shp, "--mesh", mesh,
        ]
        if save_hlo:
            cmd.append("--save-hlo")
        t0 = time.time()
        print(f"[sweep {i+1}/{len(todo)}] {arch} x {shp} x {mesh} ...", flush=True)
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout_s,
                env=_profile.child_env(),
            )
            if r.returncode != 0:
                _cell_path(arch, shp, mesh).write_text(
                    json.dumps(
                        {
                            "arch": arch, "shape": shp, "mesh": mesh, "ok": False,
                            "error": (r.stderr or "")[-4000:],
                        },
                        indent=1,
                    )
                )
                print(f"  FAILED ({time.time()-t0:.0f}s)", flush=True)
            else:
                print(f"  ok ({time.time()-t0:.0f}s)", flush=True)
        except subprocess.TimeoutExpired:
            _cell_path(arch, shp, mesh).write_text(
                json.dumps(
                    {"arch": arch, "shape": shp, "mesh": mesh, "ok": False,
                     "error": f"timeout after {timeout_s}s"}, indent=1,
                )
            )
            print("  TIMEOUT", flush=True)


def annotate_sweep(timeout_s: int = 3600):
    """Merge newly added ladder metrics into finished cells (subprocess per
    cell via --annotate-cell; skips cells that already have them)."""
    todo = []
    for p in sorted(RESULTS_DIR.glob("*__base.json")):
        d = json.loads(p.read_text())
        if (
            d.get("ok")
            and not d.get("skipped")
            and "dot_bytes_per_device_exact" not in d
        ):
            todo.append((d["arch"], d["shape"], d["mesh"]))
    print(f"[annotate] {len(todo)} cells", flush=True)
    for i, (arch, shp, mesh) in enumerate(todo):
        print(f"[annotate {i+1}/{len(todo)}] {arch} x {shp} x {mesh}", flush=True)
        cmd = [
            sys.executable,
            "-m",
            "repro.launch.dryrun",
            "--arch",
            arch,
            "--shape",
            shp,
            "--mesh",
            mesh,
            "--annotate-cell",
        ]
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout_s,
                env=_profile.child_env(),
            )
            print(
                "  ok" if r.returncode == 0 else f"  FAILED: {(r.stderr or '')[-300:]}",
                flush=True,
            )
        except subprocess.TimeoutExpired:
            print("  TIMEOUT", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument(
        "--shape", choices=["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    )
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--annotate", action="store_true")
    ap.add_argument("--annotate-cell", action="store_true")
    ap.add_argument("--photonic", action="store_true")
    ap.add_argument(
        "--photonic-scope",
        default="weights",
        choices=["none", "weights"],
        help="which weight GEMMs the engine routes (with --photonic)",
    )
    ap.add_argument(
        "--photonic-org",
        default="SMWA",
        help="DPU organization: a registered name or any valid "
        "S/A/M/W order string (with --photonic)",
    )
    ap.add_argument(
        "--dp-shardmap",
        action="store_true",
        help="shard_map-pinned DP train step (replicated params)",
    )
    ap.add_argument(
        "--dp-compress",
        action="store_true",
        help="int8-compressed gradient all-reduce (with --dp-shardmap)",
    )
    ap.add_argument(
        "--no-zero1",
        action="store_true",
        help="replicate optimizer moments across data (ablation)",
    )
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default=None)
    ap.add_argument(
        "--override",
        action="append",
        default=[],
        help="cfg overrides, e.g. --override remat=False",
    )
    args = ap.parse_args()

    if args.sweep:
        sweep(args.save_hlo, force=args.force)
        return
    if args.annotate:
        annotate_sweep()
        return
    if args.annotate_cell:
        path = _cell_path(args.arch, args.shape, args.mesh, "base")
        existing = json.loads(path.read_text())
        _profile.apply()
        out = run_cell(args.arch, args.shape, args.mesh, skip_main=True)
        out["dot_bytes_per_device_exact"] = out.get("dot_bytes_ladder_only", 0.0) + (
            existing.get("argument_size_in_bytes") or 0.0
        )
        existing.update(out)
        path.write_text(json.dumps(existing, indent=1))
        return

    assert args.arch and args.shape, "--arch and --shape required (or --sweep)"
    # Tuned launch profile (log hygiene, persistent compilation cache) for
    # the cell's own process; the sweep parents above stay off jax.
    _profile.apply()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            overrides[k] = json.loads(v)
        except json.JSONDecodeError:
            overrides[k] = v
    variant = args.variant or ("photonic" if args.photonic else "base")
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    path = _cell_path(args.arch, args.shape, args.mesh, variant)
    try:
        out = run_cell(
            args.arch, args.shape, args.mesh,
            photonic=args.photonic, photonic_scope=args.photonic_scope,
            photonic_org=args.photonic_org,
            save_hlo=args.save_hlo,
            overrides=overrides or None, variant=variant,
            zero1=not args.no_zero1,
            dp_shardmap=args.dp_shardmap, dp_compress=args.dp_compress,
        )
    except Exception:
        out = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "variant": variant, "ok": False, "error": traceback.format_exc()[-6000:],
        }
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    if not out.get("ok"):
        sys.exit(1)


if __name__ == "__main__":
    main()
