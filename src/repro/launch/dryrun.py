import os
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()
# ^ MUST precede every other import: jax locks the device count at first
# init. Merged into any pre-set XLA_FLAGS so a caller that already forces
# a device count (the 8-device subprocess test) keeps its own, while
# unrelated flags don't lose the 512-device emulation. Do NOT replicate
# this in conftest/pyproject — tests see 1 device.

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import all_cells, get_arch  # noqa: E402
from repro.configs.shapes import input_specs  # noqa: E402
from repro.dist.sharding import (activation_rules, input_shardings,  # noqa: E402
                                 opt_shardings, param_shardings)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (adapt_config, make_serve_step,  # noqa: E402
                                make_train_step, state_specs)

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in partitioned HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        for kind in COLLECTIVES:
            # match op invocations: "%x = TYPE all-reduce(" or fusion roots
            if f" {kind}(" not in ls and f" {kind}-start(" not in ls:
                continue
            lhs = ls.split("=", 1)
            if len(lhs) != 2:
                continue
            m = _SHAPE_RE.findall(lhs[1].split(kind)[0])
            nbytes = 0
            for dt, dims in m:
                if dt not in DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * DTYPE_BYTES[dt]
            out[kind]["count"] += 1
            out[kind]["bytes"] += nbytes
            break
    return out


def with_depth(arch, cfg, depth: int | None):
    """Reduced-depth config variant for loop-cost extrapolation (XLA's
    cost_analysis counts while-loop bodies once, ignoring trip count)."""
    import dataclasses
    if depth is None:
        return cfg, None
    # depth probes unroll the layer scan so HLO flops count every layer
    if arch.family == "lm":
        return (dataclasses.replace(cfg, n_layers=depth, unroll=True),
                cfg.n_layers)
    if arch.family == "gnn":
        return (dataclasses.replace(cfg, n_interactions=depth, unroll=True),
                cfg.n_interactions)
    if hasattr(cfg, "n_blocks"):  # bert4rec
        return (dataclasses.replace(cfg, n_blocks=depth, unroll=True),
                cfg.n_blocks)
    return cfg, None  # no scanned depth: costs are already exact


def lower_cell(arch_id: str, shape: str, mesh, depth: int | None = None,
               variant: str = "tp") -> tuple:
    """Build the step fn + (in_shardings, args) for one cell.

    variant "opt" = beyond-paper optimized config per cell kind:
      - LM train: FSDP/ZeRO-3 sharding (no TP activation all-reduces,
        bf16 weight gathers, two-axis param/opt sharding),
      - LM prefill: attention chunk 512 (halves transient score buffers),
      - recsys retrieval: shard_map per-shard top-k (collective = k per
        shard instead of the full candidate score vector).
    """
    import dataclasses
    arch = get_arch(arch_id)
    cfg, _ = with_depth(arch, adapt_config(arch, shape), depth)
    spec0 = input_specs(arch, shape, cfg)
    kind = spec0["kind"]
    eff = variant
    if variant == "opt":
        eff = "fsdp" if (arch.family == "lm" and kind == "train") else "tp"
        if arch.family == "lm" and kind == "prefill":
            cfg = dataclasses.replace(cfg, attn_chunk=512)
        if arch.family == "lm" and kind == "decode":
            cfg = dataclasses.replace(cfg, kv_quant=True)  # int8 KV
    spec = input_specs(arch, shape, cfg)
    rules = activation_rules(mesh, eff)
    in_sh = input_shardings(arch.family, cfg, mesh, spec, eff)
    if spec["kind"] in ("train", "gnn_mol", "gnn_full", "gnn_sampled"):
        st = state_specs(arch, shape, cfg)
        p_sh = param_shardings(arch.family, cfg, mesh, st["params"], eff)
        step = make_train_step(arch, shape, cfg, rules,
                               grad_shardings=p_sh)
        state_sh = {"params": p_sh, "opt": opt_shardings(p_sh)}
        args = (st, spec["inputs"]["batch"])
        shardings = (state_sh, in_sh["batch"])
        donate = (0,)
    else:
        step = make_serve_step(arch, shape, cfg, rules, mesh=mesh,
                               sharded_topk=(variant == "opt"))
        st = state_specs(arch, shape, cfg)["params"]
        p_sh = param_shardings(arch.family, cfg, mesh, st, eff)
        args = (st,) + tuple(spec["inputs"].values())
        shardings = (p_sh,) + tuple(in_sh[k] for k in spec["inputs"])
        donate = (2,) if spec["kind"] == "decode" else ()
    jitted = jax.jit(step, in_shardings=shardings, donate_argnums=donate)
    return jitted, args


def run_cell(arch_id: str, shape: str, mesh, mesh_name: str,
             force: bool = False, variant: str = "tp") -> dict:
    ART_DIR.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "tp" else f"__{variant}"
    out_path = ART_DIR / f"{mesh_name}__{arch_id}__{shape}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    rec = {"arch": arch_id, "shape": shape, "mesh": mesh_name,
           "variant": variant, "devices": mesh.devices.size, "ok": False}
    t0 = time.time()
    try:
        with mesh:
            jitted, args = lower_cell(arch_id, shape, mesh,
                                      variant=variant)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            coll = collective_bytes(compiled.as_text())
            # Loop-aware cost extrapolation: compile depth-1 and depth-2
            # variants; per-layer cost = f(2) - f(1); total = f(1)+(L-1)*per.
            arch = get_arch(arch_id)
            _, full_depth = with_depth(arch, adapt_config(arch, shape), 1)
            extrap = None
            if full_depth is not None and full_depth > 1:
                probes = []
                for dd in (1, 2):
                    j2, a2 = lower_cell(arch_id, shape, mesh, depth=dd,
                                        variant=variant)
                    c2 = j2.lower(*a2).compile()
                    cost2 = c2.cost_analysis()
                    probes.append({
                        "flops": float(cost2.get("flops", 0.0)),
                        "bytes": float(cost2.get("bytes accessed", 0.0)),
                        "coll": collective_bytes(c2.as_text())})
                L = full_depth

                def lin(a, b):
                    # robust per-layer estimate: f(2)-f(1) unless XLA's
                    # CSE/fusion makes the delta degenerate, then f(2)/2.
                    per = b - a
                    if per <= 0.25 * b:
                        per = b / 2.0
                    return max(a - per, 0.0) + L * per

                extrap = {
                    "depth": L,
                    "flops": lin(probes[0]["flops"], probes[1]["flops"]),
                    "bytes_accessed": lin(probes[0]["bytes"],
                                          probes[1]["bytes"]),
                    "collectives": {
                        k: {"bytes": lin(probes[0]["coll"][k]["bytes"],
                                         probes[1]["coll"][k]["bytes"])}
                        for k in probes[0]["coll"]}}
        rec.update(
            ok=True, lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory={k: int(getattr(mem, k)) for k in
                    ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes")
                    if hasattr(mem, k)},
            flops=float(cost.get("flops", -1.0)),
            bytes_accessed=float(cost.get("bytes accessed", -1.0)),
            collectives=coll, extrapolated=extrap)
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_path.write_text(json.dumps(rec, indent=1))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
    print(f"[{mesh_name}] {arch_id} x {shape}: {status} "
          f"({time.time() - t0:.0f}s)", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="tp")
    args = ap.parse_args()
    cells = [(a, s) for a, s in all_cells()
             if (args.arch in (None, a)) and (args.shape in (None, s))]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multipod2x16x16",
                       make_production_mesh(multi_pod=True)))
    n_ok = n_fail = 0
    for mesh_name, mesh in meshes:
        for arch_id, shape in cells:
            rec = run_cell(arch_id, shape, mesh, mesh_name, args.force,
                           variant=args.variant)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
