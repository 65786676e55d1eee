import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and extract memory/cost/collective statistics.

No real allocation happens: parameters, optimizer state, caches and batches
are ShapeDtypeStructs with committed shardings. A cell passes when
``.lower().compile()`` succeeds and fits; its cost_analysis/HLO feed the
roofline (benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --cell train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out benchmarks/artifacts]
"""
import argparse
import json
import re
import traceback

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.optim import adamw
from repro.sharding import ctx
from repro.train import loop as train_loop

COLLECTIVE_RE = re.compile(
    r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4, "s8": 1,
    "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2, "u16": 2, "f8e4m3fn": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective op in compiled HLO."""
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[kind] = out.get(kind, 0.0) + nbytes
    return out


def _tree_bytes(tree) -> float:
    return sum(
        float(jnp.dtype(s.dtype).itemsize) * float(jnp.prod(jnp.asarray(s.shape)))
        if s.shape else float(jnp.dtype(s.dtype).itemsize)
        for s in jax.tree.leaves(tree)
    )


def lower_cell(arch_id: str, cell: str, mesh):
    """Returns (lowered, aux) for one (arch, cell) on ``mesh``."""
    cfg = configs.get(arch_id)
    model = api.build_model(cfg)
    kind = api.SHAPE_CELLS[cell]["kind"]
    pstructs = model.param_structs(mesh)

    if kind == "train":
        opt_cfg = adamw.AdamWConfig(state_bits=cfg.opt_state_bits)
        step = train_loop.make_train_step(model, opt_cfg)
        ostructs = train_loop.opt_state_structs(model, mesh, opt_cfg)
        batch = model.input_specs(cell, mesh)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(pstructs, ostructs, batch)
        aux = dict(
            param_bytes=_tree_bytes(pstructs), opt_bytes=_tree_bytes(ostructs),
            n_params=model.n_params,
        )
    elif kind == "prefill":
        batch = model.input_specs(cell, mesh)
        s = api.SHAPE_CELLS[cell]["seq"]
        max_len = s + cfg.meta_tokens
        fn = lambda p, b: model.prefill(p, b, max_len)
        lowered = jax.jit(fn).lower(pstructs, batch)
        aux = dict(param_bytes=_tree_bytes(pstructs), n_params=model.n_params)
    else:  # decode
        c = api.SHAPE_CELLS[cell]
        cache = model.cache_structs(cell, mesh)
        toks = model.input_specs(cell, mesh)
        lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            pstructs, cache, toks["tokens"]
        )
        aux = dict(
            param_bytes=_tree_bytes(pstructs), cache_bytes=_tree_bytes(cache),
            n_params=model.n_params,
        )
    return lowered, aux


def run_cell(arch_id: str, cell: str, multi_pod: bool, out_dir: str) -> dict:
    cfg = configs.get(arch_id)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch_id, "cell": cell, "mesh": mesh_name}
    skip = api.cell_skip_reason(cfg, cell)
    if skip:
        rec["status"] = "skip"
        rec["reason"] = skip
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            slug = arch_id.replace(".", "p")
            path = os.path.join(out_dir, f"dryrun_{slug}_{cell}_{mesh_name}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        print(f"[SKIP] {arch_id} {cell} {mesh_name}: {skip}")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        with ctx.use_mesh(mesh):
            with obs.timed_section("dryrun.lower") as lower_sec:
                lowered, aux = lower_cell(arch_id, cell, mesh)
            with obs.timed_section("dryrun.compile") as compile_sec:
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            coll = collective_bytes(hlo)
        rec.update(
            status="ok",
            lower_s=round(lower_sec.dur_s, 2),
            compile_s=round(compile_sec.dur_s, 2),
            devices=mesh.devices.size,
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            collective_bytes=coll,
            memory=dict(
                argument=mem.argument_size_in_bytes,
                output=mem.output_size_in_bytes,
                temp=mem.temp_size_in_bytes,
                alias=mem.alias_size_in_bytes,
                generated_code=mem.generated_code_size_in_bytes,
            ),
            **aux,
        )
        print(
            f"[OK] {arch_id:24s} {cell:12s} {mesh_name}: "
            f"flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
            f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
            f"compile={rec['compile_s']}s"
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a finding
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch_id} {cell} {mesh_name}: {rec['error'][:200]}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        slug = arch_id.replace(".", "p")
        path = os.path.join(out_dir, f"dryrun_{slug}_{cell}_{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--cell", choices=list(api.SHAPE_CELLS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args()

    cells = [args.cell] if args.cell else list(api.SHAPE_CELLS)
    archs = [args.arch] if args.arch else configs.ARCH_IDS
    if not (args.all or args.arch):
        ap.error("pass --arch or --all")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for mp in meshes:
        for a in archs:
            for c in cells:
                results.append(run_cell(a, c, mp, args.out))
    ok = sum(r["status"] == "ok" for r in results)
    skip = sum(r["status"] == "skip" for r in results)
    fail = sum(r["status"] == "fail" for r in results)
    print(f"\n== dry-run summary: {ok} ok / {skip} skip / {fail} fail ==")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
