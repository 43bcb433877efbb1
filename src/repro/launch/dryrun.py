import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (architecture x input-shape x
mesh) cell on the production meshes and dump the roofline inputs.

This is how the distribution config is proven coherent without hardware
(DESIGN.md §6): a cell FAILS here on sharding mismatch, OOM-at-compile, or an
unsupported collective — all bugs in our system, not environment artifacts.

Per cell the artifact JSON records:
  * ``memory_analysis``  — per-device argument/output/temp bytes (fits-HBM
    proof; XLA reports post-SPMD per-partition sizes),
  * ``cost_analysis``    — per-device HLO FLOPs + bytes accessed,
  * ``collectives``      — bytes + op counts parsed from the partitioned HLO
    (cost_analysis does not expose collective traffic),
  * ``model_flops``      — analytic 6·N·D (6·N_active·D for MoE) for the
    useful-compute ratio.

Cost fidelity: cells lower with ``scan_layers=False`` (unrolled stacks)
because XLA counts while-loop bodies ONCE — a scanned 62-layer stack would
under-report FLOPs and collective bytes by 62x (DESIGN.md §8).

Run:  python -m repro.launch.dryrun --all            (spawns per-cell procs)
      python -m repro.launch.dryrun --cell qwen2-1.5b:train_4k:single
      python -m repro.launch.dryrun --arch mamba2-370m --mesh multi

(No ``from __future__ import annotations`` here: the XLA_FLAGS lines above
must be the first statements of the module, before any import.)
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, LM_SHAPES, get_config, get_shape
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.parallel import sharding as shd
from repro.parallel.collectives import collective_bytes
from repro.train import optim
from repro.train.loop import TrainState, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# TPU v5e-class constants (roofline; see benchmarks/roofline.py)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link


def make_run_config(shape: ShapeConfig, multi_pod: bool, **overrides) -> RunConfig:
    # Single-pod cells unroll layer stacks for cost-faithful HLO (roofline
    # reads them; module docstring).  Multi-pod cells keep the scanned stacks:
    # their job is proving the pod axis shards + memory fit, and scan compiles
    # ~depth-times faster — the roofline table is single-pod only.
    base = dict(tp=16, dp=32 if multi_pod else 16,
                param_dtype="float32" if shape.kind == "train" else "bfloat16",
                compute_dtype="bfloat16",
                remat=shape.kind == "train",
                scan_layers=multi_pod,
                use_flash_kernel=False)      # jnp path: Pallas is TPU-only
    base.update(overrides)
    return RunConfig(**base)


def _shardings(mesh, tree_of_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda s: isinstance(s, P))


def _dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def _count_params(params_shape, cfg: ModelConfig) -> dict:
    """Total + active (MoE-aware) parameter counts for MODEL_FLOPS."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    total = active = embed = 0
    for path, leaf in flat:
        keys = "/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                        for e in path)
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += n
        if "embed/w" in keys and "unembed" not in keys:
            embed += n
            continue
        if cfg.n_experts and "moe/w_" in keys:
            active += n * cfg.top_k / cfg.n_experts
        else:
            active += n
    return {"total": total, "active": int(active), "embed_table": embed}


def _record(compiled, lowered, *, n_devices: int) -> dict:
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    col = collective_bytes(txt)
    return {
        "n_devices": n_devices,
        "flops_per_device": float(ca.get("flops", -1.0)),
        "bytes_per_device": float(ca.get("bytes accessed", -1.0)),
        "utilization_transcendentals": float(ca.get("transcendentals", 0.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "collectives": col,
        "hlo_chars": len(txt),
    }


# ---------------------------------------------------------------------------
# cell builders: return (fn, example_args_sds, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_lm_cell(arch: str, shape: ShapeConfig, multi_pod: bool,
                  run_overrides: Optional[dict] = None):
    cfg = get_config(arch)
    run = make_run_config(shape, multi_pod, **(run_overrides or {}))
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = _dp_axes(multi_pod)
    dp_total = int(np.prod([mesh.shape[a] for a in dp]))
    api = registry.get_model(cfg)
    params_shape = registry.params_specs(cfg, run)
    if shape.kind != "train":
        # serving runs on cast weights (RunConfig.param_dtype): halves the
        # param bytes and every FSDP gather vs the fp32 training master
        pdt = jnp.dtype(run.param_dtype)
        params_shape = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, pdt)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, params_shape)
    pspecs = shd.param_partition_specs(
        params_shape, fsdp_axis="data", fsdp_size=mesh.shape["data"],
        tp_size=mesh.shape["model"])
    nparams = _count_params(params_shape, cfg)

    if shape.kind == "train":
        opt = optim.adamw(optim.warmup_cosine_schedule(3e-4, 2000, 100_000),
                          weight_decay=0.1, max_grad_norm=1.0)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        state_sds = TrainState(params=params_shape, opt=opt_shape, gc=None,
                               step=jax.ShapeDtypeStruct((), jnp.int32))
        state_specs = TrainState(
            params=pspecs, opt=type(opt_shape)(step=P(), mu=pspecs, nu=pspecs),
            gc=None, step=P())
        batch_sds = registry.train_batch_specs(cfg, run, shape)
        batch_specs = {k: P(dp, *([None] * (len(v.shape) - 1)))
                       for k, v in batch_sds.items()}
        fn = make_train_step(cfg, run, opt)
        args = (state_sds, batch_sds)
        in_specs = (state_specs, batch_specs)
        out_specs = (state_specs, None)
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        batch_sds = registry.prefill_specs(cfg, run, shape)
        batch_specs = {k: P(dp, *([None] * (len(v.shape) - 1)))
                       for k, v in batch_sds.items()}

        def fn(params, batch):
            extra = {k: v for k, v in batch.items() if k != "tokens"}
            return api.forward(params, cfg, run, batch["tokens"], **extra)
        args = (params_shape, batch_sds)
        in_specs = (pspecs, batch_specs)
        out_specs = P(dp, None, "model")
        tokens = shape.global_batch * shape.seq_len
    else:  # decode
        spec_d = registry.decode_specs(cfg, run, shape)
        state_specs = shd.decode_state_specs(
            spec_d["state"], multi_pod, batch=shape.global_batch,
            dp_size=dp_total, seq_len=shape.seq_len,
            tp_size=mesh.shape["model"])
        tok_spec = (P(dp, None) if shape.global_batch >= dp_total
                    else P(None, None))

        def fn(params, token, state):
            return api.decode_step(params, cfg, run, token, state)
        args = (params_shape, spec_d["token"], spec_d["state"])
        in_specs = (pspecs, tok_spec, state_specs)
        logits_spec = (P(dp, None, "model") if shape.global_batch >= dp_total
                       else P(None, None, "model"))
        out_specs = (logits_spec, state_specs)
        tokens = shape.global_batch
    return dict(cfg=cfg, run=run, mesh=mesh, fn=fn, args=args,
                in_specs=in_specs, out_specs=out_specs, nparams=nparams,
                tokens=tokens)


# -- compressor cells (the paper's own steps on the mesh) --------------------

COMPRESSOR_SHAPES = {
    # name: (hyper-blocks per step, k, block_elems, latent)
    "train_hb": (8192, 10, 4640, 128),     # S3D geometry (58*5*4*4 blocks)
    "gae_select": (65536, 80, 0, 0),       # GAE at 5*4*4 per-species blocks
}


def build_compressor_cell(shape_name: str, multi_pod: bool):
    from repro.core import gae as gae_mod
    from repro.core import hbae as hbae_mod
    from repro.core.training import hbae_loss

    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = _dp_axes(multi_pod)
    run = make_run_config(ShapeConfig("train_4k", 0, 0, "train"), multi_pod)

    if shape_name == "train_hb":
        n, k, d, latent = COMPRESSOR_SHAPES["train_hb"]
        opt = optim.adam(1e-3)
        params_shape = jax.eval_shape(
            lambda key: hbae_mod.hbae_init(key, in_dim=d, k=k, emb=128,
                                           hidden=256, latent=latent),
            jax.random.PRNGKey(0))
        opt_shape = jax.eval_shape(opt.init, params_shape)
        pspecs = jax.tree.map(lambda _: P(), params_shape)
        ospecs = type(opt_shape)(step=P(), mu=pspecs, nu=pspecs)

        def fn(params, opt_state, x):
            loss, grads = jax.value_and_grad(hbae_loss)(params, x)
            params, opt_state, _ = opt.update(grads, opt_state, params)
            return params, opt_state, loss
        x_sds = jax.ShapeDtypeStruct((n, k, d), jnp.float32)
        args = (params_shape, opt_shape, x_sds)
        in_specs = (pspecs, ospecs, P(dp, None, None))
        out_specs = (pspecs, ospecs, P())
        nparams = {"total": sum(int(np.prod(l.shape))
                                for l in jax.tree.leaves(params_shape)),
                   "active": 0, "embed_table": 0}
        nparams["active"] = nparams["total"]
        tokens = n * k * d   # "tokens" = elements compressed per step
        analytic_flops = 6.0 * nparams["total"] * n   # fwd+bwd per hyperblock
    else:  # gae_select: distributed PCA + one-shot batched Algorithm 1
        n, d, _, _ = COMPRESSOR_SHAPES["gae_select"]

        def fn(residuals):
            cov = residuals.T @ residuals        # GSPMD all-reduces over dp
            _, vecs = jnp.linalg.eigh(cov)
            basis = vecs[:, ::-1]
            sel = gae_mod.gae_select(residuals, basis, tau=1e-2,
                                     bin_size=1e-3)
            corrected, err = gae_mod.gae_correct(residuals, basis, sel,
                                                 bin_size=1e-3)
            return corrected, sel.m, err
        args = (jax.ShapeDtypeStruct((n, d), jnp.float32),)
        in_specs = (P(dp, None),)
        out_specs = (P(dp, None), P(dp), P(dp))
        nparams = {"total": 0, "active": 0, "embed_table": 0}
        tokens = n * d
        # analytic: project (2nd^2) + reconstruct (2nd^2) + covariance (2nd^2)
        analytic_flops = 6.0 * n * d * d
    cfg = ModelConfig(arch=f"compressor-{shape_name}", family="compressor",
                      n_layers=0, d_model=0, n_heads=0, n_kv_heads=0, d_ff=0,
                      vocab=0)
    return dict(cfg=cfg, run=run, mesh=mesh, fn=fn, args=args,
                in_specs=in_specs, out_specs=out_specs, nparams=nparams,
                tokens=tokens, analytic_flops=analytic_flops)


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str,
             out_dir: str = ARTIFACT_DIR, tag: str = "",
             run_overrides: Optional[dict] = None) -> dict:
    multi_pod = mesh_name == "multi"
    t0 = time.time()
    if arch.startswith("compressor"):
        cell = build_compressor_cell(shape_name, multi_pod)
        shape_kind = "compressor"
    else:
        shape = get_shape(shape_name)
        cfg = get_config(arch)
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "status": "skipped", "reason": why}
        cell = build_lm_cell(arch, shape, multi_pod, run_overrides)
        shape_kind = shape.kind
    mesh = cell["mesh"]
    dp_total = int(np.prod([mesh.shape[a] for a in _dp_axes(multi_pod)]))
    kv_seq = (shape_kind == "decode"
              and not arch.startswith("compressor")
              and get_shape(shape_name).global_batch < dp_total)
    with jax.set_mesh(mesh):
        with shd.activation_sharding(
                shd.activation_rules(multi_pod, sp=cell["run"].sp,
                                     kv_seq_shard=kv_seq)):
            jitted = jax.jit(cell["fn"],
                             in_shardings=_shardings(mesh, cell["in_specs"]),
                             out_shardings=_shardings(mesh, cell["out_specs"]))
            lowered = jitted.lower(*cell["args"])
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    rec = _record(compiled, lowered, n_devices=mesh.size)
    dump = os.environ.get("REPRO_DUMP_HLO")
    if dump:
        with open(dump, "w") as f:
            f.write(compiled.as_text())
    # kind-aware analytic FLOPs (benchmarks.roofline recomputes the same way)
    factor = 6.0 if shape_kind in ("train", "compressor") else 2.0
    mflops = cell.get("analytic_flops",
                      factor * cell["nparams"]["active"] * cell["tokens"])
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape_kind, "status": "ok",
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "params": cell["nparams"], "tokens_per_step": cell["tokens"],
        "model_flops": mflops,
        **rec,
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}{tag}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def all_cells() -> list[tuple[str, str, str]]:
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in LM_SHAPES:
            for mesh_name in ("single", "multi"):
                cells.append((arch, shape.name, mesh_name))
    for shape_name in COMPRESSOR_SHAPES:
        for mesh_name in ("single", "multi"):
            cells.append(("compressor", shape_name, mesh_name))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch:shape:mesh  (single process)")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--tag", default="")
    ap.add_argument("--overrides", default="",
                    help='JSON RunConfig overrides, e.g. {"remat": false}')
    ap.add_argument("--jobs", type=int, default=3,
                    help="concurrent per-cell compile subprocesses")
    args = ap.parse_args()

    if args.list:
        for c in all_cells():
            print(":".join(c))
        return 0

    overrides = json.loads(args.overrides) if args.overrides else None

    if args.cell:
        arch, shape, mesh = args.cell.split(":")
        try:
            r = run_cell(arch, shape, mesh, args.out, args.tag, overrides)
        except Exception:
            traceback.print_exc()
            print(f"FAIL {args.cell}")
            return 1
        print(json.dumps(r, indent=1))
        return 0

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    if args.mesh:
        cells = [c for c in cells if c[2] == args.mesh]

    # one subprocess per cell: fresh XLA state, crash isolation
    import concurrent.futures as cf

    def one(cell):
        arch, shape, mesh = cell
        spec = f"{arch}:{shape}:{mesh}"
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--cell", spec,
               "--out", args.out, "--tag", args.tag]
        if args.overrides:
            cmd += ["--overrides", args.overrides]
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        dt = time.time() - t0
        if proc.returncode != 0:
            return spec, None, dt, proc.stderr[-2000:]
        try:
            last = json.loads(proc.stdout[proc.stdout.index("{"):])
            status = last.get("status")
        except Exception:
            status = "ok?"
        return spec, status, dt, ""

    failures = []
    with cf.ThreadPoolExecutor(max_workers=max(args.jobs, 1)) as pool:
        for spec, status, dt, err in pool.map(one, cells):
            if status is None:
                failures.append(spec)
                print(f"[FAIL {dt:6.1f}s] {spec}\n{err}", flush=True)
            else:
                print(f"[{status:>7} {dt:6.1f}s] {spec}", flush=True)
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells passed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
