"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this produces, with ZERO device allocation
(ShapeDtypeStruct AOT lowering):

  - proof that the sharding config is coherent (compile succeeds on the
    16x16 single-pod mesh AND the 2x16x16 multi-pod mesh);
  - ``memory_analysis()``  -> per-device bytes (does it fit HBM?);
  - ``cost_analysis()``    -> per-device FLOPs / bytes for the roofline;
  - compiled HLO text      -> collective bytes (parsed, see roofline.py);
  - a single-block lowering -> corrects XLA's count-scan-body-once
    accounting (total = full + (L-1) * block).

Results are written as JSON under experiments/dryrun/ and aggregated into
EXPERIMENTS.md by benchmarks/report_roofline.py.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
  python -m repro.launch.dryrun --protocol           # paper-technique step
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, canonical
from repro.configs.shapes import SHAPES, adjust_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh, data_axes
from repro.launch.roofline import (
    analytic_model_flops,
    analyze,
    collective_bytes,
    combine_scan_collectives,
    combine_scan_costs,
)
from repro.launch.train import make_train_step
from repro.models.model import Model, batch_spec
from repro.models.transformer import block_apply_decode, block_apply_full, make_pos_info
from repro.optim import adamw

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _mem_dict(compiled):
    m = compiled.memory_analysis()
    return {
        "argument_bytes": int(m.argument_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
        "total_bytes": int(
            m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes
        ),
    }


def _lower_and_compile(jitted, args, mesh):
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


# ---------------------------------------------------------------------------
# Full-step lowering
# ---------------------------------------------------------------------------


def build_full(
    arch: str,
    shape_name: str,
    mesh,
    microbatches: int = 1,
    fsdp: bool = False,
    overrides: dict | None = None,
):
    """Returns (jitted_fn, arg_structs, cfg, model)."""
    shape = SHAPES[shape_name]
    cfg_overrides = {k: v for k, v in (overrides or {}).items() if not k.startswith("_")}
    cfg = adjust_config(get_config(arch, **cfg_overrides), shape)
    model = Model(cfg)
    p_shapes = model.init_shapes()
    p_sh = shd.params_shardings(p_shapes, mesh, fsdp=fsdp)
    p_args = shd.with_shardings(p_shapes, p_sh)

    if shape.mode == "train":
        moment_dtype = jnp.bfloat16 if (overrides or {}).get("_bf16_moments") else jnp.float32
        opt = adamw(1e-4, moment_dtype=moment_dtype)
        o_shapes = jax.eval_shape(opt.init, p_shapes)
        o_sh = shd.opt_shardings(o_shapes, mesh, p_sh, fsdp=fsdp)
        b_spec = batch_spec(cfg, shape.global_batch, shape.seq_len, "train")
        b_sh = shd.batch_shardings(b_spec, mesh)
        fn = make_train_step(model, opt, microbatches=microbatches)
        jitted = jax.jit(fn, out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
        args = (p_args, shd.with_shardings(o_shapes, o_sh), shd.with_shardings(b_spec, b_sh))
    elif shape.mode == "prefill":
        b_spec = batch_spec(cfg, shape.global_batch, shape.seq_len, "prefill")
        b_sh = shd.batch_shardings(b_spec, mesh)
        jitted = jax.jit(model.prefill)
        args = (p_args, shd.with_shardings(b_spec, b_sh))
    else:  # decode
        c_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        c_sh = shd.cache_shardings(c_shapes, mesh, cfg)
        b_spec = batch_spec(cfg, shape.global_batch, 1, "decode")
        b_sh = shd.batch_shardings(b_spec, mesh)
        jitted = jax.jit(model.decode_step, out_shardings=(None, c_sh), donate_argnums=(1,))
        args = (
            p_args,
            shd.with_shardings(c_shapes, c_sh),
            shd.with_shardings(b_spec, b_sh),
        )
    return jitted, args, cfg, model


# ---------------------------------------------------------------------------
# Single-block lowering (scan cost correction)
# ---------------------------------------------------------------------------


def build_block(
    arch: str, shape_name: str, mesh, fsdp: bool = False, overrides: dict | None = None
):
    shape = SHAPES[shape_name]
    cfg_overrides = {k: v for k, v in (overrides or {}).items() if not k.startswith("_")}
    cfg = adjust_config(get_config(arch, **cfg_overrides), shape)
    model = Model(cfg)
    p_shapes = model.init_shapes()
    lp_shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), p_shapes["layers"]
    )
    lp_sh = shd.params_shardings(lp_shapes, mesh, fsdp=fsdp)
    lp_args = shd.with_shardings(lp_shapes, lp_sh)
    dp = data_axes(mesh)
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]
    B = shape.global_batch
    S = shape.seq_len if shape.mode != "decode" else 1
    from jax.sharding import NamedSharding, PartitionSpec as P

    b_ax = dp if B % dsize == 0 else None
    x_sh = NamedSharding(mesh, P(b_ax, None, None))
    dt = jnp.dtype(cfg.dtype)
    x_arg = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt, sharding=x_sh)

    if shape.mode == "train":

        def block_loss(lp, x):
            pos_info = make_pos_info(cfg, B, S)
            out, aux, _ = block_apply_full(lp, x, cfg, pos_info, False)
            return jnp.sum(out.astype(jnp.float32)) + aux

        if cfg.remat:
            block_loss_fn = jax.checkpoint(block_loss)
        else:
            block_loss_fn = block_loss
        fn = jax.grad(block_loss_fn, argnums=(0, 1))
        jitted = jax.jit(fn, out_shardings=(lp_sh, x_sh))
        args = (lp_args, x_arg)
    elif shape.mode == "prefill":

        def block_fwd(lp, x):
            pos_info = make_pos_info(cfg, B, S)
            out, _, cache = block_apply_full(lp, x, cfg, pos_info, True)
            return out, cache

        jitted = jax.jit(block_fwd, out_shardings=(x_sh, None))
        args = (lp_args, x_arg)
    else:  # decode
        c_shapes = jax.eval_shape(lambda: model.init_cache(B, shape.seq_len))
        c_sh_full = shd.cache_shardings(c_shapes, mesh, cfg)
        cl_shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), c_shapes["layers"]
        )
        cl_sh = jax.tree.map(
            lambda sh: NamedSharding(mesh, P(*sh.spec[1:])), c_sh_full["layers"]
        )
        cl_args = shd.with_shardings(cl_shapes, cl_sh)
        pos_arg = jax.ShapeDtypeStruct(
            (B,), jnp.int32, sharding=NamedSharding(mesh, P(b_ax))
        )
        extra = {}
        if cfg.arch_type != "ssm":
            cp = c_shapes["cache_positions"]
            cp_sh = shd.cache_shardings(c_shapes, mesh, cfg)["cache_positions"]
            extra["cache_positions"] = jax.ShapeDtypeStruct(
                cp.shape, cp.dtype, sharding=cp_sh
            )

        def block_dec(lp, x, cache_l, pos, cache_positions=None):
            pos_info = {"pos": pos}
            if cache_positions is not None:
                pos_info["cache_positions"] = cache_positions
            return block_apply_decode(lp, x, cfg, cache_l, pos_info)

        jitted = jax.jit(block_dec, out_shardings=(x_sh, cl_sh))
        args = (lp_args, x_arg, cl_args, pos_arg) + (
            (extra["cache_positions"],) if extra else ()
        )
    return jitted, args, cfg


# ---------------------------------------------------------------------------
# Protocol (paper technique) distributed-step lowering
# ---------------------------------------------------------------------------


def build_protocol(mesh, n_nodes: int = 131072, max_walks: int = 64, bins: int = 512):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import make_sharded_step
    from repro.core.protocol import ProtocolConfig

    pcfg = ProtocolConfig(
        algorithm="decafork+", z0=16, max_walks=max_walks, eps=4.0, eps2=11.0,
        rt_bins=bins,
    )
    axes = data_axes(mesh)
    step = make_sharded_step(mesh, axes, n_nodes, pcfg)
    node_spec = P(axes)
    rep = NamedSharding(mesh, P())
    node_sh2 = NamedSharding(mesh, node_spec)
    i32, f32 = jnp.int32, jnp.float32
    W = max_walks
    max_deg = 16
    args = (
        jax.ShapeDtypeStruct((), i32, sharding=rep),  # t
        jax.ShapeDtypeStruct((W,), i32, sharding=rep),  # pos
        jax.ShapeDtypeStruct((W,), jnp.bool_, sharding=rep),  # active
        jax.ShapeDtypeStruct((W,), i32, sharding=rep),  # track
        jax.ShapeDtypeStruct((n_nodes, W), i32, sharding=node_sh2),  # last_seen
        jax.ShapeDtypeStruct((n_nodes, bins), f32, sharding=node_sh2),  # hist
        jax.ShapeDtypeStruct((n_nodes,), f32, sharding=node_sh2),  # total
        jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep),  # key (raw)
        jax.ShapeDtypeStruct((n_nodes, max_deg), i32, sharding=node_sh2),  # neighbors
        jax.ShapeDtypeStruct((n_nodes,), i32, sharding=node_sh2),  # degrees
        jax.ShapeDtypeStruct((n_nodes,), jnp.bool_, sharding=rep),  # node_up
        jax.ShapeDtypeStruct((n_nodes, max_deg), jnp.bool_, sharding=node_sh2),  # edge_up
    )
    # the key must be a typed PRNG key struct
    key_struct = jax.eval_shape(lambda: jax.random.key(0))
    args = args[:7] + (
        jax.ShapeDtypeStruct(key_struct.shape, key_struct.dtype, sharding=rep),
    ) + args[8:]
    return jax.jit(step), args, pcfg


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_one(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    out_dir: str,
    force: bool = False,
    with_block: bool = True,
    microbatches: int = 1,
    tag: str = "",
    fsdp: bool = False,
    overrides: dict | None = None,
):
    mesh_name = "pod512" if multi_pod else "pod256"
    slug = f"{canonical(arch)}__{shape_name}__{mesh_name}{tag}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, slug + ".json")
    if os.path.exists(path) and not force:
        print(f"[skip] {slug} (exists)")
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    rec = {
        "arch": canonical(arch),
        "shape": shape_name,
        "mesh": mesh_name,
        "microbatches": microbatches,
        "fsdp": fsdp,
        "overrides": overrides or {},
        "ok": False,
    }
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.size
        jitted, args, cfg, model = build_full(
            arch, shape_name, mesh, microbatches, fsdp=fsdp, overrides=overrides
        )
        lowered, compiled = _lower_and_compile(jitted, args, mesh)
        rec["memory"] = _mem_dict(compiled)
        full_cost = dict(compiled.cost_analysis())
        full_coll = collective_bytes(compiled.as_text())
        rec["cost_full"] = {
            "flops": full_cost.get("flops", 0.0),
            "bytes accessed": full_cost.get("bytes accessed", 0.0),
        }
        rec["coll_full"] = {k: v for k, v in full_coll.items()}

        block_cost = None
        block_coll = None
        if with_block:
            bj, bargs, _ = build_block(
                arch, shape_name, mesh, fsdp=fsdp, overrides=overrides
            )
            _, bcompiled = _lower_and_compile(bj, bargs, mesh)
            bc = dict(bcompiled.cost_analysis())
            block_cost = {
                "flops": bc.get("flops", 0.0),
                "bytes accessed": bc.get("bytes accessed", 0.0),
            }
            block_coll = collective_bytes(bcompiled.as_text())
            rec["cost_block"] = block_cost
            rec["coll_block"] = {k: v for k, v in block_coll.items()}

        costs = combine_scan_costs(rec["cost_full"], block_cost, cfg.num_layers)
        coll_total = combine_scan_collectives(full_coll, block_coll, cfg.num_layers)
        shape = SHAPES[shape_name]
        mf = analytic_model_flops(cfg, shape.global_batch, shape.seq_len, shape.mode)
        report = analyze(costs, coll_total, n_chips, mf)
        rec["roofline"] = report.to_dict()
        rec["params"] = cfg.param_count()
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record the failure, don't die
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["compile_seconds"] = round(time.time() - t0, 1)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=float)
    status = "ok" if rec["ok"] else "FAIL"
    rl = rec.get("roofline", {})
    print(
        f"[{status}] {slug} {rec['compile_seconds']}s "
        f"bottleneck={rl.get('bottleneck','-')} "
        f"mem={rec.get('memory',{}).get('total_bytes',0)/2**30:.1f}GiB"
    )
    return rec


def run_protocol(multi_pod: bool, out_dir: str, force: bool = False):
    mesh_name = "pod512" if multi_pod else "pod256"
    slug = f"protocol_decafork__{mesh_name}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, slug + ".json")
    if os.path.exists(path) and not force:
        print(f"[skip] {slug}")
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    rec = {"arch": "protocol_decafork", "mesh": mesh_name, "ok": False}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        jitted, args, pcfg = build_protocol(mesh)
        lowered, compiled = _lower_and_compile(jitted, args, mesh)
        rec["memory"] = _mem_dict(compiled)
        c = dict(compiled.cost_analysis())
        rec["cost_full"] = {
            "flops": c.get("flops", 0.0),
            "bytes accessed": c.get("bytes accessed", 0.0),
        }
        rec["coll_full"] = collective_bytes(compiled.as_text())
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["compile_seconds"] = round(time.time() - t0, 1)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=float)
    print(f"[{'ok' if rec['ok'] else 'FAIL'}] {slug} {rec['compile_seconds']}s")
    return rec


def main():
    # 512 host-platform devices for the production meshes; appended to any
    # flags already set, and only before jax first initializes a backend
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (
            os.environ.get("XLA_FLAGS", ""),
            "--xla_force_host_platform_device_count=512",
        ) if f
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--protocol", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-block", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-style param/opt sharding over the data axes")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="ModelConfig overrides, e.g. --set mla_absorb=True")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            overrides[k] = v == "True"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    if args.protocol:
        for mp in meshes:
            run_protocol(mp, args.out, force=args.force)
        return

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    n_fail = 0
    for a, s in combos:
        for mp in meshes:
            rec = run_one(
                a, s, mp, args.out,
                force=args.force,
                with_block=not args.no_block and not mp,
                microbatches=args.microbatches,
                tag=args.tag,
                fsdp=args.fsdp,
                overrides=overrides,
            )
            n_fail += 0 if rec["ok"] else 1
    print(f"done; failures={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
