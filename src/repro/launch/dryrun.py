import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("REPRO_XLA_EXTRA", ""))
# The two lines above MUST run before any other import: jax locks the device
# count at first initialization. The dry-run (and only the dry-run) needs 512
# placeholder host devices to build the production meshes.

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_supported, get_config
from repro.distributed import sharding as sh
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, abstract_train_state, abstract_model
from repro.models.model import model_template
from repro.models.params import count_params
from repro.models.stepfn import make_train_step, make_prefill_step, make_decode_step
from repro.training.optimizer import AdamW


def pick_microbatches(cfg, shape, mesh):
    """Bound per-device microbatch activations to ~8k tokens."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    B, S = shape.global_batch, shape.seq_len
    per_dev_tokens = B * S // dp
    mb = max(1, per_dev_tokens // 8192)
    while B % mb or (B // mb) % dp:
        mb -= 1
    return max(mb, 1)


def build_cell(cfg, shape, mesh, *, attn_impl="auto", kv_shard="kv_heads",
               microbatches=None, opt=()):
    """Returns (jitted_fn, example_args) for lowering."""
    template = model_template(cfg)
    pspecs = sh.param_pspecs(template, mesh)
    cons = sh.make_constrain(mesh)
    ns = lambda t: sh.named(t, mesh)
    in_ps = sh.input_pspecs(cfg, shape.kind, mesh)

    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]

    if kv_shard == "auto":
        # KV heads rarely divide a 16-way model axis; fall back to
        # sequence-sharded caches when they don't.
        ms = mesh.shape["model"]
        kv_shard = "kv_heads" if cfg.n_kv_heads % ms == 0 else "seq"

    if shape.kind == "train":
        mb = microbatches or pick_microbatches(cfg, shape, mesh)
        optimizer = AdamW(lr=3e-4)
        step = make_train_step(cfg, optimizer, microbatches=mb, remat=True,
                               attn_impl=attn_impl, constrain=cons,
                               moe_groups=dp, mesh=mesh, opt=opt)
        state = abstract_train_state(cfg)
        state_ps = {
            "params": pspecs,
            "opt_state": {"mu": pspecs, "nu": pspecs, "count": P()},
            "step": P(),
        }
        batch = input_specs(cfg, shape)
        state_ps = sh.sanitize(state_ps, state, mesh)
        in_ps = sh.sanitize(in_ps, batch, mesh)
        fn = jax.jit(step, in_shardings=(ns(state_ps), ns(in_ps)),
                     out_shardings=(ns(state_ps), None))
        return fn, (state, batch), {"microbatches": mb, "kv_shard": kv_shard}

    params = abstract_model(cfg)
    pspecs = sh.sanitize(pspecs, params, mesh)
    if shape.kind == "prefill":
        pre = make_prefill_step(cfg, attn_impl=attn_impl, constrain=cons,
                                moe_groups=dp, mesh=mesh, opt=opt)
        batch = input_specs(cfg, shape)
        in_ps = sh.sanitize(in_ps, batch, mesh)
        cache_abs = jax.eval_shape(pre, params, batch)[1]
        cache_ps = sh.sanitize(sh.cache_pspecs(cfg, mesh, kv_shard),
                               cache_abs, mesh)
        fn = jax.jit(pre, in_shardings=(ns(pspecs), ns(in_ps)),
                     out_shardings=(None, ns(cache_ps)))
        return fn, (params, batch), {"kv_shard": kv_shard}

    # decode
    dec = make_decode_step(cfg, constrain=cons, opt=opt)
    spec = input_specs(cfg, shape)
    cache_ps = sh.sanitize(sh.cache_pspecs(cfg, mesh, kv_shard),
                           spec["cache"], mesh)
    ba = sh.batch_axes(mesh)
    tok_ps, pos_ps = sh.sanitize(
        [P(ba, None), P(ba)],
        [spec["tokens"], spec["positions"]], mesh)
    fn = jax.jit(
        dec,
        in_shardings=(ns(pspecs), ns(cache_ps),
                      NamedSharding(mesh, tok_ps), NamedSharding(mesh, pos_ps)),
        out_shardings=(None, ns(cache_ps)),
    )
    return fn, (params, spec["cache"], spec["tokens"], spec["positions"]), {
        "kv_shard": kv_shard}


def run_cell(arch, shape_name, mesh_kind, *, outdir=None, attn_impl="auto",
             kv_shard="auto", microbatches=None, tag="baseline",
             save_hlo=False, opt=(), mesh_shape=None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "attn_impl": attn_impl, "kv_shard": kv_shard, "opt": list(opt),
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    if mesh_shape:  # §Perf: re-layout the same 256 chips, e.g. "128x2"
        dims = tuple(int(x) for x in mesh_shape.split("x"))
        names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh = jax.make_mesh(dims, names,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(dims))
        rec["mesh_shape"] = mesh_shape
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        # explicit NamedShardings everywhere -> no ambient mesh context needed
        fn, args, extra = build_cell(
            cfg, shape, mesh, attn_impl=attn_impl, kv_shard=kv_shard,
            microbatches=microbatches, opt=opt)
        rec.update(extra)
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        txt = compiled.as_text()
        analysis = hlo.analyze_hlo(txt)
        terms = hlo.roofline_terms(analysis)
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        n_chips = mesh.devices.size
        n_params = count_params(model_template(cfg))
        tokens = (shape.global_batch * shape.seq_len
                  if shape.kind != "decode" else shape.global_batch)
        mult = 3.0 if shape.kind == "train" else 1.0  # fwd+bwd vs fwd
        model_flops = 2.0 * mult * _active_params(cfg) * tokens
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
            n_chips=n_chips, n_params=n_params,
            per_device={
                "flops": analysis["flops"],
                "hbm_bytes": analysis["hbm_bytes"],
                "collective_wire_bytes": analysis["collective_wire_bytes"],
                "collective_by_kind": analysis["collective_by_kind"],
            },
            top_collectives=analysis["top_collectives"][:6],
            roofline=terms,
            dominant=max(terms, key=terms.get),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_per_device_gb": round(
                    (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
                    / 2**30, 3),
            },
            xla_cost_analysis={k: ca.get(k) for k in ("flops", "bytes accessed")},
            model_flops_total=model_flops,
            useful_flops_ratio=round(
                model_flops / max(analysis["flops"] * n_chips, 1.0), 4),
        )
        if save_hlo and outdir:
            os.makedirs(outdir, exist_ok=True)
            with open(os.path.join(
                    outdir, f"{arch}_{shape_name}_{mesh_kind}_{tag}.hlo"), "w") as f:
                f.write(txt)
    except Exception as e:  # record the failure; dry-run failures are bugs
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    if outdir:
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, f"{arch}_{shape_name}_{mesh_kind}_{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=float)
    return rec


def _active_params(cfg):
    """Active (per-token) params from the real template, embeddings excluded
    from the 6ND convention's N only for the unembed projection cost."""
    n_total = count_params(model_template(cfg))
    if cfg.n_experts and cfg.moe_top_k:
        moe_blocks = sum(1 for b in cfg.blocks() if b == "moe")
        per_expert = (2 if not cfg.mlp_gated else 3) * cfg.d_model * cfg.d_ff
        inactive = moe_blocks * (cfg.n_experts - cfg.moe_top_k) * per_expert
        return n_total - inactive
    return n_total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--attn-impl", default="auto")
    ap.add_argument("--kv-shard", default="auto")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--opt", default="", help="comma-separated opt flags")
    ap.add_argument("--mesh-shape", default=None,
                    help="override mesh layout, e.g. 128x2 (same chip count)")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_cell(
                    arch, shape, mk, outdir=args.out,
                    attn_impl=args.attn_impl, kv_shard=args.kv_shard,
                    microbatches=args.microbatches, tag=args.tag,
                    save_hlo=args.save_hlo,
                    opt=tuple(f for f in args.opt.split(",") if f),
                    mesh_shape=args.mesh_shape)
                if rec["status"] == "ok":
                    t = rec["roofline"]
                    print(f"OK   {arch:24s} {shape:12s} {mk:6s} "
                          f"compute={t['compute_s']:.3f}s mem={t['memory_s']:.3f}s "
                          f"coll={t['collective_s']:.3f}s dom={rec['dominant']} "
                          f"peak={rec['memory']['peak_per_device_gb']}GB "
                          f"(compile {rec['compile_s']}s)", flush=True)
                elif rec["status"] == "skipped":
                    print(f"SKIP {arch:24s} {shape:12s} {mk:6s} {rec['reason']}",
                          flush=True)
                else:
                    failures += 1
                    print(f"FAIL {arch:24s} {shape:12s} {mk:6s} {rec['error']}",
                          flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
