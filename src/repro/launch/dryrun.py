"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell the step function (train_step / prefill / serve_step) is
jitted with the production shardings, lowered against ShapeDtypeStruct
stand-ins (zero allocation), compiled for the 16×16 = 256-chip pod mesh
and the 2×16×16 = 512-chip multi-pod mesh, and the compiled artifact's

    memory_analysis()   — proves the per-chip working set fits HBM
    cost_analysis()     — per-chip HLO FLOPs / bytes for §Roofline
    as_text()           — collective traffic (launch/hlo_analysis)

are recorded as one JSON per cell under benchmarks/results/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--page-impl sp]
"""

import argparse
import functools
import json
import os
import time
import traceback


def np_prod(t):
    n = 1
    for x in t:
        n *= int(x)
    return n

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import (ARCH_IDS, LONG_CONTEXT_ARCHS, SHAPES, ShapeSpec,
                           get_config)
from repro.distributed import sharding as shard_rules
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.training.train_loop import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

#: SWA archs keep only the window resident (FPR ring recycling) — the pool
#: is sized to the window, not the table capacity.
SWA_POOL = True

#: per-arch train_4k microbatch counts (activation-memory fit at 16 GB/chip;
#: the default B//32 = 8 suits the ≤16B dense models)
TRAIN_MICROBATCHES = {
    # microbatch rows must stay ≥ the data-parallel shard count (16)
    "deepseek-v2-236b": 16,
    "jamba-v0.1-52b": 16,
    "internvl2-26b": 16,
    "qwen2.5-14b": 16,
}
#: ≥100B models: bf16 Adam moments + bf16 grad accumulation (6 B/param of
#: optimizer+accumulator state instead of 12 — the difference between
#: fitting a 256-chip pod and not)
TRAIN_LOWMEM = {"deepseek-v2-236b"}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


# ============================================================== input specs
def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        d = {"tokens": _sds((B, S), jnp.int32),
             "labels": _sds((B, S), jnp.int32)}
        if cfg.frontend == "vision":
            d["patches"] = _sds((B, cfg.prefix_tokens, cfg.d_model),
                                jnp.bfloat16)
        if cfg.enc_dec:
            d["frames"] = _sds((B, cfg.enc_len, cfg.d_model), jnp.bfloat16)
        return d
    if shape.kind == "prefill":
        d = {"tokens": _sds((B, S), jnp.int32)}
        if cfg.frontend == "vision":
            d["patches"] = _sds((B, cfg.prefix_tokens, cfg.d_model),
                                jnp.bfloat16)
        if cfg.enc_dec:
            d["frames"] = _sds((B, cfg.enc_len, cfg.d_model), jnp.bfloat16)
        return d
    return {"tokens": _sds((B,), jnp.int32)}          # decode


def state_specs(cfg: ModelConfig, shape: ShapeSpec, shards: int,
                m_round: int = 1) -> dict:
    """ShapeDtypeStructs of the decode-state pytree for this cell."""
    B, S = shape.global_batch, shape.seq_len
    extra = cfg.prefix_tokens if cfg.frontend == "vision" else 0
    max_len = S + extra + tfm.BLOCK_SIZE          # one block of decode slack
    if m_round > 1:                                # sp_opt: M divisible by
        bs = tfm.BLOCK_SIZE                        # the seq-shard count
        M = -(-max_len // bs)
        max_len = (-(-M // m_round) * m_round) * bs
    num_blocks = None
    if cfg.attn.window is not None and SWA_POOL and shape.kind == "decode":
        per_seq = (cfg.attn.window + tfm.BLOCK_SIZE - 1) // tfm.BLOCK_SIZE + 2
        num_blocks = B * per_seq
    spec = tfm.cache_spec(cfg, B, max_len, num_blocks=num_blocks,
                          dtype=jnp.bfloat16, round_to=shards)
    return {k: _sds(sh, dt) for k, (sh, dt) in spec.items()}


# ============================================================ cell lowering
def build_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               page_impl: str = "sp", attn_impl: str = "chunked",
               microbatches: int | None = None, moe_groups: int | None = None,
               compress_grads: bool = False, param_dtype=jnp.bfloat16):
    """Returns (lowered, meta) for one cell — no device allocation."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    B, S = shape.global_batch, shape.seq_len
    dp = shard_rules.dp_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]

    params_shape = jax.eval_shape(
        functools.partial(tfm.init_params, jax.random.PRNGKey(0), cfg,
                          param_dtype))
    pspec = shard_rules.param_specs(params_shape, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "mesh": list(mesh.devices.shape), "chips": chips,
            "multi_pod": multi_pod, "page_impl": page_impl,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    with mesh:
        if shape.kind == "train":
            mb = microbatches or TRAIN_MICROBATCHES.get(
                arch, max(1, B // 32))
            mb = min(mb, max(1, B // n_dp))   # microbatch rows ≥ dp shards
            groups = moe_groups if moe_groups is not None else n_dp
            lowmem = arch in TRAIN_LOWMEM
            from repro.training.optimizer import AdamWConfig, init_opt_state
            tc = TrainConfig(
                microbatches=mb, attn_impl=attn_impl, moe_groups=groups,
                compress_grads=compress_grads,
                accum_dtype="bfloat16" if lowmem else "float32",
                adamw=AdamWConfig(
                    moments_dtype="bfloat16" if lowmem else "float32"))
            _, jitted = make_train_step(cfg, tc, mesh)
            batch = input_specs(arch, shape_name)
            fn = jitted(params_shape, tuple(batch.keys()))
            opt_shape = jax.eval_shape(
                functools.partial(init_opt_state,
                                  moments_dtype=tc.adamw.moments_dtype),
                params_shape)
            err_shape = (jax.eval_shape(
                lambda p: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), p),
                params_shape) if compress_grads
                else _sds((), jnp.float32))
            meta["microbatches"] = mb
            meta["tokens_per_step"] = B * S
            lowered = fn.lower(params_shape, opt_shape, err_shape, batch)
            return lowered, meta

        ba, sa = shard_rules.decode_axes(mesh, batch=B)
        shards = 1
        for a in ba + sa:
            shards *= mesh.shape[a]
        n_seq = 1
        for a in sa:
            n_seq *= mesh.shape[a]
        st_specs = state_specs(
            cfg, shape, shards,
            m_round=n_seq if page_impl == "sp_opt" else 1)
        # XLA:CPU legalises bf16 scatter through f32 operand round-trips;
        # on TPU the paged write is a native in-place bf16 scatter.  The
        # estimate lets fits_hbm subtract the CPU-only artifact.
        pool_keys = ("kv", "mla_c", "mla_rope")
        pool_global = sum(
            int(np_prod(st_specs[k].shape)) * st_specs[k].dtype.itemsize
            for k in pool_keys if k in st_specs)
        meta["pool_bytes_per_chip"] = pool_global // shards
        meta["cpu_scatter_artifact_bytes"] = 3 * pool_global // shards
        st_part = shard_rules.filter_state_specs(
            shard_rules.decode_state_specs(cfg, mesh, batch_axes=ba,
                                           seq_axes=sa), st_specs)
        if page_impl == "sp_opt" and "tables" in st_part:
            bsp_t = ba if len(ba) != 1 else (ba[0] if ba else None)
            st_part["tables"] = P(bsp_t, sa)
        st_sh = {k: NamedSharding(mesh, v) for k, v in st_part.items()}
        bsp = ba if len(ba) != 1 else ba[0]
        groups = moe_groups if moe_groups is not None else (
            n_dp if shape.kind == "prefill" else 1)
        meta["batch_axes"] = list(ba)
        meta["seq_axes"] = list(sa)

        if shape.kind == "prefill":
            inp = input_specs(arch, shape_name)
            tok_sh = NamedSharding(mesh, P(bsp, None))

            def prefill_step(params, tokens, state, extras):
                return tfm.prefill(params, cfg, tokens, state,
                                   enc_frames=extras.get("frames"),
                                   patches=extras.get("patches"),
                                   moe_groups=groups, mesh=mesh,
                                   batch_axes=ba, seq_axes=sa)

            extras = {k: v for k, v in inp.items() if k != "tokens"}
            ex_sh = {k: NamedSharding(mesh, P(bsp, None, None))
                     for k in extras}
            fn = jax.jit(
                prefill_step,
                in_shardings=(psh, tok_sh, st_sh, ex_sh),
                out_shardings=(NamedSharding(mesh, P(bsp, None)), st_sh),
                donate_argnums=(2,))
            meta["tokens_per_step"] = B * S
            lowered = fn.lower(params_shape, inp["tokens"], st_specs, extras)
            return lowered, meta

        # decode / long-context decode
        tok_sh = NamedSharding(mesh, P(bsp))

        def serve_step(params, state, tokens):
            return tfm.decode_step(params, cfg, state, tokens,
                                   page_impl=page_impl, mesh=mesh,
                                   batch_axes=ba, seq_axes=sa,
                                   moe_groups=groups)

        fn = jax.jit(
            serve_step,
            in_shardings=(psh, st_sh, tok_sh),
            out_shardings=(NamedSharding(mesh, P(bsp, None)), st_sh),
            donate_argnums=(1,))
        meta["tokens_per_step"] = B
        lowered = fn.lower(params_shape, st_specs, input_specs(
            arch, shape_name)["tokens"])
        return lowered, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             page_impl: str = "sp", out_dir: str | None = None,
             verbose: bool = True, **kw) -> dict:
    t0 = time.time()
    lowered, meta = build_cell(arch, shape_name, multi_pod=multi_pod,
                               page_impl=page_impl, **kw)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rl, coll, mem = hlo_analysis.analyze(compiled, meta["chips"])

    # MODEL_FLOPS: 6·N_active·D train, 2·N_active·D serve (per step, global)
    n_act = meta["active_params"]
    tokens = meta["tokens_per_step"]
    factor = 6 if meta["kind"] == "train" else 2
    model_flops = factor * n_act * tokens
    hlo_global = rl.flops_per_chip * meta["chips"]
    rec = dict(meta)
    rec.update({
        "roofline": rl.as_dict(),
        "collectives": {"payload_by_kind": coll.coll_payload,
                        "count_by_kind": coll.coll_count,
                        "wire_bytes_per_chip": coll.coll_wire_bytes},
        "memory": mem,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_global,
        "useful_flops_ratio": (model_flops / hlo_global
                               if hlo_global else None),
        "lower_s": t1 - t0, "compile_s": t2 - t1,
        "fits_hbm_16g": (mem["peak_bytes"]
                         - meta.get("cpu_scatter_artifact_bytes", 0)) < 16e9,
    })
    if verbose:
        b = rl.bottleneck
        print(f"[{arch} × {shape_name} × "
              f"{'multi' if multi_pod else 'single'}-pod]  "
              f"compute {rl.compute_s*1e3:.2f}ms  "
              f"memory {rl.memory_s*1e3:.2f}ms  "
              f"collective {rl.collective_s*1e3:.2f}ms  ← {b}; "
              f"peak {mem['peak_bytes']/1e9:.2f} GB/chip  "
              f"(lower {t1-t0:.0f}s compile {t2-t1:.0f}s)")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}"
        if page_impl != "sp":
            tag += f"_{page_impl}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def iter_cells(multi_pod: bool):
    for a in ARCH_IDS:
        for s in SHAPES.values():
            if s.name == "long_500k" and a not in LONG_CONTEXT_ARCHS:
                continue
            yield a, s.name


def main():
    # 512 fake host devices for the production meshes; XLA reads the flag
    # when the first backend starts, so setting it here (before any
    # device query) is enough and importing this module changes nothing
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--page-impl", default="sp",
                    choices=["sp", "sp_opt", "ref", "pallas"])
    ap.add_argument("--microbatches", type=int)
    ap.add_argument("--moe-groups", type=int)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(RESULTS_DIR))
    args = ap.parse_args()

    kw = dict(page_impl=args.page_impl, out_dir=args.out,
              microbatches=args.microbatches, moe_groups=args.moe_groups,
              compress_grads=args.compress_grads)
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    cells = (list(iter_cells(args.multi_pod)) if args.all
             else [(args.arch, args.shape)])
    failures = []
    for mp in meshes:
        for arch, shape in cells:
            try:
                run_cell(arch, shape, multi_pod=mp, **kw)
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILED cells:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells) * len(meshes)} cells compiled OK")


if __name__ == "__main__":
    main()
