import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax import (jax locks the device
count at first init): the dry-run — and only the dry-run — sees 512
placeholder host devices so the production meshes (16x16 single-pod,
2x16x16 multi-pod) can be built.

Per cell this script:
  1. builds the abstract model/optimizer state with ``jax.eval_shape``
     (no parameter ever allocated),
  2. jits the real ``train_step`` / ``prefill`` / ``serve_step`` with the
     production in/out shardings,
  3. ``.lower(**ShapeDtypeStruct inputs).compile()`` — success proves the
     sharding config is coherent (no mismatched specs, no OOM-sized
     replicated temps, collectives all partitionable),
  4. prints ``compiled.memory_analysis()`` / ``cost_analysis()`` and
     parses collective wire bytes from the optimized HLO
     (parallel/hlo_analysis.py),
  5. writes experiments/dryrun/<arch>__<shape>__<mesh>.json for
     EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2_1_5b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
  python -m repro.launch.dryrun --mips          # paper's MIPS service cell
"""

import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_IDS, SHAPES, ModelConfig, get_config,
                                shape_cells)
from repro.data.tokens import decode_batch_specs, train_batch_specs
from repro.launch.mesh import ambient_mesh, make_production_mesh
from repro.models import lm
from repro.parallel import analytic
from repro.parallel import hlo_analysis as hlo
from repro.parallel import sharding as shd

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(lm.init_params, cfg=cfg), jax.random.PRNGKey(0))


def _abstract_cache(cfg: ModelConfig, batch: int, seq: int):
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return jax.eval_shape(
            functools.partial(encdec.init_cache, cfg, batch, seq))
    return jax.eval_shape(functools.partial(lm.init_cache, cfg, batch, seq))


def param_counts(cfg: ModelConfig, params) -> Dict[str, float]:
    total = sum(x.size for x in jax.tree.leaves(params))
    expert = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        keys = [str(getattr(p, "key", p)) for p in path]
        if any("ffn" in k for k in keys) and leaf.ndim >= 4:
            expert += leaf.size
    active = total - expert
    if cfg.moe is not None and expert:
        active += expert * cfg.moe.top_k / cfg.moe.num_experts
    return {"total": float(total), "active": float(active),
            "expert": float(expert)}


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every input of a cell (public API
    per the dry-run contract): weak-type-correct, shardable, and never
    allocated. train shapes return the batch dict; decode shapes return
    (tokens, caches, cache_pos); prefill returns (tokens[, patches/frames]).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        batch = dict(train_batch_specs(shape.global_batch, shape.seq_len))
        if cfg.num_patches:
            batch["patches"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.num_patches, cfg.d_model),
                jnp.float32)
        if cfg.is_encoder_decoder:
            batch["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_frames, cfg.d_model),
                jnp.float32)
        return batch
    if shape.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32)}
        if cfg.num_patches:
            out["patches"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.num_patches, cfg.d_model),
                jnp.float32)
        if cfg.is_encoder_decoder:
            out["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_frames, cfg.d_model),
                jnp.float32)
        return out
    return {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32),
        "caches": _abstract_cache(cfg, shape.global_batch, shape.seq_len),
        "cache_pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def build_cell(cfg: ModelConfig, shape_name: str, mesh):
    """Returns (jitted_fn, example_kwargs of ShapeDtypeStructs)."""
    shape = SHAPES[shape_name]
    dp = shd.dp_axes(mesh)
    params = _abstract_params(cfg)
    pspecs = shd.param_specs(params, cfg, fsdp_axis="data")

    if shape.kind == "train":
        from repro.launch.train import (TrainHParams, init_state_abstract,
                                        make_train_step)
        hp = TrainHParams()
        # §Perf hillclimb D: pure ZeRO DP when the global batch divides
        # the whole mesh (no TP => no per-layer activation psums).
        # Measured to help only pure-attention stacks: recurrent archs
        # trap the per-layer weight gathers inside their time-step scans
        # (xlstm 20s -> 71s) — they keep 2D FSDPxTP.
        # REPRO_TRAIN_ZERO=0 keeps the 2D baseline everywhere.
        shards = 1
        for a in mesh.axis_names:
            shards *= mesh.shape[a]
        zero_dp = (os.environ.get("REPRO_TRAIN_ZERO", "1") == "1"
                   and shape.global_batch % shards == 0
                   and all(k == "attn" for k in cfg.layer_pattern)
                   and not cfg.is_encoder_decoder)
        step = make_train_step(cfg, mesh, hp, zero_dp=zero_dp)
        state = init_state_abstract(cfg)
        batch = dict(train_batch_specs(shape.global_batch, shape.seq_len))
        if cfg.num_patches:
            batch["patches"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.num_patches, cfg.d_model),
                jnp.float32)
        if cfg.is_encoder_decoder:
            batch["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_frames, cfg.d_model),
                jnp.float32)
        args = (state, batch, jax.ShapeDtypeStruct((), jnp.int32))
        return step, args

    if shape.kind == "prefill":
        def fn(params, tokens, patches=None, frames=None):
            if cfg.is_encoder_decoder:
                from repro.models import encdec
                enc = encdec.encoder_forward(params["encoder"], frames, cfg)
                h, caches = encdec.decoder_forward(params, tokens, enc, cfg)
                return h[:, -1], caches
            return lm.prefill(params, tokens, cfg, patches)

        in_sh = [shd.to_shardings(mesh, pspecs),
                 NamedSharding(mesh, P(dp, None))]
        args = [params,
                jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                     jnp.int32)]
        if cfg.num_patches:
            in_sh.append(NamedSharding(mesh, P(dp, None, None)))
            args.append(jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.num_patches, cfg.d_model),
                jnp.float32))
        elif cfg.is_encoder_decoder:
            in_sh.append(None)
            args.append(None)
            in_sh.append(NamedSharding(mesh, P(dp, None, None)))
            args.append(jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_frames, cfg.d_model),
                jnp.float32))
        step = jax.jit(fn, in_shardings=tuple(in_sh))
        return step, tuple(args)

    # decode
    def fn(params, tokens, caches, cache_pos):
        return lm.decode_step(params, tokens, caches, cache_pos, cfg)

    # §Perf hillclimb B: serve weights STATIONARY — pure TP for dense
    # weights, 2D (expert x d_ff) sharding for MoE stacks, vocab-only for
    # embeddings. Re-gathering FSDP-sharded weights every decoded token
    # dominated the collective term (1.4 GB/step/device on llama4-scout).
    # Falls back to the FSDP axis only if the stationary layout would not
    # fit HBM. REPRO_SERVE_STATIONARY=0 restores the baseline.
    stationary = os.environ.get("REPRO_SERVE_STATIONARY", "1") == "1"
    from repro.parallel.analytic import matmul_param_counts
    counts_sv = matmul_param_counts(cfg, params)
    embed_n = counts_sv["embed"]
    expert_n = counts_sv["expert"]
    dense_n = (sum(x.size for x in jax.tree.leaves(params))
               - expert_n - embed_n)
    tp = mesh.shape["model"]
    per_chip = 2.0 * (dense_n / tp + embed_n / tp + expert_n / mesh.size)
    use_stationary = stationary and per_chip <= 12e9
    pspecs_serve = shd.param_specs(
        params, cfg, fsdp_axis=None if use_stationary else "data",
        serve_stationary=use_stationary)

    dpb = shd.dp_axes_for_batch(mesh, shape.global_batch)
    cspecs = shd.cache_specs(cfg, mesh, batch=shape.global_batch)
    caches = _abstract_cache(cfg, shape.global_batch, shape.seq_len)
    step = jax.jit(fn, in_shardings=(
        shd.to_shardings(mesh, pspecs_serve),
        NamedSharding(mesh, P(dpb)),
        shd.to_shardings(mesh, cspecs),
        NamedSharding(mesh, P())),
        donate_argnums=(2,))
    args = (params, jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32),
            caches, jax.ShapeDtypeStruct((), jnp.int32))
    return step, args


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str = OUT_DIR) -> Dict[str, Any]:
    cfg = get_config(arch)
    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.size
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(zip(mesh.axis_names,
                               [mesh.shape[a] for a in mesh.axis_names])),
        "chips": chips,
    }
    t0 = time.time()
    try:
        # the ambient mesh gives with_sharding_constraint (activation
        # anchors) a resource env during tracing.
        with ambient_mesh(mesh):
            step, args = build_cell(cfg, shape_name, mesh)
            lowered = step.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()

        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "bytes_per_device": getattr(
                    mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                          None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "peak_bytes": getattr(
                    mem, "peak_memory_in_bytes",
                    getattr(mem, "temp_size_in_bytes", None)),
            }
        except Exception as e:   # CPU backend may not implement it
            mem_d = {"error": str(e)}

        text = compiled.as_text()
        colls = hlo.parse_collectives(text, chips)
        csum = hlo.summarize_collectives(colls)

        # Roofline terms from the ANALYTIC estimator (XLA:CPU cost_analysis
        # counts while/scan bodies once — recorded raw below for reference,
        # see parallel/analytic.py docstring) + HLO-parsed collectives.
        params = _abstract_params(cfg)
        counts = param_counts(cfg, params)
        shape = SHAPES[shape_name]
        est = analytic.estimate(cfg, shape, params, chips)
        terms = hlo.roofline(est["flops"],
                             est["hbm_bytes_per_device"] * chips,
                             csum.get("total_wire_bytes", 0.0), chips,
                             model_flops=est["model_flops"])
        record.update({
            "ok": True,
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "cost_analysis_raw": {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "transcendentals": float(cost.get("transcendentals", 0.0)),
            },
            "memory_analysis": mem_d,
            "collectives": csum,
            "analytic": est,
            "roofline": terms,
            "param_counts": counts,
            "model_flops": est["model_flops"],
            "useful_flops_ratio": (est["model_flops"] / est["flops"]
                                   if est["flops"] else None),
            "hlo_bytes": len(text),
        })
    except Exception as e:
        record.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    status = "OK" if record.get("ok") else "FAIL"
    print(f"[{status}] {arch} x {shape_name} x {mesh_kind} "
          f"(compile {record.get('compile_s', '-')}s)", flush=True)
    if not record.get("ok"):
        print(record["error"], flush=True)
    return record


def run_mips_cell(mesh_kind: str, out_dir: str = OUT_DIR) -> Dict[str, Any]:
    """The paper's own workload: sharded MIPS serving on the spec API
    (DESIGN.md §11), bucket-traversal engine, abstractly lowered — the
    data-dependent bucket count is assumed at ``n // 4`` (the short-code
    collision regime the engine targets)."""
    from repro.core import distributed as dist
    from repro.core.index import IndexSpec

    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.size
    dp = shd.dp_axes(mesh)
    n, d, L, m, k, probe, nq = 2_000_000, 128, 128, 256, 10, 512, 1024
    shards = 1
    for a in dp:
        shards *= mesh.shape[a]
    record: Dict[str, Any] = {"arch": "range_lsh_mips", "shape":
                              f"n{n}_d{d}_q{nq}", "mesh": mesh_kind,
                              "chips": chips}
    t0 = time.time()
    try:
        hb = L - 8                 # 8 bits of the budget index 256 ranges
        W = (hb + 31) // 32
        B = n // 4                 # assumed occupied-bucket count
        spec = IndexSpec(family="simple", code_len=L, m=m,
                         engine="bucket")
        f32, i32 = jnp.float32, jnp.int32
        idx = dist.ShardedIndex(
            spec=spec,
            params=jax.ShapeDtypeStruct((d + 1, hb), f32),
            rank=jax.ShapeDtypeStruct((m, hb + 1), i32),
            dir_code=jax.ShapeDtypeStruct((B, W), jnp.uint32),
            dir_rid=jax.ShapeDtypeStruct((B,), i32),
            dir_size=jax.ShapeDtypeStruct((B,), i32),
            dir_shard=jax.ShapeDtypeStruct((B,), i32),
            dir_local_start=jax.ShapeDtypeStruct((B,), i32),
            items=jax.ShapeDtypeStruct((n, d), f32),
            codes=jax.ShapeDtypeStruct((n, W), jnp.uint32),
            range_id=jax.ShapeDtypeStruct((n,), i32),
            bucket_of=jax.ShapeDtypeStruct((n,), i32),
            bucket_off=jax.ShapeDtypeStruct((n,), i32),
            perm=jax.ShapeDtypeStruct((n,), i32),
            valid=jax.ShapeDtypeStruct((n,), jnp.bool_),
            num_shards=shards, rows_per_shard=n // shards,
            num_items=n, hash_bits=hb)

        # §Perf hillclimb C: queries shard over 'model' (2D decomposition)
        # unless REPRO_MIPS_2D=0 selects the paper-faithful 1D baseline.
        q_axis = ("model" if os.environ.get("REPRO_MIPS_2D", "1") == "1"
                  else None)

        def fn(params, rank, dir_code, dir_rid, dir_size, dir_shard,
               dir_lstart, items, codes, range_id, bucket_of, bucket_off,
               perm, valid, queries):
            index = idx._replace(
                params=params, rank=rank, dir_code=dir_code,
                dir_rid=dir_rid, dir_size=dir_size, dir_shard=dir_shard,
                dir_local_start=dir_lstart, items=items, codes=codes,
                range_id=range_id, bucket_of=bucket_of,
                bucket_off=bucket_off, perm=perm, valid=valid)
            eng = dist.DistributedEngine(index, mesh, axis=dp,
                                         query_axis=q_axis)
            return eng.query(queries, k, probe)

        row = NamedSharding(mesh, P(dp))
        rep = NamedSharding(mesh, P())
        step = jax.jit(fn, in_shardings=(
            rep, rep, rep, rep, rep, rep, rep,
            NamedSharding(mesh, P(dp, None)),
            NamedSharding(mesh, P(dp, None)), row, row, row, row, row,
            rep))
        args = (idx.params, idx.rank, idx.dir_code, idx.dir_rid,
                idx.dir_size, idx.dir_shard, idx.dir_local_start,
                idx.items, idx.codes, idx.range_id, idx.bucket_of,
                idx.bucket_off, idx.perm, idx.valid,
                jax.ShapeDtypeStruct((nq, d), jnp.float32))
        lowered = step.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        cost = compiled.cost_analysis() or {}
        text = compiled.as_text()
        colls = hlo.parse_collectives(text, chips)
        csum = hlo.summarize_collectives(colls)
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        terms = hlo.roofline(flops, bytes_acc,
                             csum.get("total_wire_bytes", 0.0), chips)
        record.update({"ok": True, "lower_s": round(t1 - t0, 2),
                       "compile_s": round(t2 - t1, 2),
                       "cost_analysis": {kk: float(v) for kk, v in
                                         cost.items()
                                         if isinstance(v, (int, float))},
                       "collectives": csum, "roofline": terms})
    except Exception as e:
        record.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"range_lsh_mips__{mesh_kind}.json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    print(f"[{'OK' if record.get('ok') else 'FAIL'}] MIPS x {mesh_kind}",
          flush=True)
    if not record.get("ok"):
        print(record["error"], flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mips", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    meshes = (["pod", "multipod"] if args.mesh == "both" else [args.mesh])
    ok = True
    if args.mips:
        for mk in meshes:
            ok &= run_mips_cell(mk, args.out).get("ok", False)
    elif args.all:
        for arch in ARCH_IDS:
            for shape in shape_cells(arch):
                for mk in meshes:
                    ok &= run_cell(arch, shape, mk, args.out).get("ok",
                                                                  False)
        for mk in meshes:
            ok &= run_mips_cell(mk, args.out).get("ok", False)
    else:
        if not (args.arch and args.shape):
            raise SystemExit("--arch/--shape or --all required")
        for mk in meshes:
            ok &= run_cell(args.arch, args.shape, mk, args.out).get(
                "ok", False)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
