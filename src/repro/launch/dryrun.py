"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

For each cell we build abstract inputs (ShapeDtypeStruct — never allocated),
attach production shardings, ``jit(...).lower(...).compile()``, and record
``memory_analysis()`` / ``cost_analysis()`` / collective bytes for
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]

The XLA_FLAGS lines below MUST precede any jax import (device count locks at
first init); smoke tests / benches never import this module.
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import sys
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, canonical, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as rl
from repro.models import model as model_lib
from repro.models import sharding as shd
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_loop import make_train_step

SHAPES = {
    "train_4k":    dict(seq=4096,    batch=256, step="train"),
    "prefill_32k": dict(seq=32768,   batch=32,  step="prefill"),
    "decode_32k":  dict(seq=32768,   batch=128, step="decode"),
    "long_500k":   dict(seq=524288,  batch=1,   step="decode"),
}

# long_500k only for sub-quadratic archs (DESIGN.md §Arch-applicability)
LONG_OK_KINDS = ("ssm", "hybrid")


def cell_supported(cfg: ModelConfig, shape: str) -> bool:
    if shape == "long_500k":
        return cfg.kind in LONG_OK_KINDS
    return True


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(partial(tf.init_params, cfg=cfg), jax.random.PRNGKey(0))


def input_specs(cfg: ModelConfig, shape_name: str, mesh) -> Dict[str, Any]:
    """Abstract args + shardings + the step callable for one cell."""
    info = SHAPES[shape_name]
    b, s = info["batch"], info["seq"]
    step = info["step"]
    dtype = jnp.dtype(cfg.dtype)
    params = abstract_params(cfg)
    pspecs = shd.param_specs(cfg, params, mesh)

    if step == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.frontend:
            batch["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.d_model), dtype)
        opt_cfg = OptConfig(moment_dtype=cfg.opt_moment_dtype)
        opt_state = jax.eval_shape(partial(init_opt_state, cfg=opt_cfg), params)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        bspecs = shd.batch_specs(cfg, batch, mesh)
        fn = make_train_step(cfg, opt_cfg)
        return dict(fn=fn, args=(params, opt_state, batch),
                    in_shardings=(pspecs, ospecs, bspecs),
                    tokens=b * s, kind="train")

    if step == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.frontend:
            batch["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.d_model), dtype)
        bspecs = shd.batch_specs(cfg, batch, mesh)
        fn = lambda p, bt: model_lib.prefill(p, cfg, bt)
        return dict(fn=fn, args=(params, batch), in_shardings=(pspecs, bspecs),
                    tokens=b * s, kind="fwd")

    # decode: one token against a cache of length s
    caches = jax.eval_shape(
        partial(model_lib.make_caches, cfg, b, s, dtype=jnp.bfloat16))
    cspecs = shd.cache_specs(cfg, caches, mesh)
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tspec = shd.batch_specs(cfg, {"t": tokens}, mesh)["t"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    if cfg.kind == "encdec":
        _, ndp, tp = shd.axis_sizes(mesh)
        kvspec = P(None, shd.data_axes(mesh) if b % ndp == 0 else None, None,
                   "model" if cfg.n_kv % tp == 0 else None, None)
        enc_kv = {
            "ck": jax.ShapeDtypeStruct(
                (cfg.n_layers, b, cfg.frontend_len, cfg.n_kv, cfg.head_dim), dtype),
            "cv": jax.ShapeDtypeStruct(
                (cfg.n_layers, b, cfg.frontend_len, cfg.n_kv, cfg.head_dim), dtype),
        }
        ekv_specs = {"ck": kvspec, "cv": kvspec}
        fn = lambda p, c, t, pos0, ekv: model_lib.decode_step(
            p, cfg, c, t, pos0, enc_kv=ekv)
        return dict(fn=fn, args=(params, caches, tokens, pos, enc_kv),
                    in_shardings=(pspecs, cspecs, tspec, P(), ekv_specs),
                    tokens=b, kind="decode")
    fn = lambda p, c, t, pos0: model_lib.decode_step(p, cfg, c, t, pos0)
    return dict(fn=fn, args=(params, caches, tokens, pos),
                in_shardings=(pspecs, cspecs, tspec, P()),
                tokens=b, kind="decode")


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               cfg_override: Optional[ModelConfig] = None,
               unroll: bool = True) -> Dict[str, Any]:
    import dataclasses as _dc
    cfg = cfg_override or get_config(arch)
    if unroll and not cfg.scan_unroll:
        # exact accounting: XLA cost_analysis visits while bodies once
        cfg = _dc.replace(cfg, scan_unroll=True)
    if not cell_supported(cfg, shape_name):
        return {"arch": cfg.name, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch; long_500k requires sub-quadratic"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = input_specs(cfg, shape_name, mesh)
    t0 = time.perf_counter()
    with mesh:
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec["in_shardings"],
            is_leaf=lambda x: isinstance(x, P))
        lowered = jax.jit(spec["fn"], in_shardings=shardings).lower(*spec["args"])
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        roof = rl.analyze(compiled)
    mf = rl.model_flops(cfg, spec["tokens"],
                        "train" if spec["kind"] == "train" else "fwd")
    res = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "model_flops_device": mf / mesh.devices.size,
        "useful_flops_frac": (mf / mesh.devices.size) / roof.flops if roof.flops else None,
        **roof.summary(),
    }
    return res


# ---------------------------------------------------------------------------
# ANN workload cells (the paper's own system on the production mesh)
# ---------------------------------------------------------------------------

def lower_ann_cell(multi_pod: bool = False, n_global: int = 1 << 27,
                   dim: int = 128, q_global: int = 8192,
                   merge: str = "allgather",
                   dataset_dtype: str = "int32") -> Dict[str, Any]:
    from repro.core.index import IndexConfig
    from repro.core.walks import WalkTable
    from repro.core import hashes as hashes_lib
    from repro.launch import dist_index as di

    # uint8 rows hold x // 2, so their universe stops at 510
    universe = 510 if dataset_dtype == "uint8" else 512
    cfg = IndexConfig(num_tables=8, num_hashes=16, width=256, num_probes=100,
                      candidate_cap=8, universe=universe, k=50,
                      rerank_chunk=1024, dataset_dtype=dataset_dtype)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rows = di._row_axes(mesh)
    nshards = 1
    for a in rows:
        nshards *= mesh.shape[a]

    lm = cfg.num_tables * cfg.num_hashes
    u2 = cfg.universe // 2
    params = hashes_lib.LshParams(
        family="rw", width=float(cfg.width),
        offsets=jax.ShapeDtypeStruct((cfg.num_tables, cfg.num_hashes), jnp.float32),
        mix_a=jax.ShapeDtypeStruct((cfg.num_tables, cfg.num_hashes), jnp.uint32),
        mix_c=jax.ShapeDtypeStruct((cfg.num_tables,), jnp.uint32),
        walks=WalkTable(
            pairs=jax.ShapeDtypeStruct((lm, dim, u2), jnp.int8),
            prefix=jax.ShapeDtypeStruct((lm, dim, u2 + 1), jnp.int32)),
        proj=None)
    from repro.core.index import IndexState
    state = IndexState(
        params=params,
        sorted_keys=jax.ShapeDtypeStruct((cfg.num_tables, n_global), jnp.uint32),
        sorted_ids=jax.ShapeDtypeStruct((cfg.num_tables, n_global), jnp.int32),
        dataset=jax.ShapeDtypeStruct((n_global, dim), jnp.dtype(dataset_dtype)),
        template=jax.ShapeDtypeStruct(
            (cfg.probes_per_table, 2 * cfg.num_hashes), jnp.int8),
        row_offset=jax.ShapeDtypeStruct((nshards,), jnp.int32),
        occ_from=jax.ShapeDtypeStruct((cfg.num_tables, n_global), jnp.int32),
        occ_hist=jax.ShapeDtypeStruct((cfg.num_tables, 32), jnp.int32))
    queries = jax.ShapeDtypeStruct((q_global, dim), jnp.int32)

    sspec = di.state_specs(mesh, cfg)
    qspec = P("model", None)
    query = di.dist_query_fn(cfg, mesh, merge=merge)
    t0 = time.perf_counter()
    with mesh:
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 (sspec, qspec), is_leaf=lambda x: isinstance(x, P))
        lowered = jax.jit(query, in_shardings=shardings).lower(state, queries)
        compiled = lowered.compile()
        roof = rl.analyze(compiled)
    return {
        "arch": f"mp-rw-lsh-index(n={n_global},m={dim},merge={merge},dt={dataset_dtype})",
        "shape": f"query_q{q_global}_k{cfg.k}",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "t_total_s": round(time.perf_counter() - t0, 1),
        **roof.summary(),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--ann", action="store_true", help="lower the ANN index cell")
    ap.add_argument("--merge", default="allgather",
                    choices=["allgather", "ring", "tree"])
    ap.add_argument("--dataset-dtype", default="int32",
                    choices=["int32", "int16", "uint8"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--no-unroll", action="store_true",
                    help="keep layer scans rolled (faster compile, "
                         "undercounts per-layer costs)")
    args = ap.parse_args(argv)

    results = []
    if args.ann:
        results.append(lower_ann_cell(multi_pod=args.multi_pod, merge=args.merge,
                                      dataset_dtype=args.dataset_dtype))
    elif args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                try:
                    r = lower_cell(arch, shape, multi_pod=args.multi_pod,
                                   unroll=not args.no_unroll)
                except Exception as e:  # record, keep sweeping
                    r = {"arch": arch, "shape": shape, "status": "error",
                         "error": f"{type(e).__name__}: {e}"[:300]}
                results.append(r)
                print(json.dumps(r), flush=True)
        results.append(lower_ann_cell(multi_pod=args.multi_pod, merge=args.merge))
    else:
        results.append(lower_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                                  unroll=not args.no_unroll))

    for r in results:
        print(json.dumps(r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
