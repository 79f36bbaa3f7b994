import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x shape) cell and both production meshes
(16x16 single-pod, 2x16x16 multi-pod) this lowers + compiles the real
step function against ShapeDtypeStruct inputs (no allocation), then
records memory_analysis, cost_analysis, and the while-scaled
collective-bytes breakdown (launch/analysis.py) into a JSON artifact.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
        --shape decode_32k [--multi-pod] [--variant overlap]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.distributed import sharding
from repro.launch import analysis, shapes
from repro.launch.mesh import make_production_mesh
from repro.models import abstract_params, decode_step, prefill
from repro.models import forward_train
from repro.models.config import ModelConfig
from repro.training import (TrainConfig, init_train_state, make_optimizer,
                            make_train_step)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# Giant archs get factored optimizer state (AdamW bf16 moments would
# exceed one pod's HBM; see EXPERIMENTS.md §Dry-run).
ADAFACTOR_THRESHOLD = 6e11


def _compile_and_measure(jitted, args, kwargs=None):
    t0 = time.time()
    lowered = jitted.lower(*args, **(kwargs or {}))
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    if os.environ.get("REPRO_SAVE_HLO"):
        import zstandard
        tag = os.environ.get("REPRO_HLO_TAG", f"hlo_{int(time.time()*1e3)}")
        path = os.path.join(os.environ["REPRO_SAVE_HLO"], tag + ".hlo.zst")
        with open(path, "wb") as f:
            f.write(zstandard.ZstdCompressor(level=3).compress(hlo.encode()))
    colls = analysis.collective_bytes_from_hlo(hlo)
    return {
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_per_device": (mem.argument_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 + mem.output_size_in_bytes
                                 - mem.alias_size_in_bytes),
        },
        "cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": {
            "bytes_by_kind": colls.bytes_by_kind,
            "count_by_kind": colls.count_by_kind,
            "total_bytes": colls.total_bytes,
            "unscaled_bytes": colls.unscaled_bytes,
        },
    }


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                variant: str = "baseline", options: Optional[dict] = None
                ) -> dict:
    """Lower + compile one cell.  variant: baseline | overlap.

    ``options`` are the perf-iteration knobs (EXPERIMENTS.md §Perf):
      loss_chunk: int      — fused chunked unembed+CE (train)
      seq_parallel: bool   — residual-stream sequence parallelism
      host_fraction: float — APEX offload fraction (overlap variant)
      expert_shard: str    — "ep" | "tp" | "2d" expert-weight layout
      weight_stationary: bool — serve weights TP-only (no ZeRO gathers)
    """
    options = dict(options or {})
    cfg = get_config(arch)
    shape = shapes.SHAPES[shape_name]
    skip = (shapes.overlap_applicable(cfg, shape) if variant == "overlap"
            else shapes.applicability(cfg, shape))
    record = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if skip:
        record["skipped"] = skip
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mode = "train" if shape.kind == "train" else "serve"
    rules = sharding.rules_for_mesh(mesh, mode)
    if options.get("seq_parallel"):
        rules = dict(rules, seq="model")
    if options.get("weight_stationary"):
        # serve-mode hillclimb: keep weights TP-only (no fsdp dim) so
        # decode steps never all-gather parameters
        rules = dict(rules, fsdp=None)
    if options.get("expert_shard") == "tp":
        rules = dict(rules, experts=None)
    elif options.get("expert_shard") == "ep":
        rules = dict(rules, experts="model", ffn=None)
    params_abs = abstract_params(cfg)
    pspecs = sharding.param_shardings(mesh, params_abs, rules)

    with sharding.use_sharding(mesh, rules):
        if shape.kind == "train":
            record.update(_lower_train(cfg, shape, mesh, params_abs, pspecs,
                                       options))
        elif shape.kind == "prefill":
            record.update(_lower_prefill(cfg, shape, mesh, params_abs, pspecs))
        else:
            record.update(_lower_decode(cfg, shape, mesh, params_abs, pspecs,
                                        variant, options))

    hf = options.get("host_fraction", shapes.HOST_FRACTION)
    costs = analysis.analytic_costs(
        cfg, shape.kind, seq_len=shape.seq_len,
        global_batch=shape.global_batch,
        host_fraction=hf if variant == "overlap" else 0.0)
    record["options"] = options
    record["analytic"] = {
        "flops_global": costs.flops, "hbm_bytes_global": costs.hbm_bytes,
        "model_flops_global": costs.model_flops, "chips": chips,
        "notes": costs.notes,
    }
    return record


def _lower_train(cfg: ModelConfig, shape, mesh, params_abs, pspecs,
                 options=None):
    options = options or {}
    opt_name = ("adafactor" if cfg.param_count() > ADAFACTOR_THRESHOLD
                else "adamw")
    kwargs = {} if opt_name == "adafactor" else {"moment_dtype": "bfloat16"}
    opt = make_optimizer(opt_name, **kwargs)
    tcfg = TrainConfig(optimizer=opt_name, remat=True,
                       accum_steps=options.get("accum_steps", 1),
                       loss_chunk=options.get("loss_chunk", 0))
    step = make_train_step(cfg, tcfg, opt)
    state_abs = jax.eval_shape(
        lambda p: init_train_state(cfg, tcfg, opt, p), params_abs)
    state_shard = sharding.param_shardings(mesh, state_abs,
                                           sharding.rules_for_mesh(mesh))
    batch_abs, batch_shard = shapes.train_batch_specs(cfg, shape, mesh)
    rng_abs = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    from jax.sharding import NamedSharding, PartitionSpec as P
    jitted = jax.jit(step, in_shardings=(state_shard, batch_shard,
                                         NamedSharding(mesh, P())),
                     donate_argnums=(0,))
    out = _compile_and_measure(jitted, (state_abs, batch_abs, rng_abs))
    out["optimizer"] = opt_name
    return out


def _lower_prefill(cfg: ModelConfig, shape, mesh, params_abs, pspecs):
    inputs_abs, inputs_shard = shapes.prefill_input_specs(cfg, shape, mesh)
    if cfg.is_encoder_only:
        # encoder "prefill" = one full forward (no cache)
        fn = lambda p, x: forward_train(p, cfg, x)
        jitted = jax.jit(fn, in_shardings=(pspecs, inputs_shard))
        return _compile_and_measure(jitted, (params_abs, inputs_abs))
    cache_len = shape.seq_len
    state_abs = shapes.abstract_state(cfg, device_batch=shape.global_batch,
                                      host_batch=0, cache_len=cache_len)
    sspecs = shapes.state_specs(cfg, state_abs, mesh, long_context=False,
                                for_prefill=True)
    fn = lambda p, x, st: prefill(p, cfg, x, st)
    jitted = jax.jit(fn, in_shardings=(pspecs, inputs_shard, sspecs),
                     donate_argnums=(2,))
    return _compile_and_measure(jitted, (params_abs, inputs_abs, state_abs))


def _lower_decode(cfg: ModelConfig, shape, mesh, params_abs, pspecs, variant,
                  options=None):
    options = options or {}
    long_ctx = shape.name == "long_500k"
    if variant == "overlap":
        hf = options.get("host_fraction", shapes.HOST_FRACTION)
        host_batch = int(shape.global_batch * hf)
        device_batch = shape.global_batch - host_batch
    else:
        host_batch = 0
        device_batch = shape.global_batch
    state_abs = shapes.abstract_state(cfg, device_batch=device_batch,
                                      host_batch=host_batch,
                                      cache_len=shape.seq_len)
    sspecs = shapes.state_specs(cfg, state_abs, mesh, long_context=long_ctx)
    tok_abs, tok_shard = shapes.decode_token_specs(cfg, device_batch, mesh)
    if variant == "overlap":
        host_abs, host_shard = shapes.host_io_specs(cfg, host_batch, mesh)
        fn = lambda p, t, st, h: decode_step(p, cfg, t, st, h)
        jitted = jax.jit(fn, in_shardings=(pspecs, tok_shard, sspecs,
                                           host_shard),
                         donate_argnums=(2,))
        out = _compile_and_measure(jitted,
                                   (params_abs, tok_abs, state_abs, host_abs))
    else:
        fn = lambda p, t, st: decode_step(p, cfg, t, st)
        jitted = jax.jit(fn, in_shardings=(pspecs, tok_shard, sspecs),
                         donate_argnums=(2,))
        out = _compile_and_measure(jitted, (params_abs, tok_abs, state_abs))
    out["device_batch"] = device_batch
    out["host_batch"] = host_batch
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(shapes.SHAPES))
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "overlap"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) baseline cell")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in list_archs(assigned_only=True):
            for shape_name in shapes.SHAPES:
                cells.append((arch, shape_name, args.multi_pod, "baseline"))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required without --all")
        cells.append((args.arch, args.shape, args.multi_pod, args.variant))

    for arch, shape_name, multi_pod, variant in cells:
        tag = f"{arch}__{shape_name}__{'mp' if multi_pod else 'sp'}__{variant}"
        os.environ["REPRO_HLO_TAG"] = tag
        print(f"=== {tag}")
        try:
            rec = dryrun_cell(arch, shape_name, multi_pod=multi_pod,
                              variant=variant)
        except Exception as e:  # a failure here is a sharding bug
            rec = {"arch": arch, "shape": shape_name, "variant": variant,
                   "mesh": "2x16x16" if multi_pod else "16x16",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()}
            print(rec["error"])
        path = os.path.join(args.out, tag + ".json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if "memory" in rec:
            mem = rec["memory"]["total_per_device"] / 1e9
            print(f"    compiled in {rec['compile_s']}s; "
                  f"{mem:.2f} GB/device; "
                  f"collectives {rec['collectives']['total_bytes']/1e6:.1f} MB")


if __name__ == "__main__":
    main()
