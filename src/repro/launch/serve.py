"""End-to-end online-serving driver (deliverable (b), the paper's kind).

Serves ``--arch`` at its published widths (random weights from
``--seed``) through the scheduler-driven ``InferenceServer``;
``--layers``/``--d-model`` instead serve a reduced-geometry model of
the same family (the CPU smoke size).  Requests come from a paper
workload trace (``--workload``) or the synthetic default, and
Algorithm 1 picks the execution strategy every iteration.  In
closed-loop mode the first response streams token by token; with
``--arrival-rate`` the trace is instead replayed open-loop in
wall-clock time (no streaming demo).  APEX offload is exact on CPU:
host rows emit the same tokens a device-resident run would
(tests/test_overlap.py enforces this).

    # published widths (one TPU v5e chip)
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --requests 24 --device-slots 8 --host-slots 8 --cache-len 2048

    # reduced widths (CPU)
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.1-8b \
        --layers 4 --d-model 128 --requests 16 --device-slots 2 \
        --host-slots 6 --workload azure-conv
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.serving import InferenceServer, ServerConfig
from repro.serving.workloads import WORKLOADS


def add_model_args(ap: argparse.ArgumentParser) -> None:
    """Model flags shared by the serving entry points."""
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve a reduced-geometry model of --arch with "
                         "this many layers (default: published widths)")
    ap.add_argument("--d-model", type=int, default=None,
                    help="serve a reduced-geometry model of --arch at "
                         "this width (default: published widths)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the "
                         "generated requests")


def model_config(args: argparse.Namespace) -> ModelConfig:
    """``--arch`` as published, or reduced (4 heads, vocab 512) when
    ``--layers`` or ``--d-model`` is given."""
    cfg = get_config(args.arch)
    if args.layers is None and args.d_model is None:
        return cfg
    return cfg.reduced(layers=args.layers or 4, d_model=args.d_model or 128,
                       vocab=512)


def init_weights(cfg: ModelConfig, seed: int):
    """Random params from ``seed``, built by one compiled program (an
    eager init at published widths dispatches op by op and takes
    minutes on a TPU)."""
    return jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def host_pool_pages(cfg: ModelConfig, *, host_slots: int,
                    cache_len: int) -> int:
    """Paged host-pool size that holds every host slot at full
    ``cache_len`` (pages are per attention layer)."""
    return (host_slots * -(-cache_len // ServerConfig.page_size)
            * max(cfg.num_attn_layers, 1))


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--output-len", type=int, default=24)
    ap.add_argument("--device-slots", type=int, default=4)
    ap.add_argument("--host-slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--platform", default=None,
                    help="platform backing the analytic perf-model specs "
                         "(default: the attached device's, by device_kind)")
    ap.add_argument("--perf-model", default="measured",
                    help="perf-model spec feeding Algorithm 1: analytic | "
                         "analytic:<platform> | measured | file:<path> "
                         "(default: measured — profile the real backends "
                         "at startup)")
    ap.add_argument("--profile-cache", default=None,
                    help="JSON path for the measured profile; loaded if "
                         "present, written after profiling otherwise")
    ap.add_argument("--workload", default=None,
                    choices=sorted(WORKLOADS) + ["synthetic"],
                    help="paper trace driving request generation "
                         "(default: synthetic fixed-length)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrivals in req/s (default: closed loop)")
    ap.add_argument("--host-workers", type=int, default=0,
                    help="host-attention worker threads per job "
                         "(0 = auto: cpu_count - 1)")
    ap.add_argument("--no-bucketed-prefill", action="store_true",
                    help="disable the bucketed/batched prefill fast path")
    ap.add_argument("--host-kv-dtype", default="fp32",
                    choices=["fp32", "int8"],
                    help="host KV pool storage precision; int8 stores "
                         "quantized pages with per-token scales and "
                         "dequantizes inside the host attention kernel "
                         "(docs/serving_api.md 'Host KV precision and "
                         "compression')")
    ap.add_argument("--cold-page-compress-after", type=float, default=0.0,
                    help="compress host KV pages of requests idle this "
                         "many seconds, freeing physical pages "
                         "(0 = off); pages decompress transparently "
                         "on touch")
    ap.add_argument("--chunk-tokens", type=int, default=64,
                    help="chunked prefill: prompt tokens each "
                         "prefilling request advances per iteration "
                         "(0 = whole-prompt prefill before decode)")
    ap.add_argument("--no-offload", action="store_true")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the cross-request prefix cache "
                         "(docs/serving_api.md 'Prefix cache'); tokens "
                         "are bit-identical either way")
    ap.add_argument("--prefix-cache-slots", type=int, default=2,
                    help="device-resident prefix-cache entries (0 = "
                         "host-pool-only caching)")
    ap.add_argument("--no-tier-rebalance", action="store_true",
                    help="disable host→device migration when device "
                         "slots free up (see docs/serving_api.md "
                         "'Request lifecycle, migration, and SLOs')")
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable SLO-aware preemptive admission "
                         "(urgent requests demoting low-priority "
                         "device residents to the host tier)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="TTFT deadline (seconds from arrival) stamped "
                         "on every generated request; impossible "
                         "deadlines are rejected at admission, late "
                         "first tokens count as deadline_misses")
    ap.add_argument("--host-job-slack", type=float, default=8.0,
                    help="host-job watchdog deadline = predicted t_catt "
                         "x this slack (floored at 0.25s); expired jobs "
                         "are recomputed exactly on the engine thread")
    ap.add_argument("--no-recompute-fallback", action="store_true",
                    help="disable the GPU recompute fallback and "
                         "recompute-from-scratch preemption (legacy "
                         "contract: host faults fail the engine loudly, "
                         "blocked swaps requeue)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic chaos plan, e.g. "
                         "'host_stall@3x2:0.5,pool_alloc@1' (see "
                         "repro.serving.faults; docs/serving_api.md "
                         "'Failure handling')")
    ap.add_argument("--no-stream", action="store_true",
                    help="suppress the per-token stream of request 0")
    args = ap.parse_args()

    cfg = model_config(args)
    scfg = ServerConfig(
        device_slots=args.device_slots, host_slots=args.host_slots,
        cache_len=args.cache_len, enable_offload=not args.no_offload,
        host_pool_pages=host_pool_pages(cfg, host_slots=args.host_slots,
                                        cache_len=args.cache_len),
        host_workers=args.host_workers,
        bucketed_prefill=not args.no_bucketed_prefill,
        host_kv_dtype=args.host_kv_dtype,
        cold_page_compress_after=args.cold_page_compress_after,
        chunk_tokens=args.chunk_tokens,
        prefix_cache=not args.no_prefix_cache,
        prefix_cache_slots=args.prefix_cache_slots,
        tier_rebalance=not args.no_tier_rebalance,
        preemption=not args.no_preemption, deadline=args.deadline,
        host_job_slack=args.host_job_slack,
        recompute_fallback=not args.no_recompute_fallback,
        fault_plan=args.fault_plan,
        platform=args.platform, perf_model=args.perf_model,
        profile_cache=args.profile_cache,
        workload=None if args.workload in (None, "synthetic")
        else args.workload,
        num_requests=args.requests, arrival_rate=args.arrival_rate,
        prompt_len=args.prompt_len, output_len=args.output_len,
        seed=args.seed)
    print(f"serving {cfg.name}: {cfg.param_count()/1e6:.1f}M params; "
          f"device_slots={scfg.device_slots} host_slots={scfg.host_slots} "
          f"offload={scfg.enable_offload} "
          f"workload={scfg.workload or 'synthetic'} "
          f"perf_model={scfg.perf_model}")
    params = init_weights(cfg, args.seed)
    if scfg.perf_model == "measured" and not (
            scfg.profile_cache and os.path.exists(scfg.profile_cache)):
        print("profiling backends at startup (use --profile-cache to "
              "reuse across runs, or --perf-model analytic to skip)...")

    t0 = time.time()
    with InferenceServer(cfg, params, scfg) as server:
        reqs = scfg.build_requests(vocab=cfg.vocab_size)
        if args.no_stream or args.arrival_rate:
            if args.arrival_rate and not args.no_stream:
                print("open-loop replay (--arrival-rate): per-token "
                      "streaming demo disabled")
            handles = server.serve(reqs,
                                   realtime=args.arrival_rate is not None)
        else:
            handles = [server.submit(r) for r in reqs]
            print("request 0 stream: ", end="", flush=True)
            for tok in handles[0].tokens():
                print(tok, end=" ", flush=True)
            print()
            server.run_until_idle()
        stats = server.stats
    wall = time.time() - t0

    done = [h.request for h in handles]
    lats = [r.per_token_latency() for r in done if r.per_token_latency()]
    ttfts = [r.time_to_first_token() for r in done
             if r.time_to_first_token() is not None]
    print(f"finished {len(done)} requests in {wall:.2f}s")
    print(f"tokens: device={stats.device_tokens} host={stats.host_tokens} "
          f"-> {(stats.device_tokens + stats.host_tokens) / wall:.1f} tok/s")
    print(f"strategy decisions: {stats.strategy_counts}")
    if stats.prediction_error is not None:
        print(f"scheduling accuracy ({stats.perf_model_spec}): predicted "
              f"{stats.predicted_time:.2f}s vs observed "
              f"{stats.observed_time:.2f}s "
              f"(err={100 * stats.prediction_error:.0f}%, "
              f"ewma={100 * (stats.step_error_ewma or 0):.0f}%)")
    if lats:
        print(f"avg per-token latency: {np.mean(lats) * 1e3:.1f} ms; "
              f"avg TTFT: {np.mean(ttfts) * 1e3:.1f} ms")
    if stats.ttft_p50 is not None:
        itl50 = stats.itl_p50 or 0.0
        itl95 = stats.itl_p95 or 0.0
        print(f"TTFT p50/p95: {stats.ttft_p50 * 1e3:.1f}/"
              f"{stats.ttft_p95 * 1e3:.1f} ms; "
              f"ITL p50/p95: {itl50 * 1e3:.1f}/{itl95 * 1e3:.1f} ms")
    if stats.prefill_chunks:
        print(f"chunked prefill: {stats.prefill_chunks} chunks "
              f"({stats.chunked_prefill_tokens} tokens), "
              f"{stats.chunk_co_run_iterations} iterations co-ran "
              f"with decode")
    print(f"lifecycle: {stats.migrations} migrations, "
          f"{stats.preemptions} preemptions; occupancy "
          f"device={stats.device_occupancy:.2f}/{scfg.device_slots} "
          f"host={stats.host_occupancy:.2f}/{scfg.host_slots}")
    if stats.deadline_misses or stats.deadline_rejections:
        print(f"SLO: {stats.deadline_misses} deadline misses, "
              f"{stats.deadline_rejections} impossible-deadline "
              f"rejections")
    if stats.host_fallbacks or stats.preemption_recomputes \
            or stats.cancelled:
        print(f"fault tolerance: {stats.host_fallbacks} host fallbacks "
              f"({stats.host_breaker_trips} breaker trips), "
              f"{stats.preemption_recomputes} recompute preemptions, "
              f"{stats.cancelled} cancelled; degradation="
              f"{stats.degradation()}")
    if stats.host_busy_time:
        print(f"host attention busy: {stats.host_busy_time:.2f}s "
              f"({100 * stats.host_busy_time / wall:.0f}% of wall — "
              f"overlapped)")


if __name__ == "__main__":
    main()
