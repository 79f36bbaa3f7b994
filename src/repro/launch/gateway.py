"""HTTP/SSE gateway launcher: the production front door.

Builds ``--arch`` once — at its published widths, or reduced with
``--layers``/``--d-model`` — fronts ``--replicas`` engine replicas
(replica i on ``jax.devices()[i % device_count]``, so a four-chip host
runs one replica per chip) with the asyncio gateway, and serves::

    POST /v1/chat     SSE token stream ({"prompt": [ids], "deadline",
                      "priority", "max_new_tokens"})
    GET  /health      replica liveness + queue depth
    GET  /metrics     Prometheus text format

    PYTHONPATH=src python -m repro.launch.gateway --replicas 2 \
        --layers 4 --d-model 128 --port 8080 --max-queue-depth 64

``--smoke-test`` instead runs an in-process closed-loop client burst
against the freshly started gateway, asserts non-empty SSE streams, a
green ``/health`` and parseable ``/metrics``, then exits non-zero on
any failure (the CI gateway smoke step).

The perf-model flags mirror ``repro.launch.serve``; the default here
is ``analytic`` at the attached device's platform (instant startup —
``measured`` would profile at every replica build, including respawns;
point ``--profile-cache`` at a shared file to make that cheap).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import threading
import time

import jax
import numpy as np

from repro.launch.compile_cache import configure_compile_cache
from repro.launch.serve import (add_model_args, host_pool_pages,
                                init_weights, model_config)
from repro.serving import InferenceServer, ServerConfig
from repro.serving.gateway import EngineReplicaPool, serve_in_thread
from repro.serving.gateway.client import get_json, get_text, sse_chat
from repro.serving.gateway.http import HTTPGateway


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--max-queue-depth", type=int, default=64,
                    help="bounded gateway queue: submissions beyond this "
                         "in-flight depth shed with HTTP 503")
    # model / engine flags (mirroring repro.launch.serve)
    add_model_args(ap)
    ap.add_argument("--device-slots", type=int, default=4)
    ap.add_argument("--host-slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--output-len", type=int, default=24,
                    help="default max_new_tokens when a request omits it")
    ap.add_argument("--chunk-tokens", type=int, default=64)
    ap.add_argument("--host-workers", type=int, default=0)
    ap.add_argument("--host-kv-dtype", default="fp32",
                    choices=["fp32", "int8"],
                    help="host KV pool precision per replica (int8 = "
                         "quantized pages + fused-dequant host attention)")
    ap.add_argument("--cold-page-compress-after", type=float, default=0.0,
                    help="compress idle host KV pages after this many "
                         "seconds (0 = off)")
    ap.add_argument("--platform", default=None,
                    help="analytic perf-model platform (default: the "
                         "attached device's, by device_kind)")
    ap.add_argument("--perf-model", default="analytic",
                    help="perf-model spec per replica: analytic | "
                         "analytic:<platform> | measured | file:<path>")
    ap.add_argument("--profile-cache", default=None)
    ap.add_argument("--deadline", type=float, default=None,
                    help="default TTFT SLO stamped on requests that "
                         "omit one")
    ap.add_argument("--no-offload", action="store_true")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable each replica's cross-request prefix "
                         "cache (session_id routing still works, it "
                         "just stops paying off)")
    ap.add_argument("--prefix-cache-slots", type=int, default=2,
                    help="device-resident prefix-cache entries per "
                         "replica (0 = host-pool-only caching)")
    ap.add_argument("--host-job-slack", type=float, default=8.0,
                    help="host-job watchdog deadline = predicted t_catt "
                         "x this slack (floored at 0.25s)")
    ap.add_argument("--no-recompute-fallback", action="store_true",
                    help="disable the GPU recompute fallback and "
                         "recompute-from-scratch preemption on every "
                         "replica (legacy loud-failure contract)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic chaos plan injected into every "
                         "replica, e.g. 'host_stall@3x2:0.5,pool_alloc@1' "
                         "(docs/serving_api.md 'Failure handling')")
    ap.add_argument("--smoke-test", action="store_true",
                    help="start the gateway, run a closed-loop client "
                         "burst, assert SSE/health/metrics, exit")
    return ap


def build_pool(args: argparse.Namespace) -> EngineReplicaPool:
    cfg = model_config(args)
    params = init_weights(cfg, args.seed)
    scfg = ServerConfig(
        device_slots=args.device_slots, host_slots=args.host_slots,
        cache_len=args.cache_len, enable_offload=not args.no_offload,
        host_pool_pages=host_pool_pages(cfg, host_slots=args.host_slots,
                                        cache_len=args.cache_len),
        host_workers=args.host_workers, chunk_tokens=args.chunk_tokens,
        host_kv_dtype=args.host_kv_dtype,
        cold_page_compress_after=args.cold_page_compress_after,
        platform=args.platform, perf_model=args.perf_model,
        profile_cache=args.profile_cache, deadline=args.deadline,
        prefix_cache=not args.no_prefix_cache,
        prefix_cache_slots=args.prefix_cache_slots,
        host_job_slack=args.host_job_slack,
        recompute_fallback=not args.no_recompute_fallback,
        fault_plan=args.fault_plan,
        output_len=args.output_len)
    print(f"gateway model {cfg.name}: {cfg.param_count()/1e6:.1f}M params; "
          f"{args.replicas} replicas x (device_slots={scfg.device_slots} "
          f"host_slots={scfg.host_slots}) perf_model={scfg.perf_model}")

    def factory(device) -> InferenceServer:
        # each replica gets its own config copy (engines mutate knobs
        # like enable_offload for inapplicable stacks); params are
        # read-only, one copy per device
        return InferenceServer(cfg, jax.device_put(params, device),
                               dataclasses.replace(scfg))

    devices = jax.devices()
    return EngineReplicaPool(
        factory, replicas=args.replicas,
        devices=[devices[i % len(devices)] for i in range(args.replicas)])


def smoke_test(pool: EngineReplicaPool, args: argparse.Namespace) -> int:
    """Closed-loop burst over real sockets; non-zero exit on any
    failed check (the CI gateway smoke step runs this)."""
    gateway, stop = serve_in_thread(pool, host=args.host, port=0,
                                    max_queue_depth=args.max_queue_depth)
    failures = []
    try:
        host, port = args.host, gateway.port
        rng = np.random.default_rng(0)
        clients, per_client = 4, 2
        results = []
        lock = threading.Lock()

        def client_loop() -> None:
            for _ in range(per_client):
                prompt = [int(t) for t in rng.integers(0, 256, 8)]
                r = sse_chat(host, port, prompt, max_new_tokens=6)
                with lock:
                    results.append(r)

        threads = [threading.Thread(target=client_loop)
                   for _ in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180.0)
        wall = time.perf_counter() - t0
        ok = [r for r in results if r["status"] == 200 and not r["error"]]
        if len(results) != clients * per_client:
            failures.append(f"only {len(results)}/{clients * per_client} "
                            f"requests returned")
        if not ok or any(not r["tokens"] for r in ok):
            failures.append("empty SSE stream(s) in the burst")
        health = get_json(host, port, "/health")
        if health["status"] != 200 or health["body"]["status"] != "ok":
            failures.append(f"/health not green: {health}")
        metrics = get_text(host, port, "/metrics")
        if metrics["status"] != 200 \
                or "apex_replica_up" not in metrics["body"] \
                or "apex_engine_iterations_total" not in metrics["body"]:
            failures.append("/metrics missing expected families")
        ttfts = sorted(r["ttft_s"] for r in ok if r["ttft_s"] is not None)
        print(f"smoke burst: {len(ok)}/{len(results)} streams ok in "
              f"{wall:.2f}s; TTFT p95 "
              f"{1e3 * ttfts[int(0.95 * (len(ttfts) - 1))]:.0f}ms"
              if ttfts else "smoke burst: no TTFT samples")
    finally:
        stop()
    if failures:
        print("GATEWAY SMOKE FAILED:")
        for f in failures:
            print(f"  {f}")
        return 1
    print("gateway smoke OK: SSE streams non-empty, /health green, "
          "/metrics parseable")
    return 0


def main() -> None:
    configure_compile_cache()
    args = build_parser().parse_args()
    pool = build_pool(args)
    try:
        if args.smoke_test:
            sys.exit(smoke_test(pool, args))
        import asyncio
        gateway = HTTPGateway(pool, host=args.host, port=args.port,
                              max_queue_depth=args.max_queue_depth)

        async def run() -> None:
            await gateway.start()
            print(f"listening on http://{args.host}:{gateway.port}  "
                  f"(POST /v1/chat | GET /health | GET /metrics)")
            await gateway.serve_forever()

        try:
            asyncio.run(run())
        except KeyboardInterrupt:
            print("shutting down")
    finally:
        pool.shutdown()


if __name__ == "__main__":
    main()
