"""Chip smoke test: the serving path end to end on a TPU, at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the replica pool only

One chip: ``internlm2-1.8b`` at its published widths (random bf16
weights from ``--seed``) serves 24 requests through ``InferenceServer``
with the host KV tier on — more requests than device slots, so
Algorithm 1's host strategies run — and then the same requests with the
host tier off; it prints how many greedy tokens the two runs share.

Four chips: the gateway's replica pool with one replica per chip
answers SSE requests, and the same requests go to one replica on one
chip for comparison.

Every check that fails ends the run with a non-zero exit and no result
line.  On success the last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything printed before it is a smoke fact, not a benchmark number.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"


class SmokeFailure(RuntimeError):
    """A smoke check did not hold."""


class CompileLog:
    """Backend compiles JAX reports through its monitoring hook: how
    many, and their seconds (cache hits skip the backend compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self._lock = threading.Lock()     # replicas compile concurrently
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration

    def mark(self) -> tuple:
        with self._lock:
            return self.count, self.seconds

    def since(self, mark: tuple) -> str:
        count, seconds = self.mark()
        return (f"{count - mark[0]} backend compiles, "
                f"{seconds - mark[1]:.1f}s")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def make_prompts(seed: int, n: int, lo: int, hi: int, vocab: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(t)).tolist()
            for t in rng.integers(lo, hi + 1, n)]


def agreement(a, b) -> float:
    """Share of token positions on which two runs' outputs agree."""
    same = total = 0
    for x, y in zip(a, b):
        total += max(len(x), len(y))
        same += sum(int(p == q) for p, q in zip(x, y))
    return same / max(total, 1)


def bytes_in_use(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


# ---------------------------------------------------------------------------
# One chip: InferenceServer with and without the host tier
# ---------------------------------------------------------------------------


def serve_once(cfg, params, scfg, prompts, output_len: int,
               compiles: CompileLog) -> dict:
    """Serve ``prompts`` closed-loop on a fresh server; return plain
    results so the server and its device state can be freed after."""
    import jax
    from repro.serving import InferenceServer
    from repro.serving.request import Request

    t0 = time.perf_counter()
    mark = compiles.mark()
    server = InferenceServer(cfg, params, scfg)
    setup = time.perf_counter() - t0
    try:
        reqs = [Request(prompt=list(p), max_new_tokens=output_len)
                for p in prompts]
        t0 = time.perf_counter()
        handles = server.serve(reqs, realtime=False)
        jax.block_until_ready(server.engine.state)
        wall = time.perf_counter() - t0
        stats = server.stats
        out = dict(
            setup_s=setup, wall_s=wall, compiles=compiles.since(mark),
            outputs=[list(h.output) for h in handles],
            errors=[h.error for h in handles if h.failed],
            device_tokens=stats.device_tokens, host_tokens=stats.host_tokens,
            strategy_counts=dict(stats.strategy_counts),
            host_fallbacks=stats.host_fallbacks,
            host_fallback_causes=dict(stats.host_fallback_causes),
            host_breaker_trips=stats.host_breaker_trips,
            host_busy_s=stats.host_busy_time,
            prefill_compilations=stats.prefill_compilations,
            iterations=stats.iterations, migrations=stats.migrations,
            preemptions=stats.preemptions)
    finally:
        server.shutdown()
    del server, handles, reqs
    gc.collect()
    return out


def print_run(name: str, r: dict) -> None:
    toks = r["device_tokens"] + r["host_tokens"]
    print(f"[{name}] setup {r['setup_s']:.2f}s, wall {r['wall_s']:.2f}s "
          f"(includes compiles), {r['iterations']} iterations; "
          f"{r['compiles']}; prefill_compilations="
          f"{r['prefill_compilations']}")
    print(f"[{name}] tokens device={r['device_tokens']} "
          f"host={r['host_tokens']} total={toks} "
          f"({toks / r['wall_s']:.1f} tok/s wall, smoke only)")
    print(f"[{name}] strategies {r['strategy_counts']}; migrations "
          f"{r['migrations']}, preemptions {r['preemptions']}; host busy "
          f"{r['host_busy_s']:.2f}s; host fallbacks {r['host_fallbacks']} "
          f"{r['host_fallback_causes']}, breaker trips "
          f"{r['host_breaker_trips']}")


def check_run(name: str, r: dict, n_requests: int, output_len: int) -> None:
    check(not r["errors"], f"{name}: failed requests: {r['errors'][:3]}")
    check(len(r["outputs"]) == n_requests,
          f"{name}: {len(r['outputs'])}/{n_requests} requests returned")
    short = [len(o) for o in r["outputs"] if len(o) < output_len]
    check(not short, f"{name}: {len(short)} requests produced fewer than "
                     f"{output_len} tokens: {short[:5]}")


def one_chip(cfg, compiles: CompileLog, *, seed: int, requests: int,
             device_slots: int, host_slots: int, cache_len: int,
             prompt_lo: int, prompt_hi: int, output_len: int) -> None:
    import jax
    from repro.launch.serve import host_pool_pages, init_weights
    from repro.serving import ServerConfig

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    mark = compiles.mark()
    params = jax.block_until_ready(init_weights(cfg, seed))
    print(f"params initialised in {time.perf_counter() - t0:.2f}s "
          f"({compiles.since(mark)}); device memory {bytes_in_use(dev)}")
    prompts = make_prompts(seed, requests, prompt_lo, prompt_hi,
                           cfg.vocab_size)
    scfg = ServerConfig(
        device_slots=device_slots, host_slots=host_slots,
        cache_len=cache_len, perf_model="analytic", seed=seed,
        host_pool_pages=host_pool_pages(cfg, host_slots=host_slots,
                                        cache_len=cache_len),
        output_len=output_len)
    print(f"serving {requests} requests, prompts {prompt_lo}-{prompt_hi} "
          f"tokens, {output_len} new tokens each; device_slots="
          f"{device_slots} host_slots={host_slots} cache_len={cache_len} "
          f"host_pool_pages={scfg.host_pool_pages}")

    off = serve_once(cfg, params, scfg, prompts, output_len, compiles)
    print_run("offload", off)
    check_run("offload", off, requests, output_len)
    check(off["host_tokens"] > 0, "offload: host_tokens == 0")
    host_decisions = (off["strategy_counts"].get("async_overlap", 0)
                      + off["strategy_counts"].get("asym_pipeline", 0))
    check(host_decisions > 0,
          f"offload: no async_overlap/asym_pipeline decision "
          f"({off['strategy_counts']})")
    check(off["host_fallbacks"] == 0 and off["host_breaker_trips"] == 0,
          f"offload: {off['host_fallbacks']} host fallbacks, "
          f"{off['host_breaker_trips']} breaker trips")
    print(f"device memory after offload run {bytes_in_use(dev)}")

    dev_only = serve_once(cfg, params,
                          dataclasses.replace(scfg, enable_offload=False),
                          prompts, output_len, compiles)
    print_run("device-only", dev_only)
    check_run("device-only", dev_only, requests, output_len)
    check(dev_only["host_tokens"] == 0, "device-only: host tokens emitted")
    share = agreement(off["outputs"], dev_only["outputs"])
    print(f"greedy token agreement, offload vs device-only: {share:.4f} "
          f"({sum(map(len, off['outputs']))} tokens)")
    print(f"device memory {bytes_in_use(dev)}")


# ---------------------------------------------------------------------------
# Four chips: one replica per chip behind the gateway, against one replica
# ---------------------------------------------------------------------------


def pool_run(gateway_args, prompts, output_len: int,
             compiles: CompileLog) -> dict:
    """Build the gateway's replica pool, send ``prompts`` concurrently
    as SSE requests, and return per-prompt tokens plus placement."""
    import jax
    from repro.launch.gateway import build_pool
    from repro.serving.gateway import serve_in_thread
    from repro.serving.gateway.client import get_json, sse_chat

    t0 = time.perf_counter()
    mark = compiles.mark()
    pool = build_pool(gateway_args)
    setup = time.perf_counter() - t0
    gateway, stop = serve_in_thread(pool, port=0)
    results = [None] * len(prompts)
    try:
        def send(i):
            results[i] = sse_chat("127.0.0.1", gateway.port, prompts[i],
                                  max_new_tokens=output_len, timeout=900.0)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        health = get_json("127.0.0.1", gateway.port, "/health")
        served = [int(s["device_tokens"] + s["host_tokens"])
                  for s in pool.stats()]
        # where each replica's params and decode state actually live
        placement = [
            sorted({str(d) for leaf in jax.tree.leaves(
                (r.server.engine.params, r.server.engine.state))
                for d in leaf.devices()}) for r in pool.replicas]
        memory = {str(d): bytes_in_use(d) for d in jax.devices()}
    finally:
        stop()
        pool.shutdown()
    del pool
    gc.collect()
    return dict(setup_s=setup, wall_s=wall, results=results, health=health,
                compiles=compiles.since(mark),
                served=served, placement=placement, memory=memory)


def four_chips(compiles: CompileLog, *, seed: int, requests: int,
               output_len: int, prompt_lo: int, prompt_hi: int,
               model_flags=()) -> None:
    import jax
    from repro.launch.gateway import build_parser
    from repro.launch.serve import model_config

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")

    def args(replicas: int):
        return build_parser().parse_args([
            "--arch", ARCH, *model_flags, "--seed", str(seed),
            "--replicas", str(replicas), "--device-slots", "4",
            "--host-slots", "4", "--cache-len", "1024",
            "--output-len", str(output_len)])

    cfg = model_config(args(1))
    prompts = make_prompts(seed, requests, prompt_lo, prompt_hi,
                           cfg.vocab_size)
    params_bytes = 2 * cfg.param_count()
    runs = {}
    for name, n in (("1 replica", 1), ("4 replicas", 4)):
        r = pool_run(args(n), prompts, output_len, compiles)
        runs[name] = r
        bad = [x for x in r["results"]
               if x["status"] != 200 or x["error"]
               or len(x["tokens"]) < output_len]
        print(f"[{name}] setup {r['setup_s']:.2f}s, {len(prompts)} SSE "
              f"requests in {r['wall_s']:.2f}s ({r['compiles']}); "
              f"tokens per replica {r['served']}; placement "
              f"{r['placement']}")
        print(f"[{name}] device memory {r['memory']}")
        check(not bad, f"{name}: {len(bad)} SSE requests failed or came "
                       f"back short: {bad[:2]}")
        check(r["health"]["status"] == 200
              and r["health"]["body"]["status"] == "ok",
              f"{name}: /health not green: {r['health']}")
    four = runs["4 replicas"]
    check(all(len(p) == 1 for p in four["placement"])
          and len({p[0] for p in four["placement"]}) == 4,
          f"4 replicas are not on 4 distinct devices: {four['placement']}")
    check(all(s > 0 for s in four["served"]),
          f"a replica served no tokens: {four['served']}")
    held = [m["bytes_in_use"] or 0 for m in four["memory"].values()]
    check(all(b >= params_bytes for b in held),
          f"a chip does not hold a replica's params ({params_bytes} "
          f"bytes): {held}")
    share = agreement([x["tokens"] for x in runs["1 replica"]["results"]],
                      [x["tokens"] for x in four["results"]])
    print(f"greedy token agreement, 4 replicas vs 1 replica: {share:.4f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); there is no CPU "
              f"fallback", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import configure_compile_cache

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    print(f"compile cache: {configure_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind}, "
          f"{len(jax.devices())} device(s), jax {jax.__version__}")
    cfg = get_config(ARCH)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} (q/kv), "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.param_count() / 1e9:.3f}B params ({cfg.param_dtype})")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(cfg, compiles, seed=args.seed, requests=24, device_slots=8,
                 host_slots=8, cache_len=2048, prompt_lo=128,
                 prompt_hi=512, output_len=64)
    else:
        four_chips(compiles, seed=args.seed, requests=8, output_len=16,
                   prompt_lo=64, prompt_hi=256)
    print(f"smoke phases took {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
