"""Gateway subsystem: replica pool fan-out determinism, crash
containment + respawn, HTTP/SSE wire format, and edge backpressure
(503 bounded queue / 429 deadline-impossible)."""
import re
import threading
import time

import jax
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import InferenceServer, ServerConfig
from repro.serving.gateway import EngineReplicaPool, serve_in_thread
from repro.serving.gateway.client import get_json, get_text, sse_chat


@pytest.fixture(scope="module")
def served():
    cfg = get_config("stablelm-12b").reduced(layers=2, d_model=64, vocab=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _factory(cfg, params, **kw):
    defaults = dict(device_slots=2, host_slots=3, cache_len=64,
                    prompt_len=6, output_len=5, num_requests=5)
    defaults.update(kw)

    def factory():
        return InferenceServer(cfg, params, ServerConfig(**defaults))

    return factory


def _prompts(n, base=2):
    # distinct prompts so concurrent outputs can be matched to their
    # serial counterparts regardless of completion order
    return [[base + i, 3, 5, 7] for i in range(n)]


# --- pool semantics ------------------------------------------------------

def test_concurrent_submission_bit_identical_to_serial(served):
    """Satellite 3a: 8 submitter threads through a single replica
    produce exactly the outputs a serial in-process run produces."""
    cfg, params = served
    prompts = _prompts(8)
    with InferenceServer(cfg, params,
                         ServerConfig(device_slots=2, host_slots=3,
                                      cache_len=64, output_len=5)) as ref:
        serial = {tuple(p): ref.submit(p, max_new_tokens=5).result()
                  for p in prompts}

    factory = _factory(cfg, params)
    with EngineReplicaPool(factory, replicas=1) as pool:
        results = {}
        errors = []
        lock = threading.Lock()

        def worker(p):
            try:
                out = pool.submit(p, 5).result(timeout=120.0)
                with lock:
                    results[tuple(p)] = out
            except Exception as exc:   # pragma: no cover - failure path
                with lock:
                    errors.append(exc)

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not errors
    assert results == serial           # bit-identical, all 8 present


def test_pool_places_each_replica_on_its_device(served):
    """With ``devices`` the pool calls ``factory(device)`` under that
    device's default-device context, and each replica's driver steps
    there: every array of a replica's engine lives on its device, and
    the outputs equal a plain single-server run."""
    cfg, params = served
    dev = jax.devices()[0]
    seen = []

    def factory(device):
        seen.append((device, jax.config.jax_default_device))
        return InferenceServer(cfg, jax.device_put(params, device),
                               ServerConfig(device_slots=2, host_slots=3,
                                            cache_len=64, output_len=5))

    with pytest.raises(ValueError, match="one device per replica"):
        EngineReplicaPool(factory, replicas=2, devices=[dev])
    prompts = _prompts(4)
    with InferenceServer(cfg, params,
                         ServerConfig(device_slots=2, host_slots=3,
                                      cache_len=64, output_len=5)) as ref:
        serial = [ref.submit(p, max_new_tokens=5).result() for p in prompts]
    with EngineReplicaPool(factory, replicas=2, devices=[dev, dev]) as pool:
        outs = [pool.submit(p, 5).result(timeout=120.0) for p in prompts]
        for rep in pool.replicas:
            assert rep.device == dev
            leaves = jax.tree.leaves((rep.server.engine.params,
                                      rep.server.engine.state))
            assert {d for leaf in leaves for d in leaf.devices()} == {dev}
    assert seen == [(dev, dev), (dev, dev)]
    assert outs == serial


def test_step_lock_allows_concurrent_token_iterators(served):
    """Satellite 1: two RequestHandle.tokens() iterators pulled from
    different threads both drive step(); the lock serializes them."""
    cfg, params = served
    with InferenceServer(cfg, params,
                         ServerConfig(device_slots=2, host_slots=3,
                                      cache_len=64, output_len=5)) as server:
        handles = [server.submit(p, max_new_tokens=8)
                   for p in _prompts(4, base=11)]
        outs = {}
        errors = []
        lock = threading.Lock()

        def pull(h):
            try:
                toks = list(h.tokens())
                with lock:
                    outs[h.request_id] = toks
            except Exception as exc:
                with lock:
                    errors.append(exc)

        threads = [threading.Thread(target=pull, args=(h,))
                   for h in handles]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not errors
        for h in handles:
            assert outs[h.request_id] == h.output
            assert len(h.output) == 8


def test_replica_crash_respawn_and_error_handles(served):
    """Satellite 3b: a driver fault kills one replica; its in-flight
    handles finish with errors, the pool respawns it, the other
    replica is untouched, and new submissions succeed."""
    cfg, params = served
    factory = _factory(cfg, params, output_len=64, cache_len=128)
    with EngineReplicaPool(factory, replicas=2) as pool:
        # pin long-running requests to both replicas (least-loaded
        # routing alternates because each submit bumps the load)
        h0 = pool.submit([2, 3, 5, 7], 64)
        h1 = pool.submit([11, 13, 17, 19], 64)
        reps = {h0.replica_index, h1.replica_index}
        assert reps == {0, 1}
        victim = h0.replica_index
        survivor_handle = h1 if victim == h0.replica_index else h0
        pool.inject_fault(victim)

        crashed = h0 if h0.replica_index == victim else h1
        events = list(crashed.events(timeout=60.0))
        kind, err = events[-1]
        assert kind == "done" and err is not None and "died" in err
        assert crashed.failed and crashed.error == err

        # survivor's stream completes cleanly
        out = survivor_handle.result(timeout=120.0)
        assert len(out) == 64 and survivor_handle.error is None

        # respawn: poll until the replacement driver is live
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if len(pool.live_replicas()) == 2:
                break
            time.sleep(0.05)
        health = pool.health()
        assert health["status"] == "ok"
        assert pool.respawns >= 1
        assert pool.replicas[victim].generation >= 1

        # the respawned replica serves fresh work
        out2 = pool.submit([23, 29, 31, 37], 6).result(timeout=120.0)
        assert len(out2) == 6


def test_preemption_requeue_surfaced_in_stats(served):
    """Satellite 2: an urgent request whose preemption attempt finds a
    victim but no host capacity stays queued at its EDF position and
    the fallback is counted once in EngineStats."""
    cfg, params = served
    scfg = ServerConfig(device_slots=1, host_slots=1, cache_len=256,
                        page_size=32, host_pool_pages=1, output_len=48,
                        # pin the legacy swap-to-queue contract this test
                        # asserts; with the fallback on, blocked swaps
                        # recompute the victim instead (tests/test_faults.py)
                        recompute_fallback=False)
    with InferenceServer(cfg, params, scfg) as server:
        # resident fills the only device slot; kv demand 12+48 > 32 so
        # the one-page host pool can never take it as a victim
        resident = server.submit([1] * 12, max_new_tokens=48, priority=0)
        server.step()
        assert server.active == 1
        # urgent arrival: higher priority, but the swap has nowhere to
        # put the victim -> swap-to-queue fallback (stays at EDF head)
        urgent = server.submit([2] * 200, max_new_tokens=4, priority=1)
        lowprio = server.submit([3] * 6, max_new_tokens=4, priority=0)
        for _ in range(4):
            server.step()
        stats = server.stats
        assert stats.preemption_requeues >= 1
        assert stats.preemptions == 0
        server.run_until_idle()
        assert urgent.done and not urgent.failed
        assert lowprio.done and not lowprio.failed
        # counted once per request, not once per blocked iteration
        assert server.stats.preemption_requeues == 1
        # EDF head preserved: urgent got its first token before the
        # lower-priority request that arrived behind it
        assert urgent.request.first_token_time \
            <= lowprio.request.first_token_time
        assert "preemption_requeues" in server.stats.snapshot()


# --- HTTP/SSE ------------------------------------------------------------

@pytest.fixture(scope="module")
def gateway_stack(served):
    cfg, params = served
    pool = EngineReplicaPool(_factory(cfg, params), replicas=2)
    gateway, stop = serve_in_thread(pool, port=0, max_queue_depth=8)
    yield cfg, params, pool, gateway
    stop()
    pool.shutdown()


def test_sse_stream_bit_identical_to_direct_run(served, gateway_stack):
    cfg, params, pool, gateway = gateway_stack
    prompt = [9, 8, 7, 6]
    with InferenceServer(cfg, params,
                         ServerConfig(device_slots=2, host_slots=3,
                                      cache_len=64, output_len=5)) as ref:
        expected = ref.submit(prompt, max_new_tokens=5).result()
    r = sse_chat("127.0.0.1", gateway.port, prompt, max_new_tokens=5)
    assert r["status"] == 200 and r["error"] is None
    assert r["tokens"] == expected
    assert r["done"]["done"] is True
    assert r["done"]["tokens"] == len(expected)
    assert r["ttft_s"] is not None and r["ttft_s"] >= 0.0


def test_health_and_metrics_endpoints(gateway_stack):
    _, _, pool, gateway = gateway_stack
    health = get_json("127.0.0.1", gateway.port, "/health")
    assert health["status"] == 200
    assert health["body"]["status"] == "ok"
    assert len(health["body"]["replicas"]) == 2
    assert all(rep["alive"] for rep in health["body"]["replicas"])

    metrics = get_text("127.0.0.1", gateway.port, "/metrics")
    assert metrics["status"] == 200
    sample = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
                        r"(\{[^}]*\})? -?[0-9.eE+-]+(\n|$)")
    families = set()
    for line in metrics["body"].strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*", line)
            continue
        assert sample.match(line), f"unparseable sample: {line!r}"
        families.add(line.split("{")[0].split(" ")[0])
    assert "apex_replica_up" in families
    assert "apex_engine_iterations_total" in families
    assert "apex_engine_host_wait_seconds_total" in families
    assert "apex_engine_host_device_wait_seconds_total" in families
    assert "apex_gateway_requests_total" in families
    # HELP/TYPE emitted exactly once per family
    helps = re.findall(r"# HELP (\S+)", metrics["body"])
    assert len(helps) == len(set(helps))


def test_bad_requests_rejected(gateway_stack):
    _, _, _, gateway = gateway_stack
    r = sse_chat("127.0.0.1", gateway.port, [])
    assert r["status"] == 400
    resp = get_json("127.0.0.1", gateway.port, "/nope")
    assert resp["status"] == 404


def test_backpressure_503_queue_full(served):
    cfg, params = served
    with EngineReplicaPool(_factory(cfg, params), replicas=1) as pool:
        gateway, stop = serve_in_thread(pool, port=0, max_queue_depth=0)
        try:
            r = sse_chat("127.0.0.1", gateway.port, [1, 2, 3])
            assert r["status"] == 503
            assert "queue full" in r["error"]
            metrics = get_text("127.0.0.1", gateway.port, "/metrics")
            assert 'apex_gateway_shed_total{code="503"} 1' \
                in metrics["body"]
        finally:
            stop()


def test_backpressure_429_deadline_impossible(served):
    cfg, params = served
    with EngineReplicaPool(_factory(cfg, params), replicas=1) as pool:
        gateway, stop = serve_in_thread(pool, port=0, max_queue_depth=8)
        try:
            # the analytic perf model predicts a strictly positive
            # prefill time, so a zero deadline is impossible at the edge
            r = sse_chat("127.0.0.1", gateway.port, [1, 2, 3, 4],
                         deadline=0.0)
            assert r["status"] == 429
            assert "deadline" in r["error"]
            assert pool.depth() == 0       # shed before any engine state
        finally:
            stop()


# --- session affinity (PR 8 satellite) -----------------------------------

def test_session_affinity_sticky_and_failover(served):
    """``session_id`` pins a conversation to the replica that served
    its first turn; a dead pin falls back to a live replica and
    re-pins.  Generation is part of the pin, so a respawned replica
    (same index, fresh engine, empty prefix cache) never satisfies a
    stale pin by accident."""
    cfg, params = served
    with EngineReplicaPool(_factory(cfg, params), replicas=2) as pool:
        rep = pool.route("conv-a")
        for _ in range(4):                 # sticky regardless of load
            assert pool.route("conv-a") is rep
        other = pool.route("conv-b")       # independent pin
        assert pool.route("conv-b") is other

        # submissions honor the pin end to end
        h1 = pool.submit([2, 3, 5], 4, session_id="conv-a")
        h2 = pool.submit([2, 3, 5, 7], 4, session_id="conv-a")
        assert h1.replica_index == h2.replica_index == rep.index
        assert h1.result(timeout=120.0) and h2.result(timeout=120.0)

        # unpinned submissions still balance by load
        assert pool.submit([2, 3], 4).result(timeout=120.0)

        # kill the pinned replica: the pin is invalid (dead now,
        # generation-mismatched after the respawn) so routing falls
        # back to a live replica and re-pins there
        pool.inject_fault(rep.index)
        deadline = time.time() + 30.0     # the fault lands on the
        while time.time() < deadline:     # driver's next pump — poll
            cur = pool.replicas[rep.index]
            if not cur.alive or cur.generation != rep.generation:
                break
            time.sleep(0.05)
        rep2 = pool.route("conv-a")
        assert rep2.alive
        assert (rep2.index, rep2.generation) != (rep.index, rep.generation)
        assert pool.route("conv-a").index == rep2.index


def test_session_affinity_over_http(gateway_stack):
    """The gateway forwards ``session_id`` from the request body; both
    turns of a session land on the same replica (the terminal SSE
    event reports which one served the stream)."""
    _, _, _, gateway = gateway_stack
    r1 = sse_chat("127.0.0.1", gateway.port, [4, 5, 6],
                  max_new_tokens=3, session_id="http-conv")
    r2 = sse_chat("127.0.0.1", gateway.port, [4, 5, 6, 7, 8],
                  max_new_tokens=3, session_id="http-conv")
    assert r1["status"] == r2["status"] == 200
    assert r1["done"]["replica"] == r2["done"]["replica"]
