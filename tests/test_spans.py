"""The engine's host-tier wait counters, at the boundaries of its spans:
the engine thread's time blocked on host jobs, and the host executor's
wait for the device step that makes a job's QKV."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.scheduler import Decision, StrategyKind
from repro.models import init_params
from repro.serving import Engine, EngineConfig, InferenceServer, ServerConfig
from repro.serving.request import Request, make_synthetic_request


class _Always:
    """Scheduler stub: one hybrid strategy whenever a host row decodes."""

    def __init__(self, kind: StrategyKind) -> None:
        self.kind = kind

    def schedule(self, prefill, decode_gpu, decode_cpu, *, mean_context,
                 prefill_tokens=0):
        if not decode_cpu:
            return Decision(StrategyKind.GPU_ONLY, list(prefill),
                            list(decode_gpu), [], reason="stub")
        return Decision(self.kind, list(prefill), list(decode_gpu),
                        list(decode_cpu), sub_batch_1=list(decode_gpu),
                        sub_batch_2=list(decode_cpu), reason="stub")


@pytest.fixture(scope="module")
def model():
    cfg = get_config("internlm2-1.8b").reduced(layers=None, d_model=64,
                                               vocab=64)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _requests(cfg, n=4, out_len=5):
    rng = np.random.default_rng(3)
    return [make_synthetic_request(rng, prompt_len=7, output_len=out_len,
                                   vocab=cfg.vocab_size) for _ in range(n)]


def _run(model, kind):
    cfg, params = model
    # the watchdog's deadline far off: a slow job under a loaded test
    # machine must not turn a poll into a synchronous recompute
    eng = Engine(cfg, params, EngineConfig(
        device_slots=1, host_slots=3, cache_len=64,
        host_job_min_timeout=600.0), scheduler=_Always(kind))
    try:
        return eng.run(_requests(cfg))
    finally:
        eng.shutdown()


def test_pipeline_steps_wait_for_the_host_and_overlap_steps_do_not(model):
    pipe = _run(model, StrategyKind.ASYM_PIPELINE)
    assert pipe.strategy_counts.get("asym_pipeline", 0) > 0
    assert pipe.host_tokens > 0 and pipe.host_wait_time > 0.0
    over = _run(model, StrategyKind.ASYNC_OVERLAP)
    assert over.strategy_counts.get("async_overlap", 0) > 0
    assert over.host_tokens > 0 and over.host_fallbacks == 0
    assert over.host_wait_time == 0.0


@pytest.mark.parametrize("kind", [StrategyKind.ASYM_PIPELINE,
                                  StrategyKind.ASYNC_OVERLAP])
def test_device_wait_is_part_of_the_transfer(model, kind):
    stats = _run(model, kind)
    assert stats.host_busy_time > 0.0
    assert 0.0 <= stats.host_device_wait_time <= stats.host_transfer_time
    assert stats.host_transfer_time <= stats.host_busy_time


def test_server_stats_export_the_wait_counters(model):
    cfg, params = model
    server = InferenceServer(cfg, params, ServerConfig(
        device_slots=1, host_slots=3, cache_len=64))
    try:
        for r in _requests(cfg):
            server.submit(Request(prompt=list(r.prompt),
                                  max_new_tokens=r.max_new_tokens))
        while server.engine.has_work:
            server.step()
        ex = server.engine._executor
        stats = server.stats
        assert stats.host_device_wait_time == ex.device_wait_time
        assert stats.host_busy_time == pytest.approx(
            ex.compute_time + ex.transfer_time)
        snap = stats.snapshot()
        assert snap["host_wait_seconds"] == stats.host_wait_time
        assert snap["host_device_wait_seconds"] == ex.device_wait_time
    finally:
        server.shutdown()


def test_decode_programs_have_stable_names(model):
    cfg, params = model
    eng = Engine(cfg, params, EngineConfig(device_slots=1, host_slots=1,
                                           cache_len=64))
    try:
        assert eng._decode_fn.__name__ == "_decode_traced"
        assert eng._decode_overlap_fn.__name__ == "_decode_overlap_traced"
    finally:
        eng.shutdown()
