"""Algorithm-1 scheduler + admission control + paged pool properties."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.perf_model import analytic_model
from repro.core.scheduler import (AdmissionController, ApexScheduler,
                                  StrategyKind, chunk_budget)
from repro.models.kv_cache import PagedKVPool


@pytest.fixture(scope="module")
def sched():
    return ApexScheduler(analytic_model("a10", get_config("llama3.1-8b")))


def test_rule1_no_host_requests_is_gpu_only(sched):
    d = sched.schedule([], [1, 2, 3], [], mean_context=1024)
    assert d.strategy == StrategyKind.GPU_ONLY


def test_decode_only_prefers_async_overlap_on_a10(sched):
    # N_G/N_C ~ 35 >> threshold on the A10 calibration
    d = sched.schedule([], list(range(64)), list(range(32)),
                       mean_context=1024)
    assert d.strategy == StrategyKind.ASYNC_OVERLAP
    assert "Ineq(6)" in d.reason


def test_mixed_branch_widens_window(sched):
    d = sched.schedule(["p"], list(range(64)), list(range(32)),
                       mean_context=1024, prefill_tokens=4096)
    # with a big prefill window pipelining becomes beneficial (paper
    # Algorithm 1 mixed branch)
    assert d.strategy == StrategyKind.ASYM_PIPELINE
    assert d.sub_batch_2 is not None


def test_host_min_ratio_below_threshold_gpu_aligned():
    """§4.2 admission threshold: a host cohort smaller than
    ratio * device_batch falls back to GPU-aligned handling (deferred
    sync) even when the pipeline inequality would hold."""
    sched = ApexScheduler(analytic_model("a10", get_config("llama3.1-8b")),
                          host_min_ratio=1.0)
    # identical inputs pipeline in test_mixed_branch_widens_window;
    # with the threshold (32 host < 1.0 * 64 device) they must not
    d = sched.schedule(["p"], list(range(64)), list(range(32)),
                       mean_context=1024, prefill_tokens=4096)
    assert d.strategy == StrategyKind.ASYNC_OVERLAP
    assert "host_min_ratio" in d.reason
    assert d.predicted_time > 0


def test_host_min_ratio_above_threshold_still_pipelines():
    sched = ApexScheduler(analytic_model("a10", get_config("llama3.1-8b")),
                          host_min_ratio=0.25)
    # 32 host >= 0.25 * 64 device: threshold passes, Ineq applies as-is
    d = sched.schedule(["p"], list(range(64)), list(range(32)),
                       mean_context=1024, prefill_tokens=4096)
    assert d.strategy == StrategyKind.ASYM_PIPELINE
    # decode-only path honors the threshold too
    d2 = ApexScheduler(analytic_model("a10", get_config("llama3.1-8b")),
                       host_min_ratio=8.0).schedule(
        [], list(range(64)), list(range(32)), mean_context=1024)
    assert d2.strategy == StrategyKind.ASYNC_OVERLAP
    assert "host_min_ratio" in d2.reason


def test_chunk_budget_idle_grants_whole_backlog(sched):
    """Nothing decoding => nothing to stall: a backlog that fits one
    chunk call prefills at once (the admission-burst path)."""
    d = sched.schedule(["p"], [], [], mean_context=1,
                       chunk_backlog_tokens=777, chunk_capacity=16 * 64)
    assert d.chunk_tokens == chunk_budget(777, 16 * 64) == 777


def test_chunk_budget_caps_under_active_decode(sched):
    """Device-only decode active: the chunk capacity bounds the grant,
    and the backlog bounds it from below when smaller."""
    for backlog, want in ((10_000, 256), (256, 256), (5, 5)):
        d = sched.schedule(["p"], list(range(4)), [], mean_context=1024,
                           chunk_backlog_tokens=backlog, chunk_capacity=256)
        assert d.chunk_tokens == want


@pytest.mark.parametrize("backlog", [1, 2, 63, 64, 500, 1024, 10_000])
def test_chunk_budget_fills_buffer_under_host_cohort(sched, backlog):
    """With a live host cohort the grant is min(backlog, capacity): the
    chunk program computes its whole pinned buffer whatever the grant,
    so the grant is never shaved to the host-attention window."""
    capacity = 16 * 64
    d = sched.schedule(["p"], list(range(8)), list(range(8)),
                       mean_context=1024, chunk_backlog_tokens=backlog,
                       chunk_capacity=capacity)
    assert d.decode_cpu                       # the host cohort is live
    assert d.chunk_tokens == min(backlog, capacity)


def test_decision_carries_chunk_tokens(sched):
    """schedule() with a chunk backlog surfaces the grant in
    Decision.chunk_tokens."""
    d = sched.schedule(["p"], list(range(8)), [], mean_context=256,
                       chunk_backlog_tokens=500, chunk_capacity=32)
    assert d.chunk_tokens == 32
    assert d.strategy == StrategyKind.GPU_ONLY     # no host rows
    # legacy call path keeps chunk_tokens at 0
    d2 = sched.schedule(["p"], list(range(8)), [], mean_context=256,
                        prefill_tokens=500)
    assert d2.chunk_tokens == 0


@pytest.mark.parametrize("host_rows", [0, 8])
def test_mixed_branch_priced_at_chunk_buffer(sched, host_rows):
    """A 2-token grant into a 1024-token buffer is priced as the 1024
    tokens the device computes, not as 2: predicted_time matches a
    whole-buffer prefill share on both the GPU-only and the host-cohort
    branches."""
    kw = dict(mean_context=1024)
    d = sched.schedule(["p"], list(range(8)), list(range(host_rows)),
                       chunk_backlog_tokens=2, chunk_capacity=1024, **kw)
    full = sched.schedule(["p"], list(range(8)), list(range(host_rows)),
                          prefill_tokens=1024, **kw)
    assert d.chunk_tokens == 2
    assert d.strategy == full.strategy
    assert d.predicted_time == pytest.approx(full.predicted_time)
    t2 = sched.schedule(["p"], list(range(8)), list(range(host_rows)),
                        prefill_tokens=2, **kw).predicted_time
    assert d.predicted_time > t2


def test_rule4_partial_progress_prioritized(sched):
    class R:
        def __init__(self, p):
            self.layer_progress = p
    reqs = [R(0), R(10), R(5)]
    d = sched.schedule(["p"], [1], reqs, mean_context=1024,
                       prefill_tokens=4096)
    assert d.strategy == StrategyKind.ASYM_PIPELINE
    progresses = [r.layer_progress for r in d.sub_batch_2]
    assert progresses == sorted(progresses, reverse=True)


@given(budget_d=st.integers(10, 10000), budget_h=st.integers(0, 100000),
       needs=st.lists(st.integers(1, 2000), min_size=1, max_size=60))
@settings(max_examples=100, deadline=None)
def test_admission_never_overcommits(budget_d, budget_h, needs):
    ac = AdmissionController(device_kv_budget_tokens=budget_d,
                             host_kv_budget_tokens=budget_h)
    placed = []
    for need in needs:
        tier = ac.place(need)
        placed.append((tier, need))
        assert ac.device_used <= budget_d
        assert ac.host_used <= budget_h
    # GPU-first: a request lands on host only if the device could not
    # hold it at that moment
    ac2 = AdmissionController(device_kv_budget_tokens=budget_d,
                              host_kv_budget_tokens=budget_h)
    for tier, need in placed:
        if tier == "host":
            assert ac2.device_used + need > budget_d
        got = ac2.place(need)
        assert got == tier


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_paged_pool_alloc_free_invariants(data):
    pool = PagedKVPool(num_pages=64, page_size=16, num_layers=2,
                       kv_heads=2, head_dim=8)
    live = {}
    rid = 0
    for _ in range(data.draw(st.integers(1, 30))):
        if live and data.draw(st.booleans()):
            victim = data.draw(st.sampled_from(sorted(live)))
            pool.free(victim)
            del live[victim]
        else:
            tokens = data.draw(st.integers(1, 64))
            if pool.can_admit(tokens):
                pool.allocate(rid, tokens)
                live[rid] = tokens
                rid += 1
    used = sum(len(chain) for chain in pool.page_tables.values())
    assert used + pool.num_free == 64
    # no page is referenced twice
    all_pages = [p for chain in pool.page_tables.values() for p in chain]
    assert len(all_pages) == len(set(all_pages))


def test_paged_pool_write_read_roundtrip(rng):
    pool = PagedKVPool(num_pages=32, page_size=4, num_layers=3,
                       kv_heads=2, head_dim=8)
    pool.allocate(7, 10)
    k = rng.standard_normal((10, 2, 8)).astype(np.float32)
    v = rng.standard_normal((10, 2, 8)).astype(np.float32)
    for layer in range(3):
        pool.write_prompt(7, layer, k, v, advance=(layer == 2))
    for layer in range(3):
        k2, v2 = pool.gather(7, layer)
        np.testing.assert_array_equal(k, k2)
        np.testing.assert_array_equal(v, v2)
    pool.append(7, 0, k[0], v[0], advance=False)
    pool.append(7, 1, k[0], v[0], advance=False)
    pool.append(7, 2, k[0], v[0], advance=True)
    assert pool.lengths[7] == 11
