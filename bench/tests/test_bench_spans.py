"""Device idle put down to the program's own spans: the split at span
edges on the engine's line, the readers of the host executor's
counters, and the spans a real engine writes into a profiler trace."""
import pytest

from bench import idle_by_span as ibs
from bench import trace_reduce as tr
from bench.registry import Registry
from bench.window import Window

PHASES = ("apex.admit", "apex.rebalance", "apex.tier_move", "apex.schedule",
          "apex.dispatch", "apex.host_wait", "apex.sync", "apex.host_token",
          "apex.finish_chunks", "apex.retire")
HOST_JOB = ("apex.host_job", "apex.host_job.device_wait", "apex.host_job.copy",
            "apex.host_job.attention")


def _events():
    """One device busy 100-300 and 500-600 of a 0-1000 window; the
    engine's line (1) runs a step 0-400 with an admission and a dispatch
    inside, a host wait inside the dispatch, then the client; a worker's
    line (0) runs a host job across the idle 300-500."""
    dev = {"ops": [("a", 100, 200), ("b", 150, 300), ("c", 500, 600)],
           "modules": [("jit_step", 100, 300), ("jit_step", 500, 600)]}
    lines = [("bench.step", 0, 400, 1), ("apex.step", 10, 390, 1),
             ("apex.admit", 10, 50, 1), ("apex.dispatch", 50, 380, 1),
             ("apex.host_wait", 320, 360, 1),
             ("bench.client", 400, 700, 1), ("bench.wait", 700, 1000, 1),
             ("apex.host_job", 250, 480, 0),
             ("apex.host_job.attention", 260, 470, 0),
             # outside the window of bench spans
             ("apex.step", 1100, 1200, 1)]
    spans = [(n, a, b) for n, a, b, _ in lines if n.startswith("bench.")]
    return {"devices": {"/device:TPU:0": dev}, "spans": spans,
            "lines": lines}


def test_idle_goes_to_the_innermost_engine_span_split_at_edges():
    r = ibs.attribute(_events())
    idle = {k: v * 1e9 for k, v in r["idle_by_span"].items()}
    # gap 0-100: bench.step 0-10, admit 10-50, dispatch 50-100; gap
    # 300-500: dispatch 300-320 and 360-380, host wait 320-360, apex.step
    # 380-390, bench.step 390-400, client 400-500; gap 600-1000: client
    # 600-700, wait 700-1000
    assert idle == pytest.approx({
        "bench.step": 20, "apex.admit": 40, "apex.dispatch": 90,
        "apex.host_wait": 40, "apex.step": 10, "bench.client": 200,
        "bench.wait": 300})
    assert not any(k.startswith("apex.host_job") for k in idle)
    gap = r["longest_gaps"][0]
    assert gap["s"] == pytest.approx(400e-9) and gap["at_s"] == pytest.approx(600e-9)


def test_idle_by_span_adds_up_to_the_window_less_busy():
    ev = _events()
    red = tr.reduce(ev)
    r = ibs.attribute(ev)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    shares = ibs.layer_shares(r["idle_by_span"], red["window_s"])
    assert sum(shares.values()) == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    assert shares["lifecycle.idle_share"] == pytest.approx(4.0)
    assert shares["engine.idle_share"] == pytest.approx(10.0)
    assert shares["host.wait_idle_share"] == pytest.approx(4.0)
    assert shares["sched.idle_share"] == 0.0
    assert shares[ibs.REST] == pytest.approx(52.0)


def test_program_spans_leave_the_window_and_busy_time_as_they_were():
    ev = _events()
    bare = dict(ev, lines=[s for s in ev["lines"] if s[0].startswith("bench.")])
    assert tr.reduce(ev) == tr.reduce(bare)
    r = ibs.attribute(bare)
    assert set(r["idle_by_span"]) == {"bench.step", "bench.client",
                                      "bench.wait"}
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        sum(ibs.attribute(ev)["idle_by_span"].values()))


def test_gaps_under_no_span_and_metadata_stripped():
    ev = _events()
    ev["spans"] = [("bench.step", 0, 400), ("bench.wait", 700, 1000)]
    ev["lines"] = [(ibs.span_name("apex.host_job#job=3,layer=1#"), 0, 1, 0)] \
        + [(n, a, b, 1) for n, a, b in ev["spans"]]
    assert ev["lines"][0][0] == "apex.host_job"
    idle = ibs.attribute(ev)["idle_by_span"]
    assert idle[ibs.NO_SPAN] == pytest.approx(200e-9)   # 400-500, 600-700


def _window(c0, c1, seconds=10.0):
    return Window(t0=0.0, t1=seconds, setup_s=1.0, model={}, engine={},
                  stamps={}, token_tiers={}, failed=[], counters0=c0,
                  counters1=c1, steps=[])


def test_host_work_and_hidden_share_readers():
    reg = Registry()
    c0 = {"host_busy_time": 1.0, "host_device_wait_time": 0.5,
          "host_wait_time": 0.1}
    c1 = {"host_busy_time": 8.0, "host_device_wait_time": 6.5,
          "host_wait_time": 0.35}
    w = _window(c0, c1)
    # busy 7 s, of it 6 s waiting for the device: 1 s of work in 10 s
    assert reg.reader("host.work_share").read(w) == pytest.approx(10.0)
    # the engine waited 0.25 s of that 1 s
    assert reg.reader("host.hidden_share").read(w) == pytest.approx(75.0)
    c1["host_wait_time"] = 1.6       # waited on jobs not yet started
    assert reg.reader("host.hidden_share").read(w) == pytest.approx(-50.0)


def test_host_readers_find_nothing_without_the_counters():
    """A program without the counters, or a window without host work,
    gives nothing to read, and nothing raises."""
    reg = Registry()
    old = _window({"host_busy_time": 1.0}, {"host_busy_time": 5.0})
    idle = _window({"host_busy_time": 1.0, "host_device_wait_time": 0.0,
                    "host_wait_time": 0.0},
                   {"host_busy_time": 1.0, "host_device_wait_time": 0.0,
                    "host_wait_time": 0.0})
    for name in ("host.work_share", "host.hidden_share"):
        assert reg.reader(name).read(old) is None
        assert reg.reader(name).read(idle) is None


class _Pipeline:
    """Scheduler stub: the blocking ASYM_PIPELINE step whenever a host
    row decodes, so the engine waits on the host tier."""

    def schedule(self, prefill, decode_gpu, decode_cpu, *, mean_context,
                 prefill_tokens=0):
        from repro.core.scheduler import Decision, StrategyKind

        if not decode_cpu:
            return Decision(StrategyKind.GPU_ONLY, list(prefill),
                            list(decode_gpu), [], reason="stub")
        return Decision(StrategyKind.ASYM_PIPELINE, list(prefill),
                        list(decode_gpu), list(decode_cpu),
                        sub_batch_1=list(decode_gpu),
                        sub_batch_2=list(decode_cpu), reason="stub")


def test_engine_writes_its_spans_into_a_profiler_trace(tmp_path):
    """A reduced offload engine traced from submit to drain: every phase
    span nests inside an ``apex.step`` on the engine's line, and the host
    jobs' spans sit on another line."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import Engine, EngineConfig
    from repro.serving.request import make_synthetic_request

    cfg = get_config("internlm2-1.8b").reduced(layers=None, d_model=64,
                                               vocab=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    # two short device rows free their slots, so the two host rows move up
    reqs = [make_synthetic_request(rng, prompt_len=7, output_len=n,
                                   vocab=cfg.vocab_size) for n in (3, 3, 6, 6)]
    eng = Engine(cfg, params, EngineConfig(device_slots=2, host_slots=2,
                                           cache_len=64),
                 scheduler=_Pipeline())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for r in reqs:
            eng.submit(r)
        while eng.has_work:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
        eng.shutdown()
    assert eng.stats.migrations > 0 and eng.stats.host_tokens > 0
    ev = ibs.load(tr.find_xplane(str(tmp_path)))
    engine = {(n, a, b) for n, a, b in ibs.engine_spans(ev)}
    names = {n for n, _, _ in engine}
    steps = [(a, b) for n, a, b in engine if n == "apex.step"]
    assert len(steps) == eng.stats.iterations
    assert set(PHASES) <= names, set(PHASES) - names
    for n, a, b in engine:
        if n in PHASES:
            assert any(s <= a and b <= e for s, e in steps), n
    on_engine = {line for n, _, _, line in ev["lines"] if n == "apex.step"}
    jobs = {line for n, _, _, line in ev["lines"] if n in HOST_JOB}
    assert jobs and not jobs & on_engine
    assert {n for n, _, _, _ in ev["lines"]} >= set(HOST_JOB)
