"""The trace reduction: busy union, idle share and gap attribution."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data"


def _events():
    dev = {"ops": [("a", 100, 200), ("b", 150, 300), ("c", 500, 600)],
           "modules": [("jit_step", 100, 300), ("jit_step", 500, 600)]}
    spans = [("bench.step", 0, 400), ("bench.client", 400, 700),
             ("bench.wait", 700, 1000)]
    return {"devices": {"/device:TPU:0": dev}, "spans": spans}


def test_union_and_gaps():
    assert tr.union([(1, 3), (2, 4), (6, 7)]) == [(1, 4), (6, 7)]
    assert tr.gaps([(1, 4), (6, 7)], 0, 10) == [(0, 1), (4, 6), (7, 10)]


def test_reduce_busy_idle_and_labels():
    r = tr.reduce(_events())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)     # 100-300 and 500-600
    idle = dict(r["breakdown"]["idle_gaps"])
    # gaps: 0-100 (step), 300-500 (midpoint 400: innermost covering span),
    # 600-1000 (midpoint 800: wait)
    assert idle["total bench.wait"] == pytest.approx(400e-9)
    assert idle["longest bench.wait"] == pytest.approx(400e-9)
    assert r["breakdown"]["device_ops"] == [["jit_step", pytest.approx(300e-9)]]


def test_no_device_work_is_an_error():
    ev = _events()
    ev["devices"]["/device:TPU:0"]["ops"] = []
    with pytest.raises(ValueError):
        tr.reduce(ev)


def test_recorded_tpu_trace():
    """Events read from a TPU v5e trace (four steps of a small jitted
    program under bench.step / bench.client / bench.wait spans)."""
    ev = json.loads((DATA / "tpu_trace_events.json").read_text())
    assert list(ev["devices"]) == ["/device:TPU:0"]
    r = tr.reduce(ev)
    lo = min(a for _, a, _ in ev["spans"])
    hi = max(b for _, _, b in ev["spans"])
    ops = tr.union(tr.clip([(a, b) for _, a, b in
                            ev["devices"]["/device:TPU:0"]["ops"]], lo, hi))
    assert r["busy_s"] == pytest.approx(sum(b - a for a, b in ops) * 1e-9)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["device_ops"][0][0].startswith("jit__lambda")
    idle = sum(v for k, v in r["breakdown"]["idle_gaps"] if k.startswith("total"))
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_load_reads_spans_from_a_trace_file(tmp_path):
    """A trace recorded here on the CPU: the host spans come through
    ``load``; the CPU has no device plane, so nothing counts as busy."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.client"):
            pass
    jax.profiler.stop_trace()
    ev = tr.load(tr.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ev["spans"]]
    assert names.count("bench.step") == 3 and names.count("bench.client") == 3
    assert ev["devices"] == {}
    with pytest.raises(ValueError):
        tr.reduce(ev)
