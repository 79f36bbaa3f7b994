"""Metric arithmetic: tails over every gap, window clipping, each tier's
gaps apart."""
import numpy as np
import pytest

from bench.registry import Registry
from bench.window import StepRecord, Window, gaps_in, percentile, tokens_in


def _window(stamps, tiers=None, **kw):
    tiers = tiers or {rid: ["prefill"] + ["device"] * (len(s) - 1)
                      for rid, s in stamps.items()}
    base = dict(t0=10.0, t1=20.0, setup_s=5.0, model={}, engine={"device_slots": 2},
                stamps=stamps, token_tiers=tiers, failed=[],
                counters0={}, counters1={}, steps=[])
    base.update(kw)
    return Window(**base)


def test_gaps_pool_every_request_and_clip_to_window():
    stamps = {1: [9.0, 10.0, 10.5, 11.0], 2: [12.0, 15.0, 21.0], 3: [19.9]}
    g = gaps_in(stamps, 10.0, 20.0)
    # request 1: 10.0->10.5->11.0 inside; 9.0 is before the window
    # request 2: 12->15 inside; 21 is after it; request 3 has one token
    assert sorted(g.tolist()) == [0.5, 0.5, 3.0]
    assert tokens_in(stamps, 10.0, 20.0) == 6


def test_tail_is_over_all_gaps_not_per_request_means():
    # one request with many short gaps, one with a single long gap: a
    # per-request mean would hide the long one, the tail over gaps does not
    stamps = {1: list(np.arange(10.0, 11.0, 0.01)), 2: [10.0, 14.0]}
    g = gaps_in(stamps, 10.0, 20.0)
    assert percentile(g, 100) == pytest.approx(4.0)
    assert percentile(g, 95) == pytest.approx(0.01, abs=1e-6)
    assert percentile(np.zeros(0), 95) is None


def test_each_tier_keeps_its_own_gaps():
    # request 1 moves to the host tier after its third token; a gap counts
    # for the tier that made the token that ends it
    stamps = {1: [9.0, 10.0, 10.1, 12.0, 14.0, 21.0], 2: [10.0, 10.2, 10.4]}
    tiers = {1: ["prefill", "device", "device", "host", "host", "host"],
             2: ["prefill", "device", "device"]}
    dev = gaps_in(stamps, 10.0, 20.0, tiers, "device")
    host = gaps_in(stamps, 10.0, 20.0, tiers, "host")
    # 9.0 -> 10.0 starts before the window; 14 -> 21 ends after it
    assert sorted(dev.tolist()) == pytest.approx([0.1, 0.2, 0.2])
    assert sorted(host.tolist()) == pytest.approx([1.9, 2.0])
    assert len(gaps_in(stamps, 10.0, 20.0)) == len(dev) + len(host)
    # a tier with no gaps in the window has nothing to read, not 0
    reg = Registry()
    w = _window({1: [10.0, 10.5]})
    assert reg.reader("host.itl_p95_ms").read(w) is None


def test_readers_from_files():
    reg = Registry()
    steps = [StepRecord(t_end=11.0, device_contexts=[100, 200], host_tokens=1,
                        prompt_tokens=64)]
    w = _window({1: [10.0, 10.1, 10.2], 2: [10.0, 10.5], 3: [11.0, 13.0]},
                tiers={1: ["prefill", "device", "device"],
                       2: ["prefill", "device"], 3: ["prefill", "host"]},
                counters0={"iterations": 0, "device_slot_iterations": 0,
                           "device_tokens": 0, "host_tokens": 0},
                counters1={"iterations": 10, "device_slot_iterations": 15,
                           "device_tokens": 8, "host_tokens": 2},
                steps=steps)
    assert reg.reader("tokens_per_s").read(w) == pytest.approx(0.7)
    assert reg.reader("itl_p95_ms.device").read(w) == pytest.approx(
        1e3 * np.percentile([0.1, 0.1, 0.5], 95))
    assert reg.reader("host.itl_p95_ms").read(w) == pytest.approx(2000.0)
    assert reg.reader("setup_s").read(w) == 5.0
    assert reg.reader("lifecycle.device_occupancy").read(w) == pytest.approx(75.0)
    assert reg.reader("lifecycle.host_token_share").read(w) == pytest.approx(20.0)
    assert reg.reader("engine.iter_ms").read(w) == pytest.approx(1000.0)
    # nothing traced: trace-derived readers return nothing, never 0
    assert reg.reader("device.idle_share").read(w) is None
    assert reg.reader("step.roofline_share").read(w) is None
