"""The chunk program's share of iterations and the fill of its buffer,
read from the engine's counters over a window."""
import pytest

from bench.harness import counters
from bench.registry import Registry
from bench.window import Window

from repro.serving.lifecycle import EngineStats

REG = Registry()


def _window(c0, c1):
    return Window(t0=10.0, t1=20.0, setup_s=5.0, model={}, engine={},
                  stamps={}, token_tiers={}, failed=[], counters0=c0,
                  counters1=c1, steps=[])


def _counts(iterations, chunk_iterations, prefilled, buffer_tokens):
    return {"iterations": iterations, "chunk_iterations": chunk_iterations,
            "chunked_prefill_tokens": prefilled,
            "chunk_buffer_tokens": buffer_tokens}


def test_chunk_shares_over_the_window():
    # 200 iterations, 14 of them ran a 16 x 64 buffer holding 2,940
    # prompt tokens in all; the counters' values before the window cancel
    w = _window(_counts(50, 30, 100, 30 * 1024),
                _counts(250, 44, 3040, 44 * 1024))
    assert REG.reader("sched.chunk_iter_share").read(w) == pytest.approx(7.0)
    assert REG.reader("sched.chunk_fill_share").read(w) == pytest.approx(
        100.0 * 2940 / (14 * 1024))


def test_nothing_chunked_in_the_window():
    # iterations with no chunk call: none of them ran the chunk program,
    # and there is no buffer whose fill could be read
    w = _window(_counts(50, 30, 100, 30 * 1024),
                _counts(250, 30, 100, 30 * 1024))
    assert REG.reader("sched.chunk_iter_share").read(w) == 0.0
    assert REG.reader("sched.chunk_fill_share").read(w) is None
    idle = _window(_counts(50, 30, 100, 30 * 1024),
                   _counts(50, 30, 100, 30 * 1024))
    assert REG.reader("sched.chunk_iter_share").read(idle) is None


def test_a_program_without_the_counters_reads_nothing():
    c = {"iterations": 0, "chunked_prefill_tokens": 0}
    w = _window(dict(c), {"iterations": 100, "chunked_prefill_tokens": 640})
    assert REG.reader("sched.chunk_iter_share").read(w) is None
    assert REG.reader("sched.chunk_fill_share").read(w) is None


def test_engine_stats_carry_the_counters():
    stats = EngineStats(iterations=9, chunk_iterations=3,
                        chunked_prefill_tokens=70, chunk_buffer_tokens=3 * 80)
    w = _window(counters(EngineStats()), counters(stats))
    assert REG.reader("sched.chunk_iter_share").read(w) == pytest.approx(
        100.0 / 3)
    assert REG.reader("sched.chunk_fill_share").read(w) == pytest.approx(
        100.0 * 70 / 240)
