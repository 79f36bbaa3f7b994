"""The generator: the same seed gives the same requests; every seed gets
the same sizes in the same order, and other tokens."""
import pytest

from bench.registry import Registry
from bench.traffic import Traffic, lognormal_quantiles

REG = Registry()


def _sizes(t):
    return [(len(s.prompt), s.max_new_tokens) for s in t.initial + t.queue]


def test_closed_loop_is_deterministic_per_seed():
    mix = REG.mix("reasoning-offload")
    a = Traffic(mix, 2**31 + 11, vocab=92544, cache_len=2048, streams=16)
    b = Traffic(mix, 2**31 + 11, vocab=92544, cache_len=2048, streams=16)
    c = Traffic(mix, 2**31 + 12, vocab=92544, cache_len=2048, streams=16)
    assert [s.prompt for s in a.initial + a.queue] == \
        [s.prompt for s in b.initial + b.queue]
    # the sizes and their order are fixed; the seed draws the tokens
    assert _sizes(a) == _sizes(c)
    assert a.initial[0].prompt != c.initial[0].prompt
    for s in a.initial + a.queue:
        assert len(s.prompt) + s.max_new_tokens <= 2047
        assert s.max_new_tokens >= 1


def test_only_closed_loops_are_made():
    mix = dict(REG.mix("reasoning-offload"), loop="open")
    with pytest.raises(ValueError, match="closed loops only"):
        Traffic(mix, 1, vocab=92544, cache_len=2048, streams=16)
    with pytest.raises(ValueError, match="number of streams"):
        Traffic(REG.mix("reasoning-offload"), 1, vocab=92544, cache_len=2048,
                streams=0)


def test_quantiles_follow_the_published_shape():
    x = lognormal_quantiles({"mean": 420, "cv": 0.6, "min": 1, "max": 10**6}, 4000)
    assert abs(x.mean() - 420) < 5
    assert abs(x.std() / x.mean() - 0.6) < 0.03
