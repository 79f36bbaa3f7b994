"""Model families chosen by the configuration file: InternLM2's weights,
engine configuration and operation counts are the numbers the harness
gave before families existed, and an architecture the harness did not
know is served and judged through files of its own."""
import dataclasses
import hashlib
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.peaks import peak_for
from bench.registry import Registry
from bench.window import StepRecord, Window

DATA = Path(__file__).parent / "data"
REG = Registry()
TINY = Registry(DATA / "BENCHMARK.json",
                configs=DATA / "configs").config("tiny")
INTERNLM2 = REG.family("internlm2")

# taken from the harness before the InternLM2 code moved into its family
# module: sha256 over each leaf's shape, dtype and bfloat16 bits, seed
# 2**40 + 3, the tiny configuration
TINY_WEIGHTS_SHA256 = \
    "db291a69df7302f867ebe7b48c4323f793054b4a6fd88a377a7b92effd9bdfe9"
STEPS = [StepRecord(t_end=11.0, device_contexts=[100, 200, 1500],
                    host_tokens=3, prompt_tokens=64),
         StepRecord(t_end=11.5, device_contexts=[], host_tokens=0,
                    prompt_tokens=1024),
         StepRecord(t_end=12.0, device_contexts=[2047] * 8, host_tokens=8,
                    prompt_tokens=0)]
# name: (layers, d, heads, kv heads, d_ff, vocab, rope theta, positions,
# parameters, (operations, bytes) of each of STEPS, step.mfu,
# step.roofline_share)
PINNED = {
    "tiny": ((2, 128, 4, 2, 256, 512, 1e6, 4096), 426624,
             [(43917312.0, 1645312), (603979776.0, 722176),
              (28303360.0, 9110784)],
             0.0001372995833502538, 0.0012959274829618885),
    "internlm2-1.8b": ((24, 2048, 16, 8, 8192, 92544, 1e6, 32768), 1889110016,
                       [(214021177344.0, 3576401920),
                        (3092376453120.0, 3399159808),
                        (57602998272.0, 5009772544)],
                       0.6830458129413198, 2.09448575247183),
    "internlm2-20b-8l": ((8, 6144, 48, 8, 16384, 92544, 1e6, 32768),
                         4257847296,
                         [(444055683072.0, 7437594624),
                          (6390911336448.0, 7378513920),
                          (121272532992.0, 7915384832)],
                         1.4124344269059899, 4.094974476677842),
}


def _config(name):
    return TINY if name == "tiny" else REG.config(name)


def test_internlm2_weights_are_the_parents_bit_for_bit():
    p = INTERNLM2.program_params(2**40 + 3, TINY["model"])
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(p):
        a = np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.view(np.uint16).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


@pytest.mark.parametrize("name", sorted(PINNED))
def test_internlm2_config_and_counts_are_the_parents(name):
    from repro.models.config import BlockKind, FFNKind, ModelConfig

    c = _config(name)
    assert "family" not in c and REG.family_of(c) is INTERNLM2
    (layers, d, h, kv, f, v, theta, positions), params, costs, mfu, roof = \
        PINNED[name]
    assert INTERNLM2.model_config(c) == ModelConfig(
        name=name, num_layers=layers, d_model=d, num_heads=h, num_kv_heads=kv,
        d_ff=f, vocab_size=v, block_pattern=(BlockKind.ATTN,),
        ffn_kind=FFNKind.DENSE, rope_theta=theta, norm_eps=1e-5,
        max_seq_len=positions)
    m = c["model"]
    assert INTERNLM2.param_count(m) == params
    assert [INTERNLM2.step_cost(m, s) for s in STEPS] == costs
    w = Window(t0=10.0, t1=12.5, setup_s=5.0, model=m, engine={}, stamps={},
               token_tiers={}, failed=[], counters0={}, counters1={},
               steps=STEPS, peak=peak_for("TPU v5 lite"),
               trace={"busy_s": 1.25, "window_s": 2.5}, family=INTERNLM2)
    assert REG.reader("step.mfu").read(w) == mfu
    assert REG.reader("step.roofline_share").read(w) == roof


def _wide_heads_registry():
    return Registry(DATA / "BENCHMARK.json", configs=DATA / "configs",
                    traffic=DATA / "traffic", families=DATA / "families",
                    references=DATA / "reference")


@pytest.fixture(scope="module")
def wide_heads_run():
    """A timed toy window of ``tiny-wide-heads``, a family that only the
    files under ``tests/data`` describe: heads of 64 where hidden size
    over heads is 32."""
    reg = _wide_heads_registry()
    cfg = reg.config("tiny-wide-heads")
    seed = 2**31 + 23
    run = harness.serve_window(reg, cfg, reg.mix("reasoning-tiny"), seed, 5.0,
                               t_process=time.perf_counter(),
                               log=lambda *a: None)
    return reg, cfg, seed, run


def test_a_new_family_is_served_and_judged_correct(wide_heads_run):
    reg, cfg, seed, run = wide_heads_run
    fam = reg.family_of(cfg)
    mcfg = fam.model_config(cfg)
    assert mcfg.resolved_head_dim == 64 != mcfg.d_model // mcfg.num_heads
    verdict = harness.check(reg, cfg, run.client, seed, run.failed)
    assert verdict["correct"], verdict
    w = run.window
    assert w.family is fam and w.delta("host_tokens") > 0
    # the counter the family names is recorded for every step, and its
    # count reads it
    assert all(s.counters == {"chunked_prefill_tokens": s.prompt_tokens}
               for s in w.steps)
    assert sum(s.prompt_tokens for s in w.steps) > 0
    w.peak = peak_for("TPU v5 lite")
    assert REG.reader("step.mfu").read(w) > 0


def test_a_reference_with_heads_of_hidden_over_heads_is_caught(
        wide_heads_run):
    """The plain reference taking the head size as hidden size over
    heads, as the harness did before families (8 heads of 32 on the same
    projections): the served tokens come out not correct."""
    _, cfg, seed, run = wide_heads_run
    reg = _wide_heads_registry()
    ref = reg.reference("wide_heads")
    ref.head_dim = lambda m: m["hidden_size"] // m["num_attention_heads"]
    verdict = harness.check(reg, cfg, run.client, seed, run.failed)
    assert not verdict["correct"], verdict
    gap = verdict["compared"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_step_counters_are_recorded_only_while_recording():
    @dataclasses.dataclass
    class Stats:
        chunked_prefill_tokens: int = 0
        strategy_counts: dict = dataclasses.field(default_factory=dict)

    class Engine:
        stats = Stats()
        has_work = True

    class Server:
        engine = Engine()

        def step(self):
            self.engine.stats.chunked_prefill_tokens += 16

    client = harness.Client(Server(), traffic=None,
                            step_counters=("chunked_prefill_tokens",))
    client.step()
    client.recording = []
    client.step()
    client.step()
    assert [s.counters for s in client.recording] == \
        [{"chunked_prefill_tokens": 16.0}] * 2
    assert [s.prompt_tokens for s in client.recording] == [16, 16]
