"""A whole run at toy size on the CPU, with the look for a chip skipped:
served tokens pass the reference check, and each fault planted in the
timed path makes ``correct`` come out false."""
import subprocess
import sys
import time
from pathlib import Path

from bench import harness
from bench.registry import ROOT, Registry

DATA = Path(__file__).parent / "data"
REG = Registry(DATA / "BENCHMARK.json", configs=DATA / "configs",
               traffic=DATA / "traffic")


def _run(cell, seed, seconds):
    c = REG.cell(cell)
    cfg = REG.config(c["config"])
    run = harness.serve_window(cfg, REG.mix(c["traffic"]), seed, seconds,
                               t_process=time.perf_counter(),
                               log=lambda *a: None)
    return run, harness.check(REG, cfg, run.client, seed, run.failed)


def test_closed_loop_run_is_correct_and_uses_both_tiers():
    run, verdict = _run("tiny.reasoning-tiny", 2**31 + 5, seconds=5.0)
    assert verdict["correct"], verdict
    assert verdict["compared"]["host_tokens_judged"]["value"] > 0
    w = run.window
    assert w.delta("host_tokens") > 0 and w.delta("device_tokens") > 0
    assert REG.reader("tokens_per_s").read(w) > 0


def test_warm_up_leaves_no_step_program_to_the_window():
    """Every engine step program (chunk alone, decode, decode fused with a
    chunk, with and without the host cohort) runs in set-up, so none is
    compiled or loaded inside the window; the notes name what was."""
    run, _ = _run("tiny.reasoning-tiny", 2**32 + 11, seconds=3.0)
    names = [name for _, _, name in run.programs]
    assert not [n for n in names if "_traced" in n or "lambda" in n], names
    assert run.notes[0].startswith(
        f"programs compiled or loaded in window: {len(names)}")


def test_control_in_the_programs_place_comes_out_not_correct():
    """The run's own check, with the reference in int8 put in the
    program's place at the same positions, says false."""
    run, verdict = _run("tiny.reasoning-tiny", 2**33 + 7, seconds=5.0)
    assert verdict["correct"], verdict
    c = REG.config("tiny")
    ctl = harness.check(REG, c, run.client, 2**33 + 7, run.failed,
                        control=True)
    assert not ctl["correct"], ctl
    assert ctl["compared"]["tokens_judged"] == verdict["compared"]["tokens_judged"]


def test_host_tier_in_low_precision_is_caught(monkeypatch):
    """Host-tier attention rounded to 4 bits a row: the host tier's own
    mean gap fails its limit."""
    import numpy as np

    import repro.core.overlap_engine as overlap

    orig = overlap.host_paged_attention_numpy

    def coarse(*a, **kw):
        out = orig(*a, **kw)
        s = np.abs(out).max(axis=-1, keepdims=True) / 7.0
        s[s == 0] = 1.0
        out[...] = np.round(out / s) * s
        return out

    monkeypatch.setattr(overlap, "host_paged_attention_numpy", coarse)
    _, verdict = _run("tiny.reasoning-tiny", 3, seconds=5.0)
    assert not verdict["correct"], verdict
    host = verdict["compared"]["host_mean_logit_gap"]
    assert host["value"] > host["limit"]


def test_altered_token_is_caught(monkeypatch):
    import repro.serving.engine as engine

    orig = engine.sample

    def altered(logits, **kw):
        toks = orig(logits, **kw)
        return toks.at[0].set((toks[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(engine, "sample", altered)
    _, verdict = _run("tiny.reasoning-tiny", 3, seconds=4.0)
    assert not verdict["correct"], verdict
    gap = verdict["compared"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    import repro.models.model as model
    import repro.serving.engine as engine
    from repro.models.kv_cache import StackState

    orig = model.decode_step

    def stale(params, cfg, tokens, state, host=None):
        logits, new, qkv, x = orig(params, cfg, tokens, state, host)
        return logits, StackState(per_entry=state.per_entry,
                                  lengths=new.lengths), qkv, x

    monkeypatch.setattr(model, "decode_step", stale)
    monkeypatch.setattr(engine, "decode_step", stale)
    _, verdict = _run("tiny.reasoning-tiny", 4, seconds=4.0)
    assert not verdict["correct"], verdict
    gap = verdict["compared"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_run_refuses_without_a_chip():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "internlm2-1.8b.reasoning-offload", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
