"""Profiling-informed performance model pipeline: PerfModelProvider
spec resolution, OfflineProfiler smoke (the CI tier-1 profiler check),
TablePerfModel persistence/rates, OnlineCalibrator, and the measured
model driving the live engine's scheduler."""
import json
import types

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.perf_model import (DEVICE_PLATFORMS, AnalyticPerfModel,
                                   OnlineCalibrator, PerfModelProvider,
                                   TablePerfModel, analytic_model,
                                   device_platform, resolve_perf_model)
from repro.core.profiler import OfflineProfiler
from repro.core.scheduler import ApexScheduler, StrategyKind
from repro.models import init_params
from repro.serving import InferenceServer, ServerConfig

# small enough that the profiler smoke test stays in tier-1 time budget
TINY_GRID = dict(token_counts=(1, 4), kv_positions=(1024, 4096),
                 transfer_sizes=(1 << 12,))


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("stablelm-12b").reduced(layers=2, d_model=64, vocab=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def measured(tiny):
    cfg, _ = tiny
    return OfflineProfiler(cfg).run(**TINY_GRID)


# --- provider: spec resolution ---------------------------------------------

def test_analytic_spec_resolution(tiny):
    cfg, _ = tiny
    pm = resolve_perf_model("analytic:t4", cfg)
    assert isinstance(pm, AnalyticPerfModel) and pm.platform.name == "t4"
    default = resolve_perf_model("analytic", cfg, platform="v5e")
    assert default.platform.name == "v5e"
    with pytest.raises(ValueError):
        resolve_perf_model("analytic:h100", cfg)
    with pytest.raises(ValueError):
        resolve_perf_model("nonsense", cfg)
    with pytest.raises(ValueError):
        resolve_perf_model("file:/does/not/exist.json", cfg)


@pytest.mark.parametrize("kind,expect", [
    ("TPU v5 lite", "v5e"), ("TPU v5", None), ("TPU v6 lite", None),
    ("TPU v4", None)])
def test_default_platform_follows_device_kind(tiny, monkeypatch, kind,
                                              expect):
    """With no platform named, the analytic model prices Algorithm 1
    with the attached device's rates (looked up by device_kind); a TPU
    kind without an entry is an error, never the A10 default."""
    cfg, _ = tiny
    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    if expect is None:
        with pytest.raises(ValueError, match=kind):
            resolve_perf_model("analytic", cfg)
    else:
        assert resolve_perf_model("analytic", cfg).platform.name == expect
    # a named platform is used as named, whatever is attached
    assert resolve_perf_model("analytic", cfg,
                              platform="a10").platform.name == "a10"
    assert resolve_perf_model("analytic:t4", cfg).platform.name == "t4"


def test_cpu_backend_carries_the_paper_a10_calibration(tiny):
    cfg, _ = tiny
    assert device_platform() == DEVICE_PLATFORMS["cpu"] == "a10"
    assert resolve_perf_model("analytic", cfg).platform.name == "a10"
    assert ServerConfig().platform is None


def test_file_spec_reuses_profile_without_reprofiling(tiny, measured,
                                                      tmp_path, monkeypatch):
    cfg, _ = tiny
    path = tmp_path / "profile.json"
    measured.save(str(path))

    def boom(self, **kw):
        raise AssertionError("profiler must not run for file:/cached specs")

    monkeypatch.setattr(OfflineProfiler, "run", boom)
    pm = resolve_perf_model(f"file:{path}", cfg)
    assert isinstance(pm, TablePerfModel)
    # "measured" with an existing cache loads instead of re-profiling
    pm2 = resolve_perf_model("measured", cfg, profile_cache=str(path))
    assert isinstance(pm2, TablePerfModel)
    t = pm.timings(2, 64)
    t2 = pm2.timings(2, 64)
    assert t.t_glinear == t2.t_glinear and t.n_c == t2.n_c


def test_profile_fingerprint_guards_against_foreign_tables(tiny, measured,
                                                           tmp_path,
                                                           monkeypatch):
    """A cached/explicit profile measured for a different model shape
    must not be silently reused as this model's timing tables."""
    cfg, _ = tiny
    other = get_config("llama3.1-8b").reduced(layers=4, d_model=128,
                                              vocab=64)
    path = tmp_path / "foreign.json"
    measured.save(str(path))        # fingerprinted for `tiny`, not `other`
    assert measured.fingerprint is not None
    with pytest.raises(ValueError, match="was measured for"):
        resolve_perf_model(f"file:{path}", other)
    # "measured" treats the mismatched cache as stale and re-profiles
    ran = []
    monkeypatch.setattr(OfflineProfiler, "run",
                        lambda self, **kw: ran.append(1) or measured)
    resolve_perf_model("measured", other, profile_cache=str(path))
    assert ran == [1]


def test_requested_grid_mismatch_reprofiles(tiny, measured, tmp_path,
                                            monkeypatch):
    """An explicitly requested profile_grid the cache wasn't measured
    at is stale; no requested grid accepts any cache of this model."""
    cfg, _ = tiny
    path = tmp_path / "grid.json"
    measured.save(str(path))
    ran = []
    monkeypatch.setattr(OfflineProfiler, "run",
                        lambda self, **kw: ran.append(kw) or measured)
    resolve_perf_model("measured", cfg, profile_cache=str(path),
                       profile_grid=TINY_GRID)         # measured at this grid
    resolve_perf_model("measured", cfg, profile_cache=str(path))  # any grid
    assert ran == []
    finer = dict(TINY_GRID, token_counts=(1, 4, 8))
    resolve_perf_model("measured", cfg, profile_cache=str(path),
                       profile_grid=finer)
    assert len(ran) == 1 and ran[0]["token_counts"] == (1, 4, 8)


# --- profiler smoke (runs in CI tier-1) ------------------------------------

def test_profiler_smoke_produces_schedulable_tables(measured):
    for op in ("linear", "gatt", "catt", "transfer", "prefill"):
        xs, ys = measured.tables[op]
        assert (np.diff(xs) > 0).all(), f"{op}: x not strictly increasing"
        assert (ys > 0).all(), f"{op}: non-positive measurements"
    # prefill = linear + measured causal attention, never a bare alias
    lin = measured.tables["linear"][1]
    pre = measured.tables["prefill"][1]
    assert (pre > lin).all()
    d = ApexScheduler(measured).schedule([], [1, 2], [3], mean_context=64)
    assert d.strategy in (StrategyKind.ASYNC_OVERLAP,
                          StrategyKind.ASYM_PIPELINE)
    assert d.predicted_time > 0


def test_save_load_roundtrip_preserves_timings(measured, tmp_path):
    path = str(tmp_path / "roundtrip.json")
    measured.save(path)
    loaded = TablePerfModel.load(path)
    json.load(open(path))     # persisted payload is valid JSON
    assert loaded.fingerprint == measured.fingerprint
    assert loaded.profile_grid == measured.profile_grid
    assert loaded.profile_grid is not None
    for batch, ctx, pref in ((1, 16, 0), (2, 64, 0), (4, 128, 8),
                             (8, 2048, 32)):
        a = measured.timings(batch, ctx, prefill_tokens=pref)
        b = loaded.timings(batch, ctx, prefill_tokens=pref)
        assert a == b


def test_fingerprintless_cache_treated_as_stale(tiny, measured, tmp_path,
                                                monkeypatch):
    """The managed profile_cache demands provenance: a payload without
    a fingerprint (pre-fingerprint or hand-built) is re-profiled."""
    cfg, _ = tiny
    path = tmp_path / "nofp.json"
    bare = TablePerfModel({k: list(zip(xs.tolist(), ys.tolist()))
                           for k, (xs, ys) in measured.tables.items()},
                          kv_bytes_per_pos=measured.kv_bytes_per_pos,
                          num_attn_layers=measured.num_attn_layers)
    bare.save(str(path))
    ran = []
    monkeypatch.setattr(OfflineProfiler, "run",
                        lambda self, **kw: ran.append(1) or measured)
    resolve_perf_model("measured", cfg, profile_cache=str(path))
    assert ran == [1]
    # file: is an explicit operator assertion — trusted without one
    bare.save(str(path))
    pm = resolve_perf_model(f"file:{path}", cfg)
    assert isinstance(pm, TablePerfModel) and pm.fingerprint is None


# --- measured-table semantics ----------------------------------------------

def test_table_rates_track_context():
    tm = TablePerfModel({"linear": [(1, 1e-4), (8, 2e-4)],
                         "gatt": [(1024, 1e-3), (4096, 3e-3)],
                         "catt": [(1024, 1e-2), (4096, 4e-2)],
                         "transfer": [(1.0, 1e-6), (2.0, 2e-6)],
                         "prefill": [(1, 1e-4), (64, 5e-4)]},
                        kv_bytes_per_pos=4, num_attn_layers=2)
    # rate is the secant at the actual operating context, not a fixed
    # 4096-position probe
    assert tm.n_g(1024) == pytest.approx(1024 / 1e-3)
    assert tm.n_g(4096) == pytest.approx(4096 / 3e-3)
    assert tm.n_g(1024) != tm.n_g(4096)
    assert tm.n_c(4096) == pytest.approx(4096 / 4e-2)
    # scheduler-visible effect: Ineq(6) ratio moves with context
    r1 = tm.timings(1, 1024).n_g / tm.timings(1, 1024).n_c
    r2 = tm.timings(1, 4096).n_g / tm.timings(1, 4096).n_c
    assert r1 != r2


def test_extrapolation_never_shrinks_op_time():
    """A noisy non-monotone tail must not extrapolate below the last
    sample (or to <= 0, which Timings validation would reject)."""
    tm = TablePerfModel({"linear": [(1, 1e-4), (8, 9.5e-5)],
                         "gatt": [(64, 1e-3), (128, 2e-3)],
                         "catt": [(64, 1e-2), (128, 2e-2)],
                         "transfer": [(1.0, 1e-6), (2.0, 2e-6)],
                         "prefill": [(1, 1e-4), (8, 2e-4)]},
                        kv_bytes_per_pos=4, num_attn_layers=2)
    assert tm.t_linear(512) == pytest.approx(9.5e-5)   # slope clamped to 0
    t = tm.timings(512, 16)                            # still schedulable
    assert t.t_glinear > 0


def test_mixed_branch_parity_with_analytic():
    """TablePerfModel.timings must have the analytic mixed-branch shape:
    tables sampled exactly from an AnalyticPerfModel's ops reproduce its
    Timings (device fields) including the prefill-attention term that
    t_gatt_pref was previously dropping."""
    am = analytic_model("a10", get_config("llama3.1-8b"))
    batch, ctx, pref = 4, 512, 64
    xs_lin = [1, batch, batch + pref, 1024]
    xs_att = [1.0, float(batch * ctx), 1e6]
    tables = {
        "linear": [(float(x), am.t_linear(int(x))) for x in xs_lin],
        "gatt": [(x, am.t_gatt(1, x)) for x in xs_att],
        "catt": [(x, am.t_catt(1, x, layers=am.costs.num_attn_layers))
                 for x in xs_att],
        "transfer": [(1.0, am.t_transfer(1.0)), (1e6, am.t_transfer(1e6))],
        "prefill": [(float(x), am.t_prefill(int(x), int(x)))
                    for x in (1, pref, 1024)],
    }
    tm = TablePerfModel(tables, kv_bytes_per_pos=am.costs.kv_bytes_per_pos,
                        num_attn_layers=am.costs.num_attn_layers)
    tt = tm.timings(batch, ctx, prefill_tokens=pref)
    ta = am.timings(batch, ctx, prefill_tokens=pref)
    assert tt.t_glinear == pytest.approx(ta.t_glinear, rel=1e-6)
    assert tt.t_gatt == pytest.approx(ta.t_gatt, rel=1e-6)
    assert tt.t_glinear_pref == pytest.approx(ta.t_glinear_pref, rel=1e-6)
    assert tt.t_gatt_pref == pytest.approx(ta.t_gatt_pref, rel=1e-6)
    assert tt.t_gatt_pref > tt.t_gatt   # prefill term present


# --- online calibrator ------------------------------------------------------

def test_calibrator_closed_loop_converges():
    cal = OnlineCalibrator(analytic_model("a10", get_config("llama3.1-8b")))
    true_scale = 3.0
    raw = cal.base.timings(8, 1024)
    errs = []
    for _ in range(60):
        t = cal.timings(8, 1024)
        predicted = t.t_glinear + t.t_gatt          # Eq. (1), corrected
        observed = (raw.t_glinear + raw.t_gatt) * true_scale
        cal.observe_step(predicted, observed)
        errs.append(cal.step_error_ewma)
    assert cal.device_scale == pytest.approx(true_scale, rel=0.05)
    assert errs[-1] < 0.05 < errs[0]                # accuracy improved
    t = cal.timings(8, 1024)
    assert t.t_glinear == pytest.approx(raw.t_glinear * cal.device_scale)
    assert t.n_g == pytest.approx(raw.n_g / cal.device_scale)
    # host-side: a host persistently 2x slower than the base model
    # predicts drops n_c by the converged scale
    n_c0 = cal.timings(8, 1024).n_c
    true_host = cal.base.t_catt(4, 1024, layers=1) * 2.0
    for _ in range(40):
        cal.observe_host(cal.t_catt(4, 1024, layers=1), true_host)
    assert cal.host_scale == pytest.approx(2.0, rel=0.05)
    assert cal.timings(8, 1024).n_c == pytest.approx(n_c0 / cal.host_scale)


def test_calibrator_outlier_resistance():
    cal = OnlineCalibrator(analytic_model("a10", get_config("llama3.1-8b")),
                           max_step=4.0)
    cal.observe_step(1e-3, 10.0)    # one jit-compile outlier (10000x)
    assert cal.device_scale <= 4.0 ** cal.alpha + 1e-9


# --- the measured model driving the live engine ----------------------------

def test_measured_server_schedules_off_tables(tiny, tmp_path, monkeypatch):
    """Acceptance: perf_model="measured" profiles once at startup,
    every iteration schedules off TablePerfModel timings, EngineStats
    reports strategy counts + predicted-vs-observed error, and a second
    server reuses the cached profile without re-profiling."""
    cfg, params = tiny
    cache = str(tmp_path / "profile.json")
    runs = []
    real_run = OfflineProfiler.run

    def counting_run(self, **kw):
        runs.append(kw)
        return real_run(self, **kw)

    monkeypatch.setattr(OfflineProfiler, "run", counting_run)
    scfg = ServerConfig(device_slots=2, host_slots=3, cache_len=64,
                        perf_model="measured", profile_cache=cache,
                        profile_grid=TINY_GRID,
                        prompt_len=6, output_len=5, num_requests=5)
    with InferenceServer(cfg, params, scfg) as server:
        assert len(runs) == 1                       # profiled exactly once
        cal = server.engine.scheduler.perf_model
        assert isinstance(cal, OnlineCalibrator)
        assert isinstance(cal.base, TablePerfModel)  # measured tables
        for r in scfg.build_requests(vocab=cfg.vocab_size):
            server.submit(r)
        stats = server.run_until_idle()
    assert stats.perf_model_spec == "measured"
    # every non-idle iteration ran Algorithm 1 off the measured model
    assert sum(stats.strategy_counts.values()) > 0
    assert stats.predicted_time > 0 and stats.observed_time > 0
    assert stats.prediction_error is not None
    assert stats.step_error_ewma is not None
    assert cal.steps_observed == sum(stats.strategy_counts.values())

    # second server: cache hit, no re-profiling
    with InferenceServer(cfg, params, scfg) as server2:
        assert len(runs) == 1
        cal2 = server2.engine.scheduler.perf_model
        assert isinstance(cal2.base, TablePerfModel)
        h = server2.submit([1, 2, 3], max_new_tokens=3)
        assert h.result() == h.output and len(h.output) == 3

    # file:<path> spec resolves the same saved profile
    pm = PerfModelProvider(cfg).resolve(f"file:{cache}")
    assert pm.timings(2, 32).t_glinear == \
        cal2.base.timings(2, 32).t_glinear


def test_engine_default_analytic_reports_accuracy(tiny):
    """The default (analytic) spec also feeds the calibrator loop, so
    scheduling accuracy is a first-class metric everywhere."""
    cfg, params = tiny
    scfg = ServerConfig(device_slots=2, host_slots=3, cache_len=64,
                        prompt_len=6, output_len=4, num_requests=4)
    with InferenceServer(cfg, params, scfg) as server:
        assert isinstance(server.engine.scheduler.perf_model,
                          OnlineCalibrator)
        for r in scfg.build_requests(vocab=cfg.vocab_size):
            server.submit(r)
        stats = server.run_until_idle()
    assert stats.perf_model_spec == "analytic"
    assert stats.prediction_error is not None
    assert stats.step_error_ewma is not None
    if stats.host_tokens:    # host jobs calibrate the host scale too
        assert server.engine._calibrator.host_observed > 0
