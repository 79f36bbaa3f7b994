"""The benchmark's files: configurations at published widths, the peaks
table, and finding every piece of a cell by name."""
import json
import re

import pytest

from bench.peaks import peak_for
from bench.registry import BENCH_DIR, ROOT, Registry

REG = Registry()
INTERNLM2 = REG.family("internlm2")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name,params,widths", [
    ("internlm2-1.8b", 1.889e9, (24, 2048, 16, 8, 8192, 92544)),
    ("internlm2-20b-8l", 4.258e9, (8, 6144, 48, 8, 16384, 92544)),
])
def test_configs_at_published_widths(name, params, widths):
    m = REG.config(name)["model"]
    assert (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["intermediate_size"],
            m["vocab_size"]) == widths
    assert m["hidden_size"] // m["num_attention_heads"] == 128
    assert INTERNLM2.param_count(m) == pytest.approx(params, rel=5e-4)


def test_flops_per_token_as_stated():
    assert INTERNLM2.flops_per_token(REG.config("internlm2-1.8b")["model"]) \
        == pytest.approx(3.40e9, rel=3e-3)
    assert INTERNLM2.flops_per_token(REG.config("internlm2-20b-8l")["model"]) \
        == pytest.approx(7.38e9, rel=3e-3)


def test_peaks_known_and_unknown():
    assert peak_for("TPU v5 lite").flops_per_s == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peak_for("TPU v99")


def test_every_cell_finds_its_files():
    for cell in SPEC["workloads"]:
        c = REG.cell(cell["name"])
        cfg = REG.config(c["config"])
        assert REG.mix(c["traffic"])["loop"] == "closed"
        assert (BENCH_DIR / "reference" / f"{cfg['reference']}.py").is_file()
        assert callable(REG.family_of(cfg).step_cost)
        for kind in ("end_to_end", "per_layer"):
            for entry in REG.metric_entries(c["name"], kind):
                assert callable(REG.reader(entry["name"]).read)
    with pytest.raises(KeyError):
        REG.cell("no-such-cell")


def test_contract_of_benchmark_json():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cfg_names = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size")) or k == "vocab_size"
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfg_names and len(w["why"]) <= 200
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        # every cell that reads the metric reports what it moves
        for cell in m.get("workloads", [w["name"] for w in SPEC["workloads"]]):
            assert m["moves"] in {e["name"] for e in
                                  REG.metric_entries(cell, "end_to_end")}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert len(json.dumps(SPEC)) < 64 * 1024
    rs = SPEC["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_a_metric_added_as_a_file_is_found(tmp_path):
    (tmp_path / "my.metric.py").write_text("def read(w):\n    return 7\n")
    reg = Registry(metrics=tmp_path)
    assert reg.reader("my.metric").read(None) == 7


def test_a_family_added_as_a_file_is_found(tmp_path):
    (tmp_path / "my_family.py").write_text(
        "STEP_COUNTERS = ()\n\ndef param_count(m):\n    return 7\n")
    reg = Registry(families=tmp_path)
    cfg = {"name": "x", "family": "my_family", "model": {}}
    assert reg.family_of(cfg).param_count({}) == 7
    assert REG.family_of({"name": "y", "model": {}}) is INTERNLM2
