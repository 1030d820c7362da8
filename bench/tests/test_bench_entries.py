"""Every entry of BENCHMARK.json resolves to its files, by name alone, and
keeps to the benchmark's format: names, units, bounds and run length."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p.rstrip("/") + "/")
                       for p in BENCH["paths"]), word
            assert (ROOT / word).is_file()


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(entry):
    assert NAME.match(entry["name"])
    assert _line(entry["source"]) and _line(entry["why"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    path = ROOT / entry["file"]
    assert entry["file"].startswith("bench/") and path.is_file()
    cfg = json.loads(path.read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg
        assert not key.endswith(("_dim", "_rank", "_size"))
    ref = compare.config_reference(entry["name"])
    assert callable(ref.launch) and callable(ref.degrees)
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    entry, cfg, traffic = harness.cell_parts(BENCH, cell["name"])
    assert cfg["name"] == cell["config"]
    assert (ROOT / "bench" / "requests"
            / f"{traffic['request']['kind']}.py").is_file()
    limits = compare.load_limits(cell["name"])
    for key in ("counter_gap", "model_rel_gap", "bottleneck_mismatches",
                "unverified", "window_compiles"):
        assert key in limits
    e2e = harness.metrics_for(BENCH, cell["name"], traced=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    per_layer = harness.metrics_for(BENCH, cell["name"], traced=True)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])


def test_cells_unique_and_at_most_half_on_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_resolves(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    reader = harness.load_metric(metric["name"])
    assert callable(reader.read)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%" and reader.KERNEL_NAMES
        assert metric["source"] == "device_trace"


def test_metric_groups():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_files_under_paths_are_named_from_name_characters():
    for p in (ROOT / "bench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            rel = p.relative_to(ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_peaks_table_has_the_chip_and_its_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["source"]
    v5e = peaks["kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_a_kind_missing_from_the_peaks_table_is_an_error(monkeypatch):
    import jax

    class Unlisted:
        platform = "tpu"
        device_kind = "TPU of a kind not in the table"

    monkeypatch.setattr(jax, "devices", lambda *a: [Unlisted()])
    with pytest.raises(harness.NoChip, match="peaks"):
        harness.chip(1, require=True)
    with pytest.raises(harness.NoChip, match="needs 4"):
        harness.chip(4, require=True)


def test_the_cpu_is_no_chip():
    with pytest.raises(harness.NoChip, match="not a TPU"):
        harness.chip(1, require=True)
