"""BENCHMARK.json against the files it names, and the harness's lookup
by name (CPU only, no program run)."""

from __future__ import annotations

import json
import re
import shutil

import pytest
from chipbench_tiny import BASE, ROOT

from chipbench.bench import Bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.fixture(scope="module")
def bench():
    return Bench(ROOT)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert SPEC["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(bench, cell):
    c = bench.cell(cell)
    assert c.chips in (1, 4)
    assert c.config["name"] == bench.workload(cell)["config"]
    assert (BASE / "traffic" / f"{bench.workload(cell)['traffic']}.json") \
        .is_file()
    assert c.layers, "the reference family lists no layers"
    assert set(c.limits) == {"logit_err", "missing"}
    assert c.limits["logit_err"] > 0 and c.limits["missing"] == 0
    for m in bench.metrics(cell, False) + bench.metrics(cell, True):
        assert (BASE / "metrics" / f"{m['name']}.py").is_file()
        assert callable(bench.module("metrics", m["name"]).read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_metrics_move(bench, cell):
    e2e = {m["name"] for m in bench.metrics(cell, False)}
    layer = bench.metrics(cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_names_units_and_keys(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in m.get("workloads", []):
        assert w in CELLS


#: the keys each entry of BENCHMARK.json has (a metric may add workloads)
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("key", sorted(ENTRY_KEYS))
def test_entries_have_exactly_their_keys(key):
    for entry in SPEC[key]:
        extra = {"workloads"} if key in ("end_to_end", "per_layer") else set()
        assert ENTRY_KEYS[key] <= set(entry) <= ENTRY_KEYS[key] | extra, \
            (key, entry["name"], sorted(entry))
        for field in ("why", "layer", "source"):
            if field in entry:
                assert _line(entry[field]), (entry["name"], field)
    assert all(_line(w) for w in SPEC["command"])
    assert len(SPEC["command"]) <= 32
    assert isinstance(SPEC["run_seconds"], int)


def test_names_are_unique_and_well_formed():
    for key in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(SPEC["workloads"])
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
        assert c["file"].startswith("benchmarks/chip/")


@pytest.mark.parametrize("cfg", [c["name"] for c in SPEC["configs"]])
def test_config_file_states_its_source_and_cuts(bench, cfg):
    entry = next(c for c in SPEC["configs"] if c["name"] == cfg)
    body = bench.config(cfg)
    assert body["source"] == entry["source"]
    assert sorted(body["reduced"]) == sorted(entry["reduced"])
    for key in ("precision", "weights", "assumed", "departures"):
        assert body[key], key


def test_extra_cell_from_a_temporary_directory(tmp_path):
    """A later PR adds a configuration, a mix and a metric as new files
    and entries; the harness finds them with no file edited."""
    base = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BASE, base, ignore=shutil.ignore_patterns(
        "tests", ".jax_cache", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((ROOT / SPEC["configs"][0]["file"]).read_text())
    cfg["name"] = "vgg16-96"
    cfg["input_size"] = [96, 96]
    (base / "configs" / "vgg16-96.json").write_text(json.dumps(cfg))
    traffic = json.loads((base / "traffic" / "offline32.json").read_text())
    traffic["frames_per_call"] = 8
    (base / "traffic" / "offline8.json").write_text(json.dumps(traffic))
    (base / "checks" / "vgg16-96.offline8.json").write_text(
        json.dumps({"logit_err": 0.5, "missing": 0}))
    (base / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return 8.0\n")
    spec["configs"].append({"name": "vgg16-96", "source": cfg["source"],
                            "file": "benchmarks/chip/configs/vgg16-96.json",
                            "reduced": ["head", "input_size"],
                            "why": "test"})
    spec["workloads"].append({"name": "vgg16-96.offline8",
                              "config": "vgg16-96", "traffic": "offline8",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "calls_per_s", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "facade", "moves": "frames_per_s"})
    spec["end_to_end"][0]["workloads"].append("vgg16-96.offline8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    b = Bench(tmp_path)
    cell = b.cell("vgg16-96.offline8")
    assert cell.config["input_size"] == [96, 96]
    assert cell.traffic["frames_per_call"] == 8
    assert cell.layers[0]["h"] == 96
    assert cell.limits["logit_err"] == 0.5
    names = [m["name"] for m in b.metrics("vgg16-96.offline8", True)]
    assert names == ["calls_per_s"]     # goes where frames_per_s goes
    assert b.module("metrics", "calls_per_s").read(None) == 8.0
    assert "calls_per_s" in [m["name"] for m in
                             b.metrics("vgg16-224.offline32", True)]
    with pytest.raises(KeyError):
        b.cell("no-such-cell")


def test_unknown_device_kind_is_an_error(bench):
    assert bench.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        bench.peaks("TPU v9")
