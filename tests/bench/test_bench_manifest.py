"""The benchmark manifest (``BENCHMARK.json``) against its contract, and
the files it names found by name alone.  Nothing here runs a cell."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import jobs, manifest, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
# widths that a configuration never cuts (the vocabulary may be sliced)
WIDTH = re.compile(r"((?<!vocab)_size$|_dim$|_rank$|expan|experts_per_tok)")


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(m["command"]) <= 32
    assert all(isinstance(w, str) and _line(w) for w in m["command"])
    for word in m["command"]:
        assert not word.startswith("/") and ".." not in word
    script = (manifest.ROOT / m["command"][1])
    assert script.is_file()
    assert any(m["command"][1].startswith(p + "/") for p in m["paths"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")
        assert (manifest.ROOT / p).is_dir()
    assert (manifest.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells(m):
    s = m["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    cells = 24
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200


def test_configs_are_used_found_and_cut_only_in_depth(m):
    names = [c["name"] for c in m["configs"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    used = {w["config"] for w in m["workloads"]}
    assert set(names) == used
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert c["file"] == str(manifest.config_path(c["name"]).relative_to(
            manifest.ROOT))
        body = json.loads((manifest.ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in body and key in body.get("published", {})


def test_workloads_find_their_files(m):
    names = [w["name"] for w in m["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in m["workloads"]}
    assert len(pairs) == len(names)
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(names) // 2)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert manifest.traffic_path(w["traffic"]).is_file()
        cell = manifest.cell(w["name"], m)
        assert hasattr(jobs.kind_module(cell.traffic["kind"]), "Job")


def test_metrics_names_units_and_readers(m):
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics]
    assert len(names) == len(set(names))
    for x in metrics:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.25
    setup = [x for x in m["end_to_end"] if x["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(x["layer"])
        assert callable(manifest.load_reader(x["name"]))


def test_every_per_layer_metric_moves_a_metric_its_cells_report(m):
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        for w in x.get("workloads", cells):
            assert w in cells
            assert manifest.reports(e2e[x["moves"]], w)
    for w in cells:
        reported = [x for x in m["end_to_end"] if manifest.reports(x, w)]
        assert "setup_s" in {x["name"] for x in reported}
        assert len(reported) >= 2
        assert any(manifest.reports(x, w) for x in m["per_layer"])


def test_a_cell_config_and_metric_are_added_by_files_alone(m, tmp_path,
                                                          monkeypatch):
    """A new configuration, traffic mix, kind of job and per-layer metric,
    given as new files and manifest entries only, are found by name."""
    shutil.copytree(manifest.BENCH_DIR / "configs", tmp_path / "configs")
    shutil.copytree(manifest.BENCH_DIR / "traffic", tmp_path / "traffic")
    shutil.copytree(manifest.BENCH_DIR / "metrics", tmp_path / "metrics")
    cfg = json.loads((tmp_path / "configs" / "qwen2.5-14b-decode.json")
                     .read_text())
    cfg["name"] = "qwen2.5-14b-decode-two"
    (tmp_path / "configs" / "qwen2.5-14b-decode-two.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((tmp_path / "traffic" / "decode.bs64c4k.json")
                         .read_text())
    traffic["batch"] = 8
    traffic["cache_len"] = 32768
    (tmp_path / "traffic" / "decode.bs8c32k.json").write_text(
        json.dumps(traffic))
    (tmp_path / "metrics" / "sim.jobs_per_mref.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    grown = json.loads(json.dumps(m))
    grown["workloads"].append({"name": "qwen14b.decode.bs8c32k",
                               "config": "qwen2.5-14b-decode-two",
                               "traffic": "decode.bs8c32k", "chips": 1,
                               "why": "a streaming working set"})
    grown["per_layer"].append({"name": "sim.jobs_per_mref", "unit": "1/Mref",
                               "better": "lower", "source": "program_span",
                               "layer": "simulator host",
                               "moves": "sim_refs_per_s",
                               "workloads": ["qwen14b.decode.bs8c32k"]})
    cell = manifest.cell("qwen14b.decode.bs8c32k", grown, tmp_path)
    assert cell.traffic["cache_len"] == 32768
    assert cell.config["name"] == "qwen2.5-14b-decode-two"
    assert "sim.jobs_per_mref" in {x["name"] for x in cell.per_layer}
    assert manifest.load_reader("sim.jobs_per_mref", tmp_path)(None) == 7.0
    old = manifest.cell("table3.r250k", grown, tmp_path)
    assert "sim.jobs_per_mref" not in {x["name"] for x in old.per_layer}

    import bench.kinds
    (tmp_path / "kinds").mkdir()
    (tmp_path / "kinds" / "replay.py").write_text(
        "class Job:\n    def __init__(self, config, traffic, seed):\n"
        "        self.seed = seed\n")
    monkeypatch.setattr(bench.kinds, "__path__",
                        list(bench.kinds.__path__) + [str(tmp_path / "kinds")])
    assert jobs.make(cell.config, {"kind": "replay"}, 9).seed == 9
    with pytest.raises(SystemExit):
        jobs.make(cell.config, {"kind": "no_such_kind"}, 9)


def test_result_line_shape():
    numbers = [jobs.Number("counters_differing", 0, 0),
               jobs.Number("classes_differing", 0, 0)]
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    metrics = {"setup_s": {"value": 1.5, "unit": "s"}}
    plain = run.make_result(numbers, 3, metrics, device)
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device", "check"]
    assert plain["correct"] is True and plain["failed"] == 0
    traced = run.make_result(numbers, 3, metrics,
                             dict(device, busy_s=0.2, window_s=9.0),
                             {"device_ops": [["fusion", 0.1]],
                              "idle_gaps": [["sim.scan", 8.0]]})
    assert list(traced)[-2:] == ["breakdown", "check"]
    json.loads(json.dumps(traced))
    bad = run.make_result([jobs.Number("counters_differing", 2, 0)], 3,
                          metrics, device)
    assert bad["correct"] is False and bad["failed"] == 3
    assert bad["check"]["counters_differing"] == {"value": 2, "limit": 0}
