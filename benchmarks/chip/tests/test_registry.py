"""Configurations, traffic mixes, limits and metric readers are files of
their own, found by the names BENCHMARK.json gives them."""
import ast
import json
from pathlib import Path

import pytest

import harness

BENCH = harness.load_benchmark()


def test_every_name_has_its_file():
    for c in BENCH["configs"]:
        cfg = harness.config_of(BENCH, c["name"])
        assert (harness.HERE / "kinds" / f"{cfg['kind']}.py").exists()
        assert (harness.HERE / "kinds" / f"{cfg['kind']}_ref.py").exists()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        harness.traffic_of(w["traffic"])
        assert harness.limits_of(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader_of(m["name"]).read)


def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in harness.metrics_for(BENCH, w["name"],
                                                      False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(BENCH, w["name"], True)


def test_per_layer_workloads_report_what_they_move():
    for m in BENCH["per_layer"]:
        for w in m["workloads"]:
            e2e = {e["name"] for e in harness.metrics_for(BENCH, w, False)}
            assert m["moves"] in e2e, (m["name"], w)


def test_references_import_nothing_of_the_program():
    for path in (harness.HERE / "kinds").glob("*_ref.py"):
        tree = ast.parse(path.read_text())
        mods = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names]
        mods += [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        assert not [m for m in mods if m.split(".")[0] == "repro"], path


def test_an_added_entry_is_found_by_its_name(tmp_path: Path):
    """A later cell, traffic mix and metric are new files and new entries
    in BENCHMARK.json; nothing that exists is edited."""
    for d in ("configs", "traffic", "limits", "metrics", "kinds"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "dummy-config.json").write_text(
        json.dumps({"kind": "dummy_kind", "size": 3}))
    (tmp_path / "traffic" / "dummy-mix.json").write_text(
        json.dumps({"rate": 5}))
    (tmp_path / "limits" / "dummy-cell.json").write_text(
        json.dumps({"gap": 0.5}))
    (tmp_path / "metrics" / "dummy_metric.layer.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['x']\n")
    (tmp_path / "kinds" / "dummy_kind.py").write_text("NAME = 'dummy'\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dummy-config", "source": "x",
                             "file": "configs/dummy-config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "x"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    bench["per_layer"].append({"name": "dummy_metric.layer", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "dummy_rate",
                               "workloads": ["dummy-cell"]})
    cfg = harness.config_of(bench, "dummy-config", base=tmp_path)
    assert cfg == {"kind": "dummy_kind", "size": 3, "name": "dummy-config"}
    assert harness.traffic_of("dummy-mix", base=tmp_path)["rate"] == 5
    assert harness.limits_of("dummy-cell", base=tmp_path) == {"gap": 0.5}
    assert harness.kind_of("dummy_kind", base=tmp_path).NAME == "dummy"
    reader = harness.reader_of("dummy_metric.layer", base=tmp_path)
    assert reader.read({"x": 1.5}) == 3.0
    names = [m["name"] for m in harness.metrics_for(bench, "dummy-cell",
                                                    True)]
    assert names == ["dummy_metric.layer"]
    e2e = [m["name"] for m in harness.metrics_for(bench, "dummy-cell",
                                                  False)]
    assert sorted(e2e) == ["dummy_rate", "peak_hbm_gb", "setup_s"]
    # the cells that exist are untouched by the addition
    assert (harness.metrics_for(bench, "mesh7-rational-pallas", True)
            == harness.metrics_for(BENCH, "mesh7-rational-pallas", True))


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        harness.entry(BENCH["workloads"], "no-such-cell", "workload")
    with pytest.raises(FileNotFoundError):
        harness.traffic_of("no-such-mix")
