"""Every cell of BENCHMARK.json resolves to its files, and the file keeps
to the benchmark file's format (names, keys, bounds, sizes)."""

import json
import re

import pytest

from bench import layout

BM = layout.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BM["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = layout.load_cell(cell)
    assert c.config["name"] == c.config_name
    assert callable(c.net_module().build)
    ref = c.ref_module()
    for fn in ("weights", "forward", "gemm_shapes"):
        assert callable(getattr(ref, fn))
    assert c.traffic["loop"] in ("open", "closed")
    for m in c.per_layer:
        assert callable(layout.metric_reader(m["name"]))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], cell)


def test_names_units_and_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BM[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"], m["name"]
    for w in BM["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(BM)) < 64 * 1024


def test_config_files_hold_what_the_entry_says():
    for conf in BM["configs"]:
        cfg = json.loads((layout.ROOT / conf["file"]).read_text())
        assert cfg["name"] == conf["name"]
        assert isinstance(conf["reduced"], list)
        assert cfg["reduced"] == conf["reduced"]
        assert all(key in cfg for key in conf["reduced"])


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        layout.load_cell("no.such.cell")
