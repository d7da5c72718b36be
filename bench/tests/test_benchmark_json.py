"""BENCHMARK.json keeps to the benchmark's format, and every name in it
finds its file under bench/."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


def bench():
    text = (ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= len(b["command"]) <= 32
    assert all(line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51


def test_configs_and_cells():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    assert len(configs) == len(b["configs"]) and 1 <= len(configs) <= 24
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert (ROOT / c["file"]).is_file() and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) or
                       k.startswith(("hidden", "intermediate", "ffn"))
                       for k in c["reduced"])
        cfile = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfile["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert cfile["published"][k] != cfile["config"][k]
        for k in cfile["published"]:
            assert k in c["reduced"] or \
                cfile["published"][k] == cfile["config"][k], k
    cells = b["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 2)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert line(c["why"])
        assert (BENCH / "traffic" / f"{c['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{c['name']}.json").is_file()
    assert {c["config"] for c in cells} == set(configs)


def test_metrics():
    b = bench()
    cells = {c["name"] for c in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    assert 1 <= len(b["per_layer"]) <= 128
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)

    def cells_of(m):
        return set(m.get("workloads", cells))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
        assert cells_of(m) <= cells
    layers = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line(m["layer"])
        layers.add(m["layer"])
        assert m["moves"] in e2e
        assert cells_of(m) <= cells_of(e2e[m["moves"]])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        reports = [m for m in b["end_to_end"] if cell in cells_of(m)]
        assert "setup_s" in {m["name"] for m in reports}
        assert len(reports) >= 2
        assert any(cell in cells_of(m) for m in b["per_layer"])
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf


def test_each_configuration_names_its_module_and_each_cell_its_chips():
    """A configuration's architecture module is there, and a cell's chips
    are its engine's tensor-parallel degree (1 when the mix gives none)."""
    b = bench()
    for c in b["configs"]:
        name = json.loads((ROOT / c["file"]).read_text())["reference"]
        assert NAME.match(name)
        assert (BENCH / "reference" / f"{name}.py").is_file(), name
    for c in b["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{c['traffic']}.json")
                         .read_text())
        assert mix["engine"].get("tp", 1) == c["chips"], c["name"]
