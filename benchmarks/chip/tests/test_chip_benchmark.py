"""``BENCHMARK.json`` is well formed and every name in it resolves to a
file of the benchmark's own."""
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_names_and_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_every_name_resolves_to_a_file():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("benchmarks/chip/")
        assert json.loads(path.read_text())["name"] == c["name"]
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        load = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                          .read_text())
        assert (HERE / "traffic" / f"{load['mix']}.json").is_file()
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert w["chips"] == 1 and {m["name"] for m in cfg["models"]} | {
            "router"} == set(cfg["check"]["limits"])
    for m in b["per_layer"]:
        assert (HERE / "layers" / f"{m['name']}.py").is_file()


def test_each_cell_reports_what_its_layer_metrics_move():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    e2e = {m["name"]: m for m in b["end_to_end"]}
    for cell in cells:
        mine = [m for m in b["end_to_end"] if reports(m, cell)]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(reports(m, cell) for m in b["per_layer"])
    for m in b["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(e2e[m["moves"]], cell)
