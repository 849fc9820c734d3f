"""``BENCHMARK.json`` keeps to the benchmark's format, and every name in it
has the files the harness finds it by."""

import json
import re

from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
HERE = ROOT / "chipbench"


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][0] == "python3"
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("chipbench/") and (ROOT / c["file"]).is_file()
        assert c["name"] in used
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (HERE / "expressions" / f"{cfg['expression']}.py").is_file()


def test_workloads_have_their_files():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        traffic = json.loads(
            (HERE / "traffic" / f"{w['traffic']}.json").read_text())
        assert (HERE / "drivers" / f"{traffic['kind']}.py").is_file()
        assert (HERE / "limits" / f"{w['name']}.json").is_file()


def test_metrics_have_readers_and_cover_every_cell():
    e2e, layer = BENCH["end_to_end"], BENCH["per_layer"]
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    e2e_names = {m["name"] for m in e2e}
    assert "setup_s" in e2e_names
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e_names and _line(m["layer"])
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"

    def applies(m, cell):
        return cell in m.get("workloads", [cell])

    for w in BENCH["workloads"]:
        cell = w["name"]
        mine = [m for m in e2e if applies(m, cell)]
        assert any(m["name"] == "setup_s" for m in mine) and len(mine) >= 2
        assert any(applies(m, cell) for m in layer)
        for m in layer:
            if applies(m, cell):
                moved = next(x for x in e2e if x["name"] == m["moves"])
                assert applies(moved, cell)
