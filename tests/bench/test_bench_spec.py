"""``BENCHMARK.json``: every entry resolves by name to files that exist,
and names, units and texts keep to the allowed characters and sizes."""

import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)


def test_command_and_paths_stay_in_the_benchmark():
    cmd, paths = BENCH["command"], BENCH["paths"]
    assert 1 <= len(cmd) <= 32 and all(_text(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths), word
            assert (ROOT / word).is_file()


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_exactly_their_keys(section):
    entries = BENCH[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.match(e["name"]), e["name"]


def test_configs_resolve_to_their_files():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["source"].startswith("https://") and _text(c["source"])
        assert _text(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        data = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(data["reduced"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        for k in c["reduced"]:
            assert k in data["params"]
            assert not re.search(r"(_dim|_rank)$|hidden|width|head", k)
        for suffix in (".py", "_ref.py"):
            assert (ROOT / "bench" / "configs" / f"{c['name']}{suffix}").is_file()


def test_workloads_resolve_to_their_files():
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and _text(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        # the warm-up calls every base at least once
        assert traffic["warmup_calls"] >= traffic["bases"] >= 1
        assert traffic.get("warmup_max", traffic["warmup_calls"]) >= \
            traffic["warmup_calls"] and traffic["pool"] >= 1
        cell = spec.load_cell(w["name"])
        assert cell.config_name == w["config"]


def test_metrics_resolve_to_readers():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    layers = {}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert callable(spec.metric_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert _text(m["layer"])
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values())
    for w in cells:  # each cell reports setup_s, another end-to-end and a layer
        cell = spec.load_cell(w)
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer


def test_reader_files_are_named_from_metric_names():
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    readers = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert names <= readers
