"""BENCHMARK.json against the contract's limits, and every name in it
resolves to a file of its own."""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import common, loadgen  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmark"] and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(not w.startswith("/") and ".." not in w for w in B["command"])
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 4)


def test_names_units_and_whys():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in B[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
    assert len(names) == len(set(names))
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in B["end_to_end"])
    e2e = {m["name"] for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e


def test_every_workload_resolves_to_files_that_exist():
    cfgs = {c["name"]: c for c in B["configs"]}
    cells = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        c = cfgs[w["config"]]
        cfg = common.load_config(w["config"])
        assert c["file"] == f"benchmark/configs/{w['config']}.json"
        assert cfg["source"] == c["source"] and cfg["run"]["chips"] == w["chips"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        t = loadgen.load_traffic(w["traffic"])
        kind = loadgen.load_module("traffic_kinds", t["kind"])
        assert kind.JOB == cfg["run"]["job"]
        cells.add(w["name"])
    assert {c["name"] for c in B["configs"]} == {w["config"] for w in B["workloads"]}
    e2e_cells = {}
    for m in B["end_to_end"]:
        for c in m.get("workloads", cells):
            assert c in cells
            e2e_cells.setdefault(c, set()).add(m["name"])
    for c in cells:                  # setup_s + at least one other, everywhere
        assert "setup_s" in e2e_cells[c] and len(e2e_cells[c]) >= 2


def test_every_configuration_resolves_to_a_family_with_the_whole_protocol():
    for c in B["configs"]:
        cfg = common.load_config(c["name"])
        fam = loadgen.load_family(cfg)
        assert os.path.samefile(fam.__file__, os.path.join(
            ROOT, "benchmark", "families", cfg["model_type"] + ".py"))
        for name in loadgen.FAMILY_PROTOCOL:
            assert hasattr(fam, name), (c["name"], name)
        assert callable(fam.Reference.logits)
        if cfg["run"]["job"] == "train":
            assert callable(fam.Reference.loss)
        # the toy widths replace published keys, they invent none
        assert set(fam.TOY) <= set(cfg), (c["name"], set(fam.TOY) - set(cfg))
        toy = common.hf_of(cfg, rehearsal=True)
        for k in ("num_local_experts", "num_experts_per_tok", "sliding_window"):
            assert toy.get(k) == cfg.get(k)


def test_every_per_layer_metric_has_a_reader_that_agrees_with_its_entry():
    cells = {w["name"] for w in B["workloads"]}
    where = {m["name"]: set(m.get("workloads", cells)) for m in B["end_to_end"]}
    covered = set()
    for m in B["per_layer"]:
        h = loadgen.load_module("layer_metrics", m["name"]).HEADER
        for k in ("layer", "unit", "moves", "source", "better"):
            assert h[k] == m[k], (m["name"], k)
        for c in m.get("workloads", cells):
            # a per-layer metric is reported only where the metric it moves is
            assert c in where[m["moves"]], (m["name"], c)
            covered.add(c)
    assert covered == cells
    readers = {f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics"))
               if f.endswith(".py")}
    assert readers == {m["name"] for m in B["per_layer"]}
