"""The benchmark is driven by data: a configuration, a traffic mix or a
per-layer metric is a file found by the name the manifest gives it, and
adding one edits no file that is already there. The manifest itself keeps
to the format its contract sets."""
import hashlib
import json
import os
import re
import shutil

import pytest

from harness import layers, manifest

ROOT = os.path.dirname(manifest.BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _digests(top):
    out = {}
    for base, _, files in os.walk(top):
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(manifest.BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(root / "perfbench")

    bench = root / "perfbench"
    cfg = json.loads((bench / "configs" / "paper_m5_airflow.json").read_text())
    cfg["name"] = "paper_m5_tight"
    cfg["cluster"]["capacities"] = [8, 8, 8, 8]
    (bench / "configs" / "paper_m5_tight.json").write_text(json.dumps(cfg))
    (bench / "limits" / "paper_m5_tight.json").write_text(
        json.dumps({"quality_gap": 0.05}))
    (bench / "traffic" / "burst_on_off.json").write_text(json.dumps(
        {"loop": "open", "arrivals": "poisson", "rate_per_s": 0.7,
         "arrival_seed": 3, "warm": "all"}))
    (bench / "metrics" / "queue_depth.py").write_text(
        "def read(w):\n    return float(len(w.of_type('flush')))\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "paper_m5_tight", "source": "x",
                           "file": "perfbench/configs/paper_m5_tight.json",
                           "reduced": ["cluster"], "why": "x"})
    man["workloads"].append({"name": "paper_m5_tight-burst",
                             "config": "paper_m5_tight",
                             "traffic": "burst_on_off", "chips": 1,
                             "why": "x"})
    man["per_layer"].append({"name": "queue_depth.burst", "unit": "ratio",
                             "better": "lower", "source": "program_counter",
                             "layer": "front door", "moves": "latency_p90_s",
                             "workloads": ["paper_m5_tight-burst"]})
    for m in man["end_to_end"]:
        if m["name"] in ("latency_p90_s", "latency_p50_s"):
            m["workloads"].append("paper_m5_tight-burst")
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.load_cell("paper_m5_tight-burst", root=str(root))
    assert cell.config["cluster"]["capacities"] == [8, 8, 8, 8]
    assert cell.traffic["rate_per_s"] == 0.7
    assert cell.limits == {"quality_gap": 0.05}
    names = [m["name"] for m in cell.per_layer]
    assert names == ["queue_depth.burst"]
    w = layers.Window(0.0, 1.0, [{"type": "flush", "ts": 0.5,
                                  "data": {"n": 3}}], 8, False, 256, {})
    reader = manifest.metric_reader("queue_depth.burst", root=str(root))
    assert reader.read(w) == 1.0
    # the family reader of a suffixed name is found the same way
    fill = manifest.metric_reader("batch_fill.burst", root=str(root))
    assert fill.read(w) == pytest.approx(3 / 8)
    after = _digests(root / "perfbench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_manifest_name_has_its_files():
    man = manifest.load_manifest()
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        manifest.population(cell.config["population"]["kind"])
        assert "quality_gap" in cell.limits
        for m in cell.per_layer:
            assert callable(manifest.metric_reader(m["name"]).read)
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9 imaginary")
    assert manifest.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_manifest_keeps_to_its_format():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    man = json.loads(raw)
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (man["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    text = lambda s: isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s
    assert all(text(w) for w in man["command"])
    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and text(c["source"]) and text(c["why"])
        assert c["file"].startswith("perfbench/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    cells = set()
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert text(w["why"]) and w["name"] not in cells
        cells.add(w["name"])
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and text(m["layer"])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved
    for w in cells:
        reported = [m for m in man["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", cells) for m in man["per_layer"])
