"""Every name in BENCHMARK.json resolves to its files, and a new cell,
configuration, traffic mix or metric needs only new files and entries."""
import json
import re
import shutil

import pytest

from chipbench import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_names_resolve():
    bench = H.benchmark()
    assert bench["paths"] == ["chipbench"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(H.metric_reader(m["name"]))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        assert NAME.match(w["name"])
        cell = H.resolve(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert H.driver(cell.traffic).run
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:   # each moves a metric the cell reports
            assert m["moves"] in names


def test_a_new_cell_needs_only_new_files(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH_DIR, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    bench = H.benchmark()
    new = root / "chipbench"
    cfg = json.loads((new / "configs" / "mamba2-130m.json").read_text())
    cfg["name"] = "mamba2-130m-b"
    (new / "configs" / "mamba2-130m-b.json").write_text(json.dumps(cfg))
    traffic = json.loads((new / "traffic" / "train_carousel.json")
                         .read_text())
    traffic["seq_len"] = 4096
    (new / "traffic" / "train_long.json").write_text(json.dumps(traffic))
    (new / "limits" / "mamba2-130m-b.train.long.json").write_text(
        (new / "limits" / "mamba2-130m.train.carousel.json").read_text())
    (new / "metrics" / "train.steps.py").write_text(
        "def read(run):\n    return run.counters.get('steps')\n")
    bench["configs"].append(dict(bench["configs"][0], name="mamba2-130m-b",
                                 file="chipbench/configs/mamba2-130m-b.json"))
    bench["workloads"].append({"name": "mamba2-130m-b.train.long",
                               "config": "mamba2-130m-b",
                               "traffic": "train_long", "chips": 1,
                               "why": "longer rows"})
    bench["per_layer"].append({"name": "train.steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "train step",
                               "moves": "setup_s"})
    monkeypatch.setattr(H, "ROOT", root)
    monkeypatch.setattr(H, "BENCH_DIR", new)
    cell = H.resolve("mamba2-130m-b.train.long", bench)
    assert cell.traffic["seq_len"] == 4096
    assert cell.config["name"] == "mamba2-130m-b"
    run = H.Run(cell, 1, 1.0, True, counters={"steps": 7})
    assert "train.steps" in {m["name"] for m in cell.per_layer}
    assert H.metric_values(run)["train.steps"]["value"] == 7


def test_unknown_names_do_not_resolve():
    with pytest.raises(KeyError):
        H.resolve("no-such-cell")
    with pytest.raises(KeyError):
        H.peak("no such device")
