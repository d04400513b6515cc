"""Adding a cell takes new files and a new entry only: a made-up
configuration, traffic mix and metric are found by name, with no edit to
any file the benchmark has."""
import json
import os
import shutil

from bench import common


def test_made_up_cell_is_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = common.benchmark()
    spec["configs"].append({"name": "made-up", "source": "a paper",
                            "file": "bench/configs/made-up.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "made-up.cell", "config": "made-up",
                              "traffic": "made-up-mix", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "made_up.metric", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "ingest", "moves": "setup_s",
                              "workloads": ["made-up.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/made-up.json").write_text(json.dumps(
        {"data": {"generator": "blobs", "dim": 4, "n_classes": 2,
                  "sep": 1.0}, "svm": {"budget": 8, "batch_size": 1}}))
    (root / "bench/traffic/made-up-mix.json").write_text(json.dumps(
        {"kind": "train_stream", "chunk_rows": 64}))
    (root / "bench/metrics/made_up.metric.py").write_text(
        "def read(record):\n    return 2.0 * record['setup_s']\n")

    bench = str(root / "bench")
    spec = common.benchmark(str(root))
    cell = common.find_cell(spec, "made-up.cell")
    conf = common.load_config(spec, cell["config"], str(root))
    assert conf["svm"]["budget"] == 8
    assert common.load_traffic(cell["traffic"], bench)["chunk_rows"] == 64
    assert hasattr(common.load_kind("train_stream", bench), "run")
    metrics = common.cell_metrics(spec, "made-up.cell", "per_layer")
    assert [m["name"] for m in metrics] == ["made_up.metric"]
    got = common.read_metrics(metrics, {"setup_s": 1.5}, bench)
    assert got == {"made_up.metric": {"value": 3.0, "unit": "%"}}
    # the shipped cells do not see the new metric
    assert "made_up.metric" not in [
        m["name"] for m in common.cell_metrics(spec, "mnist-ovr-train",
                                               "per_layer")]


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    spec = common.benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(common.load_metric(m["name"]), "read"), m["name"]
    for cell in spec["workloads"]:
        common.load_config(spec, cell["config"])
        kind = common.load_traffic(cell["traffic"])["kind"]
        assert os.path.exists(os.path.join(common.BENCH, "kinds",
                                           f"{kind}.py"))
