"""The per-layer metrics that read the program's own spans: a rehearsal
with ``--trace 1`` of each cell reports every one it lists, and on a
program without ``repro.obs`` each reader finds nothing and raises
nothing."""
import sys

import pytest

from bench import common
from bench.tests._runs import rehearse, result

SPAN_METRICS = ("ckpt_copy_ms", "ckpt_write_ms", "chunk_wait_ms",
                "serve_wait_ms", "serve_handoff_ms")


@pytest.mark.parametrize("cell", ["mnist-ovr-train", "mnist-ovr-serve"])
def test_traced_rehearsal_reports_the_span_metrics(cell):
    spec = common.benchmark()
    listed = [m["name"] for m in common.cell_metrics(spec, cell, "per_layer")
              if m["name"] in SPAN_METRICS]
    assert listed
    out = result(rehearse(cell, 4_000_000_007, "--trace", "1"))
    assert out["correct"], out["checks"]
    for name in listed:
        assert out["metrics"][name]["value"] >= 0.0, name


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_readers_without_spans_find_nothing(monkeypatch, kind):
    import repro
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    for name in SPAN_METRICS:
        assert common.load_metric(name).read({"kind": kind}) is None
