"""The harness finds a configuration, a traffic mix and a per-layer metric by
name: a later PR adds a cell as data, with no edit to an existing file
beyond new entries in BENCHMARK.json."""

import json
import time

from chipbench import bench
from tinycell import DEVICE, TEST_PEAKS, make_root

METRIC = '''"""Test metric: the traced window's length in seconds."""


def compute(run):
    return run.trace.window_ns / 1e9
'''


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "chipbench").rglob("*") if p.is_file()}


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    root = make_root(tmp_path)          # adds tiny config, mix and limits
    before = _snapshot(root)
    (root / "chipbench/metrics/window_seconds.py").write_text(METRIC)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "window_seconds", "unit": "s",
                            "better": "lower", "source": "device_trace",
                            "layer": "device (one v5e)",
                            "moves": "output_tokens_per_s",
                            "workloads": ["tiny.tinychat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = bench.resolve_cell(root, "tiny.tinychat")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["prompt"]["median"] == 20
    assert cell.config["serving"]["num_slots"] == 4
    assert "window_seconds" in cell.per_layer
    assert "window_seconds" not in bench.resolve_cell(
        root, "tinyrwkv.tinychat").per_layer
    out = bench.run_cell(root, "tiny.tinychat", 1, 1.0, True,
                         t_start=time.monotonic(), device=DEVICE,
                         pk=TEST_PEAKS, log=lambda *_: None)
    assert out["correct"]
    got = out["metrics"]["window_seconds"]["value"]
    assert 1.0 < got < 1.5     # the window runs to its closing step
    after = _snapshot(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_listed_file_exists():
    """Every cell of the committed BENCHMARK.json resolves, and every metric
    it names has its reader."""
    root = bench.checkout_root()
    bm = bench.load_benchmark(root)
    for w in bm["workloads"]:
        cell = bench.resolve_cell(root, w["name"])
        assert cell.config["name"] == w["config"]
        for name in cell.end_to_end + cell.per_layer:
            assert hasattr(bench.load_metric(root, name), "compute")
        assert bench.load_reference(cell.config).score
