"""A tiny DeepSeek-V2 cell through the harness on the CPU, and the latent
path's two metrics on recorded counter samples.

The cell (a dense layer and two MoE layers of width 64, MLA latent 32 +
rope 8 with YaRN, 4 of 8 routed experts held, top-2, one shared expert)
is dropped into a copy of the benchmark as data only, as ``tinycell``
drops the dense and recurrent ones. Its limit was set as the chip cells'
are, from seeds 1-12 at 1.5 s: sound runs read 0.036-0.428 (bfloat16
against float32 at width 64, routing near-ties included), the float8
control 0.867-1.430 on the same tokens.
"""

import json
import math
import time
from types import SimpleNamespace

import pytest

from chipbench import bench, check
from tinycell import DEVICE, TEST_PEAKS, TINY_LIMITS, make_root

CELL = "tinyds.tinychat"
LIMIT = 0.6
TINY_DEEPSEEK = {
    "name": "tinyds", "source": "test", "reference": "deepseek_v2",
    "deployment": "test",
    "model": {"family": "moe", "num_layers": 3, "first_k_dense": 1,
              "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
              "d_ff": 128, "vocab_size": 256, "norm": "rmsnorm",
              "tie_embeddings": False, "rope_theta": 10000.0,
              "rope_scaling": {"type": "yarn", "factor": 40,
                               "original_max_position_embeddings": 4096,
                               "beta_fast": 32, "beta_slow": 1,
                               "mscale": 0.707, "mscale_all_dim": 0.707},
              "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                      "qk_rope_head_dim": 8, "v_head_dim": 16},
              "moe": {"num_experts": 4, "router_experts": 8, "top_k": 2,
                      "d_ff_expert": 32, "num_shared_experts": 1}},
    "reduced": [], "weight_dtype": "float32", "compute_dtype": "bfloat16",
    # 60 latent pages of 16 rows: 3 layers x 16 x (32 + 8) x 2 bytes each
    "serving": {"num_slots": 4, "max_context": 128,
                "kv_budget_bytes": 3 * 16 * 40 * 2 * 60},
}


@pytest.fixture
def root(tmp_path, monkeypatch):
    from repro.launch import compile_cache
    # the tests' processes keep JAX's default cache settings
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    root = make_root(tmp_path)
    (root / "chipbench/configs/tinyds.json").write_text(
        json.dumps(TINY_DEEPSEEK))
    (root / f"chipbench/limits/{CELL}.json").write_text(
        json.dumps({**TINY_LIMITS, "max_logit_gap": LIMIT}))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tinyds", "source": "test",
                          "file": "chipbench/configs/tinyds.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tinyds",
                            "traffic": "tinychat", "chips": 1,
                            "why": "test"})
    for m in bm["per_layer"]:
        if m["name"] in ("expert_row_use", "latent_read_live_share"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def _run(root, seed=3, seconds=1.5, trace=False):
    return bench.run_cell(root, CELL, seed, seconds, trace,
                          t_start=time.monotonic(), device=DEVICE,
                          pk=TEST_PEAKS, log=lambda *_: None)


def test_sound_run_is_correct(root):
    """The harness builds the configuration from its file, serves it
    through ``runtime.Engine``'s latent backend with every slot live at
    the window's open and no compile inside it (the run fails on one),
    and the reference finds it correct."""
    out = _run(root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 4
    assert out["window"]["live_at_open"] == out["window"]["num_slots"] == 4
    assert set(out["metrics"]) == {"output_tokens_per_s", "tpot_p95_ms",
                                   "setup_s"}
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] < gap["limit"] == LIMIT


def test_warm_up_compiles_one_prefill_per_bucket(root):
    """Dropless routing leaves nothing length-dependent in the prefill
    program: warm-up traces each of the engine's buckets once, and a prompt
    of another length in a warmed bucket compiles nothing."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_host_mesh
    from repro.runtime import Engine, Request

    cell = bench.resolve_cell(root, CELL)
    cfg = bench.model_config(cell.config)
    ecfg = bench.engine_config(cfg, cell.config, cell.traffic, 0)
    mesh = make_host_mesh()
    with mesh:                          # placed as the harness places them
        eng = Engine(cfg, bench.make_params(cfg, mesh, 0), ecfg)
        bench.warm(eng, cell.traffic, NamedSharding(mesh, PartitionSpec()))
        b = ecfg.prefill_bucket
        buckets = {-(-n // b) * b
                   for n in bench.warm_prompt_lengths(eng, cell.traffic)}
        assert eng.backend._prefill._cache_size() == len(buckets)
        eng.run([Request(rid=0, prompt=np.arange(1, 18, dtype=np.int32),
                         max_new_tokens=3)])
    assert eng.backend._prefill._cache_size() == len(buckets)


def test_control_is_not_correct(root):
    """The float8 reference in the program's place reads above the limit on
    the same prompts and tokens."""
    c = bench.resolve_cell(root, CELL)
    run, _ = bench.serve(c, 5, 1.5, trace=False, t_start=time.monotonic(),
                         pk=TEST_PEAKS, log=lambda *_: None)
    v = check.check(run, bench.load_reference(c.config), 5, control=True,
                    log=lambda *_: None)
    assert v["correct"], v
    gap = v["control"]["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] == LIMIT


def test_traced_run_reads_the_latent_metrics(root, monkeypatch):
    monkeypatch.setattr(bench, "TRACE_SECONDS", 1.0)
    out = _run(root, seconds=30.0, trace=True)
    assert out["correct"]
    m = out["metrics"]
    for name in ("expert_row_use", "latent_read_live_share"):
        assert 0 < m[name]["value"] <= 100 and m[name]["unit"] == "%"


def _recorded(monkeypatch, name, fields, samples):
    from repro.runtime import tracing
    ring = tracing.Ring(fields)
    for t, *values in samples:
        ring._buf.append(ring.Sample(t, *values))
    monkeypatch.setattr(tracing, name, ring)


@pytest.mark.parametrize("metric, ring, fields, want", [
    ("expert_row_use", "ROUTES", ("claims", "rows"),
     100.0 * (30 + 50) / (400 + 400)),
    ("latent_read_live_share", "LATENT_READS", ("live", "gathered"),
     100.0 * (30 + 50) / (400 + 400)),
])
def test_latent_metric_reads_a_recorded_sample(monkeypatch, metric, ring,
                                               fields, want):
    """Sums over the window's samples only; None where the program keeps
    no such counter or its ring lost part of the window."""
    from chipbench import program
    from repro.runtime import tracing

    mod = bench.load_metric(bench.checkout_root(), metric)
    run = SimpleNamespace(open_t=10.0, close_t=20.0)
    _recorded(monkeypatch, ring, fields,
              [(9.5, 1000, 1000), (10.5, 30, 400), (20.0, 50, 400),
               (20.5, 1, 1000)])
    assert mod.compute(run) == pytest.approx(want)
    assert mod.compute(SimpleNamespace(open_t=30.0, close_t=40.0)) is None
    getattr(tracing, ring)._lost_t = 12.0
    assert mod.compute(run) is None
    monkeypatch.delattr(tracing, ring)
    assert mod.compute(run) is None
    monkeypatch.setattr(program, "counters", lambda: None)
    assert mod.compute(run) is None
    assert math.isfinite(want)
