"""A tiny dense cell dropped into a copy of the benchmark as data only: a
config file, a traffic file, a limits file and entries in BENCHMARK.json,
for the CPU tests of the harness."""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
# peaks for the backlog's size only: a low bandwidth keeps the tiny cell's
# backlog (sized for the weight-read bound) at a few thousand requests
TEST_PEAKS = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e8, "hbm_bytes": 1e9}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

TINY_CONFIG = {
    "name": "tiny", "source": "test", "reference": "olmo",
    "deployment": "test",
    "model": {"family": "dense", "num_layers": 2, "d_model": 64,
              "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
              "d_ff": 128, "vocab_size": 256, "norm": "nonparametric",
              "use_bias": False, "tie_embeddings": True,
              "rope_theta": 10000.0},
    "reduced": [], "weight_dtype": "float32", "compute_dtype": "bfloat16",
    "serving": {"num_slots": 4, "max_context": 128,
                "kv_budget_bytes": 4096 * 60},
}
TINY_RWKV = {
    "name": "tinyrwkv", "source": "test", "reference": "rwkv6",
    "deployment": "test",
    "model": {"family": "ssm", "num_layers": 2, "d_model": 64,
              "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
              "d_ff": 128, "vocab_size": 256, "norm": "layernorm",
              "recurrent": {"lru_width": 64, "conv_width": 0, "window": 0,
                            "block_pattern": ["rec"]},
              "ddlerp_lora_rank": 32, "decay_lora_rank": 64,
              "head_norm_eps": 1e-5},
    "reduced": [], "weight_dtype": "float32", "compute_dtype": "bfloat16",
    "serving": {"num_slots": 4, "max_context": 128},
}
TINY_TRAFFIC = {
    "arrival": "backlog", "group": 2,
    "prompt": {"median": 20, "sigma": 0.5, "snap_up": [12, 24, 40]},
    "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
}
# the tiny cells' limits, set as the chip cells' are, from seeds 1-12 at
# 1.5 s: the dense cell's sound runs read 0.0044-0.0073 (bfloat16 against
# float32 at width 64), its float8 control 0.065-0.131 on the same tokens,
# and a token off by one O(1); the recurrent cell's sound runs read
# 0.024-0.192, its control 0.31-1.03
TINY_LIMITS = {"max_logit_gap": 0.03, "sample_tokens": 10**6,
               "ref_batch": 4}
LIMITS = {"tiny.tinychat": 0.03, "tinyrwkv.tinychat": 0.25}


def make_root(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "chipbench/traffic/tinychat.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "chipbench/configs/tinyrwkv.json").write_text(
        json.dumps(TINY_RWKV))
    for cell, lim in LIMITS.items():
        (root / f"chipbench/limits/{cell}.json").write_text(
            json.dumps({**TINY_LIMITS, "max_logit_gap": lim}))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("tiny", "tinyrwkv"):
        bm["configs"].append({"name": name, "source": "test",
                              "file": f"chipbench/configs/{name}.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": f"{name}.tinychat", "config": name,
                                "traffic": "tinychat", "chips": 1,
                                "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root
