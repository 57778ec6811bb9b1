"""Arithmetic of the peaks table and of the operations and bytes the
per-layer metrics count, at olmo-1b and rwkv6-1.6b widths. The expected
numbers are worked out by hand in the comments."""

import json
from functools import partial

import jax
import pytest

from chipbench import bench, peaks

ROOT = bench.checkout_root()


def _model(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


def test_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_olmo_operations():
    m = _model("olmo-1b")["model"]
    p = peaks.matmul_params(m)
    # q, k, v, o: 4 x 2048 x 2048; SwiGLU: 3 x 2048 x 8192
    assert p["layer"] == 4 * 2048 * 2048 + 3 * 2048 * 8192 == 67_108_864
    assert p["head"] == 2048 * 50304 == 103_022_592
    # 2 x (16 x 67,108,864 + 103,022,592) + 16 layers x 4 x 16 x 128 x 1000
    assert peaks.decode_flops(m, 1000) == 2_484_600_832
    # 2 x 400 x 16 x 67,108,864 + 16 x 4 x 16 x 128 x (400 x 401 / 2)
    # + 2 x 103,022,592
    assert peaks.prefill_flops(m, 400) == 869_711_478_784
    f, b = peaks.paged_attn_work(m, 1000)
    assert f == 4 * 16 * 128 * 1000 == 8_192_000
    # K and V rows: 2 x 16 x 128 x 1000 x 2 bytes; q and o: 2 x 16 x 128 x 2
    assert b == 8_192_000 + 8_192
    t, bound = peaks.roofline_s(f, b, peaks.peaks("TPU v5 lite"))
    assert bound == "hbm" and t == pytest.approx(8_200_192 / 819e9)


def test_rwkv_operations():
    m = _model("rwkv6-1.6b")["model"]
    p = peaks.matmul_params(m)
    # 6 x 2048^2 + ddlerp LoRA 2 x 2048 x 5 x 32 + decay LoRA 2 x 2048 x 64
    # + channel mix 2 x 2048 x 7168
    assert p["layer"] == 25_165_824 + 655_360 + 262_144 + 29_360_128 \
        == 55_443_456
    assert p["head"] == 2048 * 65536
    # 2 x (24 x 55,443,456 + 134,217,728) + 24 x 4 x 32 x 64 x 64
    assert peaks.decode_flops(m, 1) == 2_929_721_344 + 12_582_912
    assert peaks.decode_flops(m, 1) == peaks.decode_flops(m, 2000)


@pytest.mark.parametrize("name,mats", [
    ("olmo-1b", ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")),
    ("rwkv6-1.6b", ("mix_w1", "mix_w2", "wr", "wk", "wv", "wg", "wo",
                    "w_lora_a", "w_lora_b", "ffn_k", "ffn_v", "ffn_r")),
])
def test_matmul_params_are_the_programs_weights(name, mats):
    """The counted weights are the program's matrices at full width (shapes
    only; nothing is allocated)."""
    from repro.models import get_model
    spec = _model(name)
    cfg = bench.model_config(spec)
    shapes = jax.eval_shape(partial(get_model(cfg).init_params, cfg),
                            jax.random.PRNGKey(0))
    per_layer = sum(shapes["blocks"][k].size for k in mats) // cfg.num_layers
    assert peaks.matmul_params(spec["model"])["layer"] == per_layer


@pytest.mark.parametrize("key,value", [("ddlerp_lora_rank", 64),
                                       ("decay_lora_rank", 128)])
def test_config_stating_other_widths_is_refused(key, value):
    """A configuration whose LoRA ranks are not the ones the program fixes
    in code (Finch 7B's 64 and 128) is not what would run, and is refused
    (shapes only; nothing is allocated)."""
    spec = _model("rwkv6-1.6b")
    assert bench.model_config(spec).num_layers == 24
    spec["model"] = dict(spec["model"], **{key: value})
    with pytest.raises(ValueError, match=key):
        bench.model_config(spec)
