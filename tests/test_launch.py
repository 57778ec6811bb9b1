"""Launch-layer unit tests: sharding rule engine + cell assembly logic.

Pure spec-level checks (no 512-device init — that is dryrun.py's job):
PartitionSpecs are computed from shapes and a mesh description only.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch import sharding as sh
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import default_microbatches
from repro.models.layers import serve_kv_expand


class FakeMesh:
    """Duck-typed mesh: only .shape and .axis_names are consulted."""
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


POD = FakeMesh(data=16, model=16)
MULTI = FakeMesh(pod=2, data=16, model=16)


def _specs(arch, **kw):
    cfg = get_config(arch)
    from repro.models import get_model
    params = jax.eval_shape(
        lambda k: get_model(cfg).init_params(cfg, k), jax.random.PRNGKey(0))
    return params, sh.param_pspecs(params, POD, **kw)


def test_dense_param_rules():
    params, specs = _specs("codeqwen1.5-7b")
    assert specs["embed"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    assert specs["blocks"]["wq"] == P(None, None, "model")
    assert specs["blocks"]["wo"] == P(None, "model", None)
    assert specs["blocks"]["w_down"] == P(None, "model", None)
    assert specs["blocks"]["ln1"] == P(None, None)


def test_moe_expert_parallel_rules():
    params, specs = _specs("olmoe-1b-7b")
    assert specs["blocks"]["moe"]["w_gate"] == P(None, "model", None, None)
    assert specs["blocks"]["moe"]["w_down"] == P(None, "model", None, None)
    assert specs["blocks"]["moe"]["router"] == P(None, None, None)


def test_streamed_groups_add_data_axis():
    params, specs = _specs("command-r-plus-104b",
                           streamed_groups=frozenset({"attn", "embed"}))
    assert specs["blocks"]["wq"] == P(None, "data", "model")
    assert specs["embed"] == P("model", "data")
    # non-streamed groups untouched
    assert specs["blocks"]["w_gate"] == P(None, None, "model")


def test_wide_tp_uses_both_axes():
    params, specs = _specs("command-r-plus-104b", wide_tp=True)
    assert specs["blocks"]["wq"] == P(None, None, ("model", "data"))
    assert specs["blocks"]["wo"] == P(None, ("model", "data"), None)


def test_non_divisible_dims_replicate():
    # whisper vocab 51865 is not divisible by 16 -> embed replicates
    params, specs = _specs("whisper-tiny")
    assert specs["embed"] == P(None, None)


def test_batch_spec_fallbacks():
    assert sh.batch_dim_spec(256, POD) == "data"
    assert sh.batch_dim_spec(1, POD) is None          # long_500k B=1
    assert sh.batch_dim_spec(256, MULTI) == ("pod", "data")
    assert sh.batch_dim_spec(16, MULTI) == "pod"      # 16 % 32 != 0


def test_state_specs_prefer_head_axis():
    from functools import partial
    from repro.models import get_model
    cfg = get_config("command-r-plus-104b")
    api = get_model(cfg)
    e = serve_kv_expand(cfg, 16)
    assert e == 2                                     # 8 KV heads -> 16
    st = jax.eval_shape(partial(api.init_decode_state, cfg, 128, 1024,
                                kv_expand=e))
    specs = sh.state_pspecs(st, POD)
    assert specs.k == P(None, "data", None, "model", None)
    assert specs.pos == P()


def test_serve_kv_expand_per_arch():
    expect = {"codeqwen1.5-7b": 1,       # 32 kv heads % 16 == 0
              "command-r-35b": 2,        # 8 -> 16
              "qwen2-vl-7b": 1,          # 28 heads: no aligned expansion
              "whisper-tiny": 1,         # 6 heads
              "recurrentgemma-9b": 16,   # MQA -> 16
              "deepseek-v2-lite-16b": 1}  # MLA latent cache
    for arch, e in expect.items():
        assert serve_kv_expand(get_config(arch), 16) == e, arch


def test_default_microbatches():
    assert default_microbatches(get_config("olmo-1b"),
                                SHAPES["train_4k"], POD) == 4
    assert default_microbatches(get_config("olmoe-1b-7b"),
                                SHAPES["train_4k"], POD) == 8
    assert default_microbatches(get_config("command-r-plus-104b"),
                                SHAPES["train_4k"], MULTI) == 8


def test_host_mesh_runs_train_step():
    # 1x1 mesh end-to-end micro-train (the launch.train path)
    from repro.launch.train import build
    mesh = make_host_mesh()
    with mesh:
        cfg, params, opt, stream, jitted = build(
            "olmo-1b", reduced=True, mesh=mesh, seq_len=32, batch=2,
            lr=1e-3, steps=4, microbatches=2)
        batch = stream.batch(0)
        p, o, m = jitted(params, opt, batch)
        assert jnp.isfinite(m["loss"])


def test_mesh_placed_engine_compiles_each_step_once():
    # the serve path places params on the host mesh; the engine's own
    # state must start where the jitted steps return it, or the first
    # dispatch of each step compiles a second signature
    from repro.launch.serve import init_sharded_params
    from repro.runtime import Engine, EngineConfig, poisson_trace
    cfg = get_config("olmo-1b").reduced()
    mesh = make_host_mesh()
    with mesh:
        params = init_sharded_params(cfg, mesh, 0)
        eng = Engine(cfg, params, EngineConfig(
            num_slots=4, page_size=8, num_pages=65, max_pages_per_seq=8,
            prefill_bucket=8))
        trace = poisson_trace(8, mean_interarrival=0.5, prompt_lens=(8, 16),
                              gen_lens=(4, 12), vocab_size=cfg.vocab_size)
        rep = eng.run(trace)
    assert len(rep.completed) == 8
    assert eng.backend._decode_multi._cache_size() == 1
    assert eng.backend._prefill._cache_size() == 2      # one per bucket
