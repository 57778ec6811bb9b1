"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

Each test lowers one kernel at published model widths for one chip of a
described ``v5e:2x2`` topology and checks that the compiled program holds
the Mosaic kernel (``tpu_custom_call``). A block shape, a VMEM or SMEM
budget or a layout that the TPU compiler refuses then fails here, where
interpret-mode tests cannot see it. Nothing runs: these are compiles only.

The topology is described inside a module-scoped fixture, never at import,
so only the worker that runs this file loads the TPU compiler library.
"""

import contextlib
import dataclasses
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (decode_attention, flash_attention, grouped_mvm,
                           packed_canvas_matmul, paged_decode_attention,
                           paged_kv_append)

OLMO = get_config("olmo-1b")           # served dense config: KV=16, dh=128
QWEN2_VL = get_config("qwen2-vl-7b")   # GQA: 28 query heads over 4 KV heads
OLMOE = get_config("olmoe-1b-7b")      # 64 experts, d_ff_expert=1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _assert_kernel_compiles(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    with _no_persistent_cache():
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


BF16, I32 = jnp.bfloat16, jnp.int32


POOL_LAYERS = 2     # the stacked pools' layers in these compiles


def _paged_shapes(batch, page, table_cols, pages):
    """paged_decode_attention's arguments: q, the stacked k/v pools, the
    page table, the lengths and the layer index."""
    kv, dh = OLMO.num_kv_heads, OLMO.head_dim
    g = OLMO.num_heads // kv
    pool = ((POOL_LAYERS, kv, pages, page, dh), BF16)
    return (((batch, kv, g, dh), BF16), pool, pool, ((batch, table_cols), I32),
            ((batch,), I32), ((), I32))


def _append_shapes(batch, page, pages):
    """paged_kv_append's arguments: the stacked k/v pools, the new k/v
    rows, the layer index, the page ids and the offsets."""
    kv, dh = OLMO.num_kv_heads, OLMO.head_dim
    pool = ((POOL_LAYERS, kv, pages, page, dh), BF16)
    row = ((batch, kv, dh), BF16)
    return (pool, pool, row, row, ((), I32), ((batch,), I32), ((batch,), I32))


# olmo-1b.chat in the chip benchmark: 20 slots, 1,766 pages of 16, 129 columns
BENCH_CELL = (20, 16, 129, 1766)


@pytest.mark.parametrize("batch,page,table_cols,pages", [
    pytest.param(8, 8, 32, None, id="8-8-32"),
    pytest.param(8, 16, 32, None, id="8-16-32"),
    pytest.param(8, 32, 32, None, id="8-32-32"),
    pytest.param(64, 16, 256, None, id="64-16-256"),
    pytest.param(*BENCH_CELL, id="olmo-1b.chat"),
])
def test_paged_decode_attention_compiles(one_chip, batch, page, table_cols,
                                         pages):
    _assert_kernel_compiles(
        paged_decode_attention, one_chip,
        *_paged_shapes(batch, page, table_cols,
                       pages or 1 + batch * table_cols))


@pytest.mark.parametrize("batch,page,pages", [
    pytest.param(8, 16, 257, id="8-16"),
    pytest.param(64, 16, 16385, id="64-16"),    # four grid steps of 16 slots
    pytest.param(20, 16, 1766, id="olmo-1b.chat"),
])
def test_paged_kv_append_compiles(one_chip, batch, page, pages):
    _assert_kernel_compiles(paged_kv_append, one_chip,
                            *_append_shapes(batch, page, pages))


def _hlo_instructions(text):
    """(name, shape, opcode) of every instruction of an HLO module's text."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(", line)
        if m:
            yield m.groups()


def test_paged_decode_attention_keeps_benchmark_name(one_chip, monkeypatch):
    """The benchmark finds the kernel in the device trace by the HLO name of
    its custom call, which comes from the jitted wrapper
    (``chipbench/metrics/paged_attn_roofline.KERNEL``). Called as the decode
    step calls it, on the stacked pools inside a layer scan after the
    in-place append, the compiled HLO must hold an instruction of that name,
    and the append's custom call must not match it."""
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    from chipbench.metrics.paged_attn_roofline import KERNEL

    def step(q, k_pages, v_pages, page_table, lengths, layer):
        def body(carry, _):
            kp, vp, layer = carry
            new = q[:, :, 0]                                  # (B, KV, dh)
            kp, vp = paged_kv_append(kp, vp, new, new, layer, lengths,
                                     lengths % 16)
            out = paged_decode_attention(q * 2, kp, vp, page_table, lengths,
                                         layer) + 1
            return (kp, vp, layer + 1), out
        return jax.lax.scan(body, (k_pages, v_pages, layer), None,
                            length=POOL_LAYERS)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in _paged_shapes(*BENCH_CELL)]
    with _no_persistent_cache():
        text = jax.jit(step).lower(*args).compile().as_text()
    names = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()]
    matched = [n for n in names if re.search(KERNEL, n)]
    appends = [n for n in names if n.startswith("%paged_kv_append.")]
    assert matched, KERNEL
    assert appends, "no paged_kv_append instruction"
    assert all(" custom-call(" in n for n in matched + appends)
    assert not any(re.search(KERNEL, n) for n in appends)


def test_fused_dense_decode_keeps_pool_in_place(one_chip, monkeypatch):
    """Compile guard for the dense paged decode step: the fused decode
    program (``transformer.paged_decode_multi``, the engine's horizon of
    steps, as ``chipbench/size_kv.py`` compiles it) at olmo-1b widths and
    20 slots, cut to 2 of its 16 layers to keep the compile short, at pools
    of 1,000 and 2,000 pages. The pools ride in the layer scan's carry, and
    the two paged kernels read and write them in place: the program's
    temporaries do not grow with the pool, and no instruction but
    parameters, tuples (and the loops that carry them) and the two kernels'
    custom calls has a shape with the pool's page count."""
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.runtime import EngineConfig

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # the chip's path
    cfg = dataclasses.replace(OLMO, num_layers=2)
    batch, page, table_cols, _ = BENCH_CELL

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def decode(params, state, pending, lengths, remaining, table, mask, h):
        return T.paged_decode_multi(cfg, params, state, pending, lengths,
                                    remaining, table, mask, h,
                                    hmax=EngineConfig().horizon)

    params = sds(jax.eval_shape(partial(T.init_params, cfg),
                                jax.random.PRNGKey(0)))
    vec = jax.ShapeDtypeStruct((batch,), I32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((batch, table_cols), I32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((batch,), bool, sharding=one_chip)
    h = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    temps, pool_bytes = [], []
    for pages in (1000, 2000):
        state = sds(jax.eval_shape(
            lambda: T.init_paged_decode_state(cfg, pages, page)))
        with _no_persistent_cache():
            compiled = jax.jit(decode, donate_argnums=(1, 2, 3, 4)).lower(
                params, state, vec, vec, vec, table, mask, h).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        pool_bytes.append(sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(state)))
        insts = list(_hlo_instructions(compiled.as_text()))
        pool_shaped = [
            (name, op) for name, shape, op in insts
            if re.search(rf"[\[,]{pages}[,\]]", shape)
            and op not in ("parameter", "tuple", "get-tuple-element", "while")
            and not (op == "custom-call" and re.match(
                r"(paged_kv_append|paged_decode_attention)\.\d+$", name))]
        assert not pool_shaped, pool_shaped
        kernels = {name.split(".")[0] for name, _, op in insts
                   if op == "custom-call"}
        assert {"paged_kv_append", "paged_decode_attention"} <= kernels
    growth = (temps[1] - temps[0]) / (pool_bytes[1] - pool_bytes[0])
    assert growth < 0.01, (temps, pool_bytes)


@pytest.mark.parametrize("cfg", [OLMO, QWEN2_VL], ids=lambda c: c.name)
def test_decode_attention_compiles(one_chip, cfg):
    b, t = 8, 4096
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    _assert_kernel_compiles(
        decode_attention, one_chip,
        ((b, kv, cfg.num_heads // kv, dh), BF16), ((b, kv, t, dh), BF16),
        ((b, kv, t, dh), BF16), ((b,), I32))


@pytest.mark.parametrize("cfg", [OLMO, QWEN2_VL], ids=lambda c: c.name)
def test_flash_attention_compiles(one_chip, cfg):
    s = 2048
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    _assert_kernel_compiles(
        flash_attention, one_chip,
        ((1, cfg.num_heads, s, dh), BF16), ((1, kv, s, dh), BF16),
        ((1, kv, s, dh), BF16))


def test_grouped_mvm_compiles(one_chip):
    m = OLMOE.moe
    capacity = 128
    _assert_kernel_compiles(
        grouped_mvm, one_chip,
        ((m.num_experts, capacity, OLMOE.d_model), BF16),
        ((m.num_experts, OLMOE.d_model, m.d_ff_expert), BF16))


@pytest.mark.parametrize("epilogue", [False, True], ids=["plain", "epilogue"])
def test_packed_canvas_matmul_compiles(one_chip, epilogue):
    # olmo-1b's fused QKV plane, every 128x128 block occupied, decode batch 8
    b, rb, cb = 8, OLMO.d_model // 128, 3 * OLMO.q_dim // 128
    n_blocks = rb * cb
    shapes = [((b, rb * 128), BF16), ((n_blocks, 128, 128), BF16),
              ((4, n_blocks), I32)]
    if epilogue:
        shapes += [((cb * 128,), BF16), ((b, cb * 128), BF16)]

    def fn(x, w, meta, bias=None, residual=None):
        return packed_canvas_matmul(
            x, w, meta, c_blocks=cb, bb=b, bias=bias, residual=residual,
            activation="silu" if epilogue else None)

    _assert_kernel_compiles(fn, one_chip, *shapes)
