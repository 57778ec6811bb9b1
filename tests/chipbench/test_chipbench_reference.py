"""The benchmark's plain float32 references against the program's forward.

At ``reduced()`` size on the CPU, on seeded weights, the reference made from
the seed alone must give the program's logits:

- with the program computing in float32 (its ``COMPUTE_DTYPE`` set to float32
  for the test), to float32 rounding: the two then do the same arithmetic in
  another order, so the logits agree to ~1e-6 of their spread; 1e-4 of the
  largest logit leaves room for the order of the sums and no more. A wrong
  weight, key split, norm, RoPE pairing or recurrence moves them by O(1);
- with the program as it serves, in bfloat16, to 2^-5 (olmo) or 2^-4
  (rwkv6) of the logits' spread plus as much absolute: bfloat16 keeps 8
  bits, and two layers of rounded activations and weights leave a few of
  those steps in each logit. RWKV-6 also rounds its mixing coefficients and
  decay logits, and exp(-exp(.)) doubles the decay's relative rounding, so
  it is given one bit more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmo, rwkv6
from repro.configs import get_config
from repro.models import get_model
from repro.models import layers as L

SEED = 7
T = 24


# what the program fixes in code for RWKV-6 and no ModelConfig field holds
RWKV_FIXED = {"ddlerp_lora_rank": 32, "decay_lora_rank": 64,
              "head_norm_eps": 1e-5}


def _model(arch):
    cfg = get_config(arch).reduced()
    m = {k: v for k, v in dataclasses.asdict(cfg).items()
         if isinstance(v, (bool, int, float, str))}
    if cfg.family == "ssm":
        m.update(RWKV_FIXED)
    return cfg, m


def _program_logits(cfg, tokens):
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(SEED))
    with jax.default_matmul_precision("highest"):
        out = get_model(cfg).forward(cfg, params,
                                     {"tokens": jnp.asarray(tokens)})
    return np.asarray(out, np.float32)


def _reference_logits(ref, m, tokens):
    B, V = tokens.shape[0], m["vocab_size"]
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    probes = np.broadcast_to(np.arange(V, dtype=np.int32)[None, :, None],
                             (B, V, T))
    best, at, arg = ref.score(m, SEED, tokens, pos, probes)
    at = np.asarray(at).transpose(0, 2, 1)               # (B, T, V)
    np.testing.assert_allclose(np.asarray(best), at.max(-1), rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(arg), at.argmax(-1))
    return at


@pytest.mark.parametrize("arch,ref", [("olmo-1b", olmo),
                                      ("rwkv6-7b", rwkv6)])
def test_reference_matches_program_in_float32(arch, ref, monkeypatch):
    cfg, m = _model(arch)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, T)).astype(np.int32)
    monkeypatch.setattr(L, "COMPUTE_DTYPE", jnp.float32)
    prog = _program_logits(cfg, tokens)
    want = _reference_logits(ref, m, tokens)
    np.testing.assert_allclose(prog, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("arch,ref,bits", [("olmo-1b", olmo, 5),
                                           ("rwkv6-7b", rwkv6, 4)])
def test_reference_matches_program_in_bfloat16(arch, ref, bits):
    cfg, m = _model(arch)
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, T)).astype(np.int32)
    prog = _program_logits(cfg, tokens)
    want = _reference_logits(ref, m, tokens)
    spread = float(want.std())
    np.testing.assert_allclose(prog, want, rtol=0,
                               atol=2.0 ** -bits * (spread + 1.0))


def test_reference_weights_are_the_programs():
    cfg, m = _model("olmo-1b")
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(SEED))
    k_embed, layer_keys = olmo._keys(m, SEED)
    np.testing.assert_array_equal(olmo.embedding(m, k_embed),
                                  params["embed"])
    for i in range(m["num_layers"]):
        w = olmo.layer_weights(m, layer_keys[i])
        for name, a in w.items():
            np.testing.assert_array_equal(a, params["blocks"][name][i])

    cfg, m = _model("rwkv6-7b")
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(SEED))
    _, layer_keys, _ = rwkv6._keys(m, SEED)
    names = {"mu": "mu_base", "mix_a": "mix_w1", "mix_b": "mix_w2",
             "w0": "w_base", "decay_a": "w_lora_a", "decay_b": "w_lora_b"}
    for i in range(m["num_layers"]):
        w = rwkv6.layer_weights(m, layer_keys[i])
        for name, a in w.items():
            np.testing.assert_array_equal(
                a, params["blocks"][names.get(name, name)][i])


@pytest.mark.parametrize("arch,ref", [("olmo-1b", olmo),
                                      ("rwkv6-7b", rwkv6)])
def test_control_departs_from_reference(arch, ref):
    """The float8 control reads other logits than the float32 reference:
    it is a lower precision, not the same computation."""
    cfg, m = _model(arch)
    tokens = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, T)).astype(np.int32)
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), (2, T))
    probes = np.zeros((2, 1, T), np.int32)
    best, _, _ = ref.score(m, SEED, tokens, pos, probes)
    best_q, _, _ = ref.score(m, SEED, tokens, pos, probes, quant=True)
    gap = np.abs(np.asarray(best) - np.asarray(best_q)).max()
    assert gap > 1e-3 * np.abs(np.asarray(best)).max()
