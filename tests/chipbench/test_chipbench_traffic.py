"""The traffic generator: the same seed gives the same requests, and every
seed offers the same lengths in the same order, so that a window that sees
only the first fills of the backlog holds the same work on every seed."""

import json
from collections import Counter

import numpy as np

from chipbench import bench, generator

CHAT = json.loads((bench.checkout_root() / "chipbench/traffic/chat.json")
                  .read_text())


def test_same_seed_same_requests():
    big = 2**31 + 12345                  # seeds are larger than int32
    lens = generator.lengths(CHAT, 20, 3)
    pa, pb = (generator.prompts(lens, 50304, big) for _ in range(2))
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    assert [len(p) for p in pa] == [p for p, _ in lens]
    assert all(((p >= 0) & (p < 50304)).all() for p in pa)
    # another seed draws other tokens for the same lengths
    pc = generator.prompts(lens, 50304, big + 1)
    assert not any(np.array_equal(x, y) for x, y in zip(pa, pc))


def test_seeds_share_each_blocks_lengths():
    for n in (20, 64):
        lens = generator.lengths(CHAT, n, 2)
        block = generator.block_lengths(CHAT, n)
        # every fill of the slots holds the same pairs in the same order
        assert lens == block + block
        # and every group of admissions takes one pair from each prompt
        # stratum, so prefills arrive at about the same lengths
        k, size = CHAT["group"], n // CHAT["group"]
        ranked = sorted(range(n), key=lambda i: block[i])
        stratum = {i: r // size for r, i in enumerate(ranked)}
        for g in range(0, n, k):
            assert sorted(stratum[i] for i in range(g, g + k)) == list(
                range(k))


def test_chat_lengths():
    pairs = generator.block_lengths(CHAT, 32)
    prompts = [p for p, _ in pairs]
    outputs = [o for _, o in pairs]
    assert set(prompts) <= set(CHAT["prompt"]["snap_up"])
    assert min(outputs) >= 16 and max(outputs) <= 512
    # the block's middle requests sit at the lognormals' medians: the
    # prompt just under 1020 snaps up to 1020, the one just over it to 1250
    mid = len(prompts) // 2
    assert sorted(prompts)[mid - 1:mid + 1] == [1020, 1250]
    assert abs(sorted(outputs)[mid] - 129) <= 8
    # a prompt and its answer fit the 2048 context
    assert max(prompts) + max(outputs) <= 2048
    # prompts are not paired with outputs by rank: long prompts get short
    # and long answers alike
    long_out = [o for p, o in pairs if p == 1500]
    assert min(long_out) < 129 < max(long_out)
    assert Counter(prompts)[1500] == len(long_out)
