"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the run served, drawn from the seed and always holding the one
with the most served tokens, is run through the plain float32 reference:
each prompt with its served tokens, teacher-forced, in one pass. At each
served position the reference's best logit is compared with its logit for
the token the engine served there. The number compared is the widest such
gap over the sample (``max_logit_gap``); a greedy engine that computes the
configuration's arithmetic serves tokens at or near the reference's best,
and the limit in ``limits/<cell>.json`` was set between the gaps of sound
runs and those of the control (see ``control.py`` and ``PERF.md``).

The served tokens include the first, which comes from prefill, so the
comparison covers the prefill path, the cache written by it and the decode
path that reads that cache.
"""

from __future__ import annotations

import time

import numpy as np


def sample(requests, seed: int, min_tokens: int) -> list:
    """Requests with served tokens: the one with the most, then others at
    random (finished ones first) until ``min_tokens`` tokens are in."""
    served = [r for r in requests if len(r.generated)]
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.generated))
    rng = np.random.default_rng([seed, 2])
    finished = [r for r in served
                if r is not longest and len(r.generated) >= r.max_new_tokens]
    done = {id(r) for r in finished}
    rest = [r for r in served if r is not longest and id(r) not in done]
    order = ([finished[i] for i in rng.permutation(len(finished))]
             + [rest[i] for i in rng.permutation(len(rest))])
    out, n = [longest], len(longest.generated)
    for r in order:
        if n >= min_tokens:
            break
        out.append(r)
        n += len(r.generated)
    return out


def layout(reqs: list, T: int, P: int):
    """Teacher-forced inputs: tokens (B, T) = prompt + served[:-1], padded;
    positions (B, P) where served token i was predicted; served (B, P);
    valid (B, P)."""
    B = len(reqs)
    tokens = np.zeros((B, T), np.int32)
    positions = np.zeros((B, P), np.int32)
    served = np.zeros((B, P), np.int32)
    valid = np.zeros((B, P), bool)
    for b, r in enumerate(reqs):
        gen = np.asarray(list(r.generated), np.int32)
        n, plen = len(gen), len(r.prompt)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), gen[:-1]])
        if len(seq) > T or n > P:
            raise ValueError(f"request {r.rid}: {len(seq)} tokens, {n} served"
                             f" do not fit ({T}, {P})")
        tokens[b, :len(seq)] = seq
        positions[b, :n] = plen - 1 + np.arange(n)
        served[b, :n] = gen
        valid[b, :n] = True
    return tokens, positions, served, valid


def gaps(ref, model: dict, seed: int, reqs: list, *, T: int, P: int,
         batch: int, control: bool = False) -> dict:
    """Widest gap below the reference's best of the served tokens and, with
    ``control``, of the tokens the float8 control ranks first at the same
    positions. Rows go through the reference ``batch`` at a time, padded to
    (T, P) so that one program serves every block."""
    prog, ctrl, n_tok = 0.0, 0.0, 0
    for i in range(0, len(reqs), batch):
        chunk = reqs[i:i + batch]
        chunk = chunk + [chunk[0]] * (batch - len(chunk))
        tokens, positions, served, valid = layout(chunk, T, P)
        valid[len(reqs[i:i + batch]):] = False
        if ((served[valid] < 0) | (served[valid] >= model["vocab_size"])).any():
            return {"max_logit_gap": float("inf"), "tokens": n_tok}
        probes = served[:, None]
        if control:
            _, _, c = ref.score(model, seed, tokens, positions, probes,
                                quant=True)
            probes = np.stack([served, np.asarray(c)], axis=1)
        best, at, _ = ref.score(model, seed, tokens, positions, probes)
        best, at = np.asarray(best, np.float64), np.asarray(at, np.float64)
        if not (np.isfinite(best[valid]).all()
                and np.isfinite(at[:, 0][valid]).all()):
            return {"max_logit_gap": float("inf"), "tokens": n_tok}
        prog = max(prog, float((best - at[:, 0])[valid].max()))
        if control:
            ctrl = max(ctrl, float((best - at[:, 1])[valid].max()))
        n_tok += int(valid.sum())
    out = {"max_logit_gap": prog, "tokens": n_tok}
    if control:
        out["control_gap"] = ctrl
    return out


def check(run, ref, seed: int, log=print, *, control: bool = False
          ) -> dict:
    """``correct`` and the numbers compared, each beside its limit. With
    ``control``, the same comparison is also made for the float8 control
    put in the program's place, at the same positions of the same prompts
    and served tokens, under ``"control"``: it has to come out not
    correct."""
    lim = run.cell.limits
    reqs = sample(run.requests, seed, lim["sample_tokens"])
    if not reqs:
        log("check: no request was served")
        return {"correct": False, "checks": {}}
    t0 = time.monotonic()
    g = gaps(ref, run.model, seed, reqs,
             T=run.cell.config["serving"]["max_context"],
             P=run.cell.traffic["output"]["max"], batch=lim["ref_batch"],
             control=control)
    log(f"check: {len(reqs)} requests, {g['tokens']} served tokens against "
        f"the reference in {time.monotonic() - t0:.3f} s")
    out = verdict(g["max_logit_gap"], lim["max_logit_gap"])
    if control:
        out["control"] = verdict(g["control_gap"], lim["max_logit_gap"])
    return out


def verdict(gap: float, limit: float) -> dict:
    return {"correct": bool(gap <= limit),
            "checks": {"max_logit_gap": {"value": gap, "limit": limit}}}
