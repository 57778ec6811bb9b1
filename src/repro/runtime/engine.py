"""Continuous-batching decode engine over a paged KV cache.

The static serving path (launch/serve.py --mode static) prefills one
lockstep batch and decodes until the *longest* request finishes: slots
whose request completed keep burning decode steps and the dense cache
holds ``batch x max_len`` whether occupied or not — the serving analogue
of the idle-rows / wasted-cells failure mode the paper attacks in the IMC
fabric. This engine keeps the compute fabric occupied instead:

  * an admission queue (scheduler.py) feeds free slots as requests arrive;
  * each slot advances its own request at its own length (per-slot RoPE
    positions and attention lengths — models.transformer.paged_decode_step);
  * the KV cache is a shared page pool (kv_pager.py) addressed through
    int32 page tables, so cache bytes track live tokens;
  * finished slots are recycled immediately and their pages returned;
  * on page exhaustion the youngest request is preempted (pages freed,
    request requeued) rather than stalling the whole batch.

Four backends cover the model zoo's cache shapes: PagedTransformerBackend
(dense + vlm families — a real paged KV cache), RecurrentBackend (ssm —
constant-size per-slot state, where continuous batching still removes the
lockstep drain but there is no cache growth to page), HybridBackend
(hybrid/recurrentgemma — constant-size recurrent state per slot plus a
bounded sliding-window KV held as a page-granular ring, recycling the
page that slides out of the window), and LatentBackend (MoE models with
an MLA latent cache — deepseek: pages hold compressed latent rows, not
per-head K/V, and expert weights stream through the residency planner
like any other layer slice).
"""

from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..models import get_model
from . import tracing
from .arena import ArenaConfig, DeviceArena, partition_pages  # noqa: F401
from .device_state import DeviceLoopState
from .kv_pager import PagerConfig, TRASH_PAGE
from .model_pool import ModelPool
from .prefix_index import PrefixIndex
from .scheduler import MultiQueueScheduler, Request, Scheduler


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 8
    page_size: int = 16
    num_pages: int = 257               # incl. the trash page
    max_pages_per_seq: int = 16
    prefill_bucket: int = 32           # prompt pad quantum (page multiple)
    greedy: bool = True
    temperature: float = 0.8
    seed: int = 0
    max_steps: int = 200_000
    # cross-request KV prefix sharing: admission maps prompt prefixes
    # already resident in the page pool (radix index over token ids)
    # onto refcounted shared pages and prefills only the divergence
    # suffix; a decode write into a still-shared page copies-on-write
    # exactly that page. Backends opt in via their prefix_sharing flag.
    prefix_sharing: bool = False
    # horizon-fused decode: cap on the number of decode steps one device
    # dispatch may advance (the engine shrinks it per step so no
    # schedulable event — page boundary, ring wrap, token budget,
    # arrival, stream gate — can land mid-horizon). 1 disables fusion
    # and keeps the legacy per-step dispatch; non-greedy sampling always
    # runs per-step (the host RNG draws between tokens).
    horizon: int = 32

    def __post_init__(self):
        assert self.prefill_bucket % self.page_size == 0, \
            "prefill bucket must be a page multiple"
        assert self.horizon >= 1

    @property
    def pager(self) -> PagerConfig:
        return PagerConfig(self.num_pages, self.page_size,
                           self.max_pages_per_seq)


# --- reports -------------------------------------------------------------------


def make_batch_sampler(rng: np.random.Generator, greedy: bool,
                       temperature: float):
    """Shared host-side batch sampler (engine, pooled engine and static
    baseline all draw through this one helper). Greedy argmaxes the
    whole (N, V) block at once; the temperature path draws ONE uniform
    per row and inverts the softmax CDF, so a seeded run is
    deterministic and the per-slot Python sampling loop is gone from
    every path."""
    def sample_batch(rows: np.ndarray) -> np.ndarray:
        rows = np.asarray(rows)
        if rows.ndim == 1:
            rows = rows[None]
        if rows.shape[0] == 0:
            return np.zeros((0,), np.int64)
        if greedy:
            return np.argmax(rows, axis=-1)
        z = rows.astype(np.float64) / temperature
        z -= z.max(axis=-1, keepdims=True)
        p = np.exp(z)
        cdf = np.cumsum(p, axis=-1)
        u = rng.random(rows.shape[0]) * cdf[:, -1]
        return np.minimum((cdf < u[:, None]).sum(axis=-1),
                          rows.shape[-1] - 1)
    return sample_batch


def make_sampler(rng: np.random.Generator, greedy: bool,
                 temperature: float):
    """Single-row view of make_batch_sampler (prefill samples one row)."""
    sample_batch = make_batch_sampler(rng, greedy, temperature)

    def sample(logits_row: np.ndarray) -> int:
        return int(sample_batch(logits_row[None])[0])
    return sample


def vlm_extras_fn(cfg, num_patches: int = 4):
    """Per-request extras generator for vlm traces (poisson_trace hook)."""
    def extras(rng: np.random.Generator) -> dict:
        return {"patch_embeds": rng.standard_normal(
            (num_patches, cfg.d_model)).astype(np.float32)}
    return extras


@dataclasses.dataclass
class EngineReport:
    name: str
    num_slots: int
    decode_steps: int = 0
    slot_steps: int = 0                # actual batch width summed per step
    useful_slot_steps: int = 0
    prefill_calls: int = 0
    prefill_tokens: int = 0            # computed (padded) prefill tokens
    preemptions: int = 0
    completed: list[Request] = dataclasses.field(default_factory=list)
    peak_live_pages: int = 0
    # prefix sharing
    shared_page_hits: int = 0          # pages admitted by reference
    cow_copies: int = 0                # divergence-write page copies
    prefill_tokens_saved: int = 0      # bucketed tokens NOT recomputed
    peak_demand_pages: int = 0         # live minus index-only cache
    page_bytes: int = 0                # 0 -> non-paged backend
    slot_state_bytes: int = 0          # per-slot non-paged state (hybrid)
    cache_bytes_alloc: int = 0         # full backing allocation
    wall_s: float = 0.0
    # decode-loop host<->device traffic (prefill excluded — identical on
    # every path): decode dispatches + state-sync uploads, host syncs
    # that block on a device result, and page-table bytes shipped
    device_dispatches: int = 0
    host_syncs: int = 0
    page_table_upload_bytes: int = 0

    @property
    def new_tokens(self) -> int:
        return sum(len(r.generated) for r in self.completed)

    @property
    def prefill_equiv_steps(self) -> float:
        """Prefill compute in decode-step units: a decode step advances up
        to ``num_slots`` tokens on the same fabric, so T computed prefill
        tokens occupy ~T/num_slots steps. Re-prefill after preemption
        counts again — restarted work is priced, not free."""
        return self.prefill_tokens / max(self.num_slots, 1)

    @property
    def decode_tokens_per_step(self) -> float:
        """Decode-only utilization: generated tokens per batched decode
        step (the PR-1 slot-recycling claim is stated on this metric)."""
        return self.new_tokens / max(self.decode_steps, 1)

    @property
    def tokens_per_step(self) -> float:
        """Structural throughput: generated tokens per decode-equivalent
        step of fabric time, prefill compute included in the denominator
        (see prefill_equiv_steps). Wall-clock tokens/s is this times
        steps/s, and steps cost the same for engine and baseline."""
        return self.new_tokens / max(
            self.decode_steps + self.prefill_equiv_steps, 1.0)

    @property
    def wasted_slot_fraction(self) -> float:
        return 1.0 - self.useful_slot_steps / max(self.slot_steps, 1)

    @property
    def kv_bytes_peak(self) -> int:
        """Peak cache bytes holding *live* tokens (paged) or the full
        dense allocation (static / recurrent). A paged backend with
        per-slot recurrent state (hybrid) adds that constant term so the
        comparison against the static path — whose _state_bytes includes
        the same conv/LRU arrays — stays symmetric."""
        if self.page_bytes:
            return (self.peak_live_pages * self.page_bytes
                    + self.slot_state_bytes)
        return self.cache_bytes_alloc

    @property
    def kv_demand_bytes_peak(self) -> int:
        """Peak cache bytes some request actually references (shared
        pages counted once, index-only warm cache excluded — those
        pages are reclaimable on demand, like an OS page cache). This
        is the fair peak-KV comparison against a run without sharing,
        where demand == live and the metric degrades to kv_bytes_peak.
        """
        if self.page_bytes:
            return (self.peak_demand_pages * self.page_bytes
                    + self.slot_state_bytes)
        return self.cache_bytes_alloc

    def latency_percentiles(self, qs=(50, 95)) -> dict[str, float]:
        lats = [r.latency_steps for r in self.completed] or [0]
        return {f"p{q}": float(np.percentile(lats, q)) for q in qs}

    def summary(self) -> dict:
        return {
            "name": self.name,
            "requests": len(self.completed),
            "new_tokens": self.new_tokens,
            "decode_steps": self.decode_steps,
            "prefill_tokens": self.prefill_tokens,
            "tokens_per_step": round(self.tokens_per_step, 3),
            "decode_tokens_per_step": round(self.decode_tokens_per_step, 3),
            "wasted_slot_fraction": round(self.wasted_slot_fraction, 3),
            "kv_bytes_peak": self.kv_bytes_peak,
            "kv_demand_bytes_peak": self.kv_demand_bytes_peak,
            "shared_page_hits": self.shared_page_hits,
            "cow_copies": self.cow_copies,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "preemptions": self.preemptions,
            "prefill_calls": self.prefill_calls,
            **{k: round(v, 1)
               for k, v in self.latency_percentiles().items()},
            "wall_s": round(self.wall_s, 3),
            "device_dispatches": self.device_dispatches,
            "host_syncs": self.host_syncs,
            "page_table_upload_bytes": self.page_table_upload_bytes,
        }


# --- backends ------------------------------------------------------------------
# The engine drives backends through a small protocol:
#   paged        -- does the backend allocate KV pages at all
#   ring_rows    -- None for linear page-table growth (cache grows with
#                   the context), or R for a page-granular window ring
#                   (a slot holds at most R pages; on wrap the engine
#                   frees the page that slid out of the window)
#   page_bytes   -- HBM bytes one page holds across layers (0 if unpaged)
#   expert_rows_per_step -- expert rows a decode step computes; nonzero
#                   only for routed experts over latent pages, whose
#                   decode state counts the live claims on held experts
#   supports(cfg)     -- classmethod: can this backend serve the config
#   can_ever_fit(...) -- admission feasibility for this cache shape
#   admission_rows(pgr, ctx_len) -> table rows the prefill pages fill
#   prefill(ctx, extras, slot, pages) -> last logits on the host
#   decode(...) -> logits on the device (the caller syncs) / release_slot(slot)


def _bucket_prompt(ctx: np.ndarray, ecfg: EngineConfig, pages: list[int],
                   first_page: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Pad a prompt to its prefill bucket and build the page-scatter ids:
    prompt page ``first_page + i`` maps to ``pages[i]``, every other
    bucket page (pre-window, pad) to the trash page."""
    plen = len(ctx)
    bucket = -(-plen // ecfg.prefill_bucket) * ecfg.prefill_bucket
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = ctx
    pids = np.full((bucket // ecfg.page_size,), TRASH_PAGE, np.int32)
    pids[first_page:first_page + len(pages)] = pages
    return toks, pids


class _FusedDecode:
    """Host wrapper around a backend's jitted multi-step decode.

    ``decode_fused`` takes the engine's persistent device arrays
    (DeviceLoopState), advances up to ``h`` decode steps in ONE dispatch
    with greedy sampling on device, and returns the (hmax, B) token
    buffer plus the rebound donated loop arrays — the caller adopts them
    without a download. ``teacher`` (hmax, B) int32 forces the sampled
    tokens (fused replay of a recorded sequence; used by the
    differential tests to drive state through the fused path)."""

    def decode_fused(self, pending, lengths, remaining, page_table, mask,
                     h: int, teacher=None):
        out, self.state, pending, lengths, remaining = self._decode_multi(
            self.params, self.state, pending, lengths, remaining,
            page_table, jnp.asarray(mask),
            jnp.asarray(h, jnp.int32),
            None if teacher is None else jnp.asarray(teacher, jnp.int32))
        return out, pending, lengths, remaining


class _PagedBackendBase(_FusedDecode):
    """Shared jit-dispatch plumbing for every paged backend: the decode
    wrapper marshals host arrays into the jitted step and the pages are
    owned by the allocator, so release_slot is a no-op."""

    paged = True
    slot_state_bytes = 0               # no per-slot non-paged state
    expert_rows_per_step = 0           # no routed experts, no latent pages
    prefix_sharing = False             # opt-in per backend (dense only)

    @classmethod
    def supports(cls, cfg) -> bool:
        return True

    def decode(self, tokens, page_table, lengths, active) -> jax.Array:
        logits, self.state = self._decode(
            self.params, self.state, jnp.asarray(tokens),
            jnp.asarray(page_table), jnp.asarray(lengths),
            jnp.asarray(active))
        return logits

    def release_slot(self, slot: int) -> None:
        pass                            # pages freed by the allocator


class _LinearPagedMixin(_PagedBackendBase):
    """Shared geometry for backends whose page table grows with context."""

    ring_rows = None

    def can_ever_fit(self, pgr, prompt_len: int, max_new_tokens: int,
                     ctx_len: int) -> bool:
        return pgr.can_ever_fit(prompt_len, max_new_tokens, ctx_len,
                                pgr.num_pages)

    def admission_rows(self, pgr, ctx_len: int) -> list[int]:
        return list(range(pgr.pages_for(ctx_len)))


class PagedTransformerBackend(_LinearPagedMixin):
    """Dense/vlm families: real paged KV cache + paged decode attention."""

    def __init__(self, cfg, params, ecfg: EngineConfig):
        from ..models import transformer as T

        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.T = T
        self.page_bytes = ecfg.pager.page_bytes(cfg)
        self.state = T.init_paged_decode_state(cfg, ecfg.num_pages,
                                               ecfg.page_size)
        # vlm stays out: M-RoPE position triples and per-request patch
        # embeds make "same token ids" insufficient for "same KV"
        self.prefix_sharing = cfg.family == "dense"

        def prefill_write(params, state, batch, lengths, page_ids):
            last, (k, v) = T.paged_prefill(cfg, params, batch, lengths)
            state = T.write_prefill_pages(cfg, state, (k[:, 0], v[:, 0]),
                                          page_ids)
            return last[0], state

        def prefill_shared_write(params, state, batch, lengths, page_ids,
                                 prefix_pages, prefix_len):
            last, (k, v) = T.paged_prefill_shared(
                cfg, params, state, batch, lengths, prefix_pages,
                prefix_len)
            state = T.write_prefill_pages(cfg, state, (k[:, 0], v[:, 0]),
                                          page_ids)
            return last[0], state

        def decode(params, state, tokens, page_table, lengths, active):
            return T.paged_decode_step(cfg, params, state, tokens,
                                       page_table, lengths, active)

        def decode_multi(params, state, pending, lengths, remaining,
                         page_table, mask, h, teacher):
            return T.paged_decode_multi(cfg, params, state, pending,
                                        lengths, remaining, page_table,
                                        mask, h, hmax=ecfg.horizon,
                                        teacher=teacher)

        self._prefill = jax.jit(prefill_write, donate_argnums=(1,))
        self._prefill_shared = jax.jit(prefill_shared_write,
                                       donate_argnums=(1,))
        self._copy_page = jax.jit(T.copy_kv_page, donate_argnums=(0,))
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self._decode_multi = jax.jit(decode_multi,
                                     donate_argnums=(1, 2, 3, 4))

    def prefill(self, ctx: np.ndarray, extras, slot: int,
                page_ids: list[int]) -> np.ndarray:
        """Prefill one request (padded to the bucket), scatter its KV into
        ``page_ids``, return the last live token's logits (V,)."""
        toks, pids = _bucket_prompt(ctx, self.ecfg, page_ids)
        batch = {"tokens": jnp.asarray(toks)}
        if extras:
            batch.update({k: jnp.asarray(v)[None] for k, v in extras.items()})
        logits, self.state = self._prefill(
            self.params, self.state, batch,
            jnp.asarray([len(ctx)], jnp.int32), jnp.asarray(pids))
        return np.asarray(logits)

    def prefill_shared(self, ctx: np.ndarray, extras, slot: int,
                       page_ids: list[int], prefix_pages: list[int],
                       prefix_tokens: int) -> np.ndarray:
        """Prefill only the suffix past ``prefix_tokens`` (a page
        multiple) whose KV already sits in ``prefix_pages``; scatter the
        suffix KV into ``page_ids`` and return last-live-token logits.
        The prefix-page operand is padded to the table width, so the jit
        cache stays keyed on the suffix bucket alone."""
        suffix = ctx[prefix_tokens:]
        toks, pids = _bucket_prompt(suffix, self.ecfg, page_ids)
        pref = np.full((1, self.ecfg.max_pages_per_seq), TRASH_PAGE,
                       np.int32)
        pref[0, :len(prefix_pages)] = prefix_pages
        logits, self.state = self._prefill_shared(
            self.params, self.state, {"tokens": jnp.asarray(toks)},
            jnp.asarray([len(suffix)], jnp.int32), jnp.asarray(pids),
            jnp.asarray(pref), jnp.asarray([prefix_tokens], jnp.int32))
        return np.asarray(logits)

    def copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate page ``src`` into ``dst`` before a
        shared page takes a divergence write."""
        self.state = self._copy_page(self.state,
                                     jnp.asarray(src, jnp.int32),
                                     jnp.asarray(dst, jnp.int32))


class RecurrentBackend(_FusedDecode):
    """ssm family (rwkv6): constant-size per-slot state, no paging.

    The recurrence consumes every token it sees, so prompts are prefilled
    at their exact length (no pad bucketing — traces should draw prompt
    lengths from a small set to bound jit compiles).
    """

    paged = False
    ring_rows = None
    page_bytes = 0
    slot_state_bytes = 0
    expert_rows_per_step = 0
    prefix_sharing = False

    @classmethod
    def supports(cls, cfg) -> bool:
        return True

    def __init__(self, cfg, params, ecfg: EngineConfig):
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.api = get_model(cfg)
        self.state = self.api.init_decode_state(cfg, ecfg.num_slots)
        # the whole cache IS per-slot constant state; counted so pooled
        # kv_bytes_peak matches the sum of per-tenant standalone reports
        self.slot_state_bytes = _state_bytes(self.state)
        self._prefill = jax.jit(
            lambda params, batch: self.api.prefill(cfg, params, batch, 0))
        self._decode = jax.jit(
            lambda params, state, tokens: self.api.decode_step(
                cfg, params, state, tokens),
            donate_argnums=(1,))

        def decode_multi(params, state, pending, lengths, remaining,
                         page_table, mask, h, teacher):
            del page_table              # recurrent state, nothing paged
            from ..models import rwkv6 as R
            return R.decode_multi(cfg, params, state, pending, lengths,
                                  remaining, mask, h, hmax=ecfg.horizon,
                                  teacher=teacher)

        self._decode_multi = jax.jit(decode_multi,
                                     donate_argnums=(1, 2, 3, 4))
        # slot is a traced scalar (``.at[:, slot]`` takes traced indices),
        # so admission compiles once total — not once per batch slot
        self._write = jax.jit(self._write_slot, donate_argnums=(0,))

    @staticmethod
    def _write_slot(state, single, slot):
        """Copy a B=1 prefill state into batch slot ``slot`` (every data
        leaf of RwkvState carries batch on axis 1; pos is lockstep-only
        and unused by the engine)."""
        return dataclasses.replace(
            state,
            att_prev=state.att_prev.at[:, slot].set(single.att_prev[:, 0]),
            ffn_prev=state.ffn_prev.at[:, slot].set(single.ffn_prev[:, 0]),
            wkv=state.wkv.at[:, slot].set(single.wkv[:, 0]))

    def prefill(self, ctx: np.ndarray, extras, slot: int,
                page_ids=None) -> np.ndarray:
        batch = {"tokens": jnp.asarray(ctx[None].astype(np.int32))}
        logits, single = self._prefill(self.params, batch)
        self.state = self._write(self.state, single,
                                 jnp.asarray(slot, jnp.int32))
        return np.asarray(logits[0])

    def decode(self, tokens, page_table, lengths, active) -> jax.Array:
        logits, self.state = self._decode(self.params, self.state,
                                          jnp.asarray(tokens))
        return logits

    def release_slot(self, slot: int) -> None:
        pass                            # overwritten at next admission


class HybridBackend(_PagedBackendBase):
    """hybrid family (recurrentgemma/griffin): constant-size recurrent
    state per slot + a bounded sliding-window KV cache paged as a ring.

    The window ring holds ``ring_rows = ceil(window/page) + 1`` pages per
    slot; on every page-boundary crossing the engine frees the page that
    slid fully out of the attention window and allocates a fresh one into
    the same table row, so cache bytes stay O(window) per slot no matter
    how long the request runs — arbitrarily long prompts admit with the
    same bounded page count (only the last window of KV is ever paged).
    """

    @classmethod
    def supports(cls, cfg) -> bool:
        return cfg.recurrent is not None

    def __init__(self, cfg, params, ecfg: EngineConfig):
        from ..models import griffin as G

        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.window = cfg.recurrent.window
        self.ring_rows = G.ring_rows(self.window, ecfg.page_size)
        if (self.ring_rows > ecfg.max_pages_per_seq
                or self.ring_rows > ecfg.num_pages - 1):
            # statically infeasible geometry: raise here rather than
            # fail-fast every request as "truncated" at admission
            raise ValueError(
                f"{cfg.name}: window {self.window} needs a ring of "
                f"{self.ring_rows} pages (page_size {ecfg.page_size}), "
                f"but max_pages_per_seq={ecfg.max_pages_per_seq} and "
                f"the pool holds {ecfg.num_pages - 1} usable pages")
        _, n_attn = G._state_counts(cfg)
        self.page_bytes = (2 * n_attn * ecfg.page_size * cfg.num_kv_heads
                           * cfg.head_dim * 2)
        self.state = G.init_paged_decode_state(cfg, ecfg.num_slots,
                                               ecfg.num_pages,
                                               ecfg.page_size)
        # constant per-slot recurrence bytes, reported next to the paged
        # window so kv_bytes_peak compares symmetrically with the static
        # path's state (which holds the same conv/LRU arrays)
        self.slot_state_bytes = _state_bytes(
            (self.state.conv, self.state.h))

        def prefill_write(params, state, batch, length, page_ids, slot):
            last, kv, conv, h = G.paged_prefill(cfg, params, batch, length)
            state = G.write_prefill_state(
                cfg, state, (kv[0][:, 0], kv[1][:, 0]), conv, h, page_ids,
                slot)
            return last[0], state

        def decode(params, state, tokens, page_table, lengths, active):
            return G.paged_decode_step(cfg, params, state, tokens,
                                       page_table, lengths, active)

        def decode_multi(params, state, pending, lengths, remaining,
                         page_table, mask, h, teacher):
            return G.paged_decode_multi(cfg, params, state, pending,
                                        lengths, remaining, page_table,
                                        mask, h, hmax=ecfg.horizon,
                                        teacher=teacher)

        # slot is a traced scalar (``.at[:, slot]`` takes traced indices),
        # so the compile cache is keyed on the prompt bucket alone — one
        # trace per bucket, not per (bucket, slot) pair
        self._prefill = jax.jit(prefill_write, donate_argnums=(1,))
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self._decode_multi = jax.jit(decode_multi,
                                     donate_argnums=(1, 2, 3, 4))

    def can_ever_fit(self, pgr, prompt_len: int, max_new_tokens: int,
                     ctx_len: int) -> bool:
        """Window-bounded: feasibility is the ring fitting the table row
        and the pool — prompt/generation length never disqualifies."""
        return (self.ring_rows <= pgr.max_pages_per_seq
                and self.ring_rows <= pgr.num_pages - 1)

    def admission_rows(self, pgr, ctx_len: int) -> list[int]:
        """Ring rows of the pages covering the live window — page n lands
        in row n % R; pages before the window are never allocated."""
        p, R = pgr.page_size, self.ring_rows
        n_lo = max(0, ctx_len - self.window) // p
        n_hi = (ctx_len - 1) // p
        return [n % R for n in range(n_lo, n_hi + 1)]

    def prefill(self, ctx: np.ndarray, extras, slot: int,
                page_ids: list[int]) -> np.ndarray:
        # scatter pids are indexed by prompt page number: in-window pages
        # get the allocated ring pages, everything else (pre-window +
        # pad) goes to the trash page
        n_lo = max(0, len(ctx) - self.window) // self.ecfg.page_size
        toks, pids = _bucket_prompt(ctx, self.ecfg, page_ids,
                                    first_page=n_lo)
        logits, self.state = self._prefill(
            self.params, self.state, {"tokens": jnp.asarray(toks)},
            jnp.asarray(len(ctx), jnp.int32), jnp.asarray(pids),
            jnp.asarray(slot, jnp.int32))
        return np.asarray(logits)


@lru_cache(maxsize=8)
def _latent_programs(cfg, horizon: int):
    """``LatentBackend``'s jitted prefill, decode and fused decode for one
    config. A full-width prefill takes seconds to compile for a v5e and
    there is one per bucket, so engines of a config in a process (of the
    last eight configs served) share them: a second engine (a reload, the
    next run) compiles nothing."""
    from ..models import moe as MoE

    # The state leads each program's arguments: an output takes the
    # sharding of the first argument whose sharding it matches, and the
    # 2-D pools would otherwise take a 2-D weight's spelling of it, which
    # keys a second compile of every program they reenter.
    def prefill_write(state, params, batch, lengths, page_ids):
        last, latents = MoE.paged_prefill(cfg, params, batch, lengths)
        state = MoE.write_prefill_pages(cfg, state, latents[:, 0], page_ids)
        return last[0], state

    def decode(state, params, tokens, page_table, lengths, active):
        state = dataclasses.replace(state, claims=jnp.zeros((), jnp.int32))
        return MoE.paged_decode_step(cfg, params, state, tokens,
                                     page_table, lengths, active)

    def decode_multi(state, pending, lengths, remaining, params,
                     page_table, mask, h, teacher):
        return MoE.paged_decode_multi(cfg, params, state, pending, lengths,
                                      remaining, page_table, mask, h,
                                      hmax=horizon, teacher=teacher)

    return (jax.jit(prefill_write, donate_argnums=(0,)),
            jax.jit(decode, donate_argnums=(0,)),
            jax.jit(decode_multi, donate_argnums=(0, 1, 2, 3)))


class LatentBackend(_LinearPagedMixin):
    """MoE + MLA (deepseek): pages hold compressed latent rows.

    The cache entry per token is the absorbed-MLA latent (kv_lora_rank +
    rope head), not per-head K/V — the paper's pack-the-stationary-
    operand-small idea applied to the page pool, so page_bytes is
    latent-width-sized (``PagerConfig.page_bytes``). Table growth is
    linear like the dense backend. Routing is dropless at prefill and
    decode, so one prefill program serves each bucket and a re-prefill
    after preemption reproduces the first. Engines of one config share
    their programs (``_latent_programs``). ``expert_rows_per_step`` is
    the expert rows one decode step computes; the engine reads the live
    claims on held experts back with each dispatch's tokens."""

    @classmethod
    def supports(cls, cfg) -> bool:
        return cfg.mla is not None      # GQA-MoE (olmoe) stays static

    def __init__(self, cfg, params, ecfg: EngineConfig):
        from ..models import moe as MoE

        assert cfg.mla is not None, \
            "LatentBackend pages the MLA latent cache"
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.page_bytes = ecfg.pager.page_bytes(cfg)
        self.expert_rows_per_step = MoE.expert_rows_per_step(
            cfg, ecfg.num_slots)
        self.state = MoE.init_paged_decode_state(cfg, ecfg.num_pages,
                                                 ecfg.page_size)
        self._prefill, self._decode, self._decode_multi = \
            _latent_programs(cfg, ecfg.horizon)

    def prefill(self, ctx: np.ndarray, extras, slot: int,
                page_ids: list[int]) -> np.ndarray:
        toks, pids = _bucket_prompt(ctx, self.ecfg, page_ids)
        logits, self.state = self._prefill(
            self.state, self.params, {"tokens": jnp.asarray(toks)},
            jnp.asarray([len(ctx)], jnp.int32), jnp.asarray(pids))
        return np.asarray(logits)

    def decode(self, tokens, page_table, lengths, active) -> jax.Array:
        logits, self.state = self._decode(
            self.state, self.params, jnp.asarray(tokens),
            jnp.asarray(page_table), jnp.asarray(lengths),
            jnp.asarray(active))
        return logits

    def decode_fused(self, pending, lengths, remaining, page_table, mask,
                     h: int, teacher=None):
        out, self.state, pending, lengths, remaining = self._decode_multi(
            self.state, pending, lengths, remaining, self.params,
            page_table, jnp.asarray(mask), jnp.asarray(h, jnp.int32),
            None if teacher is None else jnp.asarray(teacher, jnp.int32))
        return out, pending, lengths, remaining


ENGINE_FAMILIES = {"dense": PagedTransformerBackend,
                   "vlm": PagedTransformerBackend,
                   "ssm": RecurrentBackend,
                   "hybrid": HybridBackend,
                   "moe": LatentBackend}


def engine_backend(cfg):
    """Backend class able to serve ``cfg``, or None (static fallback)."""
    cls = ENGINE_FAMILIES.get(cfg.family)
    if cls is None or not cls.supports(cfg):
        return None
    return cls


def resolve_backend(cfg):
    """engine_backend or raise — the single source of the supported-family
    list, derived from the registry so it stays truthful as backends
    register."""
    cls = engine_backend(cfg)
    if cls is None:
        detail = ""
        if cfg.family in ENGINE_FAMILIES:
            detail = (f" ({ENGINE_FAMILIES[cfg.family].__name__} does not"
                      f" support this config)")
        raise ValueError(
            f"{cfg.name!r} (family {cfg.family!r}) has no engine backend"
            f"{detail}; families with backends: "
            f"{sorted(ENGINE_FAMILIES)}")
    return cls


# --- prefix sharing -------------------------------------------------------------


class _PrefixSharing:
    """Per-tenant prefix-sharing driver: the radix index plus the
    admission plan (which leading pages to map by reference instead of
    recomputing). One instance per eligible paged tenant — page ids are
    tenant-local, and token-id equality only implies KV equality within
    one model."""

    def __init__(self, pgr: PagerConfig):
        self.pgr = pgr
        self.index = PrefixIndex(pgr.page_size)

    def plan(self, req: Request, ctx) -> tuple[list[int], int]:
        """-> (pages, tokens): the leading ``tokens`` of ``ctx`` are
        already resident in ``pages`` and need no prefill.

        A FRESH request always recomputes the page holding its last
        prompt token — the prefill must produce that token's logits to
        sample from — so coverage caps at the last page boundary strictly
        below len(ctx) (and the suffix stays page-aligned). A
        RE-ADMITTED request needs no logits (its next decode input is
        generated[-1]), so full coverage is admissible, including a
        partial-tail match against a longer cached continuation; a
        later decode write into that shared tail page copies-on-write
        first."""
        P = self.pgr.page_size
        tokens = [int(t) for t in ctx]
        pages, covered = self.index.match(
            tokens, allow_tail=bool(req.generated))
        if not req.generated:
            n = min(len(pages), (len(tokens) - 1) // P)
            pages, covered = pages[:n], n * P
        return pages, covered

    def record(self, alloc, ctx, lengths: int, row) -> int:
        """Index the full pages of a request's written context (its
        page-table row) so later prompts can map them. Called after
        prefill and again at preempt/finish — pages completed during
        decode become matchable, and the index's NEUTRAL_OWNER refs
        keep them warm after the request's own refs drop."""
        n_full = int(lengths) // self.pgr.page_size
        if n_full <= 0:
            return 0
        toks = [int(t) for t in ctx[:n_full * self.pgr.page_size]]
        return self.index.insert(alloc, toks,
                                 [int(p) for p in row[:n_full]])


# --- engine --------------------------------------------------------------------


class Engine:
    """Host-driven continuous-batching loop around a jitted decode step."""

    def __init__(self, cfg, params, ecfg: EngineConfig | None = None):
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        self.backend = resolve_backend(cfg)(cfg, params, self.ecfg)
        self._home = _home_sharding(params)
        if self._home is not None:
            self.backend.state = jax.device_put(self.backend.state,
                                                self._home)
        self.rng = np.random.default_rng(self.ecfg.seed)
        self._sample_batch = make_batch_sampler(
            self.rng, self.ecfg.greedy, self.ecfg.temperature)
        self._sample = make_sampler(self.rng, self.ecfg.greedy,
                                    self.ecfg.temperature)
        # greedy sampling is pure argmax, so it can run on device inside
        # the fused horizon; the host RNG's temperature draw cannot
        self._fused = self.ecfg.greedy and self.ecfg.horizon > 1

    # -- main loop ---------------------------------------------------------

    def run(self, requests: list[Request]) -> EngineReport:
        e, pgr = self.ecfg, self.ecfg.pager
        B, M, page = e.num_slots, pgr.max_pages_per_seq, pgr.page_size
        paged = self.backend.paged
        sched = Scheduler(requests)
        # single-tenant arena: one lease spanning the whole page budget
        # (the same allocator path the pooled engine leases per tenant)
        arena = DeviceArena(ArenaConfig(kv_pages=e.num_pages),
                            {"default": 1.0}) if paged else None
        alloc = arena.allocator("default") if paged else None
        if paged:
            arena.register_page_bytes("default", self.backend.page_bytes)
        sharer = _PrefixSharing(pgr) if (
            paged and e.prefix_sharing
            and getattr(self.backend, "prefix_sharing", False)) else None

        slots: list[Request | None] = [None] * B
        page_table = np.zeros((B, M), np.int32)
        lengths = np.zeros((B,), np.int32)
        pending = np.zeros((B,), np.int32)      # next decode input token
        remaining = np.zeros((B,), np.int32)    # token budget left
        # device twins of the four loop arrays + the traffic ledger the
        # per-step fallback shares (so both paths report comparably)
        ds = DeviceLoopState(B, M, self._home)

        page_bytes = self.backend.page_bytes
        expert_rows = self.backend.expert_rows_per_step

        def count_latent(h: int, claims) -> None:
            """The latent path's counters for a dispatch of ``h`` steps,
            taken before the lengths advance: live claims on held experts
            against the expert rows computed, and the latent rows live
            (each slot attends over its context and the new token) against
            the rows the whole page table's gather reads, per layer."""
            tracing.ROUTES.add(int(claims), h * expert_rows)
            live = (int(lengths[active].sum()) * h
                    + len(active) * h * (h + 1) // 2)
            tracing.LATENT_READS.add(live, B * M * page * h)

        rep = EngineReport(
            name=f"engine/{self.cfg.name}", num_slots=B,
            page_bytes=page_bytes,
            slot_state_bytes=self.backend.slot_state_bytes,
            cache_bytes_alloc=_state_bytes(self.backend.state))
        t_run = time.monotonic()
        step = 0

        def clear_slot(s: int) -> None:
            req = slots[s]
            if sharer is not None:
                # index the pages this request completed (incl. during
                # decode) BEFORE dropping its refs: the neutral refs
                # keep the prefix warm for later prompts / re-admission
                sharer.record(alloc, req.context_tokens, lengths[s],
                              page_table[s])
            slots[s] = None
            page_table[s, :] = TRASH_PAGE
            lengths[s] = 0
            pending[s] = 0
            remaining[s] = 0
            ds.touch(s)
            if paged:
                alloc.free_owner(req.rid)
            self.backend.release_slot(s)

        def finish(s: int) -> None:
            slots[s].done_step = step
            rep.completed.append(slots[s])
            clear_slot(s)

        def preempt(s: int) -> None:
            req = slots[s]
            clear_slot(s)
            sched.requeue(req)

        while True:
            with StepTraceAnnotation("engine.step", step_num=step):
                sched.release_arrivals(step)

                # -- admission into free slots -------------------------------
                admitting = True
                for s in range(B):
                    # retry the same slot until it is filled (rejected or
                    # finished-at-prefill requests must not waste the slot)
                    while admitting and slots[s] is None:
                        req = sched.peek_ready()
                        if req is None:
                            admitting = False
                            break
                        with TraceAnnotation("engine.admit", rid=req.rid):
                            ctx = req.context_tokens
                            assert len(ctx) >= 1, \
                                "empty prompts are not admissible"
                            if paged:
                                rows = self.backend.admission_rows(
                                    pgr, len(ctx))
                                if not self.backend.can_ever_fit(
                                        pgr, len(req.prompt),
                                        req.max_new_tokens, len(ctx)):
                                    # can never fit: fail fast
                                    sched.pop_ready()
                                    req.truncated = True
                                    req.done_step = step
                                    rep.completed.append(req)
                                    continue
                                sh_pages, sh_tokens = (
                                    sharer.plan(req, ctx)
                                    if sharer is not None else ([], 0))
                                need = len(rows) - len(sh_pages)
                                if not alloc.can_alloc(need) \
                                        and sharer is not None:
                                    # index-only pages are cache: reclaim them
                                    # before making the request wait
                                    sharer.index.evict_lru(
                                        alloc, need - alloc.free_count,
                                        protect=set(sh_pages))
                                if not alloc.can_alloc(need):
                                    # FCFS: wait for free pages
                                    admitting = False
                                    break
                                sched.pop_ready()
                                if sh_pages:
                                    alloc.share(req.rid, sh_pages)
                                    req.shared_pages += len(sh_pages)
                                    rep.shared_page_hits += len(sh_pages)
                                pages = alloc.alloc(req.rid, need)
                                page_table[s, :] = TRASH_PAGE
                                page_table[s, rows] = sh_pages + pages
                                with TraceAnnotation("engine.prefill"):
                                    if sh_tokens >= len(ctx):
                                        # fully cached re-admission
                                        logits = None
                                    elif sh_tokens:
                                        logits = self.backend.prefill_shared(
                                            ctx, req.extras, s, pages,
                                            sh_pages, sh_tokens)
                                    else:
                                        logits = self.backend.prefill(
                                            ctx, req.extras, s, pages)
                                bucket = e.prefill_bucket
                                full = -(-len(ctx) // bucket) * bucket
                                computed = 0 if sh_tokens >= len(ctx) else (
                                    -(-(len(ctx) - sh_tokens) // bucket)
                                    * bucket)
                                rep.prefill_tokens += computed
                                rep.prefill_tokens_saved += full - computed
                                if computed:
                                    rep.prefill_calls += 1
                                    req.prefills += 1
                                    tracing.PREFILLS.add(
                                        len(ctx) - sh_tokens, computed)
                                if sharer is not None:
                                    sharer.record(alloc, ctx, len(ctx),
                                                  page_table[s])
                            else:
                                sched.pop_ready()
                                with TraceAnnotation("engine.prefill"):
                                    logits = self.backend.prefill(
                                        ctx, req.extras, s, None)
                                rep.prefill_calls += 1
                                rep.prefill_tokens += len(ctx)
                                req.prefills += 1
                                tracing.PREFILLS.add(len(ctx), len(ctx))
                            req.admitted_step = step
                            slots[s] = req
                            lengths[s] = len(ctx)
                            # re-admission after preemption
                            if req.generated:
                                pending[s] = req.generated[-1]
                                remaining[s] = (req.max_new_tokens
                                                - len(req.generated))
                                ds.touch(s)
                            else:
                                assert logits is not None
                                tok = self._sample(logits)
                                req.generated.append(tok)
                                pending[s] = tok
                                remaining[s] = req.max_new_tokens - 1
                                ds.touch(s)
                                if req.done:
                                    finish(s)   # slot freed: while re-admits

                active = [s for s in range(B) if slots[s] is not None]

                # -- page growth / CoW / preemption --------------------------
                if paged and active:
                    with TraceAnnotation("engine.grow"):
                        R = self.backend.ring_rows

                        def claim_one(s: int) -> bool:
                            """Free one page for slot ``s``: index cache
                            first, then victim preemption (whose pages may
                            land in the index — evictable next iteration, so
                            the loop still strictly shrinks live state).
                            False if ``s`` itself was preempted."""
                            while not alloc.can_alloc(1):
                                if sharer is not None \
                                        and sharer.index.evict_lru(alloc, 1):
                                    continue
                                victim = Scheduler.pick_victim(
                                    [(v, slots[v]) for v in active
                                     if slots[v] is not None], exclude=s)
                                if victim is None or victim[0] == s:
                                    preempt(s)
                                    active.remove(s)
                                    return False
                                preempt(victim[0])
                                active.remove(victim[0])
                            return True

                        for s in list(active):
                            if slots[s] is None:
                                continue
                            if lengths[s] % page != 0:
                                # mid-page: the next decode appends into the
                                # current tail page — if that page is still
                                # shared (re-admission mapped a cached tail),
                                # copy-on-write exactly that page first
                                if sharer is None:
                                    continue
                                row_i = lengths[s] // page
                                old = int(page_table[s, row_i])
                                if alloc.refcount(old) < 2:
                                    continue
                                if not claim_one(s):
                                    continue
                                new = alloc.alloc(slots[s].rid, 1)
                                self.backend.copy_page(old, new[0])
                                alloc.free_page(slots[s].rid, old)
                                page_table[s, row_i] = new[0]
                                ds.touch(s)
                                slots[s].cow_copies += 1
                                rep.cow_copies += 1
                                continue
                            pi = lengths[s] // page
                            if R is None and pi >= M:   # table row full: stop
                                slots[s].truncated = True
                                finish(s)
                                active.remove(s)
                                continue
                            row = _growth_row(self.backend, alloc,
                                              page_table, s, pi, slots[s].rid)
                            if not claim_one(s):
                                continue
                            new = alloc.alloc(slots[s].rid, 1)
                            page_table[s, row] = new[0]
                            ds.touch(s)

                # -- decode: one fused horizon, or one per-step dispatch -----
                if active:
                    act = np.zeros((B,), bool)
                    act[active] = True
                    if self._fused:
                        # safe horizon: no schedulable event may land inside
                        # it, so running h steps device-side is step-for-step
                        # identical to h per-step iterations of this loop;
                        # ``cause`` names the cut that bound h (a later cut
                        # takes it over only by cutting shorter)
                        h, cause = e.horizon, "cap"
                        nxt = sched.next_arrival()
                        if nxt is not None and nxt - step < h:
                            h, cause = nxt - step, "arrival"
                        if sched.peek_ready() is not None and \
                                any(slots[s] is None for s in range(B)):
                            # a free slot retries admission per step
                            h, cause = 1, "admit"
                        fin = min(int(remaining[s]) for s in active)
                        if fin < h:
                            h, cause = fin, "finish"
                        if paged:                      # growth/ring wrap
                            edge = min(pgr.steps_to_boundary(int(lengths[s]))
                                       for s in active)
                            if edge < h:
                                h, cause = edge, "page"
                        h = max(1, h)
                        with TraceAnnotation("engine.sync"):
                            ds.sync(page_table, lengths, pending, remaining)
                        tracing.DISPATCHES.add(h, len(active), cause)
                        with TraceAnnotation("engine.decode"):
                            out, p_d, l_d, r_d = self.backend.decode_fused(
                                ds.pending, ds.lengths, ds.remaining,
                                ds.table, act, h)
                        with TraceAnnotation("engine.wait"):
                            if expert_rows:   # claims ride with the tokens
                                toks_h, claims = jax.device_get(
                                    (out, self.backend.state.claims))
                            else:
                                toks_h = np.asarray(out)   # ONE host sync
                        if expert_rows:
                            count_latent(h, claims)
                        ds.adopt(p_d, l_d, r_d)
                        ds.count(dispatches=1, syncs=1)
                        rep.decode_steps += h
                        rep.slot_steps += B * h
                        rep.useful_slot_steps += len(active) * h
                        step += h - 1   # bookkeeping lands at horizon end
                        lengths[active] += h
                        remaining[active] -= h
                        with TraceAnnotation("engine.deliver"):
                            for s in active:
                                req = slots[s]
                                req.generated.extend(
                                    int(t) for t in toks_h[:h, s])
                                pending[s] = int(toks_h[h - 1, s])
                                if req.done:
                                    finish(s)
                    else:
                        tracing.DISPATCHES.add(1, len(active), "unfused")
                        with TraceAnnotation("engine.decode"):
                            logits = self.backend.decode(
                                pending, page_table, lengths, act)
                        with TraceAnnotation("engine.wait"):
                            if expert_rows:
                                logits, claims = jax.device_get(
                                    (logits, self.backend.state.claims))
                            else:
                                logits = np.asarray(logits)
                        if expert_rows:
                            count_latent(1, claims)
                        ds.count(dispatches=1, syncs=1,
                                 upload_bytes=page_table.nbytes)
                        rep.decode_steps += 1
                        rep.slot_steps += B    # the batch always runs full
                        rep.useful_slot_steps += len(active)
                        lengths[active] += 1
                        remaining[active] -= 1
                        with TraceAnnotation("engine.deliver"):
                            toks = self._sample_batch(logits[active])
                            for i, s in enumerate(active):
                                req = slots[s]
                                tok = int(toks[i])
                                req.generated.append(tok)
                                pending[s] = tok
                                if req.done:
                                    finish(s)
                    if paged:
                        rep.peak_live_pages = max(rep.peak_live_pages,
                                                  alloc.live_count)
                        rep.peak_demand_pages = max(rep.peak_demand_pages,
                                                    alloc.demand_count)
                elif not sched.exhausted:
                    nxt = sched.next_arrival()
                    if nxt is not None and nxt > step:
                        step = nxt      # idle: fast-forward to next arrival
                        continue
                else:
                    break

                step += 1
                if step > e.max_steps:
                    raise RuntimeError("engine exceeded max_steps")

        if paged:
            if sharer is not None:      # drop the index's neutral refs
                sharer.index.release_all(alloc)
            arena.check()
            assert alloc.live_count == 0, "pages leaked past completion"
        rep.preemptions = sched.preemptions
        rep.device_dispatches = ds.device_dispatches
        rep.host_syncs = ds.host_syncs
        rep.page_table_upload_bytes = ds.page_table_upload_bytes
        rep.wall_s = time.monotonic() - t_run
        return rep


def _home_sharding(params):
    """Where the jitted steps return the state they do not shard:
    replicated on the mesh that mesh-placed ``params`` live on, else None
    (uncommitted). Engine-made arrays start there, so a step's first
    dispatch has the signature of every later one and compiles once."""
    s = getattr(jax.tree.leaves(params)[0], "sharding", None)
    if isinstance(s, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(s.mesh,
                                          jax.sharding.PartitionSpec())
    return None


def _state_bytes(state) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))


def _growth_row(backend, alloc, page_table, s: int, pi: int, rid: int
                ) -> int:
    """Table row for a slot's next page. Linear backends grow into row
    ``pi``; ring backends wrap into ``pi % ring_rows`` — and the page
    already in that row is freed FIRST, which is safe exactly because
    the ring holds ceil(window/page)+1 rows: the wrapped-out page's
    positions are all <= pos - window, outside the attention window.
    Both engines' growth loops share this so the invariant lives in one
    place."""
    R = backend.ring_rows
    if R is None:
        return pi
    row = pi % R
    old = int(page_table[s, row])
    if old != TRASH_PAGE:
        alloc.free_page(rid, old)
        page_table[s, row] = TRASH_PAGE
    return row


# --- multi-tenant pooled engine ------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PoolEngineConfig(EngineConfig):
    """EngineConfig plus the multi-tenant activation policy.

    ``reload_aware`` (the paper-derived control loop): all hot models
    share the slot batch, cold models activate only when the slab has
    room or a hysteresis-expired idle victim exists, eviction is least
    value-per-byte first. ``round_robin`` is the naive baseline: one
    swappable model hot at a time, served in fixed cyclic quanta, with
    every switch evicting the previous occupant (and preempting its
    in-flight slots) regardless of reload cost.

    ``stream`` picks the reload granularity for reload_aware activations:
    ``model`` charges the whole reload as serial stall steps up front
    (the PR-2 behaviour); ``layer`` streams the per-layer schedule behind
    compute (ModelPool.begin_stream — the paper's folded-tile pipelining
    at serving scale), charging a stall step only when the engine has no
    decode work to hide the DMA behind. round_robin is model-granular by
    definition (every switch drops the previous occupant whole).

    ``repartition`` controls the device-memory arena's KV page leases:
    ``off`` freezes the init-time demand-proportional partition (the PR-3
    behaviour); ``epoch`` samples per-tenant live-page watermarks every
    step and, every ``epoch_steps``, shrinks under-watermark tenants and
    grows page-starved ones (free pages only — see runtime.arena).

    ``max_bypass_steps`` is the global aging bound on the admission scan:
    a page-starved tenant's head request may be bypassed by neighbouring
    tenants' later arrivals for at most this many steps, after which the
    scan BLOCKS for it (no later-arrival admissions) until its pages free
    up. 0 disables the bound (unbounded bypass, the PR-3 behaviour).
    """
    policy: str = "reload_aware"       # | "round_robin"
    rr_quantum: int = 16               # steps per round-robin turn
    stream: str = "model"              # | "layer"
    repartition: str = "off"           # | "epoch"
    epoch_steps: int = 64
    max_bypass_steps: int = 64         # 0 -> unbounded bypass

    def __post_init__(self):
        super().__post_init__()
        assert self.policy in ("reload_aware", "round_robin")
        assert self.rr_quantum >= 1
        assert self.stream in ("model", "layer")
        assert self.repartition in ("off", "epoch")
        assert self.epoch_steps >= 1
        assert self.max_bypass_steps >= 0


@dataclasses.dataclass
class PooledReport(EngineReport):
    """EngineReport plus weight-reload accounting. Reload stalls are
    serial with compute (§2.2), so they join the throughput denominator
    alongside prefill-equivalent steps: tokens/step counts stalled steps
    as steps that produced nothing."""
    policy: str = ""
    stream: str = ""
    stall_steps: int = 0
    reload_bytes: int = 0
    restream_bytes: int = 0            # bounded-slab re-fetch share
    reload_events: int = 0
    evictions: int = 0
    deferred_activations: int = 0
    repartitions: int = 0              # arena epochs executed
    pages_moved: int = 0               # leases moved between tenants
    aging_blocks: int = 0              # admission scans blocked by aging
    peak_live_page_bytes: int = 0      # tenants' page sizes differ
    peak_demand_page_bytes: int = 0    # live minus index-only, in bytes
    model_tokens: dict = dataclasses.field(default_factory=dict)
    stall_steps_by_model: dict = dataclasses.field(default_factory=dict)

    @property
    def kv_bytes_peak(self) -> int:
        """Peak live cache bytes summed per tenant at its OWN page size
        (an MLA latent page is far smaller than a dense KV page, so
        pages * max(page_bytes) would materially overstate the peak)."""
        if self.page_bytes:
            return self.peak_live_page_bytes + self.slot_state_bytes
        return self.cache_bytes_alloc

    @property
    def kv_demand_bytes_peak(self) -> int:
        """Peak referenced-by-some-request cache bytes per tenant page
        size (shared pages once, index-only cache excluded)."""
        if self.page_bytes:
            return self.peak_demand_page_bytes + self.slot_state_bytes
        return self.cache_bytes_alloc

    @property
    def decode_tokens_per_step(self) -> float:
        return self.new_tokens / max(self.decode_steps + self.stall_steps, 1)

    @property
    def tokens_per_step(self) -> float:
        return self.new_tokens / max(
            self.decode_steps + self.stall_steps + self.prefill_equiv_steps,
            1.0)

    def summary(self) -> dict:
        s = super().summary()
        s.update({
            "policy": self.policy,
            "stream": self.stream,
            "stall_steps": self.stall_steps,
            "stall_steps_by_model": dict(
                sorted(self.stall_steps_by_model.items())),
            "reload_bytes": self.reload_bytes,
            "restream_bytes": self.restream_bytes,
            "reload_events": self.reload_events,
            "evictions": self.evictions,
            "deferred_activations": self.deferred_activations,
            "repartitions": self.repartitions,
            "pages_moved": self.pages_moved,
            "aging_blocks": self.aging_blocks,
            "model_tokens": dict(sorted(self.model_tokens.items())),
        })
        return s


class PooledEngine:
    """Continuous batching for a model zoo sharing one accelerator pool.

    Per-model backends (one jitted prefill/decode pair each) split one
    modeled page budget: the page-id space is PARTITIONED into per-tenant
    proportional sub-ranges (partition_pages), each backed by its own
    device pool and host-side PageAllocator, so the physical backing
    matches the modeled shared budget instead of every tenant allocating
    the full pool. Page pressure is tenant-local (a burst on one tenant
    preempts its own requests, not its neighbours'), while one slot array
    spans all tenants, so batch width stays a shared resource.

    One engine step advances EVERY hot tenant's slots (stationary
    weights of all hot models sit in HBM at once — the packed-canvas
    premise at pool scale — so their decodes share the step the way
    packed layers share the fabric); the step still spans at most
    ``num_slots`` tokens, so tokens/step is bounded by the slot width
    for every policy. Weight reloads are serial with compute, charged
    as stall steps that produce nothing. The naive round-robin baseline
    keeps a single swappable tenant hot at a time, so it cannot use the
    shared step — that utilization gap, plus its per-switch reloads, is
    exactly what the reload-aware policy is measured against.
    """

    def __init__(self, pool: ModelPool, params: dict,
                 ecfg: PoolEngineConfig | None = None):
        if pool.plan is None:
            pool.pack()
        self.pool = pool
        self.ecfg = ecfg or PoolEngineConfig()
        assert pool.pcfg.slab_mode != "bounded" \
            or self.ecfg.stream == "layer", \
            "bounded slab mode re-streams through the layer-granular " \
            "DMA FIFO; run it with stream='layer'"
        paged_shares = {
            e.model_id: e.demand for e in pool.plan.entries
            if getattr(engine_backend(e.cfg), "paged", False)}
        # the arena owns the whole modeled budget: the KV page region
        # (per-tenant leases over one shared page budget) plus the weight
        # region (pin + slab) whose occupancy the pool reports back
        self.arena = DeviceArena(
            ArenaConfig(kv_pages=self.ecfg.num_pages,
                        pin_bytes=pool.pcfg.pin_budget_bytes,
                        slab_bytes=pool.pcfg.slab_bytes,
                        repartition=self.ecfg.repartition,
                        epoch_steps=self.ecfg.epoch_steps),
            paged_shares)
        self.page_split = self.arena.page_split
        self.backends = {}
        self._pgr = {}                 # per-tenant pager geometry
        for e in pool.plan.entries:
            backend_cls = resolve_backend(e.cfg)
            ecfg_t = self.ecfg
            if e.model_id in self.page_split:
                # tenant's device pool backs its provisioned rows (+ its
                # own trash page): with repartition off that is exactly
                # its lease, so physical bytes track the partition; in
                # epoch mode rows are provisioned up to the grow cap
                # while the MODELED leases stay conserved by the arena.
                # Admission FEASIBILITY however is judged against the
                # guaranteed INITIAL lease, not the cap — a grown lease
                # is opportunistic and can shrink back, so a request
                # must be completable under the static share alone.
                ecfg_t = dataclasses.replace(
                    self.ecfg,
                    num_pages=self.arena.cap(e.model_id) + 1)
                self._pgr[e.model_id] = dataclasses.replace(
                    self.ecfg,
                    num_pages=self.page_split[e.model_id] + 1).pager
            else:
                self._pgr[e.model_id] = ecfg_t.pager
            self.backends[e.model_id] = backend_cls(
                e.cfg, params[e.model_id], ecfg_t)
            if e.model_id in self.page_split:
                self.arena.register_page_bytes(
                    e.model_id, self.backends[e.model_id].page_bytes)
        if self.ecfg.repartition == "off":
            assert sum(n + 1 for n in self.page_split.values()) \
                <= self.ecfg.num_pages, \
                "physical pages exceed the pool budget"
        self.rng = np.random.default_rng(self.ecfg.seed)
        self._sample_batch = make_batch_sampler(
            self.rng, self.ecfg.greedy, self.ecfg.temperature)
        self._sample = make_sampler(self.rng, self.ecfg.greedy,
                                    self.ecfg.temperature)
        self._fused = self.ecfg.greedy and self.ecfg.horizon > 1

    # -- main loop ---------------------------------------------------------
    # The loop is split into start / step_once / finish_run so a caller
    # can interleave OTHER work between engine steps: the fleet tier
    # drives N replicas in lockstep ticks, injecting requests and faults
    # mid-run. ``run`` composes the three for the single-pool case.

    def start(self, requests: list[Request]) -> "PooledEngine":
        e, pool = self.ecfg, self.pool
        self._sched = MultiQueueScheduler(requests)
        # the arena hands each paged tenant its leased allocator (a fresh
        # run starts from the initial demand-proportional partition)
        self.arena.reset_runtime()
        self._allocs = {m: self.arena.allocator(m) for m in self.page_split}
        # one prefix index per eligible tenant: page ids are tenant-local
        # and token-id equality only implies KV equality within a model
        self._sharers = {
            m: _PrefixSharing(self._pgr[m]) for m in self.page_split
            if e.prefix_sharing
            and getattr(self.backends[m], "prefix_sharing", False)}
        pool.reset_runtime()

        B = e.num_slots
        self._order = list(pool.model_ids)
        self._slots: list[Request | None] = [None] * B
        self._page_table = np.zeros((B, e.pager.max_pages_per_seq),
                                    np.int32)
        self._lengths = np.zeros((B,), np.int32)
        self._pending = np.zeros((B,), np.int32)
        self._remaining = np.zeros((B,), np.int32)
        # one device twin spans every tenant: the fused dispatches chain
        # through it (model A's donated outputs feed model B's inputs)
        self._ds = DeviceLoopState(B, e.pager.max_pages_per_seq)
        self._rep = PooledReport(
            name=f"pool/{e.policy}", num_slots=B, policy=e.policy,
            stream=e.stream,
            page_bytes=max(
                (self.backends[m].page_bytes for m in self.page_split),
                default=0),
            slot_state_bytes=sum(b.slot_state_bytes
                                 for b in self.backends.values()),
            cache_bytes_alloc=sum(_state_bytes(b.state)
                                  for b in self.backends.values()),
            model_tokens={m: 0 for m in self._order},
            stall_steps_by_model={m: 0 for m in self._order})
        self._t_run = time.monotonic()
        self.step = 0
        self._rr_current: str | None = None
        self._rr_left = 0
        self._blocked_since: dict[int, int] = {}  # rid -> first blocked step
        return self

    # -- steppable-loop accessors (the fleet router reads these) -----------

    @property
    def report(self) -> PooledReport:
        return self._rep

    def inject(self, requests: list[Request]) -> None:
        """Hand this replica more requests mid-run (fleet dispatch)."""
        self._sched.inject(requests)

    def occupied_slots(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    def backlog(self) -> int:
        """Requests queued but not in a slot (ready + future arrivals)."""
        sch = self._sched
        return (sum(sch.ready_count(m) for m in sch.ready_models())
                + len(sch._pending))

    def load(self) -> int:
        """Routing load signal: occupied slots + queued requests."""
        return self.occupied_slots() + self.backlog()

    def oldest_queued_age(self) -> int:
        """Steps the longest-waiting READY request has been queued.
        Load alone hides a stuck head (two replicas at equal load, one
        with a request aging behind a page-starved tenant), so the
        fleet router folds this in as a tiebreak."""
        arr = self._sched.oldest_ready_arrival()
        return max(0, self.step - arr) if arr is not None else 0

    def drain(self) -> list[Request]:
        """Failover: preempt every in-flight request and pull the whole
        queue out, returning ALL unfinished requests for re-admission on
        another replica (their generated tokens and MoE route traces ride
        along, so nothing restarts from scratch beyond the re-prefill)."""
        for s in range(len(self._slots)):
            if self._slots[s] is not None:
                self._preempt(s)
        return self._sched.drain()

    # -- slot lifecycle -----------------------------------------------------

    def _clear_slot(self, s: int) -> None:
        req = self._slots[s]
        sharer = self._sharers.get(req.model_id)
        if sharer is not None:
            # index the completed pages before the refs drop: the
            # neutral refs keep the prefix warm for later prompts
            sharer.record(self._allocs[req.model_id], req.context_tokens,
                          self._lengths[s], self._page_table[s])
        self._slots[s] = None
        self._page_table[s, :] = TRASH_PAGE
        self._lengths[s] = 0
        self._pending[s] = 0
        self._remaining[s] = 0
        self._ds.touch(s)
        if req.model_id in self._allocs:
            self._allocs[req.model_id].free_owner(req.rid)
        self.backends[req.model_id].release_slot(s)

    def _finish(self, s: int) -> None:
        self._slots[s].done_step = self.step
        self._rep.completed.append(self._slots[s])
        self._clear_slot(s)

    def _preempt(self, s: int) -> None:
        req = self._slots[s]
        self._clear_slot(s)
        self._sched.requeue(req)

    def _reject(self, req: Request) -> None:
        req.truncated = True
        req.done_step = self.step
        self._rep.completed.append(req)

    def _active_models(self) -> list[str]:
        got = {r.model_id for r in self._slots if r is not None}
        return [m for m in self._order if m in got]

    def _pick_admissible(self, serve: list[str]) -> Request | None:
        """Earliest ready head whose tenant can admit now. Page
        pressure is tenant-local (partitioned sub-ranges), so a
        page-starved tenant waits without blocking its neighbours —
        but only up to the aging bound: once a blocked head has been
        bypassed for ``max_bypass_steps``, the scan BLOCKS for it
        instead of admitting later arrivals past it. Heads that can
        never fit are failed fast along the way."""
        e, sched, step = self.ecfg, self._sched, self.step
        while True:
            for req in sched.ready_heads(serve):
                backend = self.backends[req.model_id]
                if not backend.paged:
                    return req
                pgr_t = self._pgr[req.model_id]
                ctx_len = len(req.context_tokens)
                if not backend.can_ever_fit(pgr_t, len(req.prompt),
                                            req.max_new_tokens,
                                            ctx_len):
                    self._blocked_since.pop(req.rid, None)
                    self._reject(sched.pop_ready(req))
                    break           # queues changed: rescan heads
                rows = backend.admission_rows(pgr_t, ctx_len)
                a = self._allocs[req.model_id]
                need = len(rows)
                sharer = self._sharers.get(req.model_id)
                if sharer is not None:
                    sh_pages, _ = sharer.plan(req, req.context_tokens)
                    need -= len(sh_pages)
                    if not a.can_alloc(need):
                        # reclaim index-only cache pages, protecting the
                        # ones the plan is about to map by reference
                        sharer.index.evict_lru(a, need - a.free_count,
                                               protect=set(sh_pages))
                if a.can_alloc(need):
                    self._blocked_since.pop(req.rid, None)
                    return req
                # page-blocked head: feed the arena's load signal and
                # age it — an over-aged head stops the scan so later
                # arrivals cannot bypass it indefinitely
                first = self._blocked_since.setdefault(req.rid, step)
                self.arena.note_starved(req.model_id, step, want=need)
                if e.max_bypass_steps \
                        and step - first >= e.max_bypass_steps:
                    self._rep.aging_blocks += 1
                    return None
            else:
                return None

    def step_once(self) -> bool:
        """Advance the pool one engine step. Returns False when nothing
        can progress — every queue is empty and no slot is occupied (the
        single-pool ``run`` stops there; the fleet keeps an idle replica
        alive because the router may inject work or faults later) — and
        True otherwise, including idle fast-forwards to a future
        arrival."""
        e, pool = self.ecfg, self.pool
        B, page = e.num_slots, e.pager.page_size
        M = e.pager.max_pages_per_seq
        sched, rep, allocs = self._sched, self._rep, self._allocs
        slots, page_table = self._slots, self._page_table
        lengths, pending = self._lengths, self._pending

        sched.release_arrivals(self.step)

        # -- drain queues no backend can ever serve ------------------
        for m in sched.ready_models():
            if m not in self.backends or not pool.servable(m):
                while (req := sched.peek_ready([m])) is not None:
                    self._reject(sched.pop_ready(req))

        # -- activation policy ---------------------------------------
        if e.policy == "round_robin":
            ready = sched.ready_models()
            rr = self._rr_current
            switch = (rr is None or self._rr_left <= 0
                      or (rr not in self._active_models()
                          and sched.ready_count(rr) == 0))
            if switch and ready:
                order = self._order
                start = ((order.index(rr) + 1) % len(order)
                         if rr is not None else 0)
                nxt = next((order[(start + i) % len(order)]
                            for i in range(len(order))
                            if order[(start + i) % len(order)] in ready),
                           None)
                if nxt is not None and nxt != rr:
                    # naive swap: drop everything, load the next model
                    for s in range(B):
                        if slots[s] is not None:
                            self._preempt(s)
                    for m in list(pool.hot_models()):
                        pool.evict(m)
                    stall, _ = pool.try_activate(nxt, self.step)
                    rep.stall_steps += stall
                    rep.stall_steps_by_model[nxt] += stall
                    self.step += stall
                    self._rr_current, self._rr_left = nxt, e.rr_quantum
                elif nxt is not None:
                    self._rr_left = e.rr_quantum
            serve = [self._rr_current] if self._rr_current is not None \
                else []
        else:
            cold = [m for m in sched.ready_models()
                    if not pool.is_hot(m)]
            if cold:
                # highest queued-demand per reload byte activates
                # first; if it must wait (hysteresis), a smaller cold
                # tenant that fits the free slab may still go
                cold.sort(key=lambda m: (
                    -sched.pending_demand(m)
                    / max(pool.plan.entry(m).reload_bytes, 1), m))
                protected = frozenset(
                    m for m in pool.hot_models()
                    if m in self._active_models()
                    or sched.ready_count(m) > 0)
                for m in cold:
                    if e.stream == "layer":
                        # layer-granular: reserve the slab, then let
                        # the per-layer schedule stream behind compute
                        # (stalls only surface as prefetch misses,
                        # charged after the decode section)
                        if pool.begin_stream(m, self.step, protected) \
                                is not None:
                            break   # the DMA issues one stream at once
                    else:
                        res = pool.try_activate(m, self.step, protected)
                        if res is not None:
                            stall, _ = res
                            rep.stall_steps += stall
                            rep.stall_steps_by_model[m] += stall
                            self.step += stall
                            break   # one reload/step: stalls serialize
            if e.stream == "layer":
                # a mid-stream model joins once it heads the serial
                # DMA queue and the un-streamed tail fits inside its
                # first decode step's own layer walk
                serve = [m for m in pool.hot_models()
                         if pool.decode_ready(m)]
            else:
                serve = pool.hot_models()

        # -- admission into free slots -------------------------------
        admitting = True
        for s in range(B):
            while admitting and slots[s] is None:
                req = self._pick_admissible(serve)
                if req is None:
                    admitting = False
                    break
                backend = self.backends[req.model_id]
                ctx = req.context_tokens
                assert len(ctx) >= 1, "empty prompts are not admissible"
                if backend.paged:
                    sched.pop_ready(req)
                    a = allocs[req.model_id]
                    rows = backend.admission_rows(
                        self._pgr[req.model_id], len(ctx))
                    sharer = self._sharers.get(req.model_id)
                    sh_pages, sh_tokens = (
                        sharer.plan(req, ctx) if sharer is not None
                        else ([], 0))
                    if sh_pages:
                        a.share(req.rid, sh_pages)
                        req.shared_pages += len(sh_pages)
                        rep.shared_page_hits += len(sh_pages)
                    pages = a.alloc(req.rid, len(rows) - len(sh_pages))
                    page_table[s, :] = TRASH_PAGE
                    page_table[s, rows] = sh_pages + pages
                    if sh_tokens >= len(ctx):
                        logits = None   # fully cached re-admission
                    elif sh_tokens:
                        logits = backend.prefill_shared(
                            ctx, req.extras, s, pages, sh_pages,
                            sh_tokens)
                    else:
                        logits = backend.prefill(ctx, req.extras, s, pages)
                    full = (-(-len(ctx) // e.prefill_bucket)
                            * e.prefill_bucket)
                    computed = 0 if sh_tokens >= len(ctx) else (
                        -(-(len(ctx) - sh_tokens) // e.prefill_bucket)
                        * e.prefill_bucket)
                    rep.prefill_tokens += computed
                    rep.prefill_tokens_saved += full - computed
                    if computed:
                        rep.prefill_calls += 1
                        req.prefills += 1
                    if sharer is not None:
                        sharer.record(a, ctx, len(ctx), page_table[s])
                else:
                    sched.pop_ready(req)
                    logits = backend.prefill(ctx, req.extras, s, None)
                    rep.prefill_calls += 1
                    rep.prefill_tokens += len(ctx)
                    req.prefills += 1
                req.admitted_step = self.step
                slots[s] = req
                lengths[s] = len(ctx)
                if req.generated:   # re-admission after preemption
                    pending[s] = req.generated[-1]
                    self._remaining[s] = (req.max_new_tokens
                                          - len(req.generated))
                    self._ds.touch(s)
                else:
                    assert logits is not None
                    tok = self._sample(logits)
                    req.generated.append(tok)
                    pending[s] = tok
                    self._remaining[s] = req.max_new_tokens - 1
                    self._ds.touch(s)
                    rep.model_tokens[req.model_id] += 1
                    if req.done:
                        self._finish(s)

        # -- one fused decode step over every hot tenant's slots -----
        # Weights of all hot tenants sit in HBM simultaneously (the
        # packed-canvas premise at pool scale), so their slots advance
        # in the same engine step; the naive round-robin baseline only
        # ever holds one swappable tenant hot, so it cannot use this
        # concurrency — that utilization gap is the point.
        did_compute = False
        if self._active_models():
            # page growth / preemption for every paged tenant's slot
            for s in range(B):
                if slots[s] is None:
                    continue
                mid = slots[s].model_id
                if not self.backends[mid].paged:
                    continue
                if e.stream == "layer" and not pool.decode_ready(mid):
                    # no decode this step (mid-re-stream / queued
                    # behind the DMA): growing now would re-fire on
                    # every blocked step and orphan the previous
                    # page into the same table row
                    continue
                a = allocs[mid]
                sharer = self._sharers.get(mid)

                def claim_one(s: int, mid: str = mid, a=a,
                              sharer=sharer) -> bool:
                    """Free one page for slot ``s``: index cache first,
                    then same-tenant victim preemption (a victim's
                    pages may land in the index — evictable next
                    iteration, so the loop still strictly shrinks live
                    state). False if ``s`` itself was preempted."""
                    if not a.can_alloc(1):
                        # growth pressure is the other load signal the
                        # arena repartitions on (preempt == starvation)
                        self.arena.note_starved(mid, self.step)
                    while not a.can_alloc(1):
                        if sharer is not None \
                                and sharer.index.evict_lru(a, 1):
                            continue
                        # only same-tenant slots are useful victims —
                        # the page-id space is partitioned, so a
                        # neighbour's pages can never back this growth
                        tenant_active = [
                            (v, slots[v]) for v in range(B)
                            if slots[v] is not None
                            and slots[v].model_id == mid]
                        victim = Scheduler.pick_victim(tenant_active,
                                                       exclude=s)
                        if victim is None or victim[0] == s:
                            self._preempt(s)
                            return False
                        self._preempt(victim[0])
                    return True

                if lengths[s] % page != 0:
                    # mid-page: the next decode appends into the tail
                    # page — if it is still shared (re-admission mapped
                    # a cached tail), copy-on-write exactly that page
                    if sharer is None:
                        continue
                    row_i = lengths[s] // page
                    old = int(page_table[s, row_i])
                    if a.refcount(old) < 2:
                        continue
                    if not claim_one(s):
                        continue
                    new = a.alloc(slots[s].rid, 1)
                    self.backends[mid].copy_page(old, new[0])
                    a.free_page(slots[s].rid, old)
                    page_table[s, row_i] = new[0]
                    self._ds.touch(s)
                    slots[s].cow_copies += 1
                    rep.cow_copies += 1
                    continue
                pi = lengths[s] // page
                R = self.backends[mid].ring_rows
                if R is None and pi >= M:
                    slots[s].truncated = True
                    self._finish(s)
                    continue
                row = _growth_row(self.backends[mid], a, page_table,
                                  s, pi, slots[s].rid)
                if not claim_one(s):
                    continue
                new = a.alloc(slots[s].rid, 1)
                page_table[s, row] = new[0]
                self._ds.touch(s)

            # safe horizon: h > 1 only when no schedulable event —
            # arrival, admission retry, cold activation, rr switch,
            # stream/burst accounting, epoch boundary, page boundary,
            # slot finish — can land mid-horizon, so h fused steps are
            # step-for-step identical to h per-step iterations
            h = 1
            if self._fused:
                h = e.horizon
                if e.policy == "round_robin":
                    h = min(h, max(1, self._rr_left))
                if pool.pcfg.slab_mode == "bounded" or (
                        e.stream == "layer" and pool.streaming):
                    h = 1   # DMA ticks / decode bursts settle per step
                ready = sched.ready_models()
                if any(m not in serve for m in ready):
                    h = 1   # cold tenant retries activation every step
                if ready and any(r is None for r in slots):
                    h = 1   # free slot retries admission every step
                nxt = sched.next_arrival()
                if nxt is not None:
                    h = min(h, nxt - self.step)
                ne = self.arena.next_epoch_step()
                if ne is not None:      # boundary must land on a step
                    h = min(h, ne - self.step + 1)
                for s in range(B):
                    if slots[s] is None:
                        continue
                    h = min(h, int(self._remaining[s]))
                    if self.backends[slots[s].model_id].paged:
                        h = min(h, self._pgr[slots[s].model_id]
                                .steps_to_boundary(int(lengths[s])))
                h = max(1, h)
                self._ds.sync(page_table, lengths, pending,
                              self._remaining)
            # bookkeeping below (finish steps, arena epoch) sees the
            # horizon's last step, exactly as the per-step loop would
            self.step += h - 1
            self._rr_left -= h - 1

            served = 0
            for m in self._active_models():
                backend = self.backends[m]
                m_slots = [s for s in range(B)
                           if slots[s] is not None
                           and slots[s].model_id == m]
                if not m_slots:
                    continue
                if e.stream == "layer" and not pool.decode_ready(m):
                    # a bounded-slab tenant mid-re-stream (or a tenant
                    # queued behind the serial DMA) skips this step;
                    # its slots wait while the FIFO drains
                    continue
                act = np.zeros((B,), bool)
                act[m_slots] = True
                if self._fused:
                    # tenants chain through the shared device arrays:
                    # each fused call masks to its own slots (and blanks
                    # other tenants' table rows on device) and donates
                    # the loop arrays to the next tenant's call
                    ds = self._ds
                    out, p_d, l_d, r_d = backend.decode_fused(
                        ds.pending, ds.lengths, ds.remaining, ds.table,
                        act, h)
                    toks_h = np.asarray(out)   # one host sync/tenant
                    ds.adopt(p_d, l_d, r_d)
                    ds.count(dispatches=1, syncs=1)
                    lengths[m_slots] += h
                    self._remaining[m_slots] -= h
                    served += len(m_slots)
                    for s in m_slots:
                        req = slots[s]
                        req.generated.extend(
                            int(t) for t in toks_h[:h, s])
                        pending[s] = int(toks_h[h - 1, s])
                        rep.model_tokens[m] += h
                        if req.done:
                            self._finish(s)
                else:
                    toks = np.where(act, pending, 0).astype(np.int32)
                    # page ids are tenant-local: blank out other
                    # tenants' rows so this backend never gathers past
                    # its pool
                    pt_m = np.where(act[:, None], page_table, TRASH_PAGE)
                    len_m = np.where(act, lengths, 0).astype(np.int32)
                    logits = np.asarray(backend.decode(toks, pt_m, len_m,
                                                       act))
                    self._ds.count(dispatches=1, syncs=1,
                                   upload_bytes=page_table.nbytes)
                    lengths[m_slots] += 1
                    self._remaining[m_slots] -= 1
                    served += len(m_slots)
                    stoks = self._sample_batch(logits[m_slots])
                    for i, s in enumerate(m_slots):
                        req = slots[s]
                        tok = int(stoks[i])
                        req.generated.append(tok)
                        pending[s] = tok
                        rep.model_tokens[m] += 1
                        if req.done:
                            self._finish(s)
                # bounded slab: queue this burst's re-stream bytes
                pool.note_decode_burst(m)
            if served:
                did_compute = True
                rep.decode_steps += h
                rep.slot_steps += B * h
                rep.useful_slot_steps += served * h
            rep.peak_live_pages = max(
                rep.peak_live_pages,
                sum(a.live_count for a in allocs.values()))
            rep.peak_live_page_bytes = max(
                rep.peak_live_page_bytes,
                sum(a.live_count * self.backends[m].page_bytes
                    for m, a in allocs.items()))
            rep.peak_demand_pages = max(
                rep.peak_demand_pages,
                sum(a.demand_count for a in allocs.values()))
            rep.peak_demand_page_bytes = max(
                rep.peak_demand_page_bytes,
                sum(a.demand_count * self.backends[m].page_bytes
                    for m, a in allocs.items()))
        elif not sched.exhausted:
            nxt = sched.next_arrival()
            if nxt is not None and nxt > self.step \
                    and not sched.ready_models():
                self.step = nxt     # idle: fast-forward to next arrival
                return True
            # ready work exists but is blocked (deferred activation /
            # page wait / an in-flight layer stream): let time pass
        else:
            return False            # drained: idle until more is injected

        # -- layer-stream progress: one step of DMA bandwidth --------
        if e.stream == "layer" and pool.streaming:
            if not did_compute:
                # prefetch miss: no decode work hides the DMA, so the
                # engine idles a step waiting on the stream head
                head = pool.stream_head
                rep.stall_steps += 1
                rep.stall_steps_by_model[head] += 1
            pool.stream_tick()      # one step of the DMA channel's clock

        # -- arena bookkeeping: watermarks + epoch repartition -------
        # Shrink floor: an ADMITTED request was judged feasible against
        # its tenant's lease at admission; repartitioning must never cut
        # the lease below what the largest in-flight request still needs
        # to finish, or admission feasibility silently stops implying
        # completability (lease churn strands requests in preempt loops)
        for m in self.page_split:
            floor = 0
            for s in range(B):
                r = slots[s]
                if r is None or r.model_id != m:
                    continue
                R = self.backends[m].ring_rows
                demand = self._pgr[m].pages_for(
                    len(r.prompt) + r.max_new_tokens - 1)
                floor = max(floor, min(demand, R) if R else demand)
            self.arena.set_demand_floor(m, floor)
        self.arena.sample()
        if self.arena.maybe_repartition(self.step) is not None:
            # epoch boundary: weight-region occupancy joins the KV
            # invariants maybe_repartition already asserted
            self.arena.check(slab_used=pool.slab_used,
                             pinned_bytes=pool.plan.pinned_bytes)

        self.step += 1
        self._rr_left -= 1
        if self.step > e.max_steps:
            raise RuntimeError("pooled engine exceeded max_steps")
        return True

    def finish_run(self) -> PooledReport:
        pool, rep = self.pool, self._rep
        for m, sharer in self._sharers.items():
            sharer.index.release_all(self._allocs[m])
        self.arena.check(slab_used=pool.slab_used,
                         pinned_bytes=pool.plan.pinned_bytes)
        for a in self._allocs.values():
            assert a.live_count == 0, "pages leaked past completion"
        rep.preemptions = self._sched.preemptions
        rep.reload_bytes = pool.reload_bytes_total
        rep.restream_bytes = pool.restream_bytes_total
        rep.reload_events = pool.reload_events
        rep.evictions = pool.evictions
        rep.deferred_activations = pool.deferred_activations
        rep.repartitions = self.arena.repartitions
        rep.pages_moved = self.arena.pages_moved
        rep.device_dispatches = self._ds.device_dispatches
        rep.host_syncs = self._ds.host_syncs
        rep.page_table_upload_bytes = self._ds.page_table_upload_bytes
        rep.wall_s = time.monotonic() - self._t_run
        return rep

    def run(self, requests: list[Request]) -> PooledReport:
        self.start(requests)
        while self.step_once():
            pass
        return self.finish_run()


# --- static lockstep baseline --------------------------------------------------


def run_static(cfg, params, requests: list[Request], *, num_slots: int = 8,
               greedy: bool = True, temperature: float = 0.8,
               seed: int = 0) -> EngineReport:
    """The seed serving path as a measurable baseline: requests are taken
    in arrival order in fixed batches; each batch prefills together and
    decodes in lockstep until the *longest* generation in the group
    finishes. The dense KV cache holds batch x (max prompt + max gen)
    for the whole group.

    Mixed prompt lengths are left-padded to the group max with no pad
    masking — pad tokens sit in the cache and real tokens attend to
    them. That is the naive static path's real behaviour (and one more
    reason per-slot batching wins); this baseline's metrics are
    structural (steps/bytes), not a quality reference."""
    api = get_model(cfg)
    requests = sorted(requests, key=lambda r: r.arrival)
    rep = EngineReport(name=f"static/{cfg.name}", num_slots=num_slots)

    prefill_jit = jax.jit(partial(api.prefill, cfg),
                          static_argnames=("cache_len",))
    decode_jit = jax.jit(partial(api.decode_step, cfg),
                         donate_argnums=(1,))
    sample_batch = make_batch_sampler(np.random.default_rng(seed), greedy,
                                      temperature)

    t_run = time.monotonic()
    step = 0
    for i in range(0, len(requests), num_slots):
        group = requests[i:i + num_slots]
        step = max(step, max(r.arrival for r in group))
        plen = max(len(r.prompt) for r in group)
        gen = max(r.max_new_tokens for r in group)
        cache_len = plen + gen
        toks = np.zeros((len(group), plen), np.int32)
        for b, r in enumerate(group):
            toks[b, plen - len(r.prompt):] = r.prompt   # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        extra_keys = set().union(*(set(r.extras or {}) for r in group))
        if extra_keys:
            missing = [r.rid for r in group
                       if set(r.extras or {}) != extra_keys]
            assert not missing, \
                f"requests {missing} lack extras {sorted(extra_keys)} " \
                "their batch group carries (static groups must be uniform)"
            batch.update({k: jnp.asarray(
                np.stack([r.extras[k] for r in group]))
                for k in extra_keys})
        logits, state = prefill_jit(params, batch, cache_len=cache_len)
        toks0 = sample_batch(np.asarray(logits))
        for b, r in enumerate(group):
            r.admitted_step = step
            r.generated.append(int(toks0[b]))
        rep.prefill_calls += 1
        rep.prefill_tokens += plen * len(group)   # padded compute is paid
        rep.cache_bytes_alloc = max(rep.cache_bytes_alloc,
                                    _state_bytes(state))
        for _ in range(gen - 1):        # lockstep drain to the longest
            tok = jnp.asarray(np.asarray(
                [r.generated[-1] for r in group], np.int32))
            logits, state = decode_jit(params, state, tok)
            logits = np.asarray(logits)
            rep.decode_steps += 1
            rep.slot_steps += len(group)
            step += 1
            toks = sample_batch(logits)
            for b, r in enumerate(group):
                if not r.done:
                    r.generated.append(int(toks[b]))
                    rep.useful_slot_steps += 1
        del state
        for r in group:
            r.done_step = step          # results return with the batch
            rep.completed.append(r)
    rep.wall_s = time.monotonic() - t_run
    return rep
