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
from .scheduler import Request, Scheduler


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
    """Shared host-side batch sampler (the engine loop and the static
    baseline both draw through this one helper). Greedy argmaxes the
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
    peak_live_page_bytes: int = 0      # tenants' page sizes differ
    peak_demand_page_bytes: int = 0    # live minus index-only, in bytes
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
        """Peak cache bytes holding *live* tokens (paged; each tenant's
        pages at its own size, since an MLA latent page is far smaller
        than a dense KV page) or the full dense allocation (static /
        recurrent). A paged backend with
        per-slot recurrent state (hybrid) adds that constant term so the
        comparison against the static path — whose _state_bytes includes
        the same conv/LRU arrays — stays symmetric."""
        if self.page_bytes:
            return self.peak_live_page_bytes + self.slot_state_bytes
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
            return self.peak_demand_page_bytes + self.slot_state_bytes
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


class _Tenant:
    """One model's state in the engine loop: its backend, its pager
    geometry (admission feasibility, page boundaries), and for the run
    its page allocator (an arena lease) and prefix sharer. ``decodes`` is
    the policy's word for this step: False holds its slots back."""

    __slots__ = ("model_id", "backend", "pgr", "alloc", "sharer",
                 "decodes", "tokens")

    def __init__(self, model_id: str, backend, pgr: PagerConfig):
        self.model_id, self.backend, self.pgr = model_id, backend, pgr
        self.alloc = self.sharer = None
        self.decodes = True
        self.tokens = 0                # tokens served this run


class Engine:
    """Continuous batching over a table of tenants.

    ``step_once`` is the one engine step: arrivals and admission, page
    growth, the safe horizon, the decode dispatch and delivery.
    ``Engine(cfg, params, ecfg)`` serves one tenant over the whole page
    budget. ``PooledEngine`` builds its tenants from a pool plan and
    decides, at the ``_policy_*`` points, which tenants admit and decode
    and how far a horizon may reach; here those points return at once.
    ``run`` is ``start``, ``step_once`` until nothing can progress, and
    ``finish_run``; a caller may drive the three itself and ``inject``
    work between steps (the fleet tier does)."""

    def __init__(self, cfg, params, ecfg: EngineConfig | None = None):
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        self.backend = resolve_backend(cfg)(cfg, params, self.ecfg)
        home = _home_sharding(params)
        if home is not None:
            self.backend.state = jax.device_put(self.backend.state, home)
        self.arena = None
        if self.backend.paged:
            # one lease spanning the whole page budget
            self.arena = DeviceArena(ArenaConfig(kv_pages=self.ecfg.num_pages),
                                     {"default": 1.0})
            self.arena.register_page_bytes("default", self.backend.page_bytes)
        self._init_loop([_Tenant("default", self.backend, self.ecfg.pager)],
                        home)

    def _init_loop(self, tenants: list[_Tenant], home) -> None:
        self._tenants = tenants
        self._paged = [t for t in tenants if t.backend.paged]
        self._home = home
        self.rng = np.random.default_rng(self.ecfg.seed)
        self._sample_batch = make_batch_sampler(
            self.rng, self.ecfg.greedy, self.ecfg.temperature)
        # greedy sampling is pure argmax, so it can run on device inside
        # the fused horizon; the host RNG's temperature draw cannot
        self._fused = self.ecfg.greedy and self.ecfg.horizon > 1

    # -- policy points: the pooled engine's decisions ----------------------

    def _tenant_of(self, req: Request) -> _Tenant:
        return self._tenants[0]

    def _policy_step(self) -> list[str] | None:
        """Which models' queues may admit this step (None: every one)."""
        return None

    def _policy_waits(self, req: Request, t: _Tenant, need: int,
                      fits: bool) -> bool:
        """Whether the admission scan stops at this head, which needs
        ``need`` pages and ``fits`` or not: strict FCFS waits."""
        return not fits

    def _policy_starved(self, t: _Tenant) -> None:
        """A slot of ``t`` found no free page to grow into."""

    def _policy_horizon(self) -> int:
        """The policy's cap on this step's horizon."""
        return self.ecfg.horizon

    def _policy_end(self, h: int, decoded: list[_Tenant]) -> None:
        """After the step's ``h`` decode steps, run by ``decoded``."""

    def _new_report(self) -> EngineReport:
        return EngineReport(name=f"engine/{self.cfg.name}",
                            **self._report_base())

    def _report_base(self) -> dict:
        bs = [t.backend for t in self._tenants]
        return dict(num_slots=self.ecfg.num_slots,
                    page_bytes=max((t.backend.page_bytes
                                    for t in self._paged), default=0),
                    slot_state_bytes=sum(b.slot_state_bytes for b in bs),
                    cache_bytes_alloc=sum(_state_bytes(b.state) for b in bs))

    # -- the loop ----------------------------------------------------------

    def start(self, requests: list[Request]) -> "Engine":
        e = self.ecfg
        B, M = e.num_slots, e.max_pages_per_seq
        self._sched = Scheduler(requests)
        if self.arena is not None:
            # a fresh run starts from the initial leases' fresh allocators
            self.arena.reset_runtime()
        for t in self._tenants:
            t.alloc = (self.arena.allocator(t.model_id)
                       if t.backend.paged else None)
            # one prefix index per eligible tenant: page ids are tenant-
            # local and token-id equality only implies KV equality within
            # one model
            t.sharer = (_PrefixSharing(t.pgr)
                        if t.alloc is not None and e.prefix_sharing
                        and t.backend.prefix_sharing else None)
            t.tokens = 0
        self._slots: list[Request | None] = [None] * B
        self._owner: list[_Tenant | None] = [None] * B
        self._page_table = np.zeros((B, M), np.int32)
        self._lengths = np.zeros((B,), np.int32)
        self._pending = np.zeros((B,), np.int32)     # next decode input
        self._remaining = np.zeros((B,), np.int32)   # token budget left
        # device twins of the four loop arrays, shared by every tenant
        # (fused dispatches chain through them), and the traffic ledger
        # the per-step path shares
        self._ds = DeviceLoopState(B, M, self._home)
        self._rep = self._new_report()
        self._t_run = time.monotonic()
        self.step = 0
        return self

    def run(self, requests: list[Request]) -> EngineReport:
        self.start(requests)
        while self.step_once():
            pass
        return self.finish_run()

    def step_once(self) -> bool:
        """Advance one engine step (a fused horizon of h decode steps
        counts h). False when nothing can progress — every queue empty
        and no slot occupied (``run`` stops there; the fleet keeps an idle
        replica, since more work may be injected) — and True otherwise,
        including an idle fast-forward to the next arrival."""
        with StepTraceAnnotation("engine.step", step_num=self.step):
            sched = self._sched
            sched.release_arrivals(self.step)
            serve = self._policy_step()
            admitting = True
            for s in range(self.ecfg.num_slots):
                # retry the same slot until it is filled (rejected or
                # finished-at-prefill requests must not waste the slot)
                while admitting and self._slots[s] is None:
                    admitting = self._admit(s, serve)
            active = [s for s, r in enumerate(self._slots) if r is not None]
            h, decoded = 1, []
            if active:
                if self._paged:
                    with TraceAnnotation("engine.grow"):
                        self._grow(active)
                if active:              # growth may have emptied it
                    h, decoded = self._decode(active)
            elif sched.exhausted:
                return False
            else:
                nxt = sched.next_arrival()
                if nxt is not None and nxt > self.step \
                        and sched.peek_ready() is None:
                    self.step = nxt     # idle: fast-forward to next arrival
                    return True
                # ready work is blocked (a deferred activation, a page
                # wait, a layer stream): let time pass
            self._policy_end(h, decoded)
            self.step += 1
            if self.step > self.ecfg.max_steps:
                raise RuntimeError("engine exceeded max_steps")
            return True

    def _admit(self, s: int, serve: list[str] | None) -> bool:
        """Admit the earliest admissible ready head of the ``serve``
        queues into free slot ``s``. True if a queue changed (a request
        admitted, or failed as one that can never fit), so the slot is
        retried; False if nothing can be admitted now."""
        e, sched, rep = self.ecfg, self._sched, self._rep
        for req in sched.ready_heads(serve):
            with TraceAnnotation("engine.admit", rid=req.rid):
                t = self._tenant_of(req)
                be, ctx = t.backend, req.context_tokens
                assert len(ctx) >= 1, "empty prompts are not admissible"
                if be.paged:
                    if not be.can_ever_fit(t.pgr, len(req.prompt),
                                           req.max_new_tokens, len(ctx)):
                        self._reject(sched.pop_ready(req))   # fail fast
                        return True
                    rows = be.admission_rows(t.pgr, len(ctx))
                    sh_pages, sh_tokens = (t.sharer.plan(req, ctx)
                                           if t.sharer is not None
                                           else ([], 0))
                    need, a = len(rows) - len(sh_pages), t.alloc
                    if not a.can_alloc(need) and t.sharer is not None:
                        # index-only pages are cache: reclaim them before
                        # making the request wait, keeping the ones the
                        # plan maps by reference
                        t.sharer.index.evict_lru(a, need - a.free_count,
                                                 protect=set(sh_pages))
                    fits = a.can_alloc(need)
                    if self._policy_waits(req, t, need, fits):
                        return False
                    if not fits:
                        continue        # another tenant's head may admit
                    sched.pop_ready(req)
                    if sh_pages:
                        a.share(req.rid, sh_pages)
                        req.shared_pages += len(sh_pages)
                        rep.shared_page_hits += len(sh_pages)
                    pages = a.alloc(req.rid, need)
                    self._page_table[s, :] = TRASH_PAGE
                    self._page_table[s, rows] = sh_pages + pages
                    with TraceAnnotation("engine.prefill"):
                        if sh_tokens >= len(ctx):
                            logits = None   # fully cached re-admission
                        elif sh_tokens:
                            logits = be.prefill_shared(
                                ctx, req.extras, s, pages, sh_pages,
                                sh_tokens)
                        else:
                            logits = be.prefill(ctx, req.extras, s, pages)
                    bucket = e.prefill_bucket
                    full = -(-len(ctx) // bucket) * bucket
                    computed = 0 if sh_tokens >= len(ctx) else (
                        -(-(len(ctx) - sh_tokens) // bucket) * bucket)
                    rep.prefill_tokens += computed
                    rep.prefill_tokens_saved += full - computed
                    if computed:
                        rep.prefill_calls += 1
                        req.prefills += 1
                        tracing.PREFILLS.add(len(ctx) - sh_tokens, computed)
                    if t.sharer is not None:
                        t.sharer.record(a, ctx, len(ctx),
                                        self._page_table[s])
                else:
                    sched.pop_ready(req)
                    with TraceAnnotation("engine.prefill"):
                        logits = be.prefill(ctx, req.extras, s, None)
                    rep.prefill_calls += 1
                    rep.prefill_tokens += len(ctx)
                    req.prefills += 1
                    tracing.PREFILLS.add(len(ctx), len(ctx))
                req.admitted_step = self.step
                self._slots[s], self._owner[s] = req, t
                self._lengths[s] = len(ctx)
                self._ds.touch(s)
                if req.generated:       # re-admission after preemption
                    self._pending[s] = req.generated[-1]
                    self._remaining[s] = (req.max_new_tokens
                                          - len(req.generated))
                else:
                    tok = int(self._sample_batch(logits)[0])
                    req.generated.append(tok)
                    self._pending[s] = tok
                    self._remaining[s] = req.max_new_tokens - 1
                    t.tokens += 1
                    if req.done:
                        self._finish(s)   # the slot is retried
                return True
        return False

    def _grow(self, active: list[int]) -> None:
        """Page growth, copy-on-write and preemption for the paged slots
        of the tenants that decode this step. ``active`` loses the slots
        this preempts or finishes."""
        e, rep, ds = self.ecfg, self._rep, self._ds
        page, M = e.page_size, e.max_pages_per_seq
        slots, owner = self._slots, self._owner
        page_table, lengths = self._page_table, self._lengths

        def claim_one(s: int, t: _Tenant) -> bool:
            """Free one page of ``t``'s lease for slot ``s``: index cache
            first, then preemption of a victim of the same tenant (page
            ids are tenant-local, so a neighbour's pages cannot back this
            growth; a victim's pages may land in the index, evictable
            next iteration, so the loop still strictly shrinks live
            state). False if ``s`` itself was preempted."""
            a = t.alloc
            if not a.can_alloc(1):
                self._policy_starved(t)
            while not a.can_alloc(1):
                if t.sharer is not None and t.sharer.index.evict_lru(a, 1):
                    continue
                victim = Scheduler.pick_victim(
                    [(v, slots[v]) for v in active if owner[v] is t],
                    exclude=s)
                if victim is None or victim[0] == s:
                    self._preempt(s)
                    active.remove(s)
                    return False
                self._preempt(victim[0])
                active.remove(victim[0])
            return True

        for s in list(active):
            t = owner[s]
            if t is None or t.alloc is None or not t.decodes:
                # preempted above, unpaged, or held back this step (growing
                # a slot that does not decode would re-fire every blocked
                # step and orphan the previous page in the same row)
                continue
            req = slots[s]
            if lengths[s] % page != 0:
                # mid-page: the next decode appends into the tail page — if
                # it is still shared (re-admission mapped a cached tail),
                # copy-on-write exactly that page first
                if t.sharer is None:
                    continue
                row = lengths[s] // page
                old = int(page_table[s, row])
                if t.alloc.refcount(old) < 2 or not claim_one(s, t):
                    continue
                new = t.alloc.alloc(req.rid, 1)
                t.backend.copy_page(old, new[0])
                t.alloc.free_page(req.rid, old)
                page_table[s, row] = new[0]
                ds.touch(s)
                req.cow_copies += 1
                rep.cow_copies += 1
                continue
            row = lengths[s] // page
            R = t.backend.ring_rows
            if R is None and row >= M:  # table row full: stop
                req.truncated = True
                self._finish(s)
                active.remove(s)
                continue
            if R is not None:
                # a ring wraps into row ``pi % R`` and frees the page there
                # first: the ring holds ceil(window/page)+1 rows, so that
                # page's positions all lie outside the attention window
                row %= R
                old = int(page_table[s, row])
                if old != TRASH_PAGE:
                    t.alloc.free_page(req.rid, old)
                    page_table[s, row] = TRASH_PAGE
            if not claim_one(s, t):
                continue
            page_table[s, row] = t.alloc.alloc(req.rid, 1)[0]
            ds.touch(s)

    def _horizon(self, groups) -> tuple[int, str]:
        """The safe horizon: no schedulable event may land inside it, so h
        steps run device-side are step-for-step identical to h per-step
        iterations of this loop. ``cause`` names the cut that bound h (a
        later cut takes it over only by cutting shorter)."""
        sched, step = self._sched, self.step
        h, cause = self.ecfg.horizon, "cap"
        cap = self._policy_horizon()
        if cap < h:
            h, cause = cap, "policy"
        nxt = sched.next_arrival()
        if nxt is not None and nxt - step < h:
            h, cause = nxt - step, "arrival"
        if sched.peek_ready() is not None and \
                any(r is None for r in self._slots):
            # a free slot retries admission per step
            h, cause = 1, "admit"
        fin = min(int(self._remaining[s]) for _, g in groups for s in g)
        if fin < h:
            h, cause = fin, "finish"
        edge = min((t.pgr.steps_to_boundary(int(self._lengths[s]))
                    for t, g in groups if t.alloc is not None for s in g),
                   default=h)
        if edge < h:                    # growth / ring wrap
            h, cause = edge, "page"
        return max(1, h), cause

    def _decode(self, active: list[int]) -> tuple[int, list[_Tenant]]:
        """One fused horizon, or one per-step dispatch, per decoding
        tenant, and delivery. Returns the steps taken and the tenants
        that ran them."""
        e, rep, ds = self.ecfg, self._rep, self._ds
        B = e.num_slots
        slots, owner = self._slots, self._owner
        page_table, lengths = self._page_table, self._lengths
        pending, remaining = self._pending, self._remaining
        if len(self._tenants) == 1:
            groups = [(self._tenants[0], active)]
        else:
            groups = [(t, g) for t in self._tenants
                      if (g := [s for s in active if owner[s] is t])]
        h, cause = 1, "unfused"
        if self._fused:
            h, cause = self._horizon(groups)
            with TraceAnnotation("engine.sync"):
                ds.sync(page_table, lengths, pending, remaining)
        # bookkeeping below (finish steps, arena epochs) sees the
        # horizon's last step, exactly as the per-step loop would
        self.step += h - 1
        decoded, served = [], 0
        for t, g in groups:
            if not t.decodes:
                continue                # its slots wait this step
            be = t.backend
            act = np.zeros((B,), bool)
            act[g] = True
            tracing.DISPATCHES.add(h, len(g), cause)
            if self._fused:
                # tenants chain through the shared device arrays: each
                # call masks to its own slots and donates the loop arrays
                # to the next tenant's call
                with TraceAnnotation("engine.decode"):
                    out, p_d, l_d, r_d = be.decode_fused(
                        ds.pending, ds.lengths, ds.remaining, ds.table,
                        act, h)
                with TraceAnnotation("engine.wait"):
                    if be.expert_rows_per_step:   # claims ride along
                        out, claims = jax.device_get((out, be.state.claims))
                    else:
                        out = np.asarray(out)     # ONE host sync
                ds.adopt(p_d, l_d, r_d)
                ds.count(dispatches=1, syncs=1)
            else:
                # page ids are tenant-local: other tenants' rows are
                # blanked so this backend never gathers past its pool
                with TraceAnnotation("engine.decode"):
                    out = be.decode(
                        np.where(act, pending, 0).astype(np.int32),
                        np.where(act[:, None], page_table, TRASH_PAGE),
                        np.where(act, lengths, 0).astype(np.int32), act)
                with TraceAnnotation("engine.wait"):
                    if be.expert_rows_per_step:
                        out, claims = jax.device_get((out, be.state.claims))
                    else:
                        out = np.asarray(out)
                ds.count(dispatches=1, syncs=1,
                         upload_bytes=page_table.nbytes)
            if be.expert_rows_per_step:
                # live claims on held experts against the expert rows
                # computed, and the latent rows live (each slot attends
                # over its context and the new tokens) against the rows
                # the whole page table's gather reads, per layer; taken
                # before the lengths advance
                tracing.ROUTES.add(int(claims), h * be.expert_rows_per_step)
                live = (int(lengths[g].sum()) * h
                        + len(g) * h * (h + 1) // 2)
                tracing.LATENT_READS.add(
                    live, B * e.max_pages_per_seq * e.page_size * h)
            lengths[g] += h
            remaining[g] -= h
            with TraceAnnotation("engine.deliver"):
                if self._fused:
                    for s in g:
                        req = slots[s]
                        req.generated.extend(int(x) for x in out[:h, s])
                        pending[s] = int(out[h - 1, s])
                        if req.done:
                            self._finish(s)
                else:
                    toks = self._sample_batch(out[g])
                    for i, s in enumerate(g):
                        req = slots[s]
                        tok = int(toks[i])
                        req.generated.append(tok)
                        pending[s] = tok
                        if req.done:
                            self._finish(s)
            t.tokens += h * len(g)
            served += len(g)
            decoded.append(t)
        if served:
            rep.decode_steps += h
            rep.slot_steps += B * h     # the batch always runs full
            rep.useful_slot_steps += served * h
        if self._paged:
            live = [(t.alloc, t.backend.page_bytes) for t in self._paged]
            rep.peak_live_pages = max(rep.peak_live_pages,
                                      sum(a.live_count for a, _ in live))
            rep.peak_demand_pages = max(rep.peak_demand_pages,
                                        sum(a.demand_count for a, _ in live))
            rep.peak_live_page_bytes = max(
                rep.peak_live_page_bytes,
                sum(a.live_count * pb for a, pb in live))
            rep.peak_demand_page_bytes = max(
                rep.peak_demand_page_bytes,
                sum(a.demand_count * pb for a, pb in live))
        return h, decoded

    def finish_run(self) -> EngineReport:
        rep = self._rep
        for t in self._paged:
            if t.sharer is not None:    # drop the index's neutral refs
                t.sharer.index.release_all(t.alloc)
        if self.arena is not None:
            self.arena.check()
        for t in self._paged:
            assert t.alloc.live_count == 0, "pages leaked past completion"
        rep.preemptions = self._sched.preemptions
        rep.device_dispatches = self._ds.device_dispatches
        rep.host_syncs = self._ds.host_syncs
        rep.page_table_upload_bytes = self._ds.page_table_upload_bytes
        rep.wall_s = time.monotonic() - self._t_run
        return rep

    # -- slot lifecycle ----------------------------------------------------

    def _clear_slot(self, s: int) -> None:
        req, t = self._slots[s], self._owner[s]
        if t.sharer is not None:
            # index the pages this request completed (incl. during decode)
            # BEFORE dropping its refs: the neutral refs keep the prefix
            # warm for later prompts / re-admission
            t.sharer.record(t.alloc, req.context_tokens, self._lengths[s],
                            self._page_table[s])
        self._slots[s] = self._owner[s] = None
        self._page_table[s, :] = TRASH_PAGE
        self._lengths[s] = 0
        self._pending[s] = 0
        self._remaining[s] = 0
        self._ds.touch(s)
        if t.alloc is not None:
            t.alloc.free_owner(req.rid)
        t.backend.release_slot(s)

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

    # -- steppable-loop accessors (the fleet router reads these) -----------

    @property
    def report(self) -> EngineReport:
        return self._rep

    def inject(self, requests: list[Request]) -> None:
        """Hand the engine more requests mid-run (fleet dispatch)."""
        self._sched.inject(requests)

    def occupied_slots(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    def backlog(self) -> int:
        """Requests queued but not in a slot (ready + future arrivals)."""
        return len(self._sched)

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
        another replica (their generated tokens ride along, so nothing
        restarts from scratch beyond the re-prefill)."""
        for s, r in enumerate(self._slots):
            if r is not None:
                self._preempt(s)
        return self._sched.drain()


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
    model_tokens: dict = dataclasses.field(default_factory=dict)
    stall_steps_by_model: dict = dataclasses.field(default_factory=dict)

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


class PooledEngine(Engine):
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

    The loop is ``Engine``'s; this class builds the tenants and answers
    its policy points from the modeled pool.
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
        tenants = []
        for e in pool.plan.entries:
            ecfg_t, pgr = self.ecfg, self.ecfg.pager
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
                    self.ecfg, num_pages=self.arena.cap(e.model_id) + 1)
                pgr = dataclasses.replace(
                    self.ecfg,
                    num_pages=self.page_split[e.model_id] + 1).pager
            backend = resolve_backend(e.cfg)(e.cfg, params[e.model_id],
                                             ecfg_t)
            self.backends[e.model_id] = backend
            if e.model_id in self.page_split:
                self.arena.register_page_bytes(e.model_id,
                                               backend.page_bytes)
            tenants.append(_Tenant(e.model_id, backend, pgr))
        if self.ecfg.repartition == "off":
            assert sum(n + 1 for n in self.page_split.values()) \
                <= self.ecfg.num_pages, \
                "physical pages exceed the pool budget"
        self._by_model = {t.model_id: t for t in tenants}
        self._init_loop(tenants, None)

    def start(self, requests: list[Request]) -> "PooledEngine":
        self.pool.reset_runtime()
        super().start(requests)
        self._serve: list[str] = []
        self._rr_current: str | None = None
        self._rr_left = 0
        self._blocked_since: dict[int, int] = {}  # rid -> first blocked step
        return self

    def _new_report(self) -> PooledReport:
        e, ids = self.ecfg, self.pool.model_ids
        return PooledReport(
            name=f"pool/{e.policy}", policy=e.policy, stream=e.stream,
            model_tokens=dict.fromkeys(ids, 0),
            stall_steps_by_model=dict.fromkeys(ids, 0),
            **self._report_base())

    def finish_run(self) -> PooledReport:
        pool = self.pool
        rep = super().finish_run()
        self.arena.check(slab_used=pool.slab_used,
                         pinned_bytes=pool.plan.pinned_bytes)
        rep.reload_bytes = pool.reload_bytes_total
        rep.restream_bytes = pool.restream_bytes_total
        rep.reload_events = pool.reload_events
        rep.evictions = pool.evictions
        rep.deferred_activations = pool.deferred_activations
        rep.repartitions = self.arena.repartitions
        rep.pages_moved = self.arena.pages_moved
        rep.model_tokens.update((t.model_id, t.tokens) for t in self._tenants)
        return rep

    # -- policy points ------------------------------------------------------

    def _tenant_of(self, req: Request) -> _Tenant:
        return self._by_model[req.model_id]

    def _active_models(self) -> list[str]:
        return [t.model_id for t in self._tenants if t in self._owner]

    def _charge_stall(self, m: str, stall: int) -> None:
        self._rep.stall_steps += stall
        self._rep.stall_steps_by_model[m] += stall
        self.step += stall

    def _policy_step(self) -> list[str]:
        """Fail the queues no tenant can serve, run the activation
        policy, and gate each tenant's decode on its weights' stream."""
        e, pool, sched = self.ecfg, self.pool, self._sched
        for m in sched.ready_models():
            if m not in self._by_model or not pool.servable(m):
                while (req := sched.peek_ready([m])) is not None:
                    self._reject(sched.pop_ready(req))

        if e.policy == "round_robin":
            ready = sched.ready_models()
            rr = self._rr_current
            switch = (rr is None or self._rr_left <= 0
                      or (rr not in self._active_models()
                          and sched.ready_count(rr) == 0))
            if switch and ready:
                order = list(pool.model_ids)
                start = ((order.index(rr) + 1) % len(order)
                         if rr is not None else 0)
                nxt = next((order[(start + i) % len(order)]
                            for i in range(len(order))
                            if order[(start + i) % len(order)] in ready),
                           None)
                if nxt is not None and nxt != rr:
                    # naive swap: drop everything, load the next model
                    for s, r in enumerate(self._slots):
                        if r is not None:
                            self._preempt(s)
                    for m in list(pool.hot_models()):
                        pool.evict(m)
                    stall, _ = pool.try_activate(nxt, self.step)
                    self._charge_stall(nxt, stall)
                    self._rr_current, self._rr_left = nxt, e.rr_quantum
                elif nxt is not None:
                    self._rr_left = e.rr_quantum
            serve = [self._rr_current] if self._rr_current is not None \
                else []
        else:
            cold = [m for m in sched.ready_models() if not pool.is_hot(m)]
            if cold:
                # highest queued-demand per reload byte activates
                # first; if it must wait (hysteresis), a smaller cold
                # tenant that fits the free slab may still go
                cold.sort(key=lambda m: (
                    -sched.pending_demand(m)
                    / max(pool.plan.entry(m).reload_bytes, 1), m))
                active = self._active_models()
                protected = frozenset(
                    m for m in pool.hot_models()
                    if m in active or sched.ready_count(m) > 0)
                for m in cold:
                    if e.stream == "layer":
                        # layer-granular: reserve the slab, then let
                        # the per-layer schedule stream behind compute
                        # (stalls only surface as prefetch misses,
                        # charged at the step's end)
                        if pool.begin_stream(m, self.step, protected) \
                                is not None:
                            break   # the DMA issues one stream at once
                    else:
                        res = pool.try_activate(m, self.step, protected)
                        if res is not None:
                            self._charge_stall(m, res[0])
                            break   # one reload/step: stalls serialize
            if e.stream == "layer":
                # a mid-stream model joins once it heads the serial
                # DMA queue and the un-streamed tail fits inside its
                # first decode step's own layer walk
                serve = [m for m in pool.hot_models()
                         if pool.decode_ready(m)]
            else:
                serve = pool.hot_models()
        for t in self._tenants:
            # a bounded-slab tenant mid-re-stream, or one queued behind
            # the serial DMA, neither grows nor decodes this step
            t.decodes = e.stream != "layer" \
                or pool.decode_ready(t.model_id)
        self._serve = serve
        return serve

    def _policy_waits(self, req: Request, t: _Tenant, need: int,
                      fits: bool) -> bool:
        """Page pressure is tenant-local, so a page-starved head waits
        without blocking its neighbours — but only up to the aging bound:
        once it has been bypassed for ``max_bypass_steps``, the scan stops
        at it instead of admitting later arrivals past it."""
        if fits:
            self._blocked_since.pop(req.rid, None)
            return False
        # a page-blocked head feeds the arena's load signal and ages
        first = self._blocked_since.setdefault(req.rid, self.step)
        self.arena.note_starved(t.model_id, self.step, want=need)
        if self.ecfg.max_bypass_steps \
                and self.step - first >= self.ecfg.max_bypass_steps:
            self._rep.aging_blocks += 1
            return True
        return False

    def _policy_starved(self, t: _Tenant) -> None:
        # growth pressure is the other load signal the arena
        # repartitions on (preempt == starvation)
        self.arena.note_starved(t.model_id, self.step)

    def _policy_horizon(self) -> int:
        """Cuts for events the policy settles per step: the round-robin
        quantum, DMA ticks and decode bursts, cold tenants retrying
        activation, and arena epoch boundaries."""
        e, pool = self.ecfg, self.pool
        h = e.horizon
        if e.policy == "round_robin":
            h = min(h, max(1, self._rr_left))
        if pool.pcfg.slab_mode == "bounded" or (
                e.stream == "layer" and pool.streaming):
            h = 1
        if any(m not in self._serve for m in self._sched.ready_models()):
            h = 1
        ne = self.arena.next_epoch_step()
        if ne is not None:      # the boundary must land on a step
            h = min(h, ne - self.step + 1)
        return h

    def _policy_end(self, h: int, decoded: list[_Tenant]) -> None:
        e, pool, rep = self.ecfg, self.pool, self._rep
        for t in decoded:
            # bounded slab: queue this burst's re-stream bytes
            pool.note_decode_burst(t.model_id)
        # -- layer-stream progress: one step of DMA bandwidth
        if e.stream == "layer" and pool.streaming:
            if not decoded:
                # prefetch miss: no decode work hides the DMA, so the
                # engine idles a step waiting on the stream head
                head = pool.stream_head
                rep.stall_steps += 1
                rep.stall_steps_by_model[head] += 1
            pool.stream_tick()      # one step of the DMA channel's clock
        # -- arena bookkeeping: watermarks + epoch repartition
        # Shrink floor: an ADMITTED request was judged feasible against
        # its tenant's lease at admission; repartitioning must never cut
        # the lease below what the largest in-flight request still needs
        # to finish, or admission feasibility silently stops implying
        # completability (lease churn strands requests in preempt loops)
        floors = dict.fromkeys(self.page_split, 0)
        for r, t in zip(self._slots, self._owner):
            if r is None or t.alloc is None:
                continue
            R = t.backend.ring_rows
            demand = t.pgr.pages_for(len(r.prompt) + r.max_new_tokens - 1)
            floors[t.model_id] = max(floors[t.model_id],
                                     min(demand, R) if R else demand)
        for m, floor in floors.items():
            self.arena.set_demand_floor(m, floor)
        self.arena.sample()
        if self.arena.maybe_repartition(self.step) is not None:
            # epoch boundary: weight-region occupancy joins the KV
            # invariants maybe_repartition already asserted
            self.arena.check(slab_used=pool.slab_used,
                             pinned_bytes=pool.plan.pinned_bytes)
        self._rr_left -= h


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
