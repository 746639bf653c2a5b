"""Device-resident continuous-batching serve engine.

Decode KV is a zero-reuse stream (the paper's throughput-sensitive class):
on the chip the decode step reads it through the split-KV Pallas kernels
(``kernels.decode_attention``), which plan their blocks and splits from the
shapes they are traced with and the chip's cores.  The engine only picks
the path (`resolve_decode_kernel`).

The serving loop is built to run at hardware speed:

* **Chunked on-device decode** — one `lax.scan` dispatch decodes
  ``chunk_size`` tokens for every slot with on-device sampling and
  per-slot done flags; the host syncs once per *chunk* (to read the
  emitted tokens), not once per token.
* **Ragged slots** — the cache carries a per-slot ``lengths`` cursor
  vector, so slots free and re-admit independently: finished slots park
  (``seg_lens == 0`` leaves their state untouched) while live slots keep
  decoding, and freed slots take new prompts mid-stream: each admitted
  request is prefilled alone into its slot, right-padded to its own
  bucket (`models.common.append_kv` drops padding on the scatter).
* **Donated buffers** — the cache (and the per-slot token/budget vectors)
  are donated to each dispatch, so KV updates are in-place on device.
* **Paged KV pool** (``cfg.cache_layout == "paged"``, DESIGN.md §5.2) —
  K/V capacity is pooled into fixed-size pages shared across slots; a
  host-side free-list (`PageAllocator`) assigns each admitted request
  exactly the pages its worst case needs and admission gates on free
  pages, so a pool smaller than ``slots x max_len`` serves mixed
  long/short traffic while staying bit-identical to the contiguous ring.
* **Prefix sharing** (``cfg.prefix_sharing``, DESIGN.md §5.4) — a
  host-side radix trie over full prompt pages (`serve.prefix`) lets
  admission attach a new request to already-resident prefix pages: the
  slot's page table aliases the shared pages (refcounted in the
  `PageAllocator`; a page frees only at refcount zero) and prefill runs
  only over the unshared suffix at a page-aligned nonzero cursor.
  Divergence is copy-on-write by allocation — the first divergent page is
  always a private page, shared pages are never written.  Requires the
  paged layout and a pure-KV decoder family (dense/moe); other engines
  fall back to unshared bookkeeping.
* **Speculative decode** (``cfg.spec_k > 0``, DESIGN.md §5.3) — an
  on-device n-gram proposer (`serve.draft`) drafts ``spec_k`` tokens per
  slot from the slot's own history; ONE multi-token verify dispatch
  scores every draft position via the model's ragged ``prefill`` path,
  accepts each slot's matching prefix (1..spec_k+1 tokens per round) and
  rolls the rejected suffix back — a per-slot cursor rewind for KV
  families, a seg-gated replay for recurrent state (mamba2/zamba2).
  Output-identical to the non-speculative path under every sampling mode
  because acceptance replays the exact `(seed, token-index)`-keyed
  sampler decision the sequential loop would have made.
* **Sampling** (`serve.sampling.Sampler`) — greedy / temperature / top-k
  / top-p on device inside the chunk scan; per-request seeds fold into
  per-token keys so streams are independent of slot assignment order.
* **Request lifecycle** (DESIGN.md §5.5) — requests move through
  queued -> resident -> {finished, preempted -> re-queued, cancelled,
  expired}.  When paged admission is gated on an empty free list the
  engine *preempts* the youngest resident: its pages are released
  refcount-aware (prefix-shared pages are only dereferenced, never freed
  under sharers), its emitted tokens are already host-side, and it
  re-enqueues for a recompute-prefill over prompt + emitted — the
  `(seed, token index)` sampler keys make the restored stream
  bit-identical to the uninterrupted one by construction.  `cancel()`
  and per-request deadlines are swept between decode chunks (slots,
  pages and trie refs free mid-stream), submission is bounded with
  reject-with-reason backpressure (`AdmissionReject`), and
  `check_invariants()` + `serve.chaos` fault injection prove the
  allocator/trie/engine state machine survives all of it.
* **Crash safety + KV integrity** (DESIGN.md §5.6) — ``snapshot(path)``
  serializes host-side truth only (requests, tokens, seeds, refcounts,
  quarantine) and ``restore(path)`` rebuilds all device KV bit-identically
  through ordinary re-admission; an optional fsync'd request journal
  (``journal_path``) replays submissions/terminations past the snapshot
  after an unplanned kill.  With ``cfg.kv_integrity`` the engine stamps
  per-page fingerprints at chunk boundaries and ``verify_pages()``
  detects silent corruption, quarantines the page in the allocator
  (refcount-aware: every prefix sharer is repaired) and self-heals the
  mapped slots by recompute-restore.  ``drain()`` carries a livelock
  watchdog (``NoProgressError``) so a starved pool fails loudly.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models.common import paged_kv_spec
from repro.serve import snapshot as snap
from repro.serve.adaptive import AdaptivePolicy
from repro.serve.alloc import PageAllocator  # noqa: F401  (re-export: the
# allocator lives in serve.alloc since the chaos wrapper subclasses it;
# property tests and older call sites import it from there)
from repro.serve.chaos import ChaosAllocator, ChaosCrash
from repro.serve.draft import ngram_propose
from repro.serve.prefix import PrefixIndex
from repro.serve.spans import span
from repro.serve.snapshot import SnapshotError  # noqa: F401  (re-export:
# engine callers catch restore failures without importing serve.snapshot)
from repro.serve.sampling import (  # noqa: F401  (greedy_sample re-export)
    Sampler,
    greedy_sample,
    sample_keys,
)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # (len,) int32
    max_new_tokens: int = 16
    seed: int | None = None       # per-request sampling seed (None -> 0):
                                  # streams depend on (seed, token index)
                                  # only, never on slot assignment order
    id: str | None = None         # cancellation handle; auto-assigned at
                                  # submit when None ("req-<n>")
    deadline_s: float | None = None       # submit -> finish SLO; a resident
                                          # request past it is expired
                                          # mid-stream at the next sweep
    max_queue_wait_s: float | None = None  # submit -> admission bound
                                           # (queued requests only)
    generated: list = dataclasses.field(default_factory=list)
    slot: int = -1
    done: bool = False            # terminal: finished, cancelled or expired
                                  # (``status`` says which)
    status: str = "new"           # new -> queued -> resident -> {finished,
                                  # preempted (re-queued), cancelled, expired}
    cancel_requested: bool = False  # set by engine.cancel(); honored at the
                                    # next lifecycle sweep (chunk boundary)
    preempted_n: int = 0          # times evicted mid-stream; natural
                                  # preemption only ever victimizes
                                  # never-preempted residents, so it is
                                  # bounded by the request count
    admit_seq: int = -1           # admission order; the preemption victim
                                  # is the youngest (max) resident
    prefix_tokens: int = 0        # prompt tokens attached from shared pages
                                  # at admission (0 = fully prefilled)
    ttft_s: float | None = None        # admission -> first token (prefill)
    queue_wait_s: float | None = None  # submit -> FIRST admission (queueing
                                       # only; preemption re-queues don't
                                       # overwrite it)
    submit_t: float | None = None
    admit_t: float | None = None


class AdmissionReject(ValueError):
    """A request the engine refuses to enqueue, with a machine-readable
    ``reason``: backpressure ("queue_full") or a request that could never
    be served ("pool_too_small", "max_len", "empty_prompt", "zero_budget",
    "duplicate_id").  Raised by ``submit`` BEFORE anything in the batch is
    enqueued, so a rejection never leaves the batch half-submitted."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class NoProgressError(RuntimeError):
    """``drain()`` livelock watchdog (DESIGN.md §5.6): raised after
    ``no_progress_limit`` consecutive steps in which work remained but
    zero tokens were emitted and zero lifecycle transitions happened —
    e.g. a queue gated behind a fully quarantined pool, or pathological
    injected alloc-failure rates.  Failing loudly beats spinning forever;
    the message carries the gating state so the operator can tell a
    shrunk pool from a chaos knob."""


def _pad_bucket(n: int, cap: int) -> int:
    """Round a prefill width up to a power of two (>= 8) so the number of
    distinct prefill compilations is O(log max_len), not O(#prompt-lens)."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


def resolve_decode_kernel(cfg: ModelConfig) -> str:
    """The decode-attention path for ``cfg.decode_kernel == "auto"``: the
    paged kernel, which reads only the resident pages in place, on a paged
    cache on a TPU; XLA otherwise — on a contiguous cache, and off the chip,
    where Pallas would only run interpreted.  An explicit path wins."""
    if cfg.decode_kernel != "auto":
        return cfg.decode_kernel
    if cfg.cache_layout == "paged" and jax.default_backend() == "tpu":
        return "pallas_paged"
    return "xla"


def _refuse_window_features(cfg: ModelConfig) -> None:
    """Sliding-window layers keep a per-slot ring of exactly
    ``sliding_window`` positions beside the paged pool (DESIGN.md §5.2).
    What the ring cannot give is refused here, before anything is built:
    prefix sharing (a ring is private to its slot, so an attached prefix
    would have no window K/V), speculative decode (a rejected draft's ring
    row overwrites a position the window still needs) and KV page
    integrity (its fingerprints cover only the pool's pages)."""
    refused = [name for name, on in (
        ("prefix_sharing", cfg.prefix_sharing),
        ("spec_k", cfg.spec_k > 0),
        ("kv_integrity", cfg.kv_integrity or cfg.chaos_corrupt_p > 0.0),
    ) if on]
    if refused:
        raise ValueError(
            f"{cfg.arch} has sliding-window layers, whose per-slot rings do "
            f"not support {', '.join(refused)}; turn "
            f"{'it' if len(refused) == 1 else 'them'} off")


class ServeEngine:
    """Continuous-batching engine over a fixed pool of request slots.

    ``run(requests)`` (or ``submit`` + ``drain``) pushes requests through a
    queue: free slots are prefilled (ragged, right-padded), live slots
    decode in device-resident chunks — plain chunked decode, or draft/
    verify/rollback rounds when ``cfg.spec_k > 0`` — finished slots free at
    chunk boundaries and are immediately re-admitted from the queue.
    """

    def __init__(self, cfg: ModelConfig, params, batch_slots: int,
                 max_len: int, extras: dict[str, Any] | None = None,
                 chunk_size: int = 8, n_pages: int | None = None,
                 max_queue: int | None = None,
                 journal_path: str | None = None,
                 no_progress_limit: int = 256):
        # Decode-attention path (DESIGN.md §5.2), resolved once from what
        # the engine observes and baked into the config the model is traced
        # with.
        cfg = dataclasses.replace(cfg,
                                  decode_kernel=resolve_decode_kernel(cfg))
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.chunk_size = max(1, chunk_size)
        self.extras = extras or {}
        self.sampler = Sampler.from_config(cfg)
        # Speculative decode (DESIGN.md §5.3): k drafts verified per round,
        # emitting 1..k+1 tokens; a chunk packs enough rounds to target
        # ~chunk_size tokens per host sync at full acceptance.
        self.spec = cfg.spec_k > 0
        self.spec_k = cfg.spec_k
        self.spec_ngram = cfg.spec_ngram
        self.spec_rounds = max(1, self.chunk_size // (cfg.spec_k + 1))
        # Paged KV layout (DESIGN.md §5.2): K/V capacity is pooled into
        # fixed-size pages shared across slots; the host-side free-list
        # assigns each admitted request exactly the pages its worst case
        # needs (prompt + budget), so a pool smaller than slots x max_len
        # serves mixed long/short traffic.  ``n_pages`` None sizes the pool
        # to full contiguous capacity.
        self.paged = cfg.cache_layout == "paged"
        cache_kwargs = dict(self.extras)
        if self.paged:
            psz = cfg.kv_page_size
            assert max_len % psz == 0, (
                f"max_len={max_len} must be a multiple of kv_page_size={psz} "
                "so the gathered page view is bit-identical to the "
                "contiguous ring"
            )
            self.page_size = psz
            self.pages_per_slot, self.n_pages = paged_kv_spec(
                batch_slots, max_len, psz, n_pages
            )
            self.allocator: PageAllocator = self._make_allocator()
            self.page_table = np.full(
                (batch_slots, self.pages_per_slot), -1, np.int32
            )
            self._slot_pages: list[list[int]] = [[] for _ in range(batch_slots)]
            cache_kwargs["n_pages"] = self.n_pages
        self._cache_kwargs = cache_kwargs
        # Capacity-based MoE dispatch lets right-pad/parked garbage tokens
        # compete with valid tokens for expert capacity (silent drops);
        # serving requires the per-token dense dispatch (DESIGN.md §5.1).
        assert not cfg.n_experts or cfg.moe_dispatch == "dense", (
            "ServeEngine requires moe_dispatch='dense' (ragged slots would "
            "let padding contend for expert capacity under 'sorted')"
        )
        if cfg.windowed:
            _refuse_window_features(cfg)
        # Rows the dense expert dispatch computes per token row of a
        # dispatch: every held expert, in every layer.
        self._moe_rows = (cfg.experts_held * cfg.n_layers
                          if cfg.family == "moe" else 0)
        self.model = build_model(cfg)
        self.cache = self.model.init_cache(
            params, batch=batch_slots, max_len=max_len, **self._cache_kwargs
        )
        if self.paged and "pages" not in self.cache:
            # Cache family with no KV to page (mamba2's decode state is
            # O(1) per slot): fall back to contiguous bookkeeping rather
            # than gating admission on a phantom page pool.
            self.paged = False
        # Prefix sharing (DESIGN.md §5.4) rides the paged pool: the trie
        # indexes resident full prompt pages and admission attaches new
        # requests to them.  Pure-KV decoder families only — recurrent
        # state (mamba2/zamba2 SSM/conv) is not page-shareable, and
        # encdec/vlm prefix KV depends on per-slot source context (frames/
        # vision tokens), so those fall back to unshared bookkeeping.
        self.prefix_sharing = (
            bool(cfg.prefix_sharing) and self.paged
            and cfg.family in ("dense", "moe")
        )
        self.prefix = (
            PrefixIndex(self.page_size) if self.prefix_sharing else None
        )
        # Adaptive serve-tier cache policy (DESIGN.md §5.7): runtime
        # counters drive warm prefix retention (bounded by
        # cfg.warm_pages), cost-aware preemption victims, and per-class
        # policy re-planning through serve.adaptive's exact lattice argmin.
        # Placement-only by construction — the static path pays nothing.
        # The warm tier needs re-attachable page KV (paged + prefix
        # sharing); other engines keep victim costing + replans only.
        self.adaptive: AdaptivePolicy | None = None
        if cfg.adaptive:
            self.adaptive = AdaptivePolicy(
                warm_pages=(cfg.warm_pages
                            if self.prefix is not None else 0),
                replan_every=cfg.adaptive_replan_every,
                page_size=self.page_size if self.paged else 1,
                spec_k=self.spec_k if self.spec else 0,
            )
        self._warm_tier = (
            self.adaptive is not None and self.adaptive.warm_pages > 0
            and self.prefix is not None
        )
        # Recurrent state (SSM/conv) has no per-position validity mask, so
        # the speculative rollback cannot be a cursor rewind: those
        # families re-run the verify block from the pre-verify cache with
        # ``seg_lens = accepted`` (the dt/conv gating makes the replay
        # consume exactly the accepted prefix).  KV-only families rewind.
        self._spec_replay = "ssm" in self.cache or "conv" in self.cache
        self._reset_slots = self.model.reset_slots
        self._slot_rows = self._cache_slot_rows()
        self._prefill = jax.jit(
            self._prefill_fn, donate_argnums=(1, 7, 8, 11, 12, 13, 15)
        )
        self._decode_chunk = jax.jit(
            self._spec_chunk_fn if self.spec else self._chunk_fn,
            donate_argnums=(1, 2, 3, 4, 5, 6),
        )
        # Device-resident per-slot loop state: last sampled token, remaining
        # token budget (0 == slot parked/free), per-request token index and
        # sampling seed, and the token history the n-gram proposer mines
        # (prompt + emitted, including the not-yet-consumed current token —
        # at most max_len + 1 entries since prompt + budget <= max_len + 1).
        self.cur_tok = jnp.zeros((batch_slots,), jnp.int32)
        self.remaining = jnp.zeros((batch_slots,), jnp.int32)
        self.tok_idx = jnp.zeros((batch_slots,), jnp.int32)
        self.seeds = jnp.zeros((batch_slots,), jnp.int32)
        self.hist = jnp.zeros((batch_slots, max_len + 1), jnp.int32)
        self.hist_len = jnp.zeros((batch_slots,), jnp.int32)
        self.slot_req: list[Request | None] = [None] * batch_slots
        self.queue: collections.deque[Request] = collections.deque()
        # Request lifecycle (DESIGN.md §5.5).
        self.preemption = bool(cfg.preemption)
        self.max_queue = max_queue          # None = unbounded submission
        self._by_id: dict[str, Request] = {}   # cancellation handles
        self._next_id = 0
        self._admit_seq = 0                 # victim choice: youngest = max
        # Slots vacated mid-stream (preempt/cancel/expire) whose device
        # budget must be zeroed before the next decode chunk — a stale
        # ``remaining`` would decode into pages now owned by others.
        self._dirty_slots: set[int] = set()
        if cfg.chaos_preempt_p > 0.0:
            assert cfg.chaos_preempt_p < 1.0, (
                "chaos_preempt_p must be < 1.0 or the loop preempts forever"
            )
        self._chaos_rng = (
            np.random.default_rng(cfg.chaos_seed)
            if cfg.chaos_preempt_p > 0.0 else None
        )
        # Any chaos knob arms the per-wave invariant check: fault paths
        # must leave allocator/trie/page-table state exactly conserved.
        self._chaos = (
            cfg.chaos_preempt_p > 0.0
            or (self.paged and (cfg.chaos_alloc_fail_p > 0.0
                                or cfg.chaos_share_fail_p > 0.0
                                or cfg.chaos_corrupt_p > 0.0))
        )
        # Strict mode (DESIGN.md §5.6) arms the same per-wave sweep with
        # no fault injection — CI tier-1 sets the env var so every test
        # run audits conservation, not just the chaos legs.
        self._strict = cfg.strict_invariants or (
            os.environ.get("REPRO_STRICT_INVARIANTS", "") not in ("", "0")
        )
        # KV page integrity (DESIGN.md §5.6): fingerprints stamped at
        # chunk boundaries over pages sealed below their slot's
        # host-computed cursor; verify_pages() sweeps them every step.
        self.integrity = self.paged and (
            cfg.kv_integrity or cfg.chaos_corrupt_p > 0.0
        )
        self._page_fp: dict[int, int] = {}
        self._corrupt_rng = (
            np.random.default_rng(cfg.chaos_seed + 0x5EED)
            if self.paged and cfg.chaos_corrupt_p > 0.0 else None
        )
        # Crash safety (DESIGN.md §5.6): optional fsync'd request journal;
        # _replaying suppresses journal writes while restore re-enqueues.
        self.journal_path = journal_path
        self.journal = (
            snap.RequestJournal(journal_path)
            if journal_path is not None else None
        )
        self._replaying = False
        self.no_progress_limit = max(1, no_progress_limit)
        self.stats = {
            "host_syncs": 0,          # total device->host barriers
            "decode_syncs": 0,        # one per decode chunk
            "decode_tokens": 0,       # tokens emitted by decode chunks
            "prefill_tokens": 0,      # first tokens emitted by prefill
            "chunks": 0,
            "admission_waves": 0,
            "spec_rounds": 0,         # active draft/verify rounds
            "draft_proposed": 0,      # spec_k per active round
            "draft_accepted": 0,      # matching draft prefix per round
            "admitted_fresh": 0,      # first-time admissions (no tokens yet)
            "readmitted": 0,          # preemption-restore re-admissions
            "prefill_work_tokens": 0,  # suffix tokens actually prefilled
            "prefill_rows_computed": 0,  # pad rows the prefills ran
            # Paged decode chunks: pages the paged kernel reads (mapped
            # pages of the decoding slots) and pages the gather path reads
            # (every slot's whole table), each x chunk_size.
            "decode_pages_read": 0,
            "decode_pages_dense": 0,
            # Plain decode chunks of a model with sliding-window layers:
            # the positions one window layer reads (min(context, window)
            # per decoded token) and what an unbounded read would take
            # (the whole context).
            "decode_window_positions_read": 0,
            "decode_window_positions_dense": 0,
            # MoE: (token, expert) picks of valid tokens that land on the
            # experts held here, and the rows the dense dispatch computes
            # (dispatch rows x held experts), both summed over layers.
            "moe_picks_held": 0,
            "moe_rows_computed": 0,
            "spec_tokens": 0,         # tokens emitted by draft/verify rounds
            "prefix_hits": 0,         # admissions that attached shared pages
            "prefix_hits_fresh": 0,   # ... the fresh-admission subset
            "prefix_pages_shared": 0,  # shared-page references taken
            "prefix_tokens_shared": 0,  # prompt tokens not re-prefilled
            "warm_retained": 0,       # pages parked in the warm tier
            "warm_reclaimed": 0,      # warm pages returned to the free list
            "warm_hits": 0,           # admissions that revived warm pages
            "warm_tokens_saved": 0,   # prompt tokens attached from warm pages
            "replans": 0,             # adaptive lattice re-plans run
            "peak_pages_held": 0,     # max concurrent pool usage (paged)
            "preempted": 0,           # mid-stream evictions (incl. forced)
            "preempted_forced": 0,    # chaos-forced subset
            "recompute_tokens": 0,    # emitted tokens re-prefilled at restore
            "cancelled": 0,           # terminal via engine.cancel()
            "expired": 0,             # terminal via deadline/queue-wait
            "rejected": 0,            # submissions refused (AdmissionReject)
            "deadline_total": 0,      # deadlined requests reaching terminal
            "deadline_met": 0,        # ... that finished within deadline
            "invariant_checks": 0,    # check_invariants() sweeps run
            "integrity_sweeps": 0,    # fingerprint stamp+verify passes
            "corrupted_pages": 0,     # fingerprint mismatches detected
            "healed_requests": 0,     # slots recompute-restored after
                                      # mapping a corrupted page
            "injected_corruptions": 0,  # chaos_corrupt_p bit flips landed
            "snapshots": 0,           # snapshot() calls
            "restores": 0,            # restore() calls completed
        }

    def _cache_slot_rows(self) -> list[tuple[int, int] | None]:
        """Per cache leaf, in flattening order: the (axis, rows) that hold
        one slot's state, found where the leaf's shape differs from a
        one-slot engine's cache (traced, never built), or None for a leaf
        every slot shares (the paged pool, the MoE picks).  A slot axis
        gives one row; the window rings' slot-major pages give W /
        page_size rows (``models.common.ring_table``).  Extras are per
        slot, on axis 0."""
        kwargs = {**self._cache_kwargs,
                  **{k: v[:1] for k, v in self.extras.items()}}
        one = jax.eval_shape(
            lambda p: self.model.init_cache(p, batch=1, max_len=self.max_len,
                                            **kwargs), self.params)
        leaves, tree = jax.tree_util.tree_flatten(self.cache)
        rows = []
        for full, own in zip(leaves, tree.flatten_up_to(one)):
            axes = [i for i, (a, b) in enumerate(zip(full.shape, own.shape))
                    if a != b]
            assert len(axes) <= 1, (full.shape, own.shape)
            rows.append((axes[0], own.shape[axes[0]]) if axes else None)
        return rows

    def _make_allocator(self) -> PageAllocator:
        """Fresh pool allocator; the chaos wrapper when any injection knob
        is armed (DESIGN.md §5.5) — with cfg.chaos_alloc_fail_p /
        chaos_share_fail_p > 0 the pool refuses otherwise-satisfiable
        calls with seeded probability, driving the same gating/preemption
        paths genuine exhaustion would.  Also the restore path's reset
        (``_hard_reset``), so a restored engine re-arms identically."""
        cfg = self.cfg
        warm = cfg.warm_pages if cfg.adaptive else 0
        if cfg.chaos_alloc_fail_p > 0.0 or cfg.chaos_share_fail_p > 0.0:
            assert cfg.chaos_alloc_fail_p < 1.0, (
                "chaos_alloc_fail_p must be < 1.0 or admission can "
                "never succeed"
            )
            assert cfg.chaos_share_fail_p < 1.0, (
                "chaos_share_fail_p must be < 1.0 or attaching heads can "
                "never admit"
            )
            return ChaosAllocator(
                self.n_pages, cfg.chaos_alloc_fail_p, cfg.chaos_seed,
                share_fail_p=cfg.chaos_share_fail_p, warm_budget=warm,
            )
        return PageAllocator(self.n_pages, warm_budget=warm)

    # -- policy ------------------------------------------------------------

    @property
    def free_pages(self) -> list[int]:
        """Free-list view (paged only) — delegated to the PageAllocator."""
        return self.allocator.free_pages

    def _decode_kernel_plan(self) -> tuple[int, int]:
        """(KV positions per compute block, splits) of the Pallas decode
        kernels over this engine's full-attention cache, by the kernels'
        own rule (``kernels.decode_attention.ops``)."""
        from repro.kernels.decode_attention import ops

        if self.paged:
            page_bytes = (self.page_size * self.cfg.n_kv_heads
                          * self.cfg.head_dim_
                          * jnp.dtype(self.cfg.dtype).itemsize)
            ppb, splits = ops.paged_plan(page_bytes, self.pages_per_slot)
            return ppb * self.page_size, splits
        bkv = min(ops.DENSE_BKV, self.max_len)
        return bkv, ops.split_count(self.max_len, bkv)

    def policy_report(self) -> dict:
        """Serving-side decisions (DESIGN.md §5) and their counters."""
        report = {
            # Effective layout: "contiguous" when a paged request met a
            # cache family with no KV to page (see __init__ fallback).
            "cache_layout": "paged" if self.paged else "contiguous",
            "sampling": self.sampler.mode,
        }
        if self.spec:
            report["speculative"] = {
                "spec_k": self.spec_k,
                "spec_ngram": self.spec_ngram,
                "rounds_per_chunk": self.spec_rounds,
                "rollback": "replay" if self._spec_replay else "rewind",
            }
        if self.paged:
            report["paged_kv"] = {
                "n_pages": self.n_pages,
                "page_size": self.page_size,
                "free_pages": self.allocator.free_count(),
                "pool_positions": self.n_pages * self.page_size,
                "contiguous_positions": self.slots * self.max_len,
            }
        # "requested but not enabled" is the graceful-fallback signal
        # (contiguous layout, KV-free or source-conditioned families).
        report["prefix_sharing"] = {
            "requested": bool(self.cfg.prefix_sharing),
            "enabled": self.prefix_sharing,
        }
        if self.prefix is not None:
            report["prefix_sharing"].update({
                "trie_nodes": len(self.prefix),
                "resident_prefix_tokens": self.prefix.resident_tokens(),
            })
        # Adaptive serve-tier policy (DESIGN.md §5.7) — a NEW top-level
        # section so the schema-stable "lifecycle"/"integrity" blocks
        # stay byte-compatible for their pinned consumers.
        report["adaptive"] = {"enabled": self.adaptive is not None}
        if self.adaptive is not None:
            report["adaptive"].update({
                "warm_tier": self._warm_tier,
                "warm_pages_now": (
                    self.allocator.warm_count() if self.paged else 0
                ),
                **{k: self.stats[k] for k in (
                    "warm_retained", "warm_reclaimed", "warm_hits",
                    "warm_tokens_saved", "replans",
                )},
                **self.adaptive.report(),
            })
        # Lifecycle / robustness (DESIGN.md §5.5).  Schema is stable —
        # benches and CI parse it; tests pin the full key set.
        report["lifecycle"] = {
            "preemption_enabled": self.preemption,
            "max_queue": self.max_queue,
            "preempted": self.stats["preempted"],
            "preempted_forced": self.stats["preempted_forced"],
            "recompute_tokens": self.stats["recompute_tokens"],
            "cancelled": self.stats["cancelled"],
            "expired": self.stats["expired"],
            "rejected": self.stats["rejected"],
            "goodput_under_deadline": self._goodput(),
            "chaos": {
                "alloc_fail_p": self.cfg.chaos_alloc_fail_p,
                "preempt_p": self.cfg.chaos_preempt_p,
                "share_fail_p": self.cfg.chaos_share_fail_p,
                "corrupt_p": self.cfg.chaos_corrupt_p,
                "crash_after_wave": self.cfg.chaos_crash_after_wave,
                "seed": self.cfg.chaos_seed,
                "injected_alloc_failures": (
                    self.allocator.injected_failures
                    if self.paged
                    and isinstance(self.allocator, ChaosAllocator) else 0
                ),
                "injected_share_failures": (
                    self.allocator.injected_share_failures
                    if self.paged
                    and isinstance(self.allocator, ChaosAllocator) else 0
                ),
                "injected_corruptions": self.stats["injected_corruptions"],
            },
        }
        # Crash safety + KV integrity (DESIGN.md §5.6) — same stability
        # contract as "lifecycle": benches/CI parse it, tests pin keys.
        report["integrity"] = {
            "enabled": self.integrity,
            "strict_invariants": self._strict,
            "journal": self.journal_path is not None,
            "stamped_pages": len(self._page_fp),
            "quarantined_pages": (
                len(self.allocator.quarantined_pages)
                + len(self.allocator.doomed_pages)
                if self.paged else 0
            ),
            "corrupted_pages": self.stats["corrupted_pages"],
            "healed_requests": self.stats["healed_requests"],
            "snapshots": self.stats["snapshots"],
            "restores": self.stats["restores"],
        }
        if self.cfg.n_heads and self.cfg.head_dim_:
            # The decode-step path the model was traced with (resolved at
            # construction), and the split-K count and compute block the
            # Pallas kernels plan for it (used when != "xla").
            bkv, splits = self._decode_kernel_plan()
            report["decode_attention"] = {
                "kernel": self.cfg.decode_kernel,
                "planned_splits": splits,
                "kernel_bkv": bkv,
            }
        return report

    def serve_stats(self) -> dict:
        """Host-sync + speculative-acceptance accounting for the loop."""
        out = dict(self.stats)
        total = out["decode_tokens"] + out["prefill_tokens"]
        out["host_syncs_per_token"] = (
            out["host_syncs"] / total if total else 0.0
        )
        out["decode_syncs_per_token"] = (
            out["decode_syncs"] / out["decode_tokens"]
            if out["decode_tokens"] else 0.0
        )
        out["spec_acceptance_rate"] = (
            out["draft_accepted"] / out["draft_proposed"]
            if out["draft_proposed"] else 0.0
        )
        # Spec-round-emitted tokens only: decode_tokens also counts plain
        # chunks (spec disabled mid-run, non-spec phases), which would
        # inflate the per-round figure.
        out["spec_tokens_per_round"] = (
            out["spec_tokens"] / out["spec_rounds"]
            if out["spec_rounds"] else 0.0
        )
        # Hit rate over FRESH admissions: prefill_tokens also counts
        # preemption-restore recompute prefills, which deflated the rate
        # under memory pressure (and a restore re-attach is not a new
        # hit, so the numerator is the fresh subset too).
        out["prefix_hit_rate"] = (
            out["prefix_hits_fresh"] / out["admitted_fresh"]
            if out["admitted_fresh"] else 0.0
        )
        out["goodput_under_deadline"] = self._goodput()
        return out

    def _goodput(self) -> float:
        """Fraction of deadlined requests that reached terminal state
        within their deadline; 1.0 when no request carried one (an
        SLO-free run is vacuously good)."""
        total = self.stats["deadline_total"]
        return self.stats["deadline_met"] / total if total else 1.0

    # -- device-side step functions (jitted once) --------------------------

    def _sample(self, logits, seeds, tok_idx):
        """Sampler dispatch: per-slot keys folded from (request seed, token
        index) — a pure function of the request, so streams are independent
        of slot assignment and batch composition."""
        with jax.named_scope("sample"):
            keys = (sample_keys(seeds, tok_idx)
                    if self.sampler.needs_keys else None)
            return self.sampler(logits, keys).astype(jnp.int32)

    def _hist_append(self, hist, positions, tokens):
        """Scatter (b, k) ``tokens`` into per-slot history at (b, k)
        ``positions``; out-of-range positions (parked slots pass H) drop."""
        b = hist.shape[0]
        return hist.at[jnp.arange(b)[:, None], positions].set(
            tokens, mode="drop")

    def _prefill_fn(self, params, cache, slot, tokens, seg_len, start_len,
                    hist_toks, cur_tok, remaining, new_remaining,
                    new_tok_idx, tok_idx, hist, hist_len, new_seed, seeds):
        """Admission prefill of one request into ``slot`` (a traced int32
        scalar, so one program serves every slot): reset and position the
        slot, prefill its prompt as a batch of one over the slot's own cache
        state, sample its first token on device, and (re)seed the slot's
        history / token-index / seed state.  Nothing of another slot is
        computed.

        The batch of one is the slot's rows of every cache leaf that has
        them (``_cache_slot_rows``: cursor, page-table row, window-ring
        pages, contiguous KV, recurrent and cross-attention state), sliced
        out and written back; a leaf every slot shares (the paged pool) is
        passed whole and written in place through the page-table row.

        ``start_len`` is the attach cursor: 0 for a full prefill, a
        page-aligned shared-prefix length when the slot rides resident
        prefix pages (DESIGN.md §5.4) — ``tokens`` then holds only the
        unshared suffix, positioned (RoPE and scatter) at start + i.
        ``hist_toks`` always carries the FULL prompt, so the n-gram history
        an attached slot's drafts mine is identical to the unshared
        engine's (the full prompt length is start + seg — no extra arg).

        ``new_tok_idx`` is the stream index of the token this prefill
        samples: 0 for a fresh admission, m for a preempted request being
        restored with m tokens already emitted (its "prompt" is then
        prompt + emitted, and the sampler key for index m reproduces
        exactly the token the uninterrupted run emitted there — the whole
        bit-identical-restore argument, DESIGN.md §5.5)."""
        fpad = hist_toks.shape[1]
        H = hist.shape[1]
        view = self._slot_view(cache, slot)
        if self._reset_slots is not None:
            view = self._reset_slots(view, jnp.ones((1,), bool))
        view["lengths"] = start_len[None]
        logits, view = self.model.prefill(
            params, view, tokens, seg_lens=seg_len[None]
        )
        cache = self._slot_merge(cache, view, slot)
        nxt = self._sample(logits, new_seed[None], new_tok_idx[None])
        cur_tok = cur_tok.at[slot].set(nxt[0])
        remaining = remaining.at[slot].set(new_remaining)
        seeds = seeds.at[slot].set(new_seed)
        tok_idx = tok_idx.at[slot].set(new_tok_idx + 1)
        # History: full-prompt rows land at 0..full-1, the first token at
        # full; the padding redirects to H and drops.
        full = start_len + seg_len
        pos = jnp.arange(fpad)
        hist = hist.at[slot, jnp.where(pos < full, pos, H)].set(
            hist_toks[0], mode="drop")
        hist = hist.at[slot, full].set(nxt[0])
        hist_len = hist_len.at[slot].set(full + 1)
        return (cache, cur_tok, remaining, tok_idx, hist, hist_len, seeds,
                nxt, view.get("moe_picks_held"))

    def _slot_view(self, cache, slot):
        """The cache of a batch of one: ``slot``'s rows of each leaf that
        has them, every shared leaf whole."""
        leaves, tree = jax.tree_util.tree_flatten(cache)
        return tree.unflatten([
            x if at is None
            else jax.lax.dynamic_slice_in_dim(x, slot * at[1], at[1], at[0])
            for x, at in zip(leaves, self._slot_rows)])

    def _slot_merge(self, cache, view, slot):
        """Write a batch-of-one cache back at ``slot``'s rows; shared
        leaves are taken from it whole."""
        leaves, tree = jax.tree_util.tree_flatten(cache)
        return tree.unflatten([
            new if at is None
            else jax.lax.dynamic_update_slice_in_dim(x, new, slot * at[1],
                                                     at[0])
            for x, new, at in zip(leaves, jax.tree_util.tree_leaves(view),
                                  self._slot_rows)])

    def _chunk_fn(self, params, cache, cur_tok, remaining, tok_idx, hist,
                  hist_len, seeds):
        """Decode ``chunk_size`` tokens per slot in one dispatch: scan of
        single-token steps with on-device sampling; slots whose budget hits
        zero park (seg_lens == 0 -> state untouched).

        Only the speculative path consumes the n-gram history, so this
        (non-spec) chunk passes ``hist``/``hist_len`` through untouched —
        no per-token scatter or carry traffic on the hot loop.  A MoE
        model's steps also return their picks on held experts, fetched
        with the tokens."""

        def step(carry, _):
            cache, tok, rem, tidx = carry
            active = rem > 0
            logits, cache = self.model.decode_step(
                params, cache, tok[:, None],
                seg_lens=active.astype(jnp.int32),
            )
            nxt = self._sample(logits, seeds, tidx)
            tok = jnp.where(active, nxt, tok)
            tidx = jnp.where(active, tidx + 1, tidx)
            rem = jnp.where(active, rem - 1, rem)
            return (cache, tok, rem, tidx), (tok, active,
                                             cache.get("moe_picks_held"))

        (cache, tok, rem, tidx), (toks, actives, picks) = jax.lax.scan(
            step, (cache, cur_tok, remaining, tok_idx),
            None, length=self.chunk_size,
        )
        return cache, tok, rem, tidx, hist, hist_len, toks, actives, picks

    def _spec_chunk_fn(self, params, cache, cur_tok, remaining, tok_idx,
                       hist, hist_len, seeds):
        """``spec_rounds`` draft/verify/rollback rounds in one dispatch
        (DESIGN.md §5.3).  Each round, per active slot:

        1. *Draft*: ``ngram_propose`` mines the slot's history for spec_k
           draft tokens.
        2. *Verify*: ONE ragged multi-token ``prefill`` over
           ``[cur_tok, d_1..d_k]`` returns logits for every position;
           position j's sampler decision (keyed by token index
           ``tok_idx + j``) is exactly the token the sequential loop would
           emit there, so the target tokens double as the emissions.
        3. *Accept*: the emitted count is ``min(matching prefix + 1,
           remaining)`` — always >= 1 (the sampler's own token at the first
           mismatch), at most spec_k + 1 (all drafts + the bonus token).
        4. *Rollback*: KV families keep the verify-pass cache and rewind
           ``lengths`` to base + accepted (rejected KV is stale-but-masked,
           overwritten as the cursor advances — the ring invariant);
           recurrent families replay the block from the pre-verify cache
           with ``seg_lens = accepted`` (dt/conv gating consumes exactly
           the accepted prefix).
        """
        b = self.slots
        k, k1 = self.spec_k, self.spec_k + 1
        H = hist.shape[1]

        def round_fn(carry, _):
            cache, tok, rem, tidx, hist, hlen = carry
            active = rem > 0
            base_len = cache["lengths"]
            drafts = ngram_propose(hist, hlen, self.spec_ngram, k)
            vt = jnp.concatenate([tok[:, None], drafts], axis=1)  # (b, k1)
            seg_v = jnp.where(active, k1, 0).astype(jnp.int32)
            logits_all, cache_v = self.model.prefill(
                params, cache, vt, seg_lens=seg_v, all_logits=True
            )
            # Target token at position j = sampler decision for token index
            # tidx + j: identical to what sequential decode would sample.
            if self.sampler.needs_keys:
                keys = sample_keys(
                    jnp.broadcast_to(seeds[:, None], (b, k1)).reshape(-1),
                    (tidx[:, None] + jnp.arange(k1)[None, :]).reshape(-1),
                )
            else:
                keys = None
            targets = self.sampler(
                logits_all.reshape(b * k1, -1), keys
            ).astype(jnp.int32).reshape(b, k1)
            match = (drafts == targets[:, :k]).astype(jnp.int32)
            accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)   # (b,)
            m = jnp.where(active, jnp.minimum(accepted + 1, rem), 0)
            # Acceptance accounting reflects USABLE drafts only: a slot
            # with rem remaining tokens can consume at most rem - 1 drafts
            # this round, so matches past the budget clip neither count as
            # accepted nor as proposed (they produced no tokens).
            usable = jnp.where(
                active, jnp.minimum(jnp.int32(k), rem - 1), 0
            )
            acc_used = jnp.maximum(m - 1, 0)
            if self._spec_replay:
                # Recurrent rollback: consume exactly the accepted prefix
                # from the pre-verify cache (discard the polluted verify
                # state).  Also rewrites the accepted KV — same bytes.
                _, cache = self.model.prefill(
                    params, cache, vt, seg_lens=m
                )
            else:
                # KV rollback: rejected positions are beyond the rewound
                # cursor — stale-but-masked, overwritten as it advances.
                cache = dict(cache_v)
                cache["lengths"] = base_len + m
            emit = jnp.arange(k1)[None, :] < m[:, None]              # (b, k1)
            hist = self._hist_append(
                hist,
                jnp.where(emit, hlen[:, None] + jnp.arange(k1)[None, :], H),
                targets,
            )
            last = jnp.take_along_axis(
                targets, jnp.clip(m - 1, 0, k)[:, None], axis=1
            )[:, 0]
            tok = jnp.where(active, last, tok)
            hlen = hlen + m
            tidx = tidx + m
            rem = rem - m
            return (cache, tok, rem, tidx, hist, hlen), (
                targets, emit, acc_used, usable, active
            )

        carry = (cache, cur_tok, remaining, tok_idx, hist, hist_len)
        (cache, tok, rem, tidx, hist, hlen), ys = jax.lax.scan(
            round_fn, carry, None, length=self.spec_rounds
        )
        toks, emits, accepts, proposed, actives = ys
        return (cache, tok, rem, tidx, hist, hlen,
                toks, emits, accepts, proposed, actives)

    # -- host-side scheduling ----------------------------------------------

    def _positions_needed(self, r: Request) -> int:
        """Worst-case cache positions: the prompt plus every decoded token
        except the last sampled one (which is never written back)."""
        return len(r.prompt) + r.max_new_tokens - 1

    def _pages_needed(self, r: Request) -> int:
        return -(-self._positions_needed(r) // self.page_size)

    def _effective_prompt(self, r: Request) -> np.ndarray:
        """The token stream admission must prefill: the prompt — plus, for
        a preempted request being restored, every token it had already
        emitted (including the last: its prefill logits are what sample
        the restored stream's next token, see ``_prefill_fn``).  Its
        worst-case positions equal the original's (prompt + budget - 1),
        so ``_positions_needed``/``_pages_needed`` need no restore case."""
        if not r.generated:
            return np.asarray(r.prompt, np.int32)
        return np.concatenate([
            np.asarray(r.prompt, np.int32),
            np.asarray(r.generated, np.int32),
        ])

    def _shared_prefix(self, eff: np.ndarray, chunks) -> tuple[list[int], int]:
        """(pages, tokens): the longest resident full-page prefix of the
        effective prompt ``eff`` (pre-chunked into ``chunks``) this
        request can attach to (DESIGN.md §5.4).

        Capped below the prompt's full-page count so the prompt's last
        token is ALWAYS re-prefilled: the logits seeding decode are
        computed fresh, never assumed resident — a prompt that is exactly
        its shared pages would otherwise have an empty suffix and park
        forever.  The cap also makes the COW case concrete: a prompt
        ending exactly at a shared-page boundary re-materializes that last
        page's K/V into a private page (same bytes, private residency)."""
        pages = self.prefix.lookup(eff, chunks=chunks)
        cap = (len(eff) - 1) // self.page_size
        pages = pages[:cap]
        return pages, len(pages) * self.page_size

    def _reject(self, reason: str, message: str, n: int = 1):
        self.stats["rejected"] += n
        raise AdmissionReject(reason, message)

    def submit(self, requests: list[Request]) -> None:
        # Validate the whole batch before enqueuing any of it, so a
        # rejected request doesn't leave earlier ones half-submitted.
        for r in requests:
            if r.max_new_tokens < 1:
                # Admission always emits the prefill-sampled first token, so
                # a zero budget would generate one token anyway — reject
                # instead of silently over-generating.
                self._reject("zero_budget", (
                    f"max_new_tokens must be >= 1, got {r.max_new_tokens} "
                    "(prefill emits the first token at admission)"
                ))
            if len(r.prompt) == 0:
                self._reject("empty_prompt", (
                    "empty prompt: seg_lens==0 marks a parked slot, so a "
                    "zero-length admission would never start decoding"
                ))
            need = self._positions_needed(r)
            if need > self.max_len:
                self._reject("max_len", (
                    f"request needs {need} cache positions, "
                    f"max_len={self.max_len}"
                ))
            if self.paged and self._pages_needed(r) > self.allocator.usable_pages():
                # An over-pool request can NEVER be admitted; under the
                # FIFO head-of-line gate it would queue forever and wedge
                # everything behind it — reject at submit instead.  The
                # bound is USABLE capacity: quarantined pages (DESIGN.md
                # §5.6) never return to circulation.  (A pool that shrinks
                # below an already-queued request's demand is the drain()
                # watchdog's business.)
                self._reject("pool_too_small", (
                    f"request needs {self._pages_needed(r)} pages, pool "
                    f"has {self.allocator.usable_pages()} usable of "
                    f"{self.n_pages} — it could never be admitted and "
                    "would block the FIFO queue forever"
                ))
            if r.id is not None:
                # Identity check, not ==: dataclass equality on array
                # fields is both wrong and throwing.
                prev = self._by_id.get(r.id)
                if prev is not None and prev is not r:
                    self._reject("duplicate_id", (
                        f"request id {r.id!r} already submitted to "
                        "this engine"
                    ))
        if (self.max_queue is not None
                and len(self.queue) + len(requests) > self.max_queue):
            # Backpressure: the bounded queue rejects the WHOLE batch with
            # a machine-readable reason; the caller retries after a drain.
            self._reject("queue_full", (
                f"submitting {len(requests)} request(s) would exceed "
                f"max_queue={self.max_queue} ({len(self.queue)} queued)"
            ), n=len(requests))
        now = time.perf_counter()
        for r in requests:
            if r.id is None:
                r.id = f"req-{self._next_id}"
                self._next_id += 1
            self._by_id[r.id] = r
            r.submit_t = now
            r.status = "queued"
            self.queue.append(r)
            if self.journal is not None and not self._replaying:
                self.journal.append(snap.submit_event(r))
        if self.journal is not None and not self._replaying:
            # One fsync per submit batch: an accepted request is durable
            # before the caller regains control.
            self.journal.flush()

    def cancel(self, request_id: str) -> bool:
        """Request cancellation of a queued or resident request.  Takes
        effect at the next lifecycle sweep (a chunk boundary): the slot,
        pages and trie refs free mid-stream, ``generated`` keeps whatever
        was emitted.  Returns False for unknown or already-terminal ids
        (cancellation raced completion) — never raises."""
        r = self._by_id.get(request_id)
        if r is None or r.done:
            return False
        r.cancel_requested = True
        return True

    def _live(self) -> list[tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.slot_req) if r is not None]

    def _release_slot(self, r: Request) -> None:
        """Vacate ``r``'s slot host-side (finish, preempt, cancel, expire).
        Drops the slot's page references — pages shared with live slots
        survive (refcount > 0); pages reaching zero return to the pool and
        their trie nodes evict.  The device page table is refreshed lazily
        at the next admission wave; until then the stale row is harmless —
        the parked slot neither writes KV (seg_lens == 0 drops the
        scatter) nor has its output read.  The slot lands in
        ``_dirty_slots`` so its device budget is zeroed before the next
        chunk (moot for natural finishes, where it already hit zero)."""
        slot = r.slot
        assert slot >= 0 and self.slot_req[slot] is r
        self.slot_req[slot] = None
        r.slot = -1
        r.__dict__.pop("_prefix_chunks", None)
        if self.paged:
            freed = self.allocator.release(self._slot_pages[slot])
            if self._warm_tier and freed:
                # Adaptive retention (DESIGN.md §5.7): trie-registered
                # prefix pages may park in the warm tier instead of
                # freeing; what survives comes back shorn of its trie
                # eviction and stamp drop below.
                freed = self._maybe_retain(r, freed)
            if self.prefix is not None and freed:
                self.prefix.evict(freed)
            for p in freed:
                # A page leaving circulation (freed or quarantined) sheds
                # its integrity stamp; its next holder re-stamps fresh
                # bytes.  Pages still held by sharers keep theirs — their
                # content is immutable below every sharer's cursor.
                self._page_fp.pop(p, None)
            self._slot_pages[slot] = []
            self.page_table[slot] = -1
        self._dirty_slots.add(slot)

    def _maybe_retain(self, r: Request, freed: list[int]) -> list[int]:
        """Warm-retention pass over pages that just reached refcount zero
        (DESIGN.md §5.7).  Returns the pages that must still be evicted
        (trie node dropped, stamp shed); retained pages keep both — a
        warm page's KV stays attachable until reclaimed.

        Closure rules that keep the trie's leaf-upward eviction sound:

        * retention goes shallowest-first and a page is retained only if
          its parent is held, warm, or retained in this same pass — so
          the warm set stays a depth-prefix of each chain;
        * evicting a page whose descendants were retained EARLIER (by a
          shorter sharer that finished first) reclaims that warm subtree
          along with it — a trie node never outlives its parent.
        """
        key = getattr(r, "_adaptive_key", None)
        kept: set[int] = set()
        if key is not None:
            deciding = getattr(r, "_adaptive_class", key)
            quota = self.adaptive.retain_quota(key)
            for p in sorted(freed,
                            key=lambda q: (self.prefix.depth_of(q), q)):
                depth = self.prefix.depth_of(p)
                if depth <= 0:
                    continue          # tail/decode page: never in the trie
                if self.adaptive.class_warm_count(deciding) >= quota:
                    break             # class share of the budget exhausted
                parent = self.prefix.parent_page(p)
                if depth > 1 and not (
                        parent in kept
                        or self.allocator.is_warm(parent)
                        or self.allocator.ref_count(parent) > 0):
                    continue          # chain cut above: stay a prefix
                if self.allocator.retain(p):
                    self.adaptive.note_retained(p, deciding)
                    self.stats["warm_retained"] += 1
                    kept.add(p)
        evict = [p for p in freed if p not in kept]
        # Warm-subtree closure on the evict side: descendants of an
        # evicted page can only be warm (a held child implies a held
        # parent) or in this same freed batch.
        extra: list[int] = []
        for p in evict:
            for q in self.prefix.subtree_pages(p):
                if (q != p and self.allocator.is_warm(q)
                        and q not in extra):
                    extra.append(q)
        if extra:
            self.allocator.reclaim(extra)
            self.adaptive.note_reclaimed(extra)
            self.stats["warm_reclaimed"] += len(extra)
        return evict + extra

    def _reclaim_warm(self, n_needed: int, protect: set[int]) -> int:
        """Return up to ``n_needed`` warm pages to the free list so a
        gated admission can allocate (reclaim-before-preempt).  The
        adaptive rank orders candidates; each candidate takes its warm
        subtree along (closure).  ``protect`` is the shared chain the
        admission is about to revive — never reclaimed out from under
        it.  Not policy-gated: capacity pressure always wins over
        retention, so the warm tier can never starve admission."""
        taken: list[int] = []
        warm = sorted(self.allocator.warm_pages)
        for p in self.adaptive.reclaim_order(warm):
            if len(taken) >= n_needed:
                break
            if p in protect or p in taken:
                continue
            sub = [q for q in self.prefix.subtree_pages(p)
                   if q not in taken]
            if any(q in protect for q in sub):
                continue
            taken.extend(sub)
        if taken:
            self.prefix.evict(taken)
            for q in taken:
                self._page_fp.pop(q, None)
            self.allocator.reclaim(taken)
            self.adaptive.note_reclaimed(taken)
            self.stats["warm_reclaimed"] += len(taken)
        return len(taken)

    def _retire(self, r: Request, status: str) -> None:
        """Terminal transition for a non-finish exit (cancelled/expired)."""
        r.status = status
        r.done = True
        r.cancel_requested = False
        r.__dict__.pop("_prefix_chunks", None)
        self.stats[status] += 1
        if r.deadline_s is not None:
            # An expired/cancelled deadlined request counts against
            # goodput: it reached terminal state without finishing.
            self.stats["deadline_total"] += 1
        if self.journal is not None and not self._replaying:
            self.journal.append(snap.terminal_event(r))

    def _finish(self, r: Request) -> None:
        r.done = True
        r.status = "finished"
        if self.journal is not None and not self._replaying:
            self.journal.append(snap.terminal_event(r))
        if r.deadline_s is not None:
            self.stats["deadline_total"] += 1
            if (r.submit_t is None
                    or time.perf_counter() - r.submit_t <= r.deadline_s):
                self.stats["deadline_met"] += 1
        slot = r.slot
        self._release_slot(r)
        # Budget exhausted on device (len(generated) == max_new_tokens
        # implies remaining == 0): no zeroing needed for a natural finish.
        self._dirty_slots.discard(slot)

    def _pick_victim(self, head: Request, wave_slots: set[int]
                     ) -> Request | None:
        """Choose a preemption victim for the page-gated ``head``.

        Static engine: the YOUNGEST (most recently admitted) resident.
        Adaptive engine (DESIGN.md §5.7): the CHEAPEST to recompute —
        estimated replay tokens (prompt + emitted) discounted one page's
        worth per page other slots still share (those pages stay
        resident either way), ties youngest-first.  Victim choice is
        placement-only: recompute-restore is bit-identical regardless of
        who gets evicted, so the two engines may pick different victims
        and still emit identical streams.

        Anti-livelock double guard (both engines): a head that was
        itself preempted never triggers another preemption, and only
        never-preempted residents are eligible victims — so natural
        preemptions are bounded by the request count and a
        preempt/restore ping-pong cannot form.  Slots admitted earlier
        in the current wave are off-limits (their prefill hasn't run;
        evicting them would corrupt the wave's buffers)."""
        if not self.preemption or head.preempted_n > 0:
            return None
        cands = [
            r for i, r in enumerate(self.slot_req)
            if r is not None and i not in wave_slots and r.preempted_n == 0
        ]
        if not cands:
            return None
        if self.adaptive is not None:
            return min(cands, key=lambda r: (
                self.adaptive.victim_cost(
                    r, self.allocator, self._slot_pages[r.slot]
                ),
                -r.admit_seq,
            ))
        return max(cands, key=lambda r: r.admit_seq)

    def _preempt(self, victim: Request, forced: bool = False) -> None:
        """Evict a resident mid-stream and re-enqueue it for restore.
        Pages release refcount-aware (shared pages are only dereferenced);
        emitted tokens are already host-side in ``victim.generated``, and
        re-admission prefills prompt + emitted (``_effective_prompt``) so
        the restored stream is bit-identical by construction.  The victim
        re-enters at the queue FRONT: residents are always older than
        anything queued (FIFO admission), so appendleft preserves global
        arrival order."""
        with span("serve.preempt", id=victim.id):
            self._release_slot(victim)
            victim.status = "preempted"
            victim.preempted_n += 1
            self.queue.appendleft(victim)
            self.stats["preempted"] += 1
            if forced:
                self.stats["preempted_forced"] += 1

    def _chaos_forced_preempt(self) -> None:
        """Chaos knob: with seeded probability cfg.chaos_preempt_p, force-
        preempt the youngest resident at a wave boundary — exercising the
        preempt/restore path even when the pool never gates (and for
        non-paged layouts, where genuine page pressure can't arise)."""
        if self._chaos_rng.random() >= self.cfg.chaos_preempt_p:
            return
        cands = [r for r in self.slot_req if r is not None]
        if not cands:
            return
        self._preempt(max(cands, key=lambda r: r.admit_seq), forced=True)

    def _deadline_hit(self, r: Request, now: float) -> bool:
        return (r.deadline_s is not None and r.submit_t is not None
                and now - r.submit_t > r.deadline_s)

    def _sweep_lifecycle(self) -> None:
        """Chunk-boundary sweep: retire cancelled/expired requests, queued
        or resident.  Resident exits free the slot/pages/trie refs
        mid-stream and keep the partial ``generated``."""
        now = time.perf_counter()
        if self.queue:
            keep = []
            for r in self.queue:
                if r.cancel_requested:
                    self._retire(r, "cancelled")
                elif self._deadline_hit(r, now) or (
                    r.max_queue_wait_s is not None
                    and r.submit_t is not None
                    and now - r.submit_t > r.max_queue_wait_s
                ):
                    self._retire(r, "expired")
                else:
                    keep.append(r)
            if len(keep) != len(self.queue):
                self.queue = collections.deque(keep)
        for _, r in self._live():
            if r.cancel_requested:
                self._release_slot(r)
                self._retire(r, "cancelled")
            elif self._deadline_hit(r, now):
                self._release_slot(r)
                self._retire(r, "expired")

    def _acquire_pages(self, head: Request, eff: np.ndarray,
                       wave_slots: set[int]):
        """Allocate the page table for the queue head (paged only):
        shared resident prefix pages (refcount bump) + freshly allocated
        private pages.  While the pool is short, preempt one eligible
        victim per retry — each iteration either admits or removes a
        resident, so the loop terminates.  The prefix lookup re-runs
        every attempt (releasing a victim can shrink the resident chain);
        alloc goes first and share only on success, so a gated head
        leaves every refcount untouched.  Returns
        ``(table, chunks, shared_tokens)`` or ``(None, None, 0)``."""
        need = self._pages_needed(head)
        while True:
            shared, shared_len = [], 0
            chunks = None
            if self.prefix is not None:
                # Chunk the effective prompt once per queue stint
                # (memoized on the request): a page-gated head re-tried
                # every chunk boundary doesn't rebuild it.  Preemption
                # invalidates the memo (the effective prompt grows).
                chunks = getattr(head, "_prefix_chunks", None)
                if chunks is None:
                    chunks = self.prefix.chunks(eff)
                    head._prefix_chunks = chunks
                shared, shared_len = self._shared_prefix(eff, chunks)
            n_fresh = need - len(shared)
            if self._warm_tier:
                # Capacity beats retention: before letting a short alloc
                # gate (or preempt for) this head, reclaim warm pages the
                # policy is merely speculating on.  The head's own shared
                # chain is protected — reclaiming it would evict trie
                # nodes we are about to attach.
                short = n_fresh - self.allocator.free_count()
                if short > 0 and self.allocator.warm_count():
                    self._reclaim_warm(short, protect=set(shared))
            ids = self.allocator.alloc(n_fresh)
            if ids is not None:
                # A shared chain may end in WARM pages (retained at
                # refcount zero): those are revived to refcount 1, not
                # share()d.  Held pages are always a chain prefix and
                # warm ones a suffix (a held child implies a held
                # parent), but membership — not position — is what the
                # allocator cares about.
                warm_set = (
                    {p for p in shared if self.allocator.is_warm(p)}
                    if self._warm_tier else set()
                )
                held_part = [p for p in shared if p not in warm_set]
                if not held_part or self.allocator.share(held_part):
                    if warm_set:
                        warm_part = [p for p in shared if p in warm_set]
                        self.allocator.revive(warm_part)
                        self.adaptive.note_revived(warm_part)
                        self.stats["warm_hits"] += 1
                        self.stats["warm_tokens_saved"] += (
                            len(warm_part) * self.page_size
                        )
                    return shared + ids, chunks, shared_len
                # Injected share refusal (ChaosAllocator): roll back the
                # fresh alloc so the gated head leaves every refcount
                # untouched — the same atomicity a failed alloc gives.
                # The pages were never trie-registered or stamped, so the
                # bare allocator release is the whole rollback.  Warm
                # pages were not revived yet, so they need no rollback.
                self.allocator.release(ids)
            victim = self._pick_victim(head, wave_slots)
            if victim is None:
                return None, None, 0
            self._preempt(victim)

    def _admit_wave(self) -> None:
        with span("serve.admit", wave=self.stats["admission_waves"] + 1):
            if self._chaos_rng is not None:
                self._chaos_forced_preempt()
            if self.adaptive is not None:
                self.adaptive.begin_wave()
            # Wave entries carry the request's EFFECTIVE prompt (prompt +
            # previously emitted tokens for a preempted request being
            # restored, DESIGN.md §5.5) — everything downstream (page
            # demand, prefix chunks, prefill buffers, history) treats it
            # as the prompt.
            wave: list[tuple[int, Request, np.ndarray]] = []
            wave_slots: set[int] = set()
            now = time.perf_counter()
            while self.queue:
                slot = next(
                    (i for i, q in enumerate(self.slot_req) if q is None), None
                )
                if slot is None:
                    break
                # Pop the head BEFORE any preemption retry: victims re-enter
                # at the queue front (appendleft), which would displace a head
                # still sitting at queue[0].
                head = self.queue.popleft()
                with span("serve.admit.request", id=head.id) as sp:
                    eff = self._admit_head(head, slot, wave_slots, now)
                    if eff is not None:
                        sp.set_metadata(
                            prefix=head.prefix_tokens,
                            suffix=len(eff) - head.prefix_tokens)
                if eff is None:
                    self.queue.appendleft(head)
                    break
                wave.append((slot, head, eff))
                wave_slots.add(slot)
            # Park slots vacated mid-stream (preempt/cancel/expire) that this
            # wave did not refill: their device budget must hit zero before
            # the next chunk, or they would keep decoding into pages now
            # owned by others.  (Wave slots are re-armed by the prefill's
            # admitted mask, so they need no zeroing.)
            stale = sorted(self._dirty_slots - wave_slots)
            self._dirty_slots.clear()
            if stale:
                self.remaining = self.remaining.at[jnp.asarray(stale)].set(0)
        if not wave:
            if self._chaos or self._strict:
                self.check_invariants()
            return
        # Each admitted request is prefilled alone, at the bucket of its
        # own unshared suffix (prefix_tokens is 0 without sharing): a
        # wave computes the rows of the requests it admits and no others.
        pads = [_pad_bucket(len(eff) - r.prefix_tokens, self.max_len)
                for _, r, eff in wave]
        with span("serve.prefill", rows=sum(pads)):
            nxt, picks = self._prefill_wave(wave, pads)
        self.stats["prefill_rows_computed"] += sum(pads)
        with span("serve.prefill.sync"):
            first, picks = jax.device_get((nxt, picks))  # 1 sync per wave
        self.stats["host_syncs"] += 1
        if self._moe_rows:
            self.stats["moe_picks_held"] += sum(int(p) for p in picks)
            self.stats["moe_rows_computed"] += sum(pads) * self._moe_rows
        self.stats["admission_waves"] += 1
        if (self.adaptive is not None and self.adaptive.pinned is None
                and self.stats["admission_waves"]
                % self.adaptive.replan_every == 0):
            # Re-plan boundary: feed the counters through the serve-policy
            # lattice (serve/adaptive.py) and install per-class combos.
            # Placement-only — outputs are bit-identical either way.
            with span("serve.replan"):
                self.adaptive.replan(self.stats)
            self.stats["replans"] += 1
        if self.paged:
            self.stats["peak_pages_held"] = max(
                self.stats["peak_pages_held"],
                self.n_pages - self.allocator.free_count(),
            )
        with span("serve.emit"):
            now = time.perf_counter()
            for (_, r, _), tok in zip(wave, first):
                r.generated.append(int(tok[0]))
                self.stats["prefill_tokens"] += 1
                if r.ttft_s is None and r.admit_t is not None:
                    # True TTFT: admission -> first token (prefill compute);
                    # queueing is reported separately as queue_wait_s.
                    r.ttft_s = now - r.admit_t
                if len(r.generated) >= r.max_new_tokens:
                    self._finish(r)
        if self._chaos or self._strict:
            self.check_invariants()

    def _admit_head(self, head: Request, slot: int, wave_slots: set[int],
                    now: float) -> np.ndarray | None:
        """Admit the popped queue head into ``slot``: pages, prefix attach
        and registration, lifecycle state.  Returns its effective prompt,
        or None where the pool gates it (then nothing was taken)."""
        eff = self._effective_prompt(head)
        if self.paged:
            # Admission gates on free pages (FIFO head-of-line: a
            # request that doesn't fit waits — or preempts — rather
            # than being overtaken).  With prefix sharing the head
            # only needs pages for its UNSHARED suffix; the shared
            # prefix rides resident pages via a refcount bump.
            table, chunks, shared_len = self._acquire_pages(
                head, eff, wave_slots
            )
            if table is None:
                return None
            head.prefix_tokens = shared_len
            self._slot_pages[slot] = table
            self.page_table[slot] = -1
            self.page_table[slot, :len(table)] = table
            if self.prefix is not None:
                # Index this prompt's own full pages so later requests
                # can attach; already-resident chunks keep their
                # existing (shared) nodes.
                self.prefix.register(eff, table[:len(chunks)],
                                     chunks=chunks)
                if self.adaptive is not None:
                    # Classify by prompt content (first full page) and
                    # remember which class DECIDES this request's
                    # retention at release time.  A readmission keeps
                    # its original deciding class — its effective
                    # prompt grew, so re-hashing would re-classify.
                    key = self.adaptive.class_key(chunks)
                    head._adaptive_key = key
                    if head.generated:
                        head._adaptive_class = getattr(
                            head, "_adaptive_class", key
                        )
                    else:
                        head._adaptive_class = self.adaptive.note_arrival(
                            key, len(eff),
                            ((len(eff) - 1) // self.page_size)
                            * self.page_size,
                        )
                    self.adaptive.touch(table)
                if shared_len:
                    self.stats["prefix_hits"] += 1
                    if not head.generated:
                        self.stats["prefix_hits_fresh"] += 1
                    self.stats["prefix_pages_shared"] += (
                        shared_len // self.page_size
                    )
                    self.stats["prefix_tokens_shared"] += shared_len
        else:
            head.prefix_tokens = 0    # contiguous: always a full prefill
        # The chunk memo exists only to amortize head-of-line retries;
        # drop it at admission so engine-private (and page-size-
        # dependent) state never outlives the queue.
        head.__dict__.pop("_prefix_chunks", None)
        head.admit_t = now
        if head.submit_t is not None and head.queue_wait_s is None:
            head.queue_wait_s = now - head.submit_t
        head.status = "resident"
        head.slot = slot
        head.admit_seq = self._admit_seq
        self._admit_seq += 1
        self.slot_req[slot] = head
        if head.generated:
            # Preemption restore: its prefill replays work already
            # done once, so it must NOT dilute fresh-admission rates
            # (the serve_stats prefix_hit_rate bug this split fixes).
            self.stats["readmitted"] += 1
            self.stats["recompute_tokens"] += len(head.generated)
        else:
            self.stats["admitted_fresh"] += 1
        return eff

    def _prefill_wave(self, wave: list[tuple[int, Request, np.ndarray]],
                      pads: list[int]) -> tuple[list, list]:
        """Dispatch one prefill per admitted request, in wave order, each
        of shape (1, its pad bucket), so a same-wave sharer reads its
        owner's fresh K/V (DESIGN.md §5.4); returns the device arrays of
        each request's first token and MoE picks on held experts (None
        without MoE), fetched by the caller in one sync."""
        if self.paged:
            # Push the host free-list's view of the page table to device.
            # The table is tiny; replacing the leaf keeps the jitted prefill
            # signature layout-independent (donation still applies).
            self.cache = {**self.cache, "pages": jnp.asarray(self.page_table)}
        firsts, picks = [], []
        for (slot, r, eff), pad in zip(wave, pads):
            n = len(eff) - r.prefix_tokens
            # Actual prefill compute demand (suffix tokens only — shared
            # or warm-revived prefixes cost nothing).  Unlike
            # prefill_tokens (emitted first tokens) this measures WORK,
            # which is what the adaptive-vs-static bench compares.
            self.stats["prefill_work_tokens"] += n
            toks = np.zeros((1, pad), np.int32)
            toks[0, :n] = eff[r.prefix_tokens:]         # right-pad
            toks_d = jnp.asarray(toks)
            if r.prefix_tokens:
                # The n-gram history seeds from the FULL prompt, so
                # drafting under sharing matches the unshared engine; its
                # buffer is the bucket of the full prompt.
                htoks = np.zeros(
                    (1, _pad_bucket(len(eff), self.max_len)), np.int32)
                htoks[0, :len(eff)] = eff
                htoks_d = jnp.asarray(htoks)
            else:
                htoks_d = toks_d
            # Restore-aware seeding: a fresh request samples stream index
            # 0 with a full budget; a restored one samples index
            # len(generated) with the unconsumed remainder (its last
            # emitted token is part of the prefill, whose final logits
            # reproduce the uninterrupted run's next sample).  Arbitrary
            # Python ints (64-bit hashes, negatives) fold into int32 range:
            # still a pure function of the request's seed, so determinism
            # and order-independence are preserved.
            (self.cache, self.cur_tok, self.remaining, self.tok_idx,
             self.hist, self.hist_len, self.seeds, nxt, pk) = self._prefill(
                self.params, self.cache, np.int32(slot), toks_d,
                np.int32(n), np.int32(r.prefix_tokens), htoks_d,
                self.cur_tok, self.remaining,
                np.int32(r.max_new_tokens - len(r.generated) - 1),
                np.int32(len(r.generated)), self.tok_idx, self.hist,
                self.hist_len,
                np.int32((0 if r.seed is None else r.seed) % (2 ** 31)),
                self.seeds,
            )
            firsts.append(nxt)
            picks.append(pk)
        return firsts, picks

    def _count_window_reads(self) -> None:
        """Positions one sliding-window layer reads over this chunk's
        decode steps, against an unbounded read: each live slot decodes
        min(chunk, budget left) tokens, the step at cache length L reading
        min(L + 1, W) keys where full attention reads L + 1."""
        window = self.cfg.sliding_window
        read = dense = 0
        for _, r in self._live():
            length = len(r.prompt) + len(r.generated) - 1
            steps = min(self.chunk_size, r.max_new_tokens - len(r.generated))
            ctx = length + 1 + np.arange(steps)
            read += int(np.minimum(ctx, window).sum())
            dense += int(ctx.sum())
        self.stats["decode_window_positions_read"] += read
        self.stats["decode_window_positions_dense"] += dense

    def _run_chunk(self) -> None:
        if self.paged:
            live = [slot for slot, _ in self._live()]
            self.stats["decode_pages_read"] += self.chunk_size * int(
                (self.page_table[live] >= 0).sum())
            self.stats["decode_pages_dense"] += (
                self.chunk_size * self.slots * self.pages_per_slot)
        if self.cfg.windowed:
            self._count_window_reads()
        with span("serve.decode"):
            (self.cache, self.cur_tok, self.remaining, self.tok_idx,
             self.hist, self.hist_len, toks, actives,
             picks) = self._decode_chunk(
                self.params, self.cache, self.cur_tok, self.remaining,
                self.tok_idx, self.hist, self.hist_len, self.seeds,
            )
        with span("serve.decode.sync"):
            # 1 sync/chunk
            t_np, a_np, picks = jax.device_get((toks, actives, picks))
        self.stats["host_syncs"] += 1
        self.stats["decode_syncs"] += 1
        self.stats["chunks"] += 1
        if self._moe_rows:
            self.stats["moe_picks_held"] += int(picks.sum())
            self.stats["moe_rows_computed"] += (
                self.chunk_size * self.slots * self._moe_rows)
        with span("serve.emit"):
            for slot, r in self._live():
                emitted = a_np[:, slot]
                for i in np.nonzero(emitted)[0]:
                    r.generated.append(int(t_np[i, slot]))
                self.stats["decode_tokens"] += int(emitted.sum())
                if len(r.generated) >= r.max_new_tokens:
                    self._finish(r)

    def _run_spec_chunk(self) -> None:
        with span("serve.decode"):
            (self.cache, self.cur_tok, self.remaining, self.tok_idx,
             self.hist, self.hist_len, toks, emits, accepts, proposed,
             actives) = self._decode_chunk(
                self.params, self.cache, self.cur_tok, self.remaining,
                self.tok_idx, self.hist, self.hist_len, self.seeds,
            )
        # toks/emits: (rounds, b, k+1); accepts/proposed/actives: (rounds, b).
        with span("serve.decode.sync"):
            t_np, e_np, acc_np, prop_np, act_np = jax.device_get(
                (toks, emits, accepts, proposed, actives)
            )                                          # host sync: 1 per chunk
        self.stats["host_syncs"] += 1
        self.stats["decode_syncs"] += 1
        self.stats["chunks"] += 1
        with span("serve.emit"):
            for slot, r in self._live():
                for j in range(t_np.shape[0]):
                    if not act_np[j, slot]:
                        continue
                    row = e_np[j, slot]
                    for t in t_np[j, slot][row]:
                        r.generated.append(int(t))
                    self.stats["decode_tokens"] += int(row.sum())
                    # Spec-round-emitted tokens in their OWN counter: the old
                    # spec_tokens_per_round divided ALL decode tokens (non-
                    # spec chunks included) by spec_rounds, inflating the
                    # ratio whenever plain decode ran in the same session.
                    self.stats["spec_tokens"] += int(row.sum())
                    self.stats["spec_rounds"] += 1
                    self.stats["draft_proposed"] += int(prop_np[j, slot])
                    self.stats["draft_accepted"] += int(acc_np[j, slot])
                if len(r.generated) >= r.max_new_tokens:
                    self._finish(r)

    def check_invariants(self) -> None:
        """Assert engine/allocator/trie conservation (DESIGN.md §5.5);
        called after every wave under chaos and by the fault-injection
        tests.  Uses identity (never ``==``) for request membership —
        dataclass equality on array fields is both wrong and throwing.

        * slot/queue partition: a request is resident in exactly the slot
          that maps it, never also queued, and never terminal;
        * pages held ≡ slot page tables: the allocator's held set is
          exactly the union of resident slots' pages, refcounts equal the
          number of slot tables mapping each page (the trie holds no
          references), and free + held partitions the pool — zero leaks;
        * the device-visible page-table rows mirror the host tables;
        * trie residency ⊆ held pages (no node outlives its storage).

        With quarantine (DESIGN.md §5.6) the pool partition is
        free + held + quarantined, and doomed pages are always held.
        With the adaptive warm tier (DESIGN.md §5.7) it is
        free + held + warm + quarantined; warm pages stay within budget,
        are always trie-registered (warm retention exists only to keep
        prefix nodes attachable), and keep their integrity stamps (their
        content is live KV a future request may attach to).
        """
        self.stats["invariant_checks"] += 1
        queued = list(self.queue)
        for slot, r in enumerate(self.slot_req):
            if r is None:
                continue
            assert r.slot == slot, f"slot {slot} maps request at {r.slot}"
            assert not r.done and r.status == "resident", (
                f"slot {slot} holds a {r.status!r} request"
            )
            assert not any(q is r for q in queued), (
                f"request {r.id!r} is both resident and queued"
            )
            assert len(r.generated) < r.max_new_tokens
        for q in queued:
            assert not q.done and q.status in ("queued", "preempted"), (
                f"queued request {q.id!r} has status {q.status!r}"
            )
        if not self.paged:
            return
        slot_refs: collections.Counter[int] = collections.Counter()
        for slot in range(self.slots):
            pages = self._slot_pages[slot]
            row = self.page_table[slot]
            if self.slot_req[slot] is None:
                assert pages == [], f"vacant slot {slot} leaks pages {pages}"
                assert (row == -1).all(), f"vacant slot {slot} maps {row}"
                continue
            assert len(pages) == len(set(pages)), (
                f"slot {slot} maps a page twice: {pages}"
            )
            assert list(row[:len(pages)]) == pages, (
                f"device/host page-table drift in slot {slot}"
            )
            assert (row[len(pages):] == -1).all()
            slot_refs.update(pages)
        held = self.allocator.held_pages
        assert held == set(slot_refs), (
            f"held/mapped drift: leaked={sorted(held - set(slot_refs))} "
            f"phantom={sorted(set(slot_refs) - held)}"
        )
        for page, refs in slot_refs.items():
            assert self.allocator.ref_count(page) == refs, (
                f"page {page}: allocator refcount "
                f"{self.allocator.ref_count(page)} != {refs} mapping slots"
            )
        free = self.allocator.free_pages
        quar = self.allocator.quarantined_pages
        warm = self.allocator.warm_pages
        assert len(free) == len(set(free)) and not held & set(free)
        assert not quar & held and not quar & set(free), (
            f"quarantined pages back in circulation: "
            f"{sorted(quar & (held | set(free)))}"
        )
        assert self.allocator.doomed_pages <= held, (
            "doomed (pending-quarantine) pages must still be held"
        )
        assert len(warm) <= self.allocator.warm_budget, (
            f"warm tier over budget: {len(warm)} > "
            f"{self.allocator.warm_budget}"
        )
        assert not warm & held and not warm & set(free) and not warm & quar, (
            f"warm pages double-booked: {sorted(warm & (held | set(free) | quar))}"
        )
        assert (sorted(list(free) + list(held) + list(warm) + list(quar))
                == list(range(self.n_pages))), (
            "free + held + warm + quarantined is not a partition of the pool"
        )
        assert not set(self._page_fp) - held - warm, (
            f"integrity stamps outlive their pages: "
            f"{sorted(set(self._page_fp) - held - warm)}"
        )
        if self.prefix is not None:
            resident = self.prefix.resident_pages()
            stray = resident - held - warm
            assert not stray, f"trie nodes outlive their pages: {stray}"
            assert warm <= resident, (
                f"warm pages outside the trie (retention exists only to "
                f"keep prefix nodes attachable): {sorted(warm - resident)}"
            )
        else:
            assert not warm, f"warm pages without a prefix index: {warm}"

    # -- KV page integrity (DESIGN.md §5.6) --------------------------------

    def _pool_leaf_ids(self, leaves: list) -> list[int]:
        """Indices of the paged K/V pool leaves in the flattened cache:
        the arrays whose trailing axes are (n_pages, page_size, heads *
        head_dim).  Slot-indexed leaves (contiguous cross K/V, recurrent
        state, the page table itself) never carry that pair of axes."""
        return [
            i for i, x in enumerate(leaves)
            if hasattr(x, "ndim") and x.ndim >= 3
            and x.shape[-3] == self.n_pages
            and x.shape[-2] == self.page_size
            and x.shape[-1] == self.cfg.n_kv_heads * self.cfg.head_dim_
            and jnp.issubdtype(x.dtype, jnp.floating)
        ]

    def _fingerprint_pages(self, pages, pools=None) -> dict[int, int]:
        """CRC32 per page over the concatenated bytes of every pool leaf's
        page slice — cheap, deterministic, and sensitive to any single
        flipped value.  One host sync pulls the pools unless the caller
        already did (``pools``)."""
        if pools is None:
            leaves = jax.tree_util.tree_leaves(self.cache)
            # One batched transfer for every pool leaf (R001): per-leaf
            # np.asarray would pay one blocking round-trip per leaf.
            pools = jax.device_get(
                [leaves[i] for i in self._pool_leaf_ids(leaves)]
            )
            self.stats["host_syncs"] += 1
        out = {}
        for p in pages:
            c = 0
            for pool in pools:
                c = zlib.crc32(
                    np.ascontiguousarray(pool[..., p, :, :]).tobytes(), c
                )
            out[p] = c
        return out

    def _sealed_pages(self) -> set[int]:
        """Pages wholly below some resident slot's host-computed write
        cursor (len(prompt) + len(generated) - 1 — the §5.5 cursor
        identity).  Sealed content is immutable: per-slot cursors are
        monotone for the life of a residency (spec rollback rewinds only
        within the current round's window, never below a chunk boundary),
        and shared pages sit below EVERY sharer's cursor by construction."""
        sealed: set[int] = set()
        for slot, r in self._live():
            cur = len(r.prompt) + len(r.generated) - 1
            sealed.update(self._slot_pages[slot][: cur // self.page_size])
        return sealed

    def _corrupt_page(self, page: int) -> None:
        """Chaos bit-flip: perturb one element of ``page`` in the first
        pool leaf (every leading stack entry, so any layer's read would
        expose it).  Device-side, exactly like real HBM corruption."""
        leaves, treedef = jax.tree_util.tree_flatten(self.cache)
        i = self._pool_leaf_ids(leaves)[0]
        leaves[i] = leaves[i].at[..., page, 0, 0].add(1)
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def _integrity_sweep(self) -> list[int]:
        """Chunk-boundary integrity pass: stamp newly sealed pages, land
        any injected corruption (chaos_corrupt_p), then verify every
        stamp.  Ordering matters: corruption is injected AFTER stamping
        and BEFORE verification, so a flipped page is detected and healed
        before any subsequent chunk could read it — which is what keeps
        chaos corruption runs bit-identical."""
        self.stats["integrity_sweeps"] += 1
        leaves = jax.tree_util.tree_leaves(self.cache)
        # Batched pull (R001): the sweep's "one host sync" accounting was
        # only honest when the pool had a single leaf; per-leaf
        # np.asarray paid one blocking round-trip per pool leaf.
        pools = jax.device_get(
            [leaves[i] for i in self._pool_leaf_ids(leaves)]
        )
        self.stats["host_syncs"] += 1
        new = self._sealed_pages() - self._page_fp.keys()
        if new:
            self._page_fp.update(
                self._fingerprint_pages(sorted(new), pools=pools)
            )
        if (self._corrupt_rng is not None and self._page_fp
                and self._corrupt_rng.random() < self.cfg.chaos_corrupt_p):
            stamped = sorted(self._page_fp)
            victim = stamped[int(self._corrupt_rng.integers(len(stamped)))]
            self._corrupt_page(victim)
            self.stats["injected_corruptions"] += 1
            pools = None     # device bytes changed; verify must re-pull
        return self.verify_pages(_pools=pools)

    def verify_pages(self, _pools=None) -> list[int]:
        """Re-fingerprint every stamped page; quarantine mismatches and
        self-heal by recompute-restore (DESIGN.md §5.6).

        A corrupted page is quarantined in the allocator (a held page is
        doomed: it leaves circulation at its last release, never the free
        list), then EVERY slot whose table maps it is preempted — the
        refcount-aware release tears all sharers off the bad page, and
        re-admission recomputes their KV into healthy pages from host
        truth, bit-identically.  Victims re-enter the queue oldest-first
        (descending-admit_seq appendleft), preserving arrival order.
        Returns the corrupted page ids."""
        if not self.paged or not self._page_fp:
            return []
        current = self._fingerprint_pages(sorted(self._page_fp), pools=_pools)
        bad = sorted(
            p for p, fp in self._page_fp.items() if current[p] != fp
        )
        if not bad:
            return []
        badset = set(bad)
        for p in bad:
            if p not in self._page_fp:
                continue   # already handled as part of a warm subtree
            if self._warm_tier and self.allocator.is_warm(p):
                # A corrupted WARM page has no sharers to heal — just
                # drop it from circulation.  Its warm descendants (a warm
                # page's children are never held) lose their ancestor
                # chain, so the whole subtree leaves the trie; clean
                # descendants reclaim to the free list while the bad
                # page — and any corrupted descendant — quarantines.
                sub = self.prefix.subtree_pages(p)
                self.prefix.evict(sub)
                for q in sub:
                    self._page_fp.pop(q, None)
                sub_bad = [q for q in sub if q in badset]
                clean = [q for q in sub if q not in badset]
                for q in sub_bad:
                    self.stats["corrupted_pages"] += 1
                    self.allocator.quarantine(q)
                if clean:
                    self.allocator.reclaim(clean)
                    self.stats["warm_reclaimed"] += len(clean)
                self.adaptive.note_reclaimed(sub)
                continue
            self._page_fp.pop(p)
            self.stats["corrupted_pages"] += 1
            self.allocator.quarantine(p)
        victims = [
            r for slot, r in self._live()
            if badset & set(self._slot_pages[slot])
        ]
        # Corruption healing is exempt from the once-only victim guard —
        # a slot reading poisoned KV must be restored no matter its
        # preemption history.
        for r in sorted(victims, key=lambda r: r.admit_seq, reverse=True):
            self.stats["healed_requests"] += 1
            self._preempt(r)
        if self._chaos or self._strict:
            self.check_invariants()
        return bad

    # -- snapshot / restore (DESIGN.md §5.6) -------------------------------

    def request(self, request_id: str) -> Request | None:
        """Live handle for a submitted request id (terminal ones kept)."""
        return self._by_id.get(request_id)

    def results(self) -> dict[str, list[int]]:
        """Emitted tokens per known request id — the stream-identity view
        the recovery gates compare."""
        return {rid: list(r.generated) for rid, r in self._by_id.items()}

    def snapshot(self, path: str) -> dict:
        """Serialize host-side truth to ``path`` (atomic, checksummed).

        Nothing device-resident is saved: the §5.5 restore-identity
        invariant makes every KV byte recomputable from (prompt, emitted
        tokens, seed, token index), so in-flight requests are recorded as
        re-queueable work and terminal requests keep their streams.  The
        journal offset recorded here is where replay resumes after an
        unplanned kill.  Callers invoke it between steps (chunk
        boundaries) — exactly where all host state is consistent."""
        self.stats["snapshots"] += 1
        residents = sorted(
            (r for _, r in self._live()), key=lambda r: r.admit_seq
        )
        queued = list(self.queue)
        terminal = [r for r in self._by_id.values() if r.done]
        records = (
            [snap.request_record(r) for r in terminal]
            # Residents re-enter as "preempted": re-queued work with
            # tokens already emitted.  Their crash-eviction does NOT
            # consume the anti-livelock budget (preempted_n untouched).
            + [snap.request_record(r, status="preempted") for r in residents]
            + [snap.request_record(r) for r in queued]
        )
        alloc = None
        if self.paged:
            alloc = {
                "refcounts": {
                    str(p): self.allocator.ref_count(p)
                    for p in sorted(self.allocator.held_pages)
                },
                "quarantined": sorted(self.allocator.quarantined_pages),
                "doomed": sorted(self.allocator.doomed_pages),
                "page_tables": {
                    str(slot): list(self._slot_pages[slot])
                    for slot in range(self.slots)
                    if self._slot_pages[slot]
                },
            }
        payload = {
            "cfg": snap.cfg_fingerprint(self.cfg),
            "geometry": {
                "slots": self.slots,
                "max_len": self.max_len,
                "paged": self.paged,
                "page_size": self.page_size if self.paged else None,
                "n_pages": self.n_pages if self.paged else None,
            },
            "counters": {
                "next_id": self._next_id, "admit_seq": self._admit_seq,
            },
            "stats": dict(self.stats),
            # Adaptive class knowledge survives restore (a counter-driven
            # policy must not diverge after crash-recovery); warm pages
            # themselves are volatile — restore starts with a cold warm
            # tier and relearns residency, which is placement-only.
            "adaptive": (
                self.adaptive.snapshot_state()
                if self.adaptive is not None else None
            ),
            "requests": records,
            "allocator": alloc,
            "journal": {
                "path": self.journal_path,
                "offset": (
                    self.journal.offset() if self.journal is not None else 0
                ),
            },
        }
        snap.write_snapshot(path, payload)
        return {
            "path": path,
            "requests": len(records),
            "in_flight": len(residents) + len(queued),
        }

    @staticmethod
    def _audit_snapshot(payload: dict) -> None:
        """Cross-check the snapshot's allocator section against its page
        tables — a snapshot whose refcounts don't equal the number of
        mapping tables was corrupt at WRITE time and must not restore."""
        alloc = payload.get("allocator")
        if not alloc:
            return
        mapped: collections.Counter[int] = collections.Counter()
        for pages in alloc["page_tables"].values():
            mapped.update(pages)
        refs = {int(p): n for p, n in alloc["refcounts"].items()}
        if refs != dict(mapped):
            raise SnapshotError("inconsistent", (
                "snapshot refcounts disagree with its page tables: "
                f"refcounts={refs} mapped={dict(mapped)}"
            ))

    def _request_from_record(self, rec: dict, now: float) -> Request:
        r = Request(
            prompt=np.asarray(rec["prompt"], np.int32),
            max_new_tokens=rec["max_new_tokens"],
            seed=rec["seed"],
            id=rec["id"],
            deadline_s=rec["deadline_s"],
            max_queue_wait_s=rec["max_queue_wait_s"],
        )
        r.generated = list(rec["generated"])
        r.status = rec["status"]
        r.preempted_n = rec["preempted_n"]
        r.cancel_requested = rec["cancel_requested"]
        r.ttft_s = rec["ttft_s"]
        r.queue_wait_s = rec["queue_wait_s"]
        r.done = rec["status"] in ("finished", "cancelled", "expired")
        if not r.done:
            # SLO clocks restart at recovery: wall time spent dead isn't
            # chargeable to the request's deadline.
            r.submit_t = now
        return r

    def _hard_reset(self) -> None:
        """Discard ALL engine state — device buffers, slots, queue,
        allocator, trie, stamps, counters — returning to the just-
        constructed blank.  The jitted dispatches survive (same shapes),
        so a restore re-uses every compilation."""
        b = self.slots
        self.cache = self.model.init_cache(
            self.params, batch=b, max_len=self.max_len, **self._cache_kwargs
        )
        self.cur_tok = jnp.zeros((b,), jnp.int32)
        self.remaining = jnp.zeros((b,), jnp.int32)
        self.tok_idx = jnp.zeros((b,), jnp.int32)
        self.seeds = jnp.zeros((b,), jnp.int32)
        self.hist = jnp.zeros((b, self.max_len + 1), jnp.int32)
        self.hist_len = jnp.zeros((b,), jnp.int32)
        self.slot_req = [None] * b
        self.queue = collections.deque()
        self._by_id = {}
        self._next_id = 0
        self._admit_seq = 0
        self._dirty_slots = set()
        self._page_fp = {}
        if self.paged:
            self.allocator = self._make_allocator()
            self.page_table = np.full((b, self.pages_per_slot), -1, np.int32)
            self._slot_pages = [[] for _ in range(b)]
            if self.prefix is not None:
                self.prefix = PrefixIndex(self.page_size)
        if self.adaptive is not None:
            self.adaptive = AdaptivePolicy(
                warm_pages=self.adaptive.warm_pages,
                replan_every=self.adaptive.replan_every,
                page_size=self.adaptive.page_size,
                spec_k=self.adaptive.spec_k,
                pinned=self.adaptive.pinned,
            )
        for k in self.stats:
            self.stats[k] = 0

    def restore(self, path: str | None = None) -> dict:
        """Rebuild the engine from a snapshot and/or the request journal.

        Validates BEFORE discarding anything: a corrupt/mismatched
        snapshot raises a typed ``SnapshotError`` and leaves the live
        engine untouched.  Then hard-resets, re-installs the quarantine
        set, re-enqueues every in-flight request (snapshot residents
        first, in admission order, then the queue — global arrival
        order), and replays the journal suffix past the snapshot's
        offset: unknown submits re-enter the queue, journaled terminal
        events re-retire their requests with the exact tokens they had
        emitted.  ``path=None`` replays the whole journal (snapshotless
        recovery).  Device KV is rebuilt entirely by the ordinary
        recompute-prefill admission path, so the restored streams are
        bit-identical to the uninterrupted run (§5.5/§5.6)."""
        if path is None and self.journal_path is None:
            raise SnapshotError(
                "no_source", "restore() needs a snapshot path or a journal"
            )
        payload = None
        if path is not None:
            payload = snap.load_snapshot(path)
            mine = snap.cfg_fingerprint(self.cfg)
            if payload.get("cfg") != mine:
                drift = sorted(
                    k for k in set(mine) | set(payload.get("cfg") or {})
                    if mine.get(k) != (payload.get("cfg") or {}).get(k)
                )
                raise SnapshotError("config_mismatch", (
                    f"snapshot was taken under a different config: {drift}"
                ))
            geo = {
                "slots": self.slots,
                "max_len": self.max_len,
                "paged": self.paged,
                "page_size": self.page_size if self.paged else None,
                "n_pages": self.n_pages if self.paged else None,
            }
            if payload.get("geometry") != geo:
                raise SnapshotError("geometry_mismatch", (
                    f"snapshot geometry {payload.get('geometry')} != "
                    f"engine geometry {geo}"
                ))
            self._audit_snapshot(payload)
        self._hard_reset()
        now = time.perf_counter()
        restored = replayed = 0
        journal_offset = 0
        self._replaying = True
        try:
            if payload is not None:
                self._next_id = payload["counters"]["next_id"]
                self._admit_seq = payload["counters"]["admit_seq"]
                for k, v in payload["stats"].items():
                    if k in self.stats:
                        self.stats[k] = v
                if (self.adaptive is not None
                        and payload.get("adaptive")):
                    self.adaptive.restore_state(payload["adaptive"])
                alloc = payload.get("allocator")
                if self.paged and alloc:
                    # Doomed pages' holders died with the crash: they are
                    # quarantined outright (refcount 0 now).
                    for p in alloc["quarantined"] + alloc["doomed"]:
                        self.allocator.quarantine(p)
                for rec in payload["requests"]:
                    r = self._request_from_record(rec, now)
                    self._by_id[r.id] = r
                    if not r.done:
                        self.queue.append(r)
                        restored += 1
                journal_offset = (payload.get("journal") or {}).get(
                    "offset", 0
                )
            if (self.journal_path is not None
                    and os.path.exists(self.journal_path)):
                for ev in snap.RequestJournal.replay(
                        self.journal_path, journal_offset):
                    replayed += 1
                    if ev.get("ev") == "submit":
                        if ev["id"] in self._by_id:
                            continue
                        r = Request(
                            prompt=np.asarray(ev["prompt"], np.int32),
                            max_new_tokens=ev["max_new_tokens"],
                            seed=ev["seed"],
                            id=ev["id"],
                            deadline_s=ev["deadline_s"],
                            max_queue_wait_s=ev["max_queue_wait_s"],
                        )
                        r.status = "queued"
                        r.submit_t = now
                        self._by_id[r.id] = r
                        self.queue.append(r)
                        restored += 1
                    elif ev.get("ev") == "terminal":
                        r = self._by_id.get(ev["id"])
                        if r is None:
                            continue
                        if not r.done and any(
                                q is r for q in self.queue):
                            self.queue = collections.deque(
                                q for q in self.queue if q is not r
                            )
                            restored -= 1
                        r.generated = list(ev["generated"])
                        r.status = ev["status"]
                        r.done = True
        finally:
            self._replaying = False
        self.stats["restores"] += 1
        if self._chaos or self._strict:
            self.check_invariants()
        return {
            "restored": restored,
            "replayed_events": replayed,
            "terminal": sum(1 for r in self._by_id.values() if r.done),
        }

    # -- scheduler loop ----------------------------------------------------

    def step(self) -> bool:
        """One scheduler tick: lifecycle sweep (cancel/expire), admission
        (with preemption), one decode chunk if anything is resident, then
        the integrity sweep and a journal flush — so every step ends on a
        durable, verified chunk boundary.  Returns True while work
        remains — callers interleave ``cancel()`` / ``submit()`` with
        ``step()`` for mid-stream control."""
        with span("serve.step"):
            with span("serve.sweep"):
                self._sweep_lifecycle()
            self._admit_wave()
            if self.slot_req.count(None) < self.slots:
                (self._run_spec_chunk if self.spec else self._run_chunk)()
            if self.integrity:
                with span("serve.integrity"):
                    self._integrity_sweep()
            if self.journal is not None:
                with span("serve.journal"):
                    self.journal.flush()
        if (self.cfg.chaos_crash_after_wave > 0
                and self.stats["admission_waves"]
                >= self.cfg.chaos_crash_after_wave):
            # Injected kill (DESIGN.md §5.6): the journal is flushed and
            # every host structure sits at a chunk boundary — exactly
            # the state an external SIGKILL between steps would leave on
            # disk.  The engine object is dead; recovery restores a
            # fresh one from snapshot + journal.
            raise ChaosCrash(self.stats["admission_waves"])
        return bool(self.queue) or self.slot_req.count(None) < self.slots

    def _progress_marker(self) -> tuple:
        """Observable progress: tokens emitted or lifecycle transitions.
        Anything that changes one of these is forward motion; a step that
        changes none was pure spin."""
        s = self.stats
        return (s["decode_tokens"], s["prefill_tokens"], s["preempted"],
                s["cancelled"], s["expired"])

    def drain(self) -> None:
        """Run the scheduler until no work remains (all requests reach a
        terminal state: finished, cancelled or expired).

        Watchdog (DESIGN.md §5.6): ``no_progress_limit`` consecutive
        zero-progress steps with work still pending raise a typed
        ``NoProgressError`` instead of spinning forever — the failure
        mode of a queue gated behind a quarantine-shrunk pool, or of
        pathological injected alloc/share-failure rates."""
        idle = 0
        while True:
            before = self._progress_marker()
            if not self.step():
                return
            if self._progress_marker() != before:
                idle = 0
                continue
            idle += 1
            if idle >= self.no_progress_limit:
                gating = {
                    "queued": len(self.queue),
                    "resident": sum(
                        1 for r in self.slot_req if r is not None
                    ),
                    "free_pages": (
                        self.allocator.free_count() if self.paged else None
                    ),
                    "usable_pages": (
                        self.allocator.usable_pages() if self.paged else None
                    ),
                    "quarantined": (
                        len(self.allocator.quarantined_pages)
                        + len(self.allocator.doomed_pages)
                        if self.paged else 0
                    ),
                    "chaos_alloc_fail_p": self.cfg.chaos_alloc_fail_p,
                    "chaos_share_fail_p": self.cfg.chaos_share_fail_p,
                }
                raise NoProgressError(
                    f"drain() made no progress for {idle} consecutive "
                    f"steps: {gating}"
                )

    def run(self, requests: list[Request]) -> list[Request]:
        self.submit(requests)
        self.drain()
        return requests
