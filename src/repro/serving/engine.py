"""Continuous-batching LLM engine (the "LLM serving instance" of Def. 2.3).

Real-execution engine: actual JAX models (reduced configs on CPU; the same
code path jit-compiles for TPU), iteration-level scheduling a la
Orca/vLLM:

  * fixed slot array (``max_slots``) holding the running batch,
  * paged KV accounting via ``BlockManager`` (admission + preemption),
  * **chunked, length-bucketed prefill**: prompts are split into chunks of
    at most ``prefill_chunk_tokens``; every ``step()`` runs ONE chunk for
    all mid-prefill slots as a single batched jit call (chunk length padded
    to a power-of-two bucket so jit shapes stay bounded) and THEN a decode
    iteration for the fully-prefilled slots — a long batch-job prompt no
    longer stalls interactive decodes (SLOs-Serve / chunked-prefill
    co-scheduling),
  * ``step()`` = admit-from-pull-source, one prefill chunk round, one
    decode iteration for all decode-ready slots,
  * **device-resident hot loop**: the jitted decode / chunk calls DONATE
    the KV cache (``jax.jit(..., donate_argnums)``) so the page pool is
    updated in place instead of copied every iteration; the block table is
    maintained incrementally by ``BlockManager`` (persistent fixed-shape
    int32 array + version counter) and its device copy refreshed only when
    it changed; ``steps(k)`` fuses up to ``EngineConfig.decode_burst``
    decode iterations into ONE jitted ``lax.while_loop`` dispatch
    (device-side argmax, length increments and EOS / max-token finish
    flags accumulated in a mask) with a single host sync per burst —
    falling back to single-step whenever a slot is mid-prefill or the
    block pool is at the preemption edge,
  * request eviction with host-side KV/state snapshots (the paper's
    eviction LSO — resume skips prefill entirely; mid-prefill evictions
    resume from the last completed chunk),
  * model swapping (flush KV, replace weights; paper's swap LSO),
  * selectable attention backend: ``"xla"`` / ``"pallas"`` keep the dense
    per-slot KV arrays (Pallas kernels interpret on CPU, Mosaic on TPU);
    ``"paged-xla"`` / ``"paged-pallas"`` store KV as a single physical page
    pool ``(layers, num_blocks, KVH, block_size, D)`` addressed through the
    ``BlockManager`` block tables — the PagedAttention layout the paper's
    LSOs assume from their vLLM backend.  Paged mode makes KV capacity
    ``kv_blocks * block_size`` tokens SHARED across slots (vs
    ``max_slots * max_seq_len`` dense), eviction snapshots copy only the
    sequence's pages, and freed pages are physically reused by later
    admissions.  Token-for-token identical to the dense backends.

Backend support matrix (rows = engine capabilities; see
``models/attention.py`` for the kernel-level view):

  backend        KV layout       prefill chunk        decode
  "xla"          per-slot dense  jnp two-segment      jnp masked SDPA
  "pallas"       per-slot dense  jnp two-segment      Pallas blocked kernel
  "paged-xla"    page pool       stacked-gather SDPA  gather + masked SDPA
  "paged-pallas" page pool       fused paged-prefill  paged multi-page-tile
                                 Pallas kernel        Pallas kernel

  * dense backends: all archs, incl. SWA (rolling cache) and kv_quant;
    SSM/hybrid/enc-dec ride the legacy single-shot prefill.
  * paged backends: full-attention transformer archs with chunked prefill
    only (engine __init__ gates); kv_quant supported via int8 page pools
    with fused-dequant kernels; ``EngineConfig.pages_per_tile`` tunes the
    kernels' multi-page kv tiles (None = auto from block_size).
  * donation + burst apply to ALL four backends: every backend's decode /
    chunk jit call donates the cache (``EngineConfig.donate_buffers``,
    default on), and ``steps()`` bursts ``decode_burst`` iterations per
    dispatch token-identically to the single-step loop (KV blocks for the
    whole burst are reserved up front, so a burst can never write an
    unallocated page; completion timestamps within a burst collapse to
    the burst's host sync).
  * **prefix sharing** (``EngineConfig.prefix_sharing``, default on) is a
    paged-backend capability — dense per-slot KV has no physical pages to
    share, so the flag is inert on "xla"/"pallas".  On the paged backends
    admission matches the incoming prompt against the ``BlockManager``
    prefix index (full blocks published as their chunks complete) and
    attaches the hit chain refcounted instead of re-prefilling it:
    chunked prefill starts at the first unshared token, page writes only
    ever target private blocks (copy-on-write peels a shared tail block
    before any divergent write — ``_apply_cow`` runs the pending page
    copies before every dispatch), eviction pins shared blocks instead of
    freeing or copying them (snapshots hold only privately-owned pages),
    and ``fork_slot`` clones a running decode onto a free slot with zero
    page copies.  Token-for-token identical to ``prefix_sharing=False``
    on every backend; a pinned (shared) snapshot resumes only on the
    engine that evicted it — cross-engine mid-decode migration of a
    shared sequence raises, like cross-layout resume.

Dense cache pytrees have layout (layers/sites, batch, ...), so slot insert
/ extract are uniform ``tree_map``s over axis 1; paged caches have no
batch axis and are extracted/restored by page id instead.

**Cancellation contract** (``cancel_request`` / ``shed_slots`` — the async
front end's hooks, ``serving.frontend``):

  * ``cancel_request(req)`` terminates ``req`` wherever it lives: a
    resident slot is freed mid-decode or mid-prefill (pending COW copies
    are applied first so no queued page copy can land on a page the free
    list hands to a later admission), an eviction snapshot is discarded
    (releasing any shared-prefix pins on its source pool), and a request
    the engine has never seen is a no-op returning False.  On success the
    request is marked ``cancelled`` with ``completion_time`` stamped, its
    KV blocks are back on the free list (shared blocks: its refcount is
    dropped; the pages live on for the other sharers / the prefix index),
    and the slot is immediately admittable.  Cancellation between the
    dispatch that produced a token and the host sync that records it is
    safe: the hook runs on the orchestrator thread between ``steps()``
    calls, never concurrently with a dispatch.
  * ``shed_slots(should_shed, drop=)`` applies a predicate over the
    running batch: matching slots are EVICTED (snapshot to host, resumable
    later — ``drop=False``, the deferral policy) or CANCELLED outright
    (``drop=True``); the returned requests have ``_in_flight`` cleared so
    the virtual-queue owner can re-pull or account them.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.request import Request
from repro.models.model_factory import Model
from repro.serving.kv_cache import BlockManager
from repro.spans import span

ATTENTION_BACKENDS = ("xla", "pallas", "paged-xla", "paged-pallas")


def _single_device(tree) -> Optional[jax.Device]:
    """The one device holding every array leaf of ``tree``; None when the
    leaves span several devices (or there are none)."""
    devices = {d for leaf in jax.tree.leaves(tree)
               if isinstance(leaf, jax.Array) for d in leaf.devices()}
    return devices.pop() if len(devices) == 1 else None


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq_len: int = 512
    block_size: int = 16
    kv_blocks: Optional[int] = None    # None => max_slots*max_seq_len worth
    eos_token: Optional[int] = None
    dtype: Any = jnp.float32
    # Chunked prefill: max prompt tokens processed per slot per step().
    # 0 disables chunking (legacy single-shot batch=1 prefill at admit).
    prefill_chunk_tokens: int = 128
    # Chunk-length padding buckets; None => powers of two up to
    # prefill_chunk_tokens.  Bounded buckets keep the number of distinct
    # jit shapes (and thus compiles) small.
    prefill_buckets: Optional[Tuple[int, ...]] = None
    # Serving attention backend: None follows the model config's
    # use_pallas_attention flag; "xla" / "pallas" force the jnp or Pallas
    # (flash / blocked-decode, interpret mode off-TPU) paths respectively.
    # "paged-xla" / "paged-pallas" switch the KV cache to a physically
    # paged block-table pool (full-attention transformer archs with
    # chunked prefill only).
    attention_backend: Optional[str] = None
    # KV pages per kernel grid step for the paged Pallas kernels (decode +
    # fused prefill-chunk): multi-page tiles keep MXU tiles full when
    # block_size is small.  None = auto-derive from block_size (targets
    # 128-row tiles); forwarded to the model config's paged_pages_per_tile.
    pages_per_tile: Optional[int] = None
    # Fused multi-step decode dispatch: ``steps()`` runs up to this many
    # decode iterations inside one jitted lax.while_loop (one host sync per
    # burst instead of per token).  1 = the single-step ``step()`` loop.
    decode_burst: int = 1
    # Donate the KV cache (and decode token array) into the jitted decode /
    # chunk calls so XLA updates the pool in place instead of copying it
    # every iteration.  Off only for A/B comparisons (tests).
    donate_buffers: bool = True
    # Maintain the (max_slots, max_blocks_per_seq) block table incrementally
    # inside BlockManager (refreshing the device copy only when it changed)
    # instead of rebuilding it in Python twice per step.  Off only for A/B
    # benchmarking against the seed behavior.
    incremental_block_table: bool = True
    # Run repro.analysis.invariants.check_engine at every step()/steps()
    # round boundary (BlockManager conservation, refcount accounting,
    # slot-table sync, per-slot length contracts).  Also forced on by
    # QLINT_INVARIANTS=1; QLINT_INVARIANTS_SAMPLE=N checks every Nth
    # round.  Debug aid — O(pool + slots) python per checked round.
    debug_invariants: bool = False
    # Refcounted prefix sharing + copy-on-write pages (paged backends only;
    # inert on the dense layouts, which have no physical pages to share).
    # Admission matches prompts against the BlockManager prefix index and
    # skips prefill for cached full blocks.  Off for A/B comparison — token
    # streams are identical either way, only pool usage / prefill work and
    # the prefix_* stats change.
    prefix_sharing: bool = True

    @property
    def paged(self) -> bool:
        return self.attention_backend is not None \
            and self.attention_backend.startswith("paged")

    def resolved_kv_blocks(self) -> int:
        if self.kv_blocks is not None:
            return self.kv_blocks
        return (self.max_slots * self.max_seq_len) // self.block_size

    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    def resolved_buckets(self) -> Tuple[int, ...]:
        if self.prefill_buckets:
            buckets = sorted(self.prefill_buckets)
            if self.prefill_chunk_tokens > 0 \
                    and buckets[-1] < self.prefill_chunk_tokens:
                # buckets must cover the largest possible chunk, else the
                # padding falls back to exact lengths and the jit-shape
                # bound is lost
                buckets.append(self.prefill_chunk_tokens)
            return tuple(buckets)
        if self.prefill_chunk_tokens <= 0:
            return ()
        buckets = []
        b = 16
        while b < self.prefill_chunk_tokens:
            buckets.append(b)
            b *= 2
        buckets.append(self.prefill_chunk_tokens)
        return tuple(buckets)


@dataclasses.dataclass
class EngineStats:
    decode_iterations: int = 0
    prefills: int = 0
    prefill_chunks: int = 0
    evictions: int = 0
    resumes: int = 0
    model_swaps: int = 0
    tokens_generated: int = 0
    preemptions: int = 0
    decode_time: float = 0.0
    prefill_time: float = 0.0
    swap_time: float = 0.0
    decode_bursts: int = 0         # fused multi-token decode dispatches
    # prefix sharing (paged backends with EngineConfig.prefix_sharing)
    prefix_lookups: int = 0        # fresh chunked admissions that probed
    prefix_hits: int = 0           # ... and attached a shared chain
    prefix_shared_blocks: int = 0  # blocks attached without re-prefill
    prefix_shared_tokens: int = 0  # prompt tokens skipped by prefill
    prompt_tokens_admitted: int = 0  # denominator for the hit-rate counters
    cow_copies: int = 0            # copy-on-write page copies applied
    forks: int = 0                 # fork_slot clones
    # async front-end hooks (frontend cancellation / overload shedding)
    cancellations: int = 0         # cancel_request frees (slot or snapshot)
    sheds: int = 0                 # shed_slots evict/drop actions
    # cross-engine snapshot migration (self-healing cluster lifecycle)
    migrations_out: int = 0        # snapshots made portable on request
    migrations_in: int = 0         # foreign snapshots resumed here


class ContinuousBatchingEngine:
    def __init__(self, model: Model, params, cfg: EngineConfig,
                 model_name: str = "default",
                 clock: Callable[[], float] = time.monotonic):
        if cfg.attention_backend not in ATTENTION_BACKENDS + (None,):
            raise ValueError(
                f"attention_backend must be one of {ATTENTION_BACKENDS} "
                f"or None, got {cfg.attention_backend!r}")
        self.cfg = cfg
        # Two explicit time bases.  ``self.clock`` stamps the REQUEST
        # LIFECYCLE (first_token_time, completion_time, redelivery
        # backoff gates) so virtual-clock drivers own the schedule;
        # ``self._wall`` is ALWAYS real wall time and feeds the
        # calibration stats (prefill_time / decode_time / swap_time),
        # which measure actual compute even when the lifecycle clock is
        # simulated.  Timed regions must never mix the two.
        self.clock = clock
        self._wall = time.monotonic
        # Serializes engine-internal state (slots, page pool, snapshots)
        # between the agent thread's rounds and cross-thread LSOs
        # (migration_sweep materialize, drain eviction).  The controller
        # only ever acquires it NON-blocking while holding its own lock
        # (see core/qlm.py), so lock order engine -> controller is the
        # one that may block and no cycle exists.
        self.lock = threading.RLock()
        self.paged = cfg.paged
        # sharing needs a physical page pool: inert on the dense layouts
        self.prefix_sharing = bool(cfg.prefix_sharing) and self.paged
        self.model = self._with_backend(model)
        self.params = params
        # every per-round input is uploaded to the weights' device, so an
        # engine on chip i never routes its dispatches through chip 0
        self.device = _single_device(params)
        self.model_name = model_name
        self.stats = EngineStats()
        if self.paged:
            if self.model.init_paged_cache is None:
                raise ValueError(
                    f"attention_backend {cfg.attention_backend!r} requires an "
                    f"arch with pageable KV (got {self.model.cfg.arch_type})")
            if self.model.cfg.sliding_window is not None:
                raise ValueError(
                    "paged attention backends support full attention only "
                    "(rolling SWA page reuse is a ROADMAP follow-on)")
            if cfg.prefill_chunk_tokens <= 0:
                raise ValueError(
                    "paged attention backends require chunked prefill "
                    "(prefill_chunk_tokens > 0): the legacy single-shot "
                    "path writes per-slot dense caches")

        # prefix sharing keeps freed-but-indexed blocks cached so follow-up
        # turns (same leading tokens, submitted after the original request
        # finished) still match the chain
        self.block_mgr = BlockManager(cfg.resolved_kv_blocks(),
                                      cfg.block_size,
                                      cache_freed=self.prefix_sharing)
        if cfg.incremental_block_table:
            self.block_mgr.attach_slot_table(cfg.max_slots,
                                             cfg.max_blocks_per_seq())
        # persistent device copy of the slot block table, refreshed only
        # when BlockManager.table_version moves
        self._bt_device = None
        self._bt_version_seen = -1
        self.slots: List[Optional[Request]] = [None] * cfg.max_slots
        self.lengths = np.zeros(cfg.max_slots, np.int32)
        # prompt tokens already prefilled per slot; a slot is mid-prefill
        # while prefill_pos < prompt_len (decode-ready otherwise)
        self.prefill_pos = np.zeros(cfg.max_slots, np.int32)
        self.cache = self._init_cache()
        self.pull_source: Optional[Callable[[], Optional[Request]]] = None
        self.completed: List[Request] = []
        # requests whose eviction snapshot pins shared blocks in OUR pool:
        # before a pool reset (model swap) kills the pins, the pinned pages
        # are materialized into the snapshots so the requests stay
        # resumable (see _materialize_pinned_snapshots)
        self._pinned_snapshots: List[Request] = []
        self._pushback: Optional[Request] = None
        # requests that finished INSIDE admit() (legacy path, EOS/max_new on
        # the prefill token); drained into the next step()'s return value
        self._admit_completed: List[Request] = []

        self._jit_compute()

    def _with_backend(self, model: Model) -> Model:
        """Route the model's attention through the configured backend
        (None = keep the model config's own use_pallas_attention) and
        forward the paged-kernel tile tunable."""
        backend = self.cfg.attention_backend
        changes = {}
        if backend is not None:
            want = backend.endswith("pallas")
            if model.cfg.use_pallas_attention != want:
                changes["use_pallas_attention"] = want
        if self.cfg.pages_per_tile is not None \
                and model.cfg.paged_pages_per_tile != self.cfg.pages_per_tile:
            changes["paged_pages_per_tile"] = self.cfg.pages_per_tile
        if changes:
            from repro.models.model_factory import build_model
            return build_model(dataclasses.replace(model.cfg, **changes))
        return model

    def _init_cache(self):
        # the pool lives on the device that holds the weights, so engines
        # whose params sit on different chips each fill their own HBM
        with jax.default_device(self.device):
            if self.paged:
                cache = self.model.init_paged_cache(
                    self.cfg.resolved_kv_blocks(), self.cfg.block_size,
                    self.cfg.dtype)
            else:
                cache = self.model.init_cache(
                    self.cfg.max_slots, self.cfg.max_seq_len, self.cfg.dtype)
        return self._put(cache)  # commits it there, no copy

    def _put(self, x) -> jax.Array:
        """Upload a host array to the weights' device (JAX's default
        device when the weights span several)."""
        return jax.device_put(x, self.device)

    def release_cache(self) -> None:
        """Free the KV pool's device buffers now instead of at garbage
        collection (a throwaway calibration engine hands its HBM back
        before the serving engines allocate theirs).  The engine is
        unusable afterwards."""
        for leaf in jax.tree.leaves(self.cache):
            leaf.delete()
        self.cache = None

    def _jit_compute(self) -> None:
        # donate the cache (arg 1) — the page pool is the whole KV budget,
        # donating it lets XLA update it in place instead of copying it
        # every iteration — and the decode token array (arg 2), which is
        # consumed by the same-shaped next_tokens output.  The block table
        # (last paged arg) is NEVER donated: it is the persistent device
        # copy reused across steps.
        donate = (1, 2) if self.cfg.donate_buffers else ()
        chunk_donate = (1,) if self.cfg.donate_buffers else ()
        if self.paged:
            self._decode_fn = jax.jit(self._decode_paged_impl,
                                      donate_argnums=donate)
            self._chunk_fn = jax.jit(self._prefill_chunk_paged_impl,
                                     donate_argnums=chunk_donate)
        else:
            self._decode_fn = jax.jit(self._decode_impl, donate_argnums=donate)
            self._chunk_fn = jax.jit(self._prefill_chunk_impl,
                                     donate_argnums=chunk_donate)
        self._burst_fn = jax.jit(self._decode_burst_impl,
                                 donate_argnums=chunk_donate)
        # COW page copy: dst pages <- src pages across every pool leaf
        # (axis 1 = blocks).  Donated so XLA updates the pool in place.
        self._cow_fn = jax.jit(
            lambda cache, src, dst: jax.tree.map(
                lambda full: full.at[:, dst].set(full[:, src]), cache),
            donate_argnums=(0,) if self.cfg.donate_buffers else ())
        self._prefill_cache = {}  # per-length jitted single-shot prefill
        self._bt_device = None
        self._bt_version_seen = -1

    # ------------------------------------------------------------------
    # jitted compute
    # ------------------------------------------------------------------
    def _decode_impl(self, params, cache, tokens, lengths):
        logits, new_cache = self.model.decode_step(params, cache, tokens, lengths)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, new_cache

    def _prefill_chunk_impl(self, params, cache, tokens, starts, valid):
        logits, new_cache = self.model.prefill_chunk(params, cache, tokens,
                                                     starts, valid)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, new_cache

    def _decode_paged_impl(self, params, cache, tokens, lengths, block_table):
        logits, new_cache = self.model.decode_step_paged(
            params, cache, tokens, lengths, block_table)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, new_cache

    def _prefill_chunk_paged_impl(self, params, cache, tokens, starts, valid,
                                  block_table):
        logits, new_cache = self.model.prefill_chunk_paged(
            params, cache, tokens, starts, valid, block_table)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, new_cache

    def _decode_burst_impl(self, params, cache, tokens, lengths, remaining,
                           active, n_steps, block_table):
        """Up to ``decode_burst`` decode iterations in ONE device dispatch:
        a ``lax.while_loop`` carrying (tokens, lengths, remaining-new-token
        budgets, active mask, cache) with the argmax, length increments and
        EOS / max-token / max-seq-len finish flags all computed on device.
        Returns the (decode_burst, max_slots) token buffer (sentinel -1 for
        slots inactive at that iteration) and the final cache — ONE host
        sync per burst instead of one per token.

        ``n_steps`` is traced (bursts shrink near the KV-capacity edge
        without recompiling); the buffer width is the static
        ``cfg.decode_burst``.  The caller pre-reserves every block a full
        burst can write, so no iteration ever lands on an unallocated page.
        Finished slots keep re-writing their final token's k/v at their
        (frozen) last position — idempotent, and their pages are freed at
        the host sync.  ``block_table`` is None for the dense backends.
        """
        K = max(int(self.cfg.decode_burst), 1)
        max_seq = self.cfg.max_seq_len
        eos = self.cfg.eos_token

        def body(state):
            i, tokens, lengths, remaining, active, cache, out = state
            if self.paged:
                logits, cache = self.model.decode_step_paged(
                    params, cache, tokens, lengths, block_table)
            else:
                logits, cache = self.model.decode_step(
                    params, cache, tokens, lengths)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            produced = jnp.where(active, nxt, tokens)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(active, nxt, jnp.int32(-1)), i, axis=0)
            step = active.astype(jnp.int32)
            lengths = lengths + step
            remaining = remaining - step
            # mirror _finish_if_done exactly (post-increment conditions)
            fin = (remaining <= 0) | (lengths >= max_seq)
            if eos is not None:
                fin = fin | (produced == eos)
            return (i + 1, produced, lengths, remaining,
                    active & ~fin, cache, out)

        def cond(state):
            return (state[0] < n_steps) & jnp.any(state[4])

        out0 = jnp.full((K, self.cfg.max_slots), -1, jnp.int32)
        state = (jnp.int32(0), tokens, lengths, remaining, active, cache, out0)
        state = jax.lax.while_loop(cond, body, state)
        return state[6], state[5]

    def _block_table_array(self) -> np.ndarray:
        """From-scratch rebuild of the (max_slots, max_blocks_per_seq) int32
        block table (sentinel ``num_blocks`` for unallocated logical blocks
        and empty slots — writes dropped, reads clamped+masked).

        This is the REFERENCE path: the hot loop uses the incremental table
        ``BlockManager.slot_table()`` via ``_device_block_table`` and only
        falls back here when ``cfg.incremental_block_table`` is off (seed
        behavior, kept for A/B benchmarking).  The property suite asserts
        the two always agree."""
        sentinel = self.block_mgr.num_blocks
        bt = np.full((self.cfg.max_slots, self.cfg.max_blocks_per_seq()),
                     sentinel, np.int32)
        for i in self.active_slots():
            r = self.slots[i]
            if self.block_mgr.has(r.req_id):
                row = self.block_mgr.block_table(r.req_id)
                assert len(row) <= bt.shape[1], (len(row), bt.shape)
                bt[i, :len(row)] = row
        return bt

    def _device_block_table(self):
        """Device copy of the slot block table, re-uploaded only when the
        BlockManager's incremental table changed since the last dispatch
        (the seed rebuilt + re-uploaded the full table twice per step)."""
        if not self.cfg.incremental_block_table:
            return self._put(self._block_table_array())
        version = self.block_mgr.table_version
        if self._bt_device is None or self._bt_version_seen != version:
            # .copy(): the manager mutates its table in place and device_put
            # may alias host memory on CPU — the device copy must be a
            # snapshot of THIS version
            self._bt_device = self._put(self.block_mgr.slot_table().copy())
            self._bt_version_seen = version
        return self._bt_device

    def _prefill_one(self, prompt: np.ndarray, extras: Dict[str, Any]):
        """Prefill a single request (batch=1, exact length — SSM-state safe)."""
        L = len(prompt)
        key = (L,) + tuple(sorted(extras))
        if key not in self._prefill_cache:
            def fn(params, batch, cache):
                logits, new_cache = self.model.prefill(params, batch, cache)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return tok, new_cache
            self._prefill_cache[key] = jax.jit(fn)
        with jax.default_device(self.device):
            batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
            batch.update({k: jnp.asarray(v)[None] for k, v in extras.items()})
            cache1 = self.model.init_cache(1, self.cfg.max_seq_len,
                                           self.cfg.dtype)
        tok, cache1 = self._prefill_cache[key](self.params, batch, cache1)
        return int(tok[0]), cache1

    # ------------------------------------------------------------------
    # slot plumbing
    # ------------------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def _insert_cache(self, slot_cache, b: int) -> None:
        self.cache = jax.tree.map(
            lambda full, one: full.at[:, b].set(one[:, 0]), self.cache, slot_cache)

    def _extract_cache(self, b: int):
        # qlint: disable=host-sync-in-hot-path -- intended device->host copy: the eviction snapshot must leave the pool
        return jax.tree.map(lambda full: np.asarray(full[:, b]), self.cache)

    def _restore_cache(self, snapshot, b: int) -> None:
        self.cache = jax.tree.map(
            lambda full, snap: full.at[:, b].set(self._put(snap)),
            self.cache, snapshot)

    def _extract_pages(self, block_ids: List[int]):
        """Paged eviction snapshot: copy ONLY the given pages (axis 1 of
        each (layers, num_blocks, ...) pool leaf) to host memory — the
        physical reclamation the dense per-slot layout couldn't do.  Under
        prefix sharing the caller passes only the PRIVATE tail (shared
        blocks stay alive in the pool, pinned by the snapshot)."""
        bt = np.asarray(block_ids, np.int32)  # qlint: disable=host-sync-in-hot-path -- host list -> int32 index array, no device sync
        # qlint: disable=host-sync-in-hot-path -- intended device->host copy: paged eviction snapshot leaves the pool
        return jax.tree.map(lambda full: np.asarray(full[:, bt]), self.cache)

    def _restore_pages(self, snapshot, block_ids: List[int],
                       offset: int = 0) -> None:
        """Scatter snapshotted page contents into freshly allocated pages
        starting at logical position ``offset`` (the pinned shared prefix,
        already resident, precedes them).  The allocation may be LARGER
        than the snapshot (the resume also reserves the next decode step's
        slot); extra pages are written before they are ever read."""
        n_snap = jax.tree.leaves(snapshot)[0].shape[1]
        assert len(block_ids) - offset >= n_snap, \
            (len(block_ids), offset, n_snap)
        ids = self._put(np.asarray(block_ids[offset:offset + n_snap],  # qlint: disable=host-sync-in-hot-path -- host list -> device upload, no sync
                                   np.int32))
        self.cache = jax.tree.map(
            lambda full, snap: full.at[:, ids].set(self._put(snap)),
            self.cache, snapshot)

    def _apply_cow(self) -> None:
        """Apply pending copy-on-write page copies (BlockManager re-pointed
        the tables; the page CONTENTS move here) — must run before any
        dispatch that could write a COW destination page, and before an
        eviction snapshot reads one."""
        if not self.paged:
            return
        ops = self.block_mgr.take_cow_ops()
        if not ops:
            return
        # pad to a power-of-two width so _cow_fn compiles O(log max_ops)
        # distinct shapes, not one per pending-op count (a mid-serve
        # compile is exactly the host-side stall class the device-resident
        # loop removed).  Padding repeats the last real op: duplicate
        # scatter indices carrying IDENTICAL values are deterministic,
        # whereas an identity pad could collide with a real op on the same
        # destination page
        width = 1
        while width < len(ops):
            width *= 2
        pad = [ops[-1]] * (width - len(ops))
        src = self._put(np.asarray([s for s, _ in ops] + [p[0] for p in pad],  # qlint: disable=host-sync-in-hot-path -- host op list -> device upload, no sync
                                   np.int32))
        dst = self._put(np.asarray([d for _, d in ops] + [p[1] for p in pad],  # qlint: disable=host-sync-in-hot-path -- host op list -> device upload, no sync
                                   np.int32))
        self.cache = self._cow_fn(self.cache, src, dst)
        self.stats.cow_copies += len(ops)

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    def decode_slots(self) -> List[int]:
        """Slots whose prefill is complete (participate in decode)."""
        return [i for i, r in enumerate(self.slots)
                if r is not None and self.prefill_pos[i] >= r.prompt_len]

    def prefilling_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots)
                if r is not None and self.prefill_pos[i] < r.prompt_len]

    def num_active(self) -> int:
        return len(self.active_slots())

    def running_requests(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    # ------------------------------------------------------------------
    # admission (request pulling LSO actuation point)
    # ------------------------------------------------------------------
    def _owed_prefill_blocks(self) -> int:
        """KV blocks committed to mid-prefill slots but not yet allocated
        (admission reserves only the first chunk; the rest arrives
        chunk-by-chunk via ``BlockManager.extend``)."""
        owed = 0
        for i in self.prefilling_slots():
            r = self.slots[i]
            have = len(self.block_mgr.block_table(r.req_id)) \
                if self.block_mgr.has(r.req_id) else 0
            owed += max(self.block_mgr.blocks_needed(r.prompt_len + 1) - have, 0)
        return owed

    def _usable_pins(self, snap) -> Optional[List[int]]:
        """The pinned shared blocks of an eviction snapshot, IF they live in
        THIS engine's current pool (owner + epoch match).  ``[]`` for an
        unshared snapshot; None when the pins belong to another pool (or a
        pool epoch that has since been reset) — the prefix KV is then
        unreachable here."""
        pinned = snap.get("pinned") or []
        if not pinned:
            return []
        if snap.get("pin_owner") is self.block_mgr \
                and snap.get("pin_epoch") == self.block_mgr.epoch:
            return pinned
        return None

    def _discard_snapshot(self, req: Request) -> None:
        """Drop a snapshot, releasing any pins it holds on its SOURCE pool
        (the snapshot carries its owner, so this is safe cross-engine;
        stale epochs no-op inside release_pins)."""
        snap, req.snapshot = req.snapshot, None
        if snap and snap.get("pinned"):
            snap["pin_owner"].release_pins(snap["pinned"], snap["pin_epoch"])

    def can_admit(self, req: Request) -> bool:
        if self._free_slot() is None:
            return False
        if self.paged and req.extras:
            # modality extras ride the legacy single-shot prefill, which has
            # no paged variant: refuse (pull loop hands the request back via
            # pushback) instead of exploding inside admit()
            return False
        snap = req.snapshot
        shared_blocks = 0
        if snap is not None:
            pins = self._usable_pins(snap)
            if pins is None and req.generated > 0:
                # shared blocks pinned in another pool: not resumable here
                # mid-decode (admit would raise) — let the pull loop hand
                # the request back instead
                return False
            shared_blocks = len(pins or ())
        elif self.prefix_sharing and self._use_chunked(req.extras or {}):
            # admission-time prefix match: LIVE indexed chains arrive from
            # the pool, not the free list.  Freed-but-cached matches (ref 0)
            # don't count — share_prefix revives them OUT of the allocatable
            # pool, so capacity-wise they cost as much as a fresh block.
            shared_blocks = sum(
                1 for b in self.block_mgr.match_prefix(req.prompt_tokens)
                if self.block_mgr.ref_count(b) >= 1)
        if snap is not None \
                and snap.get("prefill_pos", req.prompt_len) >= req.prompt_len:
            # decode-phase resume: only the snapshotted tokens plus the next
            # decode step's KV slot are needed (a request evicted at the
            # max_seq_len boundary must stay re-admittable so it can emit
            # its final token)
            need = snap["length"] + 1
        else:
            need = req.prompt_len + req.generated + 1
        if need > self.cfg.max_seq_len:
            return False
        # conservative: the WHOLE prompt must be coverable up front — counting
        # blocks still owed to other mid-prefill slots — even though chunked
        # prefill allocates chunk-by-chunk; otherwise two long prompts could
        # both pass the check and one would be guaranteed to preempt
        # mid-prefill.
        return self.block_mgr.can_allocate(
            need, reserve_blocks=self._owed_prefill_blocks(),
            shared_blocks=shared_blocks)

    def _use_chunked(self, extras: Dict[str, Any]) -> bool:
        return (self.cfg.prefill_chunk_tokens > 0
                and self.model.prefill_chunk is not None
                and not extras)

    def _chunk_quantum(self) -> int:
        """Effective chunk size: clamped to the rolling SWA cache length so
        a single chunk can never write the same cache slot twice (duplicate
        scatter indices resolve nondeterministically)."""
        C = self.cfg.prefill_chunk_tokens
        w = self.model.cfg.sliding_window
        if C > 0 and w is not None:
            C = min(C, min(self.cfg.max_seq_len, w))
        return C

    def admit(self, req: Request, extras: Optional[Dict[str, Any]] = None) -> bool:
        """Start prefill for (or snapshot-restore) ``req`` in a free slot.

        On the chunked path admission only reserves the first chunk's KV
        blocks and marks the slot mid-prefill; the actual compute happens
        inside subsequent ``step()`` calls, interleaved with decode.
        """
        slot = self._free_slot()
        if slot is None or not self.can_admit(req):
            return False
        t0 = self._wall()
        admitted = self.clock()
        ex = extras or req.extras or {}
        my_layout = "paged" if self.paged else "dense"
        if req.snapshot is not None \
                and req.snapshot.get("layout", "dense") != my_layout:
            # snapshot taken under the OTHER KV layout: page contents can't
            # be transplanted across layouts.  Recompute the prefill when
            # nothing was generated yet; past that the generated tokens'
            # KV is unrecoverable.
            if req.generated == 0:
                self._discard_snapshot(req)
            else:
                raise ValueError(
                    f"cannot resume a {req.snapshot.get('layout', 'dense')} "
                    f"KV snapshot on a {my_layout} engine mid-decode")
        if req.snapshot is not None \
                and self._usable_pins(req.snapshot) is None:
            # the snapshot's shared-prefix blocks are STILL pinned in
            # another engine's pool (or an epoch that has been reset):
            # only the private pages travelled with the snapshot, so the
            # prefix KV is unreachable here.  The migration path
            # (materialize_snapshot on the owner, driven by
            # QLMController.migration_sweep) makes such snapshots
            # portable BEFORE they reach a foreign engine; recompute when
            # nothing was generated yet (the discard releases the
            # foreign pins).
            if req.generated == 0:
                self._discard_snapshot(req)
            else:
                raise ValueError(
                    "cannot resume a live-pinned KV snapshot outside the "
                    "engine that evicted it mid-decode (materialize it "
                    "first: cross-engine migration)")
        if req.snapshot is not None \
                and req.snapshot.get("prefill_pos", req.prompt_len) < req.prompt_len \
                and not self._use_chunked(ex):
            # mid-prefill snapshot but THIS engine can't continue chunking
            # (chunking disabled, or the arch has no prefill_chunk): drop it
            # and recompute the full prefill instead of spinning on a
            # zero-token chunk round
            self._discard_snapshot(req)
        if req.snapshot is not None:
            # eviction resume: restore KV/state, no prefill recompute.
            # Mid-prefill snapshots resume chunking from the last chunk.
            snap = req.snapshot
            length = int(snap["length"])
            ppos = int(snap.get("prefill_pos", req.prompt_len))
            if ppos >= req.prompt_len:
                # decode-phase: cover the snapshotted tokens AND the next
                # decode step's write slot (kv_tokens can be one short when
                # the eviction was an append_token-failure preemption)
                kv_tokens = int(snap.get("kv_tokens", length + 1))
                alloc_tokens = max(kv_tokens, length + 1)
            else:
                alloc_tokens = int(snap.get("kv_tokens", ppos))
            pinned = self._usable_pins(snap) or []
            if pinned:
                # the shared prefix never left the pool (snapshot-pinned):
                # the pins transfer back to the sequence, only the private
                # tail below is re-scattered from host memory
                blocks = self.block_mgr.resume_pinned(req.req_id, pinned,
                                                      alloc_tokens)
            else:
                blocks = self.block_mgr.allocate(req.req_id, alloc_tokens)
            self.block_mgr.bind_slot(req.req_id, slot)
            if self.paged:
                self._restore_pages(snap["cache"], blocks,
                                    offset=len(pinned))
            else:
                self._restore_cache(snap["cache"], slot)
            self.lengths[slot] = length
            self.prefill_pos[slot] = ppos
            if snap.get("pin_owner") is not None \
                    and snap.get("pin_owner") is not self.block_mgr:
                # the snapshot was taken in ANOTHER engine's pool and
                # arrived portable (materialized): a completed migration
                self.stats.migrations_in += 1
            req.snapshot = None  # pins were transferred, not released
            self.stats.resumes += 1
            self.slots[slot] = req
        elif self._use_chunked(ex):
            shared: List[int] = []
            if self.prefix_sharing:
                self.stats.prefix_lookups += 1
                shared = self.block_mgr.match_prefix(req.prompt_tokens)
            # first unshared token: chunked prefill starts here (the match
            # is capped at prompt_len - 1, so the final chunk always has at
            # least one real token and produces the first-token logits)
            start = len(shared) * self.cfg.block_size
            first = min(self._chunk_quantum(), req.prompt_len - start)
            if shared:
                self.block_mgr.share_prefix(req.req_id, start + first, shared)
                self.stats.prefix_hits += 1
                self.stats.prefix_shared_blocks += len(shared)
                self.stats.prefix_shared_tokens += start
            else:
                self.block_mgr.allocate(req.req_id, first)
            # unconditional: a re-admission that missed the cache (e.g. a
            # recompute on another engine) must clear any stale hit record
            req.prefix_shared_tokens = start
            self.stats.prompt_tokens_admitted += req.prompt_len
            self.block_mgr.bind_slot(req.req_id, slot)
            self.prefill_pos[slot] = start
            self.lengths[slot] = start
            self.slots[slot] = req
        else:
            if self.paged:
                # only reachable by an explicit admit(req, extras={...})
                # call — pull-source requests with req.extras are refused in
                # can_admit above
                raise ValueError(
                    "paged attention backends have no legacy single-shot "
                    "prefill path (modality extras and non-chunking archs "
                    "need a dense backend)")
            # legacy single-shot path (SSM/hybrid/enc-dec state carry, and
            # modality extras that must ride the full-prompt prefill).
            # Compute first — a raising prefill must leave the engine clean.
            tok, cache1 = self._prefill_one(np.asarray(req.prompt_tokens), ex)  # qlint: disable=host-sync-in-hot-path -- host prompt list -> array for the one-shot prefill path
            self.slots[slot] = req
            self._insert_cache(cache1, slot)
            self.lengths[slot] = req.prompt_len
            self.prefill_pos[slot] = req.prompt_len
            self.block_mgr.allocate(req.req_id, req.prompt_len + 1)
            self.block_mgr.bind_slot(req.req_id, slot)
            now = self.clock()
            if req.first_token_time is None:
                req.first_token_time = now
            req.output_tokens.append(tok)
            req.generated += 1
            self.stats.prefills += 1
            # same first-token completion check as the chunked path (EOS on
            # the prefill token / max_new_tokens == 1) — may free the slot.
            # Lands in self.completed immediately; the _admit_completed
            # buffer lets the next step() also RETURN it.
            n0 = len(self._admit_completed)
            self._finish_if_done(slot, tok, now, self._admit_completed)
            self.completed.extend(self._admit_completed[n0:])
        if req.admitted_time is None:
            req.admitted_time = admitted
        self.stats.prefill_time += self._wall() - t0
        return True

    # ------------------------------------------------------------------
    # eviction LSO
    # ------------------------------------------------------------------
    def evict_slot(self, slot: int) -> Request:
        """Snapshot the slot's KV/state to host memory and free it.

        TPU adaptation of the paper's async GPU→CPU copy: ``device_get`` of
        the slot slice (the engine overlaps this with the next decode
        iteration when dispatch is async).  Mid-prefill slots keep their
        chunk progress in the snapshot and resume without recompute.
        """
        req = self.slots[slot]
        assert req is not None
        kv_tokens = self.block_mgr.seq_tokens(req.req_id) \
            if self.block_mgr.has(req.req_id) else 0
        if self.paged:
            # pending COW copies must land before the snapshot reads pages
            self._apply_cow()
            # shared leading blocks are NOT freed and NOT copied: the
            # departing sequence's reference becomes a snapshot pin, so the
            # chain survives in the pool (and stays prefix-matchable) even
            # if every other sharer finishes before this request resumes.
            # Only the privately-owned tail pages travel to host memory.
            pinned, private = self.block_mgr.evict_split(req.req_id)
            cache_snap = self._extract_pages(private)
        else:
            pinned = []
            cache_snap = self._extract_cache(slot)
            self.block_mgr.free(req.req_id)
        req.snapshot = {
            "cache": cache_snap,
            "length": int(self.lengths[slot]),
            "prefill_pos": int(self.prefill_pos[slot]),
            # blocks to re-allocate on resume (paged restore needs the page
            # count to match; dense resume keeps the same accounting)
            "kv_tokens": kv_tokens,
            "layout": "paged" if self.paged else "dense",
            # prefix-sharing pin bookkeeping (empty without sharing)
            "pinned": pinned,
            "pin_owner": self.block_mgr,
            "pin_epoch": self.block_mgr.epoch,
            "shared_tokens": len(pinned) * self.cfg.block_size,
        }
        req.n_evictions += 1
        if pinned:
            # opportunistic purge: entries whose snapshot was consumed by a
            # resume (or discarded) need no materialization at swap time
            self._pinned_snapshots = [
                r for r in self._pinned_snapshots
                if r.snapshot is not None and r.snapshot.get("pinned")]
            self._pinned_snapshots.append(req)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self.prefill_pos[slot] = 0
        self.stats.evictions += 1
        return req

    def evict_request(self, req_id: int) -> Optional[Request]:
        for i, r in enumerate(self.slots):
            if r is not None and r.req_id == req_id:
                return self.evict_slot(i)
        return None

    def flush(self) -> List[Request]:
        """Evict everything (used before a model swap)."""
        return [self.evict_slot(i) for i in self.active_slots()]

    # ------------------------------------------------------------------
    # cancellation + shedding hooks (async front end; contract in the
    # module docstring)
    # ------------------------------------------------------------------
    def _cancel_slot(self, slot: int) -> Request:
        """Free a resident slot WITHOUT a snapshot: the request is done
        (cancelled), so its KV pages go straight back to the free list.
        Pending COW copies must land first — a queued (src, dst) page copy
        whose dst this free releases would otherwise overwrite a page a
        later admission already owns."""
        req = self.slots[slot]
        assert req is not None, slot
        if self.paged:
            self._apply_cow()
        self.block_mgr.free(req.req_id)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self.prefill_pos[slot] = 0
        req._in_flight = False
        req.cancelled = True
        if req.completion_time is None:
            req.completion_time = self.clock()
        self.stats.cancellations += 1
        return req

    def cancel_request(self, req: Request) -> bool:
        """Terminate ``req`` wherever it lives in THIS engine: resident
        slot (freed mid-decode/mid-prefill) or eviction snapshot
        (discarded, shared-prefix pins released).  Returns False when the
        engine holds no state for it (still queued elsewhere — the caller
        marks it cancelled itself)."""
        for i, r in enumerate(self.slots):
            if r is not None and r.req_id == req.req_id:
                self._cancel_slot(i)
                return True
        if req.snapshot is not None:
            self._discard_snapshot(req)
            req.cancelled = True
            if req.completion_time is None:
                req.completion_time = self.clock()
            self.stats.cancellations += 1
            return True
        return False

    def shed_slots(self, should_shed: Callable[[Request], bool],
                   drop: bool = False) -> List[Request]:
        """Overload shedding over the running batch: every active slot
        whose request matches ``should_shed`` is evicted (``drop=False``:
        snapshot to host, resumable when pressure clears) or cancelled
        outright (``drop=True``: KV freed, ``req.shed`` marked).  Returns
        the shed requests with ``_in_flight`` cleared."""
        out: List[Request] = []
        for i in list(self.active_slots()):
            req = self.slots[i]
            if req is None or not should_shed(req):
                continue
            if drop:
                self._cancel_slot(i)
                req.shed = True
            else:
                self.evict_slot(i)
                req._in_flight = False
            self.stats.sheds += 1
            out.append(req)
        return out

    def abandon(self) -> List[Request]:
        """Crash salvage (``serving.faults`` / ``QLMController.mark_dead``):
        reclaim every resident request WITHOUT stamping it terminal — the
        requests go back to the global queue for redelivery, so unlike
        ``_cancel_slot`` this sets no ``cancelled`` / ``completion_time``.
        Host-side bookkeeping only: the pool's contents are garbage after
        a crash, so no device compute runs, and pending COW page copies
        are dropped with the pool (their destinations are freed here, not
        handed to a future owner).  Returns the abandoned requests —
        resident slots plus any pushback limbo — with ``_in_flight``
        cleared and BlockManager accounting conserved (every allocation
        freed)."""
        out: List[Request] = []
        self.block_mgr._cow_ops.clear()
        for i in self.active_slots():
            req = self.slots[i]
            self.block_mgr.free(req.req_id)
            self.slots[i] = None
            self.lengths[i] = 0
            self.prefill_pos[i] = 0
            req._in_flight = False
            out.append(req)
        pushed = self.take_pushback()
        if pushed is not None:
            pushed._in_flight = False
            out.append(pushed)
        return out

    def _materialize_one(self, req: Request) -> bool:
        """Promote one still-live pinned snapshot to a self-contained one:
        copy the pinned pages' CONTENTS into the snapshot (prepended
        before the private tail) and release the pins.  After this the
        snapshot is PORTABLE: any engine with the same KV layout resumes
        it token-identically (the cross-engine migration primitive).
        Returns False when there is nothing to save (snapshot resumed /
        discarded / pinned elsewhere / stale epoch)."""
        snap = req.snapshot
        if not snap or not snap.get("pinned") \
                or snap.get("pin_owner") is not self.block_mgr \
                or snap.get("pin_epoch") != self.block_mgr.epoch:
            return False
        pinned = snap["pinned"]
        shared_pages = self._extract_pages(pinned)
        snap["cache"] = jax.tree.map(
            lambda shared, private: np.concatenate([shared, private],
                                                   axis=1),
            shared_pages, snap["cache"])
        self.block_mgr.release_pins(pinned, snap["pin_epoch"])
        snap["pinned"] = []
        return True

    def materialize_snapshot(self, req: Request) -> bool:
        """Cross-engine migration hook (``QLMController.migration_sweep``
        / ``drain_instance``): make ``req``'s eviction snapshot portable
        so a DIFFERENT engine can resume it.  Single-request form of
        ``_materialize_pinned_snapshots``; the request drops out of this
        engine's pinned-snapshot ledger once its pins are gone."""
        out = self._materialize_one(req)
        if out:
            self.stats.migrations_out += 1
            self._pinned_snapshots = [
                r for r in self._pinned_snapshots
                if r.snapshot is not None and r.snapshot.get("pinned")]
        return out

    def _materialize_pinned_snapshots(self) -> None:
        """Promote every still-live pinned snapshot to a self-contained one
        (see ``_materialize_one``).  Must run while the pool buffers are
        still alive — called before a pool reset (model swap) would kill
        the pins, so a request evicted with a shared prefix stays
        resumable after the engine swaps back to its model (the
        pre-sharing behavior)."""
        for req in self._pinned_snapshots:
            self._materialize_one(req)
        self._pinned_snapshots = []

    # ------------------------------------------------------------------
    # fork (parallel-sampling style sequence cloning)
    # ------------------------------------------------------------------
    def fork_slot(self, slot: int) -> Optional[Request]:
        """Clone a decode-phase request into a free slot, sharing EVERY KV
        page with the source (refcounts, zero page copies; the manager
        copy-on-writes a partial tail block so the two decodes never
        scatter into the same page — the copy lands at the next dispatch).
        Greedy decoding makes the clone deterministic: it continues exactly
        as the source would.  Returns None when no slot is free; raises
        OutOfBlocksError when the tail COW can't get a block.  Paged
        backends with ``prefix_sharing`` only."""
        if not self.prefix_sharing:
            raise ValueError(
                "fork_slot requires a paged attention backend with "
                "EngineConfig.prefix_sharing enabled")
        src = self.slots[slot]
        assert src is not None, slot
        if self.prefill_pos[slot] < src.prompt_len:
            raise ValueError("cannot fork a mid-prefill slot")
        new_slot = self._free_slot()
        if new_slot is None:
            return None
        clone = Request(
            prompt_tokens=list(src.prompt_tokens), model=src.model,
            slo=src.slo, arrival_time=src.arrival_time,
            max_new_tokens=src.max_new_tokens, slo_class=src.slo_class,
            priority=src.priority)
        clone.output_tokens = list(src.output_tokens)
        clone.generated = src.generated
        clone.first_token_time = src.first_token_time
        self.block_mgr.fork(src.req_id, clone.req_id)
        self.block_mgr.bind_slot(clone.req_id, new_slot)
        self.slots[new_slot] = clone
        self.lengths[new_slot] = self.lengths[slot]
        self.prefill_pos[new_slot] = self.prefill_pos[slot]
        self.stats.forks += 1
        return clone

    # ------------------------------------------------------------------
    # model swapping LSO
    # ------------------------------------------------------------------
    def swap_model(self, model: Model, params, model_name: str) -> List[Request]:
        t0 = self._wall()
        evicted = self.flush()
        # swapped-out requests' snapshots belong to the OLD model: drop them
        # (their KV is meaningless under the new weights; discard releases
        # any prefix-sharing pins before the pool reset below)
        for r in evicted:
            self._discard_snapshot(r)
        # EARLIER evictions' snapshots stay valid (the VQ re-feeds them only
        # when their model is loaded again): the pool reset below would kill
        # their pins, so copy the pinned page contents into the snapshots
        # while the old pool buffers are still alive
        self._materialize_pinned_snapshots()
        self.model = self._with_backend(model)
        self.params = params
        self.device = _single_device(params)
        self.model_name = model_name
        if self.paged and self.model.init_paged_cache is None:
            raise ValueError(
                f"cannot swap a {self.model.cfg.arch_type} model into a "
                "paged-backend engine (no pageable KV)")
        self.cache = self._init_cache()
        self.block_mgr.reset()
        self._jit_compute()
        self.stats.model_swaps += 1
        self.stats.swap_time += self._wall() - t0
        return evicted

    # ------------------------------------------------------------------
    # one iteration
    # ------------------------------------------------------------------
    def take_pushback(self) -> Optional[Request]:
        r, self._pushback = self._pushback, None
        return r

    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.resolved_buckets():
            if n <= b:
                return b
        return n

    def _finish_if_done(self, slot: int, tok: int, now: float,
                        done: List[Request]) -> bool:
        req = self.slots[slot]
        eos = (self.cfg.eos_token is not None and tok == self.cfg.eos_token)
        # capacity finish fires at max_seq_len, NOT max_seq_len - 1: a slot
        # at lengths == max_seq_len - 1 still has one legal decode step
        # (its write lands at cache slot max_seq_len - 1) whose token must
        # be emitted before the slot retires — the final token itself
        # needs no KV slot because nothing attends after it.
        if eos or req.generated >= req.max_new_tokens \
                or self.lengths[slot] >= self.cfg.max_seq_len:
            req.completion_time = now
            done.append(req)
            self.block_mgr.free(req.req_id)
            self.slots[slot] = None
            self.lengths[slot] = 0
            self.prefill_pos[slot] = 0
            return True
        return False

    def _prefill_chunk_round(self, done: List[Request]) -> None:
        """One chunk of prefill for EVERY mid-prefill slot, batched into a
        single jit call padded to the smallest covering length bucket."""
        work = self.prefilling_slots()
        if not work:
            return
        with span("qlm.engine.prefill", slots=len(work)):
            with span("qlm.engine.prep"):
                t0 = self._wall()
                C = self._chunk_quantum()
                chunks: Dict[int, Tuple[np.ndarray, int, bool]] = {}
                for i in work:
                    req = self.slots[i]
                    pos = int(self.prefill_pos[i])
                    n = min(C, req.prompt_len - pos)
                    final = pos + n >= req.prompt_len
                    # chunk-granular KV growth (+1 slot for the first
                    # decode token on the final chunk, mirroring
                    # single-shot accounting)
                    need = req.prompt_len + 1 if final else pos + n
                    if not self.block_mgr.extend(req.req_id, need):
                        # mid-prefill OOM: preempt; the snapshot keeps
                        # chunk progress and the request becomes
                        # re-pullable (sim _evict_seq parity)
                        self.stats.preemptions += 1
                        self.evict_slot(i)
                        req._in_flight = False
                        continue
                    chunk = np.asarray(req.prompt_tokens[pos:pos + n], np.int32)  # qlint: disable=host-sync-in-hot-path -- host prompt slice -> chunk array, no device sync
                    chunks[i] = (chunk, n, final)
                if not chunks:
                    return
                # COW copies from the extends above (shared partial
                # tails) must land before this dispatch writes the
                # destination pages
                self._apply_cow()
                bucket = self._bucket_for(
                    max(n for _, n, _ in chunks.values()))
                tokens = np.zeros((self.cfg.max_slots, bucket), np.int32)
                starts = np.zeros(self.cfg.max_slots, np.int32)
                valid = np.zeros(self.cfg.max_slots, np.int32)
                for i, (chunk, n, _) in chunks.items():
                    tokens[i, :n] = chunk
                    starts[i] = self.prefill_pos[i]
                    valid[i] = n
            with span("qlm.engine.dispatch"):
                if self.paged:
                    # table refreshed AFTER the extends above so it names
                    # this chunk's freshly allocated pages
                    toks_out, self.cache = self._chunk_fn(
                        self.params, self.cache, self._put(tokens),
                        self._put(starts), self._put(valid),
                        self._device_block_table())
                else:
                    toks_out, self.cache = self._chunk_fn(
                        self.params, self.cache, self._put(tokens),
                        self._put(starts), self._put(valid))
            with span("qlm.engine.device_wait"):
                # sync INSIDE the timed region: np.asarray(toks_out) alone
                # only waits for the token array, leaving the cache update
                # in flight — prefill_time would otherwise time async
                # dispatch, not compute (and RWT calibration via profile()
                # would under-report)
                jax.block_until_ready(self.cache)  # qlint: disable=host-sync-in-hot-path -- documented timed-region sync: one per chunk round, feeds prefill_time / RWT calibration
                toks_out = np.asarray(toks_out)  # qlint: disable=host-sync-in-hot-path -- the round's single device->host result copy, inside the timed region
            with span("qlm.engine.post"):
                self.stats.prefill_chunks += 1
                now = self.clock()
                for i, (_, n, final) in chunks.items():
                    req = self.slots[i]
                    self.prefill_pos[i] += n
                    self.lengths[i] = self.prefill_pos[i]
                    if self.prefix_sharing:
                        # publish the prompt blocks this chunk completed:
                        # later admissions with the same leading tokens
                        # attach to these pages instead of re-prefilling
                        self.block_mgr.register_prefix(
                            req.req_id, req.prompt_tokens,
                            int(self.prefill_pos[i]))
                    if final:
                        tok = int(toks_out[i])
                        if req.first_token_time is None:
                            req.first_token_time = now
                        req.output_tokens.append(tok)
                        req.generated += 1
                        self.stats.prefills += 1
                        self._finish_if_done(i, tok, now, done)
                self.stats.prefill_time += self._wall() - t0

    def _decode_round(self, done: List[Request]) -> None:
        active = self.decode_slots()
        if not active:
            return
        with span("qlm.engine.decode", slots=len(active)):
            with span("qlm.engine.prep"):
                t0 = self._wall()
                # pending COW copies (previous round's append_token,
                # fork_slot) must land before this dispatch writes the
                # destination pages
                self._apply_cow()
                tokens = np.zeros(self.cfg.max_slots, np.int32)
                for i in active:
                    tokens[i] = self.slots[i].output_tokens[-1] \
                        if self.slots[i].output_tokens \
                        else self.slots[i].prompt_tokens[-1]
            with span("qlm.engine.dispatch"):
                if self.paged:
                    next_tokens, self.cache = self._decode_fn(
                        self.params, self.cache, self._put(tokens),
                        self._put(self.lengths),
                        self._device_block_table())
                else:
                    next_tokens, self.cache = self._decode_fn(
                        self.params, self.cache, self._put(tokens),
                        self._put(self.lengths))
            with span("qlm.engine.device_wait"):
                # sync the cache too (see _prefill_chunk_round):
                # decode_time feeds the RWT estimator's decode_per_token
                # via profile()
                jax.block_until_ready(self.cache)  # qlint: disable=host-sync-in-hot-path -- documented timed-region sync: one per decode round, feeds decode_time / RWT
                next_tokens = np.asarray(next_tokens)  # qlint: disable=host-sync-in-hot-path -- the round's single device->host result copy, inside the timed region
            with span("qlm.engine.post"):
                self.stats.decode_iterations += 1
                self.stats.decode_time += self._wall() - t0
                now = self.clock()
                for i in active:
                    req = self.slots[i]
                    # record the token FIRST: the decode step that produced
                    # it has already written its KV (at slot lengths), so
                    # neither a finish nor an OOM preemption below may
                    # drop it.
                    self.lengths[i] += 1
                    tok = int(next_tokens[i])
                    req.output_tokens.append(tok)
                    req.generated += 1
                    self.stats.tokens_generated += 1
                    if req.first_token_time is None:
                        req.first_token_time = now
                    if self._finish_if_done(i, tok, now, done):
                        continue
                    # reserve the NEXT decode step's KV slot; preempt on
                    # OOM (vLLM-style) — the just-produced token rides
                    # along in the eviction snapshot instead of being
                    # recomputed on resume.
                    if not self.block_mgr.append_token(req.req_id):
                        self.stats.preemptions += 1
                        self.evict_slot(i)
                        req._in_flight = False

    def _plan_burst(self, active: List[int], k: int) -> int:
        """Largest burst width n <= k whose KV writes are FULLY coverable by
        the pool right now: each slot needs its allocation extended to
        ``lengths + min(n, rem) + 1`` tokens (every in-burst write plus the
        surviving slots' next-step reservation, capped at max_seq_len —
        a slot that retires at the boundary writes nothing past it).
        Returns 0 when not even n=2 fits — the caller falls back to the
        single-step round, whose per-token append/preempt logic owns the
        pool-exhaustion endgame (vLLM-style preemption parity).

        Under prefix sharing a slot whose partial tail block is still
        shared (refcount > 1) needs ONE extra free block: ``extend`` will
        copy-on-write the tail before the burst may scatter into it."""
        rem, cur = {}, {}
        cow_extra = 0
        for i in active:
            r = self.slots[i]
            rem[i] = min(r.max_new_tokens - r.generated,
                         self.cfg.max_seq_len - int(self.lengths[i]))
            cur[i] = len(self.block_mgr.block_table(r.req_id))
            if self.prefix_sharing \
                    and self.block_mgr.append_needs_cow(r.req_id):
                cow_extra += 1

        def blocks_short(n: int) -> int:
            need = cow_extra
            for i in active:
                tokens = min(int(self.lengths[i]) + min(n, rem[i]) + 1,
                             self.cfg.max_seq_len)
                need += max(self.block_mgr.blocks_needed(tokens) - cur[i], 0)
            return need

        n = max(k, 0)
        free = self.block_mgr.free_blocks
        while n > 1 and blocks_short(n) > free:
            n -= 1
        if n <= 1:
            return 0
        for i in active:
            tokens = min(int(self.lengths[i]) + min(n, rem[i]) + 1,
                         self.cfg.max_seq_len)
            ok = self.block_mgr.extend(self.slots[i].req_id, tokens)
            assert ok, (i, tokens)  # blocks_short(n) <= free guarantees it
        return n

    def _decode_burst_round(self, done: List[Request], k: int) -> None:
        """Fused decode: one jitted dispatch covering up to ``k`` decode
        iterations (device-side argmax + finish masks, single host sync),
        then replay the per-token bookkeeping from the burst's token
        buffer.  Token-identical to running ``_decode_round`` k times: the
        per-slot decode depends only on that slot's own cache/lengths, and
        the finish conditions are evaluated with the same post-increment
        convention on device and host."""
        active = self.decode_slots()
        if not active:
            return
        with span("qlm.engine.burst", slots=len(active)):
            with span("qlm.engine.prep"):
                n = self._plan_burst(
                    active, min(k, max(self.cfg.decode_burst, 1)))
            if n == 0:
                # pool at the preemption edge: the seed single-step logic
                # owns OOM preemption ordering
                self._decode_round(done)
                return
            with span("qlm.engine.prep"):
                t0 = self._wall()
                # COW copies from _plan_burst's extends (and any earlier
                # fork / append) must land before the fused loop writes
                # those pages
                self._apply_cow()
                tokens = np.zeros(self.cfg.max_slots, np.int32)
                remaining = np.zeros(self.cfg.max_slots, np.int32)
                active_mask = np.zeros(self.cfg.max_slots, bool)
                for i in active:
                    r = self.slots[i]
                    tokens[i] = r.output_tokens[-1] if r.output_tokens \
                        else r.prompt_tokens[-1]
                    remaining[i] = r.max_new_tokens - r.generated
                    active_mask[i] = True
                bt = self._device_block_table() if self.paged else None
            with span("qlm.engine.dispatch", n=n):
                out, self.cache = self._burst_fn(
                    self.params, self.cache, self._put(tokens),
                    self._put(self.lengths), self._put(remaining),
                    self._put(active_mask), self._put(np.int32(n)), bt)
                self.stats.decode_bursts += 1
            with span("qlm.engine.device_wait"):
                jax.block_until_ready(self.cache)  # qlint: disable=host-sync-in-hot-path -- documented timed-region sync: THE single per-burst host sync the device-resident loop budgets for
                out = np.asarray(out)  # qlint: disable=host-sync-in-hot-path -- the burst's single device->host result copy, inside the timed region
            with span("qlm.engine.post"):
                executed = int((out >= 0).any(axis=1).sum())
                self.stats.decode_iterations += executed
                self.stats.decode_time += self._wall() - t0
                now = self.clock()
                for i in active:
                    req = self.slots[i]
                    for j in range(executed):
                        tok = int(out[j, i])
                        if tok < 0:
                            break  # slot went inactive on device at step j
                        self.lengths[i] += 1
                        req.output_tokens.append(tok)
                        req.generated += 1
                        self.stats.tokens_generated += 1
                        if req.first_token_time is None:
                            req.first_token_time = now
                        if self._finish_if_done(i, tok, now, done):
                            break
                    else:
                        # survived the whole burst: the up-front
                        # reservation left exactly the single-step
                        # invariant (lengths + 1 tokens)
                        assert self.block_mgr.seq_tokens(req.req_id) \
                            == int(self.lengths[i]) + 1

    def _admit_from_pull(self) -> None:
        """Request pulling: admit while capacity allows; a refused request
        is handed back to the virtual-queue owner via take_pushback()."""
        if self.pull_source is None:
            return
        # NOTE: the loop must keep calling pull_source even after a past
        # refusal — taking the pushback back into the queue happens inside
        # the puller (lso._pull), so gating the loop on `_pushback is None`
        # would freeze admission forever after the first refusal
        with span("qlm.engine.admit"):
            while self._free_slot() is not None:
                req = self.pull_source()
                if req is None:
                    break
                if not self.admit(req):
                    # pool-pressure valve: evicted requests' snapshot pins
                    # can accumulate until no admission fits (sustained
                    # shedding under overload).  Materialize the pinned
                    # snapshots — their prefix pages move to host memory
                    # and the pins are released — then retry once before
                    # pushing back.
                    if self._pinned_snapshots:
                        self._materialize_pinned_snapshots()
                        if self.admit(req):
                            continue
                    self._pushback = req
                    break

    def step(self) -> List[Request]:
        """Admit from the pull source, run one prefill chunk round, then one
        decode iteration.  Returns requests completed this step."""
        self._admit_from_pull()
        # requests that finished inside admit() since the last step are
        # already in self.completed; return them alongside this step's
        done: List[Request] = []
        # one prefill chunk for every mid-prefill slot (batched), then a
        # continuous-batching decode iteration for decode-ready slots
        self._prefill_chunk_round(done)
        self._decode_round(done)
        self.completed.extend(done)
        admit_done, self._admit_completed = self._admit_completed, []
        self._check_invariants()
        return admit_done + done

    def steps(self, k: Optional[int] = None) -> List[Request]:
        """Fast-path iteration: like ``step()`` but the decode side runs up
        to ``k`` iterations (default ``cfg.decode_burst``, which also caps
        the fused buffer width) in ONE jitted dispatch, syncing to host
        once per burst instead of once per token.

        Automatic single-step fallback whenever the fused loop can't run
        soundly at width >= 2: a slot is mid-prefill (the chunk round must
        interleave with decode at token granularity), or the block pool is
        at the preemption edge (the single-step append/preempt path owns
        eviction-LSO ordering).  Pull / evict / swap LSOs act between
        bursts — external evict_request / swap_model calls bump the block
        table version, so the next dispatch sees a fresh device table.
        Token-identical to the ``step()`` loop on every backend."""
        k = self.cfg.decode_burst if k is None else k
        if k <= 1:
            return self.step()
        self._admit_from_pull()
        done: List[Request] = []
        if self.prefilling_slots():
            self._prefill_chunk_round(done)
            self._decode_round(done)
        else:
            self._decode_burst_round(done, k)
        self.completed.extend(done)
        admit_done, self._admit_completed = self._admit_completed, []
        self._check_invariants()
        return admit_done + done

    # ------------------------------------------------------------------
    # runtime invariant checking (repro.analysis.invariants)
    # ------------------------------------------------------------------
    _inv_sampler = None

    def _check_invariants(self) -> None:
        """Round-boundary hook: the per-slot length/allocation contracts
        and the BlockManager state machine are only quiescent here — the
        checker must not run mid-round."""
        if not self.cfg.debug_invariants:
            from repro.analysis.invariants import invariants_enabled
            if not invariants_enabled():
                return
        if self._inv_sampler is None:
            from repro.analysis.invariants import InvariantSampler
            self._inv_sampler = InvariantSampler()
        if self._inv_sampler.due():
            from repro.analysis.invariants import check_engine
            with span("qlm.engine.invariants"):
                check_engine(self, where=f"engine:{self.model_name}/round")

    # ------------------------------------------------------------------
    # profiling (feeds the RWT estimator + simulator)
    # ------------------------------------------------------------------
    def profile(self, prompts: List[np.ndarray], max_new_tokens: int = 32) -> Dict[str, float]:
        """Run one batch (paper §6 "Hardware Profiling": a single batch run)
        and return {prefill_time P, decode_per_token d, throughput theta}.

        Prefill compute happens inside ``step()`` on the chunked path, so
        the phase split comes from the engine's own stats accounting."""
        import repro.core.request as req_mod
        reqs = [req_mod.Request(prompt_tokens=p, model=self.model_name,
                                slo=1e9, max_new_tokens=max_new_tokens)
                for p in prompts]
        s = self.stats
        pf0, dt0, it0, tok0 = (s.prefill_time, s.decode_time,
                               s.decode_iterations, s.tokens_generated)
        for r in reqs:
            if not self.admit(r):
                break
        n_admitted = self.num_active()
        while self.num_active() > 0:
            # steps() so calibration measures the engine's configured
            # operating mode: burst engines amortize dispatch across the
            # burst, and decode_per_token must reflect that (burst 1 ==
            # the plain step() loop)
            self.steps()
        # the timed regions inside the rounds block_until_ready the step
        # outputs (cache included), so the phase stats below measure real
        # compute, not async dispatch; this final sync is belt-and-braces
        # for any admit-path work still in flight
        jax.block_until_ready(self.cache)
        prefill_t = s.prefill_time - pf0
        decode_t = s.decode_time - dt0
        iters = s.decode_iterations - it0
        tokens = s.tokens_generated - tok0
        return {
            "prefill_time": prefill_t / max(n_admitted, 1),
            "decode_per_token": decode_t / max(iters, 1),
            "throughput": tokens / max(decode_t, 1e-9),
            "batch_size": float(n_admitted),
        }
