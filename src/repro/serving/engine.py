"""Batched decode serving with packed KV — the occupancy story, deployed.

The paper's chain (Section 2): pack registers -> more warps resident ->
latency hidden -> IPC up. The serving analogue: pack the KV cache at the
statically tuned width -> more sequences resident in HBM -> bigger decode
batch -> each weight read amortized over more tokens -> tokens/s up.

``ServeEngine`` implements the deployment side:
  * a **residency planner** (``core.occupancy.decode_residency``) sizes
    the slot count from HBM, weight bytes and packed KV bytes/token —
    the occupancy calculator of Table 1, for chips;
  * **continuous batching**: a slot map (the indirection-table analogue —
    logical request -> physical KV slot) admits new requests the moment a
    slot frees; admission and slot queues are deques so a deep backlog
    costs O(1) per admit, not O(queue);
  * **chunked prefill**: admitted prompts stream through
    ``lm.prefill_step`` ``prefill_chunk`` tokens at a time (one jitted
    multi-token KV-append per chunk), so a long prompt costs
    ceil(len/chunk) calls instead of one decode tick per prompt token.
    On the dense and vlm families a chunk is one forward over all
    ``n_slots x chunk`` rows, one pass over the weights; the others scan
    the decode step, one pass per position (``LM.weight_passes``);
  * decode runs one jitted ``decode_step`` over the whole slot array per
    tick. The per-tick token generation lives in ``_generate`` — a
    pluggable stepper: ``serving.speculative.SpeculativeEngine`` overrides
    it with a draft-propose / full-width-verify tick that commits several
    tokens per call.

Each tick's host work is timed in phases (``TICK_PHASES``): a tracer
span each, and a running total of nanoseconds that ``metrics_snapshot()``
reports as ``host_<phase>_s`` whether or not anyone reads the spans.

``pack_weights=True`` packs every matmul-eligible weight at the config's
planned width (``core.compress.uniform_plan`` over the parameter shapes,
packed leaf by leaf in ``LM.init_packed``), putting the
fused packed-matmul and packed-embed-gather paths on the serving hot
path. Sequences must fit ``max_seq_len`` (prompt + new tokens); the
engine does not evict mid-sequence.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import secrets
import time
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compat import jit, prng_fold_in, prng_key
from repro.core.allocator import KVPagePool, PoolExhausted
from repro.core.compress import uniform_plan
from repro.core.occupancy import (
    TPUChipConfig,
    chip_for_device,
    decode_residency,
)
from repro.core.tensor_store import tree_bytes, weight_pass_bytes
from repro.models.config import ModelConfig
from repro.models.lm import LM


#: host phases of a serving tick, in order: build the step's tokens and
#: page bookkeeping; push the page table; enqueue the step; block on its
#: sampled tokens; commit them (append, finish, evict); admit queued
#: requests and dispatch their prefill. ``admit`` also counts admissions
#: that ``submit()`` runs outside a tick.
TICK_PHASES = ("prepare", "table", "dispatch", "wait", "commit", "admit")


def _pool_copy_page(kv, src: int, dst: int):
    """Copy one physical page across every layer buffer of a KV pool —
    the single stacked ``{"k", "v"}`` dict, or the tuple of per-segment
    dicts the width-segmented (per-layer ``kv_layer_bits``) layout
    allocates. Page indices are width-agnostic: every segment's pool has
    the same page axis, only the packed word count differs."""
    if isinstance(kv, tuple):
        return tuple(_pool_copy_page(seg, src, dst) for seg in kv)
    return {
        name: kv[name].at[:, dst].set(kv[name][:, src])
        for name in ("k", "v")
    }


def sample_per_slot(key: jax.Array, logits: jnp.ndarray) -> jnp.ndarray:
    """One categorical draw per row of (slots, V) logits, each row under
    its own slot-folded key — the one place the per-slot key derivation
    lives, shared by the plain engine's sampler and the speculative
    draft loop so the two streams can never drift apart."""
    keys = jax.vmap(prng_fold_in, (None, 0))(
        key, jnp.arange(logits.shape[0]))
    return jax.vmap(jax.random.categorical)(keys, logits).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    output: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False
    # time.perf_counter() stamps: submit(), admission to a slot, the
    # tick that committed the first output token, the tick that finished
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0
    # speculative per-request acceptance stats (0/0 on the plain engine)
    draft_proposed: int = 0
    draft_accepted: int = 0
    # paged-KV bookkeeping (all zero in dense mode)
    kv_len: int = 0          # host mirror of the device cache length
    n_pages: int = 0         # page-table entries currently held
    reserved_pages: int = 0  # promised-but-unallocated pool pages
    shared_pages: int = 0    # prefix pages retained from the registry
    pages_peak: int = 0      # max pages held: the actual-length footprint
    prefix_keys: List[bytes] = dataclasses.field(default_factory=list)
    # shareable pages this request writes itself: published to the
    # registry only once prefill has actually filled them
    deferred_register: List[Any] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ServeEngine:
    cfg: ModelConfig
    max_seq_len: int = 256
    max_slots: Optional[int] = None
    chip: Optional[TPUChipConfig] = None   # None: this process's device
    greedy: bool = True
    bos_token: int = 0             # fed when a request has no prompt
    max_results: int = 65536       # finished-output retention (FIFO)
    pack_weights: bool = False     # pack params at the planned width
    prefill_chunk: int = 16        # prompt tokens ingested per prefill call
    sample_seed: Optional[int] = None  # None: fresh nonce per engine
    # a calibrated per-leaf CompressionPlan (core.calibrate / --plan file);
    # supplying one implies packing — it replaces the uniform_plan the
    # config width would otherwise pin, so every leaf packs at its tuned
    # width and draft derivation steps each leaf individually
    plan: Optional[Any] = None
    # paged KV mode: the cache becomes a block-granular page pool shared
    # by all slots (core.allocator.KVPagePool) with per-request page
    # tables — per-request KV bytes scale with *actual* length instead of
    # slots x max_seq_len, admission over-commits slots against the pool,
    # and identical prompt prefixes share refcounted pages
    paged: bool = False
    kv_page_size: int = 16         # rows per page (must divide max_seq_len)
    kv_pool_pages: Optional[int] = None  # None: slots x pages/seq (no
    #                                      over-commit); smaller values
    #                                      over-commit slots vs. the pool
    # paged decode routing: True (default) attends straight through the
    # page table with the fused kernel (kernels.paged_attention) — KV
    # bytes read per tick scale with pages actually live; False demotes
    # to the gather-materialize parity oracle. No effect in dense mode.
    paged_attn: bool = True
    # observability: a Tracer for span/event emission (None: the
    # process-wide ring-only default) and an optional cadence — every
    # ``metrics_interval`` ticks a full ``serve.metrics`` snapshot event
    # is emitted and mirrored into obs.REGISTRY gauges (0: drain only)
    tracer: Optional[obs.Tracer] = None
    metrics_interval: int = 0

    def __post_init__(self):
        if self.tracer is None:
            self.tracer = obs.default_tracer()
        self.tracer.watch_gc()
        # a plan carrying per-layer KV widths (the static analysis pass's
        # activation-width family) rewrites the config before the LM is
        # built: uniform widths normalize to the scalar knob (the exact
        # legacy decode program — what makes equal-width outputs bitwise
        # identical), mixed widths install the segmented layout
        if self.plan is not None and getattr(self.plan, "kv_bits", None):
            n_kv = self.cfg.n_kv_layers
            widths = self.plan.kv_layer_widths(
                n_kv, default=self.cfg.resolved_kv_bits)
            comp = self.cfg.compression
            if len(set(widths)) <= 1:
                comp = dataclasses.replace(
                    comp, kv_bits=widths[0] if widths else comp.kv_bits,
                    kv_layer_bits=None)
            else:
                comp = dataclasses.replace(
                    comp, kv_bits=max(widths), kv_layer_bits=widths)
            self.cfg = dataclasses.replace(self.cfg, compression=comp)
        self.lm = LM(self.cfg, paged_attn=self.paged_attn)
        if self.chip is None:
            self.chip = chip_for_device(jax.devices()[0])
        # the plan comes from the parameter *shapes*, and every leaf is
        # initialised and packed in its own jitted call: the dense tree
        # of a full-width model never exists, only its packed form
        key = prng_key(0)
        self.weight_plan = None
        if self.pack_weights or self.plan is not None:
            self.weight_plan = self.plan or uniform_plan(
                jax.eval_shape(self.lm.init, key),
                self.cfg.resolved_weight_bits)
        self.params = self.lm.init_packed(key, self.weight_plan)
        # per-pass byte figures, fixed at init: the live byte counters are
        # these constants times host-side pass counts (execution-accurate
        # under jit, where kernel-level dispatch counters are trace-time).
        # No explicit bits argument: with per-layer widths installed the
        # accessor sums each layer at its own width (mixed accounting)
        self._pass_bytes = weight_pass_bytes(self.params)
        self._kv_bytes_per_row = self.cfg.kv_bytes_per_token()
        # the planner sizes slots around the weight bytes this engine
        # actually holds (packed leaves at their words, plain leaves at
        # their dtype), and kv_bytes_per_token reads the resolved KV
        # widths, so neither side of the accounting can skew
        held = self._pass_bytes["fused"] + self._pass_bytes["dense"]
        plan = decode_residency(
            weight_bytes=held,
            kv_bytes_per_token=self.cfg.kv_bytes_per_token(),
            seq_len=self.max_seq_len,
            chip=self.chip,
        )
        self.residency = plan
        self.n_slots = self.max_slots or max(min(plan.max_sequences, 64), 1)
        self.pool: Optional[KVPagePool] = None
        if self.paged:
            if not self.lm.supports_rollback:
                raise ValueError(
                    f"family {self.cfg.family!r} keeps recurrent O(1) "
                    "decode state — there are no KV rows to page; serve "
                    "it in dense KV mode (paged KV mode refused)"
                )
            if self.max_seq_len % self.kv_page_size:
                raise ValueError(
                    f"kv_page_size {self.kv_page_size} must divide "
                    f"max_seq_len {self.max_seq_len} (paged KV mode)"
                )
            self._max_pages = self.max_seq_len // self.kv_page_size
            if self.kv_pool_pages is None:
                self.kv_pool_pages = self.n_slots * self._max_pages
            self.pool = KVPagePool(self.kv_pool_pages, self.kv_page_size)
            # The authoritative page table is DEVICE-resident: it rides
            # through every donated jitted call inside the state dict, so
            # it survives donation. The host copy here is a *shadow* for
            # admission/eviction bookkeeping; per-tick mutations mark
            # their rows dirty and _push_tables scatters only those rows
            # (skipping the transfer entirely on clean ticks).
            self._table = np.zeros((self.n_slots, self._max_pages),
                                   np.int32)
            self._dirty_rows: set = set()
            # one scatter-update program per pow-2 dirty-row bucket
            self._table_scatter = jit(
                lambda t, i, r: t.at[i].set(r), donate_argnums=(0,))
            self.state = self.lm.init_paged_decode_state(
                self.n_slots, self.max_seq_len, self.kv_page_size,
                self.kv_pool_pages)
        else:
            self.state = self.lm.init_decode_state(self.n_slots,
                                                   self.max_seq_len)
        if self.cfg.family == "encdec":
            self.state["clen"] = jnp.full((self.n_slots,),
                                          self.cfg.encoder_seq, jnp.int32)
        # deques: admission pops the head of both queues every _admit —
        # under a deep backlog list.pop(0) makes each admit O(queue),
        # visible as tick-time drift in the soak test.
        self._free: Deque[int] = collections.deque(range(self.n_slots))
        # _active holds only in-flight requests (bounded by n_slots);
        # finished outputs move to _results so per-tick scans stay O(slots)
        # under sustained traffic instead of O(total requests ever served).
        # _results itself is FIFO-capped at max_results so memory is
        # bounded too — clients must collect outputs within that window.
        self._active: Dict[int, Request] = {}
        self._results: Dict[int, List[int]] = {}
        self._queue: Deque[Request] = collections.deque()
        self._next_rid = 0
        self._step = jit(self.lm.decode_step, donate_argnums=(1,))
        self._prefill = jit(self.lm.prefill_step, donate_argnums=(1,))
        self._last_tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        self._pending_prefill: Dict[int, List[int]] = {}
        self.ticks = 0
        self.tokens_out = 0
        # host-side execution counters behind metrics_snapshot(); every
        # field is a plain int/float so snapshotting never touches device
        self._decode_calls = 0
        self._prefill_calls = 0
        self._weight_passes = 0
        self._prefill_tokens = 0
        self._prefill_weight_passes = 0
        self._kv_rows_appended = 0
        self._kv_rows_committed = 0
        self._finished_total = 0
        self._admitted_total = 0
        self._admission_wait_sum = 0.0
        self._cow_copies = 0
        self._table_uploads = 0
        self._table_upload_bytes = 0
        self._table_rows_uploaded = 0
        self._kv_pages_read = 0
        self._kv_pages_read_dense_equiv = 0
        # host nanoseconds per tick phase; _mark closes a phase at the
        # clock read that opens the next one
        self._host_ns = dict.fromkeys(TICK_PHASES, 0)
        self._lap_ns = 0
        # Sampling key derivation: base = PRNGKey(tag) folded with a
        # per-engine nonce, then per tick fold in the tick counter and per
        # slot the slot index. Without the nonce a restarted engine
        # replays the identical sample stream; without the tick/slot
        # folds every slot of a tick would share one key (and a key would
        # recur every restart). ``sample_seed`` pins the nonce for
        # reproducible tests/replays; it is masked to fold_in's 31-bit
        # operand range, so wide seeds (time_ns and the like) work at the
        # cost of colliding with their masked twin.
        self._sample_nonce = (
            int(self.sample_seed) & 0x7FFFFFFF
            if self.sample_seed is not None
            else secrets.randbits(31))
        self._sample_base = prng_fold_in(
            prng_key(0x5A3B1E), self._sample_nonce)

    # -- client API -----------------------------------------------------------
    @property
    def _seq_headroom(self) -> int:
        """Extra KV rows a tick may append past the committed length (0
        here; k for the speculative engine, whose rolled-back rows still
        occupy slots at the peak)."""
        return 0

    def submit(self, prompt: List[int], max_new_tokens: int = 16) -> int:
        # A sequence feeds prompt + all-but-the-last generated token, so
        # it needs p + m - 1 rows, plus this engine's speculation
        # headroom. Past max_seq_len the append path would clamp and
        # silently overwrite the last valid row — refuse instead. Only
        # linear KV caches can overflow: recurrent state is O(1) in
        # sequence length and windowed (hybrid) KV wraps.
        need = (max(len(prompt), 1) + max_new_tokens - 1
                + self._seq_headroom)
        if self.lm.supports_rollback and need > self.max_seq_len:
            mode = ("paged KV mode: page table holds "
                    f"{self._max_pages} pages of {self.kv_page_size}"
                    if self.paged else "dense KV mode")
            raise ValueError(
                f"request needs {need} KV rows (prompt {len(prompt)} + "
                f"{max_new_tokens} new + headroom {self._seq_headroom}) "
                f"but max_seq_len is {self.max_seq_len} [{mode}]"
            )
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(
            rid=rid, prompt=list(prompt), max_new_tokens=max_new_tokens,
            submitted_at=time.perf_counter(),
        ))
        self._lap_ns = time.perf_counter_ns()
        with self._phase("admit", "serve.admission"):
            self._admit()
        return rid

    def result(self, rid: int) -> Optional[List[int]]:
        return self._results.get(rid)

    @property
    def occupancy(self) -> float:
        return (self.n_slots - len(self._free)) / self.n_slots

    @property
    def pool_utilization(self) -> float:
        """Pages used / pool pages (0.0 in dense mode)."""
        return self.pool.utilization if self.pool is not None else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Prefix-page registry hit rate across admissions (0.0 dense)."""
        return self.pool.prefix_hit_rate if self.pool is not None else 0.0

    @property
    def weight_read_bytes(self) -> int:
        """Bytes one full weight pass streams (packed where packed)."""
        return tree_bytes(self.params)[0]

    # -- scheduler ------------------------------------------------------------
    def _set_slot_len(self, slot: int, n: int) -> None:
        """Set one slot's device cache length. Overridable — the
        speculative engine keeps its draft cache in lockstep."""
        self.state["len"] = self.state["len"].at[slot].set(n)

    def _reset_slot(self, slot: int) -> None:
        """Recycle a slot: zero its cache length (rows past len are
        dead)."""
        self._set_slot_len(slot, 0)

    def _admit(self) -> None:
        admitted = False
        while self._queue and self._free:
            if self.paged and not self._try_reserve(self._queue[0]):
                break   # pool-aware headroom: the head waits for pages
            req = self._queue.popleft()
            slot = self._free.popleft()
            req.slot = slot
            self._active[req.rid] = req
            admitted = True
            req.admitted_at = time.perf_counter()
            wait = req.admitted_at - req.submitted_at
            self._admitted_total += 1
            self._admission_wait_sum += wait
            obs.REGISTRY.histogram(
                "serve_admission_wait_seconds",
                "Submit-to-admit wait per request.",
            ).observe(wait)
            self.tracer.event("serve.admit", rid=req.rid, slot=slot,
                              wait_s=wait, prompt_len=len(req.prompt))
            # reset this slot's KV length; prompt ingestion is chunked
            # below. An empty prompt still needs one deterministic first
            # token — without it the first tick would replay whatever
            # value the slot's previous occupant left in _last_tokens.
            self._reset_slot(slot)
            pending = list(req.prompt) or [self.bos_token]
            if self.paged:
                pending = self._attach_pages(req, pending)
            self._pending_prefill[req.rid] = pending
        # chunked ingestion needs the rollback property (padding rows must
        # be dead rows); recurrent families fold every fed token into O(1)
        # state, so they keep the token-by-token replay in _generate.
        if admitted and self.lm.supports_rollback:
            self._ingest_prompts()

    # -- paged-KV page management ---------------------------------------------
    def _try_reserve(self, req: Request) -> bool:
        """Admission headroom check against the *pool*, not max_seq_len:
        reserve exactly the pages this request's own worst case needs
        (prompt + max_new - 1 + speculation headroom rows), minus any
        prompt-prefix pages already resident in the registry. Slots
        over-commit against the pool whenever requests are shorter than
        max_seq_len — the capacity the dense layout strands."""
        pending = list(req.prompt) or [self.bos_token]
        need = len(pending) + req.max_new_tokens - 1 + self._seq_headroom
        pages_needed = -(-need // self.kv_page_size)
        # full pages strictly below the held-back last prompt token are
        # shareable; probe the chain left-to-right (a miss ends it)
        shareable = (len(pending) - 1) // self.kv_page_size
        keys: List[bytes] = []
        parent: Optional[bytes] = None
        for i in range(shareable):
            toks = pending[i * self.kv_page_size:
                           (i + 1) * self.kv_page_size]
            parent = KVPagePool.chain_key(parent, toks)
            keys.append(parent)
        matched = 0
        for key in keys:
            if self.pool.lookup(key) is None:
                break
            matched += 1
        reservation = pages_needed - matched
        if not self.pool.can_reserve(reservation):
            return False
        self.pool.reserve(reservation)
        req.reserved_pages = reservation
        req.shared_pages = matched
        req.prefix_keys = keys
        return True

    def _attach_pages(self, req: Request, pending: List[int]) -> List[int]:
        """Wire the admitted request's page table: retain matched prefix
        pages (their KV rows are already resident — those prompt tokens
        skip prefill entirely), then allocate the remaining shareable
        pages. Those only *publish* to the registry once prefill has
        actually written them (``_flush_registrations``) — a key in the
        registry is a promise that the rows exist, and a sharer admitted
        in the same batch would otherwise attend over unwritten pages.
        Pages past the shareable prefix allocate lazily as the sequence
        grows (``_ensure_rows``)."""
        slot, pool = req.slot, self.pool
        for i, key in enumerate(req.prefix_keys):
            if i < req.shared_pages:
                page = pool.lookup(key)
                pool.prefix_queries -= 1   # re-probe, not a new query
                pool.prefix_hits -= 1
                pool.retain(page)
            else:
                page = pool.alloc(reserved=True)
                req.reserved_pages -= 1
                req.deferred_register.append((i, key))
            self._table[slot, i] = page
            self._dirty_rows.add(slot)
            req.n_pages += 1
        req.pages_peak = max(req.pages_peak, req.n_pages)
        skip = req.shared_pages * self.kv_page_size
        if skip:
            req.kv_len = skip
            self._set_slot_len(slot, skip)
        return pending[skip:]

    def _alloc_page(self, req: Request) -> int:
        """One page for ``req`` — reserved bucket first, free bucket as
        the (copy-on-write) fallback."""
        if req.reserved_pages > 0:
            page = self.pool.alloc(reserved=True)
            req.reserved_pages -= 1
            return page
        try:
            return self.pool.alloc()
        except PoolExhausted as e:
            raise PoolExhausted(
                f"{e} [paged KV mode: request {req.rid} needs a page "
                "beyond its admission reservation]") from e

    def _ensure_rows(self, req: Request, rows: int) -> None:
        """Grow the request's page table to cover ``rows`` cache rows
        before a jitted call appends them (writes through unallocated
        table entries land on the scrap page — harmless, but real rows
        must land on owned pages)."""
        needed = min(-(-rows // self.kv_page_size), self._max_pages)
        self._ensure_tail_private(req)
        while req.n_pages < needed:
            page = self._alloc_page(req)
            self._table[req.slot, req.n_pages] = page
            self._dirty_rows.add(req.slot)
            req.n_pages += 1
        req.pages_peak = max(req.pages_peak, req.n_pages)

    def _ensure_tail_private(self, req: Request) -> None:
        """Copy-on-write at the first divergent page: if the page about
        to receive this request's next append is shared (refcount > 1),
        give the request a private copy first. Full-page-only sharing
        means organic traffic appends past every shared page, but a
        defensive check keeps the invariant local and testable."""
        idx = req.kv_len // self.kv_page_size
        if idx >= req.n_pages:
            return
        if idx < len(req.prefix_keys):
            # registered prefix region: content is fully determined by the
            # prompt tokens hashed into the key, so the registering writer
            # filling it during prefill is what sharers *expect* — copying
            # here would strand them on a half-written original
            return
        page = int(self._table[req.slot, idx])
        if self.pool.refcount(page) <= 1:
            return
        fresh = self._alloc_page(req)
        self._copy_page(page, fresh)
        self._cow_copies += 1
        self.tracer.event("serve.cow", rid=req.rid, src=page, dst=fresh)
        self._table[req.slot, idx] = fresh
        self._dirty_rows.add(req.slot)
        self.pool.free(page)               # drop our share of the original
        if idx < req.shared_pages:
            req.shared_pages = idx

    def _copy_page(self, src: int, dst: int) -> None:
        """Device-side copy of one physical page (all layers, K and V).
        Overridable — the speculative engine mirrors into its draft
        pool."""
        self.state["kv"] = _pool_copy_page(self.state["kv"], src, dst)

    def _trim_pages(self, req: Request) -> None:
        """Free pages past the committed length (speculation rolled the
        cache back). Each freed page's capacity swaps back into the
        request's reservation, so pool *usage* tracks committed rows
        while the admission guarantee holds."""
        keep = max(-(-req.kv_len // self.kv_page_size), 1)
        while req.n_pages > keep:
            req.n_pages -= 1
            page = int(self._table[req.slot, req.n_pages])
            self._table[req.slot, req.n_pages] = 0
            self._dirty_rows.add(req.slot)
            sole = self.pool.refcount(page) == 1
            self.pool.free(page)
            if sole:
                self.pool.reserve(1)
                req.reserved_pages += 1

    def _flush_registrations(self, req: Request) -> None:
        """Publish shareable pages whose rows prefill has now written
        (``kv_len`` crossed their boundary). A racing writer of the same
        prefix in the same batch registers first; the loser's pages just
        stay private."""
        while req.deferred_register:
            i, key = req.deferred_register[0]
            if (i + 1) * self.kv_page_size > req.kv_len:
                return
            req.deferred_register.pop(0)
            if not self.pool.is_registered(key):
                self.pool.register(key, int(self._table[req.slot, i]))

    def _release_pages(self, req: Request) -> None:
        """Eviction at finish: drop every held page (shared pages just
        lose one holder; a last holder returns the page — and its
        prefix-registry entry — to the pool) plus any unused
        reservation."""
        for i in range(req.n_pages):
            self.pool.free(int(self._table[req.slot, i]))
        self._table[req.slot, :] = 0
        if req.n_pages:
            self._dirty_rows.add(req.slot)
        req.n_pages = 0
        req.deferred_register.clear()      # unpublished keys die with us
        self.pool.release(req.reserved_pages)
        req.reserved_pages = 0

    def _table_delta(self):
        """(idx, rows) int32 arrays covering the dirty slots, padded up
        to a power-of-two bucket by repeating the first dirty index
        (idempotent under ``at[].set`` — same row, same data), so the
        scatter jit compiles O(log slots) programs instead of one per
        distinct dirty count."""
        idx = sorted(self._dirty_rows)
        n = 1
        while n < len(idx):
            n *= 2
        idx = np.asarray(idx + [idx[0]] * (n - len(idx)), np.int32)
        return idx, self._table[idx]

    def _push_tables(self) -> None:
        """Sync the device-resident page table before a jitted call.

        The authoritative table lives on device and rides through every
        donated call inside the state dict; the host ``_table`` is a
        shadow for admission/eviction bookkeeping. A tick that mutated
        no table row skips the transfer entirely; otherwise only the
        dirty rows travel, through a small scatter-update jit — unless
        at least half the slots are dirty (admission bursts), where one
        full upload beats many scattered rows. Overridable table
        application (``_apply_table_update``) lets the speculative
        engine mirror the same delta into its draft state."""
        if not self._dirty_rows:
            return
        rows_dirty = len(self._dirty_rows)
        if 2 * rows_dirty >= self.n_slots:
            idx, rows = None, None
            nbytes = self._table.nbytes
        else:
            idx, rows = self._table_delta()
            nbytes = int(idx.nbytes + rows.nbytes)
        with self.tracer.span("serve.h2d_table", bytes=nbytes,
                              rows=rows_dirty,
                              mode="full" if idx is None else "delta"):
            self._apply_table_update(idx, rows)
        self._dirty_rows.clear()
        self._table_uploads += 1
        self._table_upload_bytes += nbytes
        self._table_rows_uploaded += rows_dirty

    def _apply_table_update(self, idx, rows) -> None:
        """Apply one table delta (or a full refresh when ``idx`` is
        None) to the device-resident table. Overridable — the
        speculative engine applies the identical update to its draft
        state's table."""
        if idx is None:
            self.state["table"] = jnp.asarray(self._table)
        else:
            self.state["table"] = self._table_scatter(
                self.state["table"], jnp.asarray(idx), jnp.asarray(rows))

    def _count_pages_read(self, len0s, positions: int) -> Dict[str, int]:
        """Analytic pages the fused paged-attention path touches in one
        jitted call that walks ``positions`` KV-append steps: at step i
        a resident slot with ``len0`` committed rows attends over
        ``ceil((len0 + i) / page_size)`` live pages (one *logical* page
        spans every layer's K and V rows for those positions — the same
        convention as ``kv_bytes_per_token``). Dead slots sit on the
        scrap page, which the kernel's revisit elision dedupes. Accrues
        them, and the dense-equivalent figure so the pages-actually-live
        win is reportable, and returns both with ``positions`` as the
        attributes of the call's span. Empty when the call does not
        attend through the table (dense mode, or the gather oracle —
        which always reads slots x max_pages)."""
        if not (self.paged and self.paged_attn):
            return {}
        pg = self.kv_page_size
        pages = 0
        for len0 in len0s:
            for i in range(1, positions + 1):
                pages += -(-min(len0 + i, self.max_seq_len) // pg)
        dense = positions * self.n_slots * self._max_pages
        self._kv_pages_read += pages
        self._kv_pages_read_dense_equiv += dense
        return {"pages": pages, "positions": positions,
                "dense_equiv_pages": dense}

    @contextlib.contextmanager
    def _phase(self, phase: str, span: str, **attrs):
        """Host phase ``phase`` of serving as the tracer span ``span``,
        closed by :meth:`_mark` when the span ends."""
        with self.tracer.span(span, **attrs) as live:
            yield live
        self._mark(phase)

    def _mark(self, phase: str) -> None:
        """End host phase ``phase`` here: the nanoseconds since the
        previous mark (or the tick's start) add to its running total."""
        now = time.perf_counter_ns()
        self._host_ns[phase] += now - self._lap_ns
        self._lap_ns = now

    def _ingest_prompts(self) -> None:
        """Stream pending prompts through ``lm.prefill_step`` in chunks of
        ``prefill_chunk`` tokens, leaving exactly one token pending per
        request — the next decode tick feeds it and samples the first
        output (same contract the token-by-token replay had). Slots not
        prefilling ride along with n_valid = 0: their length is restored
        inside ``prefill_step`` and the padding rows land past ``len``
        where they are dead (masked now, overwritten later)."""
        while True:
            pending = {
                rid: toks for rid, toks in self._pending_prefill.items()
                if len(toks) > 1 and rid in self._active
            }
            if not pending:
                return
            # bucket the chunk width to a power of two: the jitted
            # prefill compiles once per distinct (n_slots, chunk) shape,
            # so raw remainder widths would recompile per prompt length;
            # padding past n_valid is already free (dead rows)
            need = min(self.prefill_chunk,
                       max(len(t) - 1 for t in pending.values()))
            chunk = 1
            while chunk < need:
                chunk *= 2
            chunk = min(chunk, self.prefill_chunk)
            tokens = np.zeros((self.n_slots, chunk), np.int32)
            n_valid = np.zeros((self.n_slots,), np.int32)
            len0s = ([r.kv_len for r in self._active.values()]
                     if self.paged else ())
            for rid, toks in pending.items():
                req = self._active[rid]
                take = min(chunk, len(toks) - 1)
                tokens[req.slot, :take] = toks[:take]
                n_valid[req.slot] = take
                del toks[:take]
                if self.paged:
                    self._ensure_rows(req, req.kv_len + take)
                    req.kv_len += take
                    self._flush_registrations(req)
            if self.paged:
                self._push_tables()
            rows = int(n_valid.sum())
            self._kv_rows_appended += rows
            self._kv_rows_committed += rows
            self._prefill_tokens += rows
            pages = self._count_pages_read(len0s, chunk)
            with self.tracer.span("serve.prefill", chunk=chunk, rows=rows,
                                  requests=len(pending), **pages):
                self._prefill_call(jnp.asarray(tokens),
                                   jnp.asarray(n_valid))

    def _prefill_call(self, tokens: jnp.ndarray,
                      n_valid: jnp.ndarray) -> None:
        """One chunked KV-append over the slot array. Overridable — the
        speculative engine mirrors every chunk into its draft cache."""
        passes = self.lm.weight_passes(tokens.shape[1], prefill=True)
        self._prefill_calls += 1
        self._prefill_weight_passes += passes
        self._weight_passes += passes
        self.state = self._prefill(self.params, self.state, tokens, n_valid)

    def _tick_key(self, salt: int = 0):
        """Per-tick sampling key: engine nonce + tick counter (+ salt for
        auxiliary streams like the speculative draft)."""
        key = prng_fold_in(self._sample_base, self.ticks)
        return prng_fold_in(key, salt) if salt else key

    def _sample_tokens(self, logits: jnp.ndarray) -> jnp.ndarray:
        """Sample one token per slot from (n_slots, V) logits with
        *per-slot* keys — two slots with identical logits in the same
        tick draw independently, and no key ever repeats across ticks or
        engine restarts (the per-engine nonce)."""
        return sample_per_slot(self._tick_key(), logits)

    def _generate(self) -> Any:
        """One decode step, through the ``prepare``, ``table``,
        ``dispatch`` and ``wait`` phases of the tick: returns what the
        step sampled, on the host, for :meth:`_commit`. With ``_commit``
        the pluggable stepper — ``SpeculativeEngine`` replaces both with
        a draft/verify tick that can commit up to k+1 tokens per
        request."""
        with self._phase("prepare", "serve.prepare"):
            tokens = np.array(self._last_tokens)     # writable host copy
            for req in self._active.values():
                pend = self._pending_prefill.get(req.rid)
                if pend:
                    tokens[req.slot, 0] = pend.pop(0)
            if self.paged:
                # every resident slot appends one row this tick
                for req in self._active.values():
                    self._ensure_rows(req, req.kv_len + 1)
            rows = len(self._active)
            pages = self._count_pages_read(
                [r.kv_len for r in self._active.values()], 1)
            self._decode_calls += 1
            self._weight_passes += self.lm.weight_passes(1)
            self._kv_rows_appended += rows
            self._kv_rows_committed += rows
            toks = jnp.asarray(tokens)
        if self.paged:
            self._push_tables()
        self._mark("table")
        with self._phase("dispatch", "serve.decode", requests=rows,
                         **pages):
            logits, self.state = self._step(self.params, self.state, toks)
            nxt = (jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
                   if self.greedy
                   else self._sample_tokens(logits[:, 0, :]))
        with self._phase("wait", "serve.wait"):
            return np.asarray(nxt)

    def _commit(self, nxt: Any) -> Dict[int, List[int]]:
        """The tokens :meth:`_generate`'s step commits, per request id,
        and the device-side state for the next step."""
        if self.paged:
            for req in self._active.values():
                req.kv_len = min(req.kv_len + 1, self.max_seq_len)
        out: Dict[int, List[int]] = {}
        for req in self._active.values():
            if self._pending_prefill.get(req.rid):
                continue                   # still prefilling: ignore sample
            out[req.rid] = [int(nxt[req.slot])]
        self._last_tokens = jnp.asarray(nxt[:, None].astype(np.int32))
        return out

    def step(self) -> int:
        """One tick for every resident sequence. Returns number of tokens
        emitted to finished outputs this tick."""
        if not self._active:
            return 0
        self._lap_ns = time.perf_counter_ns()
        with self.tracer.span("serve.tick", tick=self.ticks) as sp:
            sampled = self._generate()
            with self._phase("commit", "serve.commit"):
                emitted, finished = self._commit_outputs(
                    self._commit(sampled))
            with self._phase("admit", "serve.admission"):
                self._admit()
            self.ticks += 1
            self.tokens_out += emitted
            sp["emitted"] = emitted
            sp["finished"] = finished
        if self.metrics_interval and (
                self.ticks % self.metrics_interval == 0):
            self._emit_metrics()
        return emitted

    def _commit_outputs(self, committed: Dict[int, List[int]]):
        """Append each request's committed tokens to its output, up to
        its budget; finish and evict the requests that reached it.
        Returns (tokens emitted, requests finished)."""
        now = time.perf_counter()
        emitted = 0
        finished: List[int] = []
        for rid, toks in committed.items():
            req = self._active[rid]
            take = toks[:req.max_new_tokens - len(req.output)]
            if take and not req.output:
                req.first_token_at = now
            req.output.extend(take)
            emitted += len(take)
            if len(req.output) >= req.max_new_tokens:
                req.done = True
                req.finished_at = now
                finished.append(rid)
        for rid in finished:           # evict: _active stays bounded
            req = self._active.pop(rid)
            self._results[rid] = req.output
            if self.paged:
                self._release_pages(req)  # pages to the pool first,
            self._free.append(req.slot)   # then the slot: occupancy
            self._pending_prefill.pop(rid, None)
            self.tracer.event(
                "serve.request", rid=rid, prompt_len=len(req.prompt),
                output_len=len(req.output),
                queue_wait_s=req.admitted_at - req.submitted_at,
                ttft_s=req.first_token_at - req.submitted_at,
                e2e_s=req.finished_at - req.submitted_at)
        self._finished_total += len(finished)
        while len(self._results) > self.max_results:
            self._results.pop(next(iter(self._results)))
        return emitted, len(finished)

    # -- observability --------------------------------------------------------
    def metrics_snapshot(self) -> Dict[str, Any]:
        """Point-in-time stats, callable mid-run. Pure read: every value
        comes from host-side counters or O(1) properties, so calling it
        never perturbs the engine (the schema-stability test drives a
        snapshotting engine and a twin in lockstep and asserts identical
        outputs). Key set is exactly ``obs.schema.snapshot_keys(paged,
        speculative)``; ``run_until_drained`` returns this plus wall_s."""
        snap: Dict[str, Any] = {
            "ticks": self.ticks,
            "tokens": self.tokens_out,
            "slots": self.n_slots,
            "active_requests": len(self._active),
            "queued_requests": len(self._queue),
            "finished_requests": self._finished_total,
            "admitted_requests": self._admitted_total,
            "admission_wait_s_mean": (
                self._admission_wait_sum / self._admitted_total
                if self._admitted_total else 0.0),
            "slot_occupancy": self.occupancy,
            "residency_max_sequences": self.residency.max_sequences,
            "arithmetic_intensity": self.residency.arithmetic_intensity,
            "decode_calls": self._decode_calls,
            "prefill_calls": self._prefill_calls,
            "weight_passes": self._weight_passes,
            "weight_read_bytes_fused":
                self._weight_passes * self._pass_bytes["fused"],
            "weight_read_bytes_dense":
                self._weight_passes * self._pass_bytes["dense"],
            "fused_bytes_per_pass": self._pass_bytes["fused"],
            "fused_analytic_bytes_per_pass": self._pass_bytes["analytic"],
            "fused_f32_bytes_per_pass": self._pass_bytes["fused_f32"],
            "dense_bytes_per_pass": self._pass_bytes["dense"],
            "kv_rows_appended": self._kv_rows_appended,
            "kv_rows_committed": self._kv_rows_committed,
            "kv_bytes_appended":
                self._kv_rows_appended * self._kv_bytes_per_row,
            "prefill_tokens": self._prefill_tokens,
            "prefill_weight_passes": self._prefill_weight_passes,
            "gc_collections": self.tracer.gc_collections,
            "gc_pause_s": self.tracer.gc_pause_ns * 1e-9,
        }
        for phase, ns in self._host_ns.items():
            snap[f"host_{phase}_s"] = ns * 1e-9
        if self.pool is not None:
            ev = self.pool.events
            snap.update({
                "kv_page_size": self.kv_page_size,
                "kv_pool_pages": self.kv_pool_pages,
                "pool_utilization": self.pool.utilization,
                "pool_peak_utilization": self.pool.peak_utilization,
                "pool_pages_used": self.pool.used,
                "pool_pages_reserved": self.pool.reserved,
                "pool_pages_free": self.pool.free_pages,
                "prefix_hit_rate": self.pool.prefix_hit_rate,
                "prefix_hits": self.pool.prefix_hits,
                "prefix_queries": self.pool.prefix_queries,
                "pool_alloc_total": ev["alloc"],
                "pool_free_total": ev["free"],
                "pool_retain_total": ev["retain"],
                "pool_evict_total": ev["evict"],
                "pool_reserve_total": ev["reserve"],
                "pool_release_total": ev["release"],
                "cow_copies": self._cow_copies,
                "table_uploads": self._table_uploads,
                "table_upload_bytes": self._table_upload_bytes,
                "table_rows_uploaded": self._table_rows_uploaded,
                "paged_attn": bool(self.paged_attn),
                "kv_pages_read": self._kv_pages_read,
                "kv_pages_read_dense_equiv":
                    self._kv_pages_read_dense_equiv,
                # one logical page read = page_size rows across every
                # layer's K+V at the resolved widths — the same per-row
                # constant kv_bytes_appended uses, which is what the
                # obs.validate paged cross-check pins
                "kv_pages_read_bytes":
                    self._kv_pages_read * self.kv_page_size
                    * self._kv_bytes_per_row,
            })
        return snap

    def _emit_metrics(self) -> Dict[str, Any]:
        """Snapshot -> tracer event ``serve.metrics`` + REGISTRY gauges
        (``serve_<key>``, last-writer-wins across engines)."""
        snap = self.metrics_snapshot()
        self.tracer.event("serve.metrics", **snap)
        gauge = obs.REGISTRY.gauge
        for key, val in snap.items():
            if isinstance(val, bool) or not isinstance(val, (int, float)):
                continue
            gauge(f"serve_{key}", f"ServeEngine {key} (live mirror)."
                  ).set(float(val))
        return snap

    def run_until_drained(self, max_ticks: int = 10000) -> Dict[str, Any]:
        t0 = time.perf_counter()
        while (self._queue or self._active) and self.ticks < max_ticks:
            self.step()
        dt = time.perf_counter() - t0
        stats: Dict[str, Any] = self._emit_metrics()
        stats["wall_s"] = dt
        return stats
