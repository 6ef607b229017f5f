"""Narrow-draft self-speculative decoding: the quality/width tradeoff as
a *lossless* speed knob.

The paper buys register-file capacity with "modest output-quality
degradation" — a narrower static format for the same values. Speculative
decoding inverts that bargain for serving: a **draft** derivation of the
same model (weights re-encoded one Table 3 ladder step down via
``core.compress.derive_plan`` + ``repack`` — no re-tuning) proposes ``k``
tokens per tick through its own decode state, and the **target** model
scores all ``k+1`` positions in one ``LM.verify_step`` call. A greedy
prefix rule (or rejection sampling, when sampling) commits the longest
agreeing prefix plus one target token, then both KV caches roll back to
the committed length (``LM.rollback_decode_state`` — a pure length reset,
because KV rows past ``len`` are dead).

The result: emitted tokens are **exactly** the full-width model's output
— quality degradation becomes an *acceptance-rate statistic* instead of
an output artifact — while the narrow model's bytes/token dominates the
hot path whenever acceptance is high. Per tick the draft streams its
(narrower) weights k+1 times for single tokens and the target streams its
weights once for k+1 positions, so target weight bytes per committed
token beat the plain engine whenever more than one token commits per
tick, i.e. acceptance > 1/(k+1).

This is the first subsystem where two packed widths of the same model run
concurrently: the packed store holds both plans over shared structure,
the fused matmul dispatches each leaf at its own width, and the KV
machinery appends/rolls back two caches in lockstep.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compat import jit
from repro.core.compress import derive_plan, repack, uniform_plan
from repro.core.formats import FLOAT_LADDER, ladder_snap
from repro.core.tensor_store import tree_bytes
from repro.models.lm import LM
from repro.serving.engine import (
    ServeEngine,
    _pool_copy_page,
    sample_per_slot,
    weight_pass_bytes,
)


@dataclasses.dataclass
class DraftController:
    """Adaptive retuning of the draft's (width, k) from live acceptance.

    A single static ladder rung is demonstrably the wrong knob across
    configs (BENCH_speculative.json: stablelm's AF8 draft accepts 0.15
    of its proposals while qwen3's AF12 accepts 0.89), so the controller
    closes the loop at serve time: it maintains an EWMA of the
    per-window acceptance rate (committed drafts / proposed drafts) and,
    once a window has accrued ``min_proposals`` proposals,

    * **widens** the draft one Table 3 rung (re-derive + repack, never
      re-tune) when the EWMA falls below ``floor`` — a draft that is
      wrong most of the time wastes every byte it streams;
    * at the widest legal rung (one below the target) it **shrinks k**
      instead, down to ``min_k`` — fewer wasted proposals per tick;
    * **narrows** one rung when the EWMA exceeds ``ceiling`` (saturated
      acceptance means the draft is paying for precision the prefix
      rule never examines), floored at AF8.

    ``k`` never *increases*: admission validated every resident request
    against ``max_seq_len`` headroom at the initial k, so growing k
    mid-flight could overflow the KV cache of an in-flight sequence.
    Retuning repacks draft weights only — both KV caches keep their
    shapes, so a retune is safe between any two ticks. All of this moves
    acceptance statistics, never emitted tokens: the full-width target
    still verifies every committed token.
    """

    floor: float = 0.5          # EWMA below this: widen (or shrink k)
    ceiling: float = 0.95       # EWMA above this: narrow
    alpha: float = 0.5          # EWMA weight of the newest window
    min_proposals: int = 64     # proposals per decision window
    min_k: int = 1

    def __post_init__(self):
        if not (0.0 <= self.floor < self.ceiling <= 1.0):
            raise ValueError(
                f"need 0 <= floor < ceiling <= 1, got "
                f"({self.floor}, {self.ceiling})")
        if self.min_proposals < 1:
            raise ValueError("min_proposals must be >= 1")

    def update(self, ewma: Optional[float], rate: float) -> float:
        return rate if ewma is None else (
            self.alpha * rate + (1 - self.alpha) * ewma)

    def decide(self, ewma: float, draft_bits: int, k: int,
               wbits: int) -> Optional[Any]:
        """Pure policy: -> ("widen"|"narrow", bits) | ("shrink_k", k) |
        None. Separated from the engine so the ladder walk is unit-
        testable without packing any weights."""
        if ewma < self.floor:
            wider = next((r for r in FLOAT_LADDER
                          if draft_bits < r < wbits), None)
            if wider is not None:
                return ("widen", wider)
            if k > self.min_k:
                return ("shrink_k", k - 1)
            return None
        if ewma > self.ceiling and draft_bits > FLOAT_LADDER[0]:
            return ("narrow", ladder_snap(draft_bits, below=True))
        return None


def resolve_draft_bits(cfg) -> int:
    """Draft width: the config's ``draft_weight_bits`` knob, else one
    Table 3 ladder step below the target's planned weight width."""
    comp = cfg.compression
    if comp.draft_weight_bits:
        return comp.draft_weight_bits
    return ladder_snap(cfg.resolved_weight_bits, below=True)


def resolve_draft_kv_bits(cfg) -> Optional[int]:
    """Draft KV width: the ``draft_kv_bits`` knob, else one Table 3
    ladder rung below the target's ``kv_bits`` when the target packs its
    KV cache; a dense-KV target keeps a dense draft cache (None). Like
    the draft weight width, this only moves the acceptance rate — the
    full-width target verifies every token, so emitted tokens never
    change."""
    comp = cfg.compression
    if comp.draft_kv_bits:
        return ladder_snap(comp.draft_kv_bits)
    if comp.kv_bits:
        return ladder_snap(comp.kv_bits, below=True)
    return None


@dataclasses.dataclass
class SpeculativeEngine(ServeEngine):
    """``ServeEngine`` with the speculative stepper plugged in.

    Per tick and per resident slot: the draft proposes ``k`` tokens, the
    target verifies ``k+1`` positions in one call, the longest agreeing
    prefix (plus the target's own next token) commits, and both decode
    states roll back to the committed length. Greedy outputs are
    token-for-token identical to the plain engine's; sampling outputs are
    distributionally identical via rejection sampling. Speculated rows
    are appended before the roll-back, so ``submit`` requires k extra
    rows of ``max_seq_len`` headroom beyond the plain engine's
    prompt + max_new_tokens - 1."""

    k: int = 4                          # drafted tokens per tick
    draft_bits: Optional[int] = None    # override the config knob
    draft_kv_bits: Optional[int] = None  # override the draft-KV knob
    adaptive: bool = False              # retune (width, k) from acceptance
    controller: Optional[DraftController] = None

    def __post_init__(self):
        super().__post_init__()
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if not self.lm.supports_rollback:
            mode = "paged KV mode" if self.paged else "dense KV mode"
            raise ValueError(
                f"family {self.cfg.family!r} cannot roll its decode state "
                f"back; speculation needs KV-length rollback [{mode}]"
            )
        wbits = self.cfg.resolved_weight_bits
        dbits = self.draft_bits or resolve_draft_bits(self.cfg)
        # snap to the ladder *before* validating or reporting: the packed
        # store only has Table 3 rungs, and stats must state the width
        # the weights are actually packed at
        dbits = ladder_snap(dbits)
        if dbits >= wbits:
            raise ValueError(
                f"draft width {dbits} (ladder-snapped) must be narrower "
                f"than the target's {wbits}"
            )
        self.draft_bits = dbits
        # Derive the draft's plan from the target's and re-encode the
        # *existing* leaves (packed target: code-level repack; plain
        # target: first packing) — never re-tuned. The base plan is kept:
        # the adaptive controller re-derives from it at other rungs.
        self._base_plan = self.weight_plan or uniform_plan(
            self.params, wbits)
        self.draft_plan = derive_plan(self._base_plan, wbits - dbits)
        self.draft_params = repack(self.params, self.draft_plan)
        # The draft's KV stream narrows too: its decode state packs at
        # draft_kv_bits (knob, else one ladder rung below the target's
        # kv_bits), through a draft LM whose config pins that width. The
        # two caches still append/roll back in lockstep — only the bytes
        # per appended row differ.
        explicit_draft_kv = (self.draft_kv_bits is not None
                             or bool(self.cfg.compression.draft_kv_bits))
        if self.draft_kv_bits is None:
            self.draft_kv_bits = resolve_draft_kv_bits(self.cfg)
        elif self.draft_kv_bits:
            self.draft_kv_bits = ladder_snap(self.draft_kv_bits)
        tgt_kv = self.cfg.compression.kv_bits
        if self.draft_kv_bits and tgt_kv and self.draft_kv_bits > tgt_kv:
            # a wider draft cache inverts the whole point and would make
            # the reported draft/target KV split lie about which stream
            # is the narrow one (equal = explicit mirror, allowed)
            raise ValueError(
                f"draft KV width {self.draft_kv_bits} (ladder-snapped) "
                f"must not be wider than the target's {tgt_kv}"
            )
        draft_klb = None
        if (self.cfg.compression.kv_layer_bits is not None
                and self.draft_kv_bits and not explicit_draft_kv):
            # mixed-width target: each draft layer steps one rung below
            # its *own* planned width (ladder_snap floors at AF8), so the
            # draft KV stream narrows layer-for-layer; the scalar
            # draft_kv_bits stays the max (the kv_layer_bits contract)
            draft_klb = tuple(
                ladder_snap(b, below=True)
                for b in self.cfg.compression.kv_layer_bits)
            self.draft_kv_bits = max(draft_klb)
            if len(set(draft_klb)) <= 1:
                draft_klb = None          # collapsed uniform: scalar knob
        self.draft_cfg = dataclasses.replace(
            self.cfg, compression=dataclasses.replace(
                self.cfg.compression, kv_bits=self.draft_kv_bits,
                kv_layer_bits=draft_klb))
        self.draft_lm = LM(self.draft_cfg, paged_attn=self.paged_attn)
        if self.paged:
            # the draft's paged pool mirrors the target's: same page ids,
            # same per-slot table, its own (narrower) physical buffers —
            # one KVPagePool allocator governs both
            self.draft_state = self.draft_lm.init_paged_decode_state(
                self.n_slots, self.max_seq_len, self.kv_page_size,
                self.kv_pool_pages)
        else:
            self.draft_state = self.draft_lm.init_decode_state(
                self.n_slots, self.max_seq_len)
        if self.cfg.family == "encdec":
            self.draft_state["clen"] = jnp.full(
                (self.n_slots,), self.cfg.encoder_seq, jnp.int32)
        self._draft_prefill = jit(self.draft_lm.prefill_step,
                                  donate_argnums=(1,))
        self._verify = jit(self.lm.verify_step, donate_argnums=(1,))
        self._draft_k = jit(self._make_draft_fn(), donate_argnums=(1,))
        # engine-level acceptance stats. slot_ticks counts participating
        # (slot, tick) pairs so per-slot commit averages stay honest under
        # ragged traffic (drain-phase ticks run partially occupied).
        self.spec_ticks = 0
        self.slot_ticks = 0
        self.proposed = 0
        self.accepted = 0
        # adaptive controller state: EWMA over per-window acceptance,
        # window anchors into the monotone counters, and an event log
        # with counter snapshots so before/after acceptance is computable
        # from the stats alone (benchmarks/calibration.py reads it).
        if self.adaptive and self.controller is None:
            self.controller = DraftController()
        self._initial_k = self.k
        self._ewma: Optional[float] = None
        self._window_proposed = 0
        self._window_accepted = 0
        self.retune_events: List[Dict[str, Any]] = []
        # draft-stream byte accounting: per-pass figures change when the
        # controller repacks, so cumulative bytes accrue at call time
        # (passes x the figures in force) instead of passes x a constant
        self._draft_pass_bytes = weight_pass_bytes(self.draft_params)
        self._draft_kv_bytes_per_row = self.draft_kv_bytes_per_token
        self._draft_weight_passes = 0
        self._draft_bytes_fused = 0
        self._draft_bytes_analytic = 0
        self._draft_bytes_dense = 0
        self._draft_kv_rows_appended = 0

    @property
    def _seq_headroom(self) -> int:
        # headroom is pinned at the *initial* k: the controller may
        # shrink k later, but admitted requests were validated against
        # this bound and k never grows past it
        return self._initial_k

    # -- draft ---------------------------------------------------------------
    def _make_draft_fn(self):
        lm, k, greedy = self.draft_lm, self.k, self.greedy

        def draft_fn(params, state, t0, key):
            """t0 (B, 1) -> (drafts (B, k), draft logits (B, k, V), state
            advanced k+1 rows — the extra append stores d_k's KV row so
            the draft cache mirrors the target's input stream)."""
            def body(carry, key_i):
                st, cur = carry
                logits, st = lm.decode_step(params, st, cur)
                lg = logits[:, 0]
                if greedy:
                    nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                else:
                    # per-slot keys through the shared derivation: slots
                    # with identical logits draw independently
                    nxt = sample_per_slot(key_i, lg)
                return (st, nxt[:, None]), (nxt, lg)

            keys = jax.random.split(key, k)
            (st, cur), (drafts, dlogits) = jax.lax.scan(
                body, (state, t0), keys)
            _, st = lm.decode_step(params, st, cur)
            return (jnp.moveaxis(drafts, 0, 1),
                    jnp.moveaxis(dlogits, 0, 1), st)

        return draft_fn

    # -- the speculative stepper ---------------------------------------------
    def _generate(self) -> Any:
        with self._phase("prepare", "serve.prepare"):
            tokens = np.array(self._last_tokens)
            for req in self._active.values():
                pend = self._pending_prefill.get(req.rid)
                if pend:
                    # chunked ingestion left exactly one token: the slot's
                    # first real input. It feeds both models this tick.
                    tokens[req.slot, 0] = pend.pop(0)
            if self.paged:
                # peak rows this tick: k+1 appends (draft and target
                # alike) from the committed length, before the roll-back
                for req in self._active.values():
                    self._ensure_rows(req, min(req.kv_len + self.k + 1,
                                               self.max_seq_len))
            t0 = jnp.asarray(tokens)
            len0 = np.asarray(self.state["len"]).astype(np.int64)
            dlen0 = np.asarray(self.draft_state["len"]).astype(np.int64)
            # the draft scan runs k single-token decodes plus one extra
            # append (d_k's KV row): k+1 passes over the draft weights
            self._count_draft_passes(self.k + 1)
            self._decode_calls += 1
            self._weight_passes += self.lm.weight_passes(self.k + 1)
            # fused-paged verify walks k+1 appended positions through the
            # target's page table; the draft's (narrower) pool reads ride
            # the same tables and are not double-counted here
            pages = self._count_pages_read(
                [r.kv_len for r in self._active.values()], self.k + 1)
            peak_rows = (self.k + 1) * len(self._active)
            self._kv_rows_appended += peak_rows
            self._draft_kv_rows_appended += peak_rows
        if self.paged:
            self._push_tables()
        self._mark("table")

        # dispatch: the draft's k+1 steps, then the verify. The draft
        # stream is salted off the engine's sampling base: unique per
        # (engine nonce, tick). The salt sits far above any slot index so
        # the draft key can never coincide with a per-slot sampling key
        # derived from the same tick key.
        key = self._tick_key(salt=0x0D4AF7)
        with self.tracer.span("serve.draft", k=self.k,
                              bits=self.draft_bits):
            drafts, dlogits, self.draft_state = self._draft_k(
                self.draft_params, self.draft_state, t0, key)
        vt = jnp.concatenate([t0, drafts], axis=1)       # (B, k+1)
        with self.tracer.span("serve.verify",
                              **{"positions": self.k + 1, **pages}):
            vlogits, self.state = self._verify(self.params, self.state, vt)
            if self.greedy:
                # device-side argmax: bit-identical to the plain engine's
                # sampling rule on bit-identical logits
                cand = jnp.argmax(vlogits, axis=-1)           # (B, k+1)
        self._mark("dispatch")

        with self._phase("wait", "serve.wait"):
            drafts_np = np.asarray(drafts)
            if self.greedy:
                commit = self._accept_greedy(drafts_np, np.asarray(cand))
            else:
                commit = self._accept_sampled(drafts_np, drafts, vlogits,
                                              dlogits)
        return commit, len0, dlen0

    def _commit(self, sampled: Any) -> Dict[int, List[int]]:
        commit, len0, dlen0 = sampled
        out: Dict[int, List[int]] = {}
        commits = np.zeros((self.n_slots,), np.int64)
        last = np.array(self._last_tokens)
        for req in self._active.values():
            b = req.slot
            toks = commit[b]
            req.draft_proposed += self.k
            req.draft_accepted += len(toks) - 1
            self.proposed += self.k
            self.accepted += len(toks) - 1
            self.slot_ticks += 1
            out[req.rid] = toks
            commits[b] = len(toks)
            last[b, 0] = toks[-1]
        # roll both caches back to the committed length; free slots roll
        # back to where they started, so their dead rows never accumulate
        self.state = self.lm.rollback_decode_state(
            self.state, len0 + commits)
        self.draft_state = self.draft_lm.rollback_decode_state(
            self.draft_state, dlen0 + commits)
        if self.paged:
            # speculated rows past the committed length are dead again:
            # return their tail pages to the reservation bucket
            for req in self._active.values():
                req.kv_len = min(req.kv_len + int(commits[req.slot]),
                                 self.max_seq_len)
                self._trim_pages(req)
        self._last_tokens = jnp.asarray(last)
        self._kv_rows_committed += int(commits.sum())
        self.spec_ticks += 1
        if self.adaptive:
            self._maybe_retune()
        return out

    def _count_draft_passes(self, n: int) -> None:
        """Accrue ``n`` draft weight passes at the figures currently in
        force (they move when the controller repacks)."""
        self._draft_weight_passes += n
        self._draft_bytes_fused += n * self._draft_pass_bytes["fused"]
        self._draft_bytes_analytic += (
            n * self._draft_pass_bytes["analytic"])
        self._draft_bytes_dense += n * self._draft_pass_bytes["dense"]

    # -- adaptive retuning ----------------------------------------------------
    def _maybe_retune(self) -> None:
        """One controller step: fold the finished window into the EWMA
        and apply at most one ladder move. Runs between ticks, so the
        repacked draft weights are next used on a fresh draft pass."""
        wp = self.proposed - self._window_proposed
        wa = self.accepted - self._window_accepted
        if wp < self.controller.min_proposals:
            return
        self._ewma = self.controller.update(self._ewma, wa / max(wp, 1))
        self._window_proposed = self.proposed
        self._window_accepted = self.accepted
        action = self.controller.decide(
            self._ewma, self.draft_bits, self.k,
            self.cfg.resolved_weight_bits)
        if action is None:
            return
        kind, val = action
        self.retune_events.append({
            "tick": self.spec_ticks,
            "action": kind,
            "from_bits": self.draft_bits,
            "to_bits": val if kind != "shrink_k" else self.draft_bits,
            "from_k": self.k,
            "to_k": val if kind == "shrink_k" else self.k,
            "ewma": self._ewma,
            "proposed": self.proposed,
            "accepted": self.accepted,
        })
        self.tracer.event("serve.retune", **self.retune_events[-1])
        obs.REGISTRY.counter(
            "serve_retune_total",
            "Draft-controller retunes by action.",
        ).inc(1, action=kind)
        if kind == "shrink_k":
            self._set_k(val)
        else:
            self._set_draft_bits(val)
        # the old operating point's evidence doesn't describe the new
        # one — restart the EWMA so the next decision is post-retune only
        self._ewma = None

    def _set_draft_bits(self, bits: int) -> None:
        """Re-derive the draft at another rung and repack its weights
        from the target's leaves — no re-tuning, no KV-shape change (the
        draft *cache* keeps its width; only weight codes re-encode)."""
        wbits = self.cfg.resolved_weight_bits
        if not bits < wbits:
            raise ValueError(
                f"retuned draft width {bits} must stay below {wbits}")
        self.draft_bits = bits
        self.draft_plan = derive_plan(self._base_plan, wbits - bits)
        self.draft_params = repack(self.params, self.draft_plan)
        self._draft_pass_bytes = weight_pass_bytes(self.draft_params)

    def _set_k(self, k: int) -> None:
        """Shrink the per-tick proposal count. Never grows past the
        initial k — admission headroom was validated against it."""
        if not 1 <= k <= self._initial_k:
            raise ValueError(
                f"k must be in [1, {self._initial_k}], got {k}")
        self.k = k
        self._draft_k = jit(self._make_draft_fn(), donate_argnums=(1,))

    def _accept_greedy(self, drafts: np.ndarray,
                       cand: np.ndarray) -> List[List[int]]:
        """Longest agreeing prefix + the target's own next token.

        cand[b, i] is the target's greedy token after consuming inputs
        [t0, d_1..d_i]; it is only valid while every earlier d matched —
        the first mismatch position already *is* the target's token there,
        so it commits and the tail is discarded."""
        out: List[List[int]] = []
        for b in range(drafts.shape[0]):
            toks: List[int] = []
            for i in range(self.k):
                t = int(cand[b, i])
                toks.append(t)
                if t != int(drafts[b, i]):
                    break
            else:
                toks.append(int(cand[b, self.k]))   # bonus token
            out.append(toks)
        return out

    def _accept_sampled(self, drafts_np: np.ndarray, drafts, vlogits,
                        dlogits) -> List[List[int]]:
        """Rejection sampling (Leviathan et al.): accept d_i with prob
        min(1, p_t/p_d); on reject, sample the residual max(0, p_t - p_d)
        — the committed stream is distributed exactly as the target's.

        Only the drafted tokens' probabilities (B, k) cross to the host
        up front; full vocab rows transfer lazily — one target+draft row
        per rejection and one target row per bonus token — instead of the
        whole (B, k+1, V) tensor every tick."""
        # host-side residual sampling: seeded from (engine nonce, tick) so
        # acceptance draws neither repeat across restarts nor collide with
        # the device-side draft stream
        rng = np.random.default_rng((self._sample_nonce, self.ticks))
        pt = jax.nn.softmax(vlogits.astype(jnp.float32), axis=-1)
        pd = jax.nn.softmax(dlogits.astype(jnp.float32), axis=-1)
        idx = drafts[..., None]
        pt_tok = np.asarray(
            jnp.take_along_axis(pt[:, :self.k], idx, -1)[..., 0])
        pd_tok = np.asarray(jnp.take_along_axis(pd, idx, -1)[..., 0])
        out: List[List[int]] = []
        for b in range(drafts_np.shape[0]):
            toks: List[int] = []
            for i in range(self.k):
                d = int(drafts_np[b, i])
                ratio = pt_tok[b, i] / max(pd_tok[b, i], 1e-30)
                if rng.uniform() < ratio:
                    toks.append(d)
                    continue
                resid = np.maximum(
                    np.asarray(pt[b, i], np.float64)
                    - np.asarray(pd[b, i], np.float64), 0.0)
                z = resid.sum()
                p = (resid / z if z > 0
                     else np.asarray(pt[b, i], np.float64))
                toks.append(int(rng.choice(p.shape[0], p=p / p.sum())))
                break
            else:
                bonus = np.asarray(pt[b, self.k], np.float64)
                toks.append(int(rng.choice(
                    bonus.shape[0], p=bonus / bonus.sum())))
            out.append(toks)
        return out

    # -- prefill: the draft cache must ingest the same prompts ---------------
    def _prefill_call(self, tokens: jnp.ndarray,
                      n_valid: jnp.ndarray) -> None:
        super()._prefill_call(tokens, n_valid)
        self._count_draft_passes(
            self.draft_lm.weight_passes(tokens.shape[1], prefill=True))
        self._draft_kv_rows_appended += int(np.asarray(n_valid).sum())
        self.draft_state = self._draft_prefill(
            self.draft_params, self.draft_state, tokens, n_valid)

    def _set_slot_len(self, slot: int, n: int) -> None:
        super()._set_slot_len(slot, n)    # draft cache length in lockstep
        self.draft_state["len"] = self.draft_state["len"].at[slot].set(n)

    def _copy_page(self, src: int, dst: int) -> None:
        super()._copy_page(src, dst)      # COW mirrors into the draft pool
        self.draft_state["kv"] = _pool_copy_page(
            self.draft_state["kv"], src, dst)

    def _apply_table_update(self, idx, rows) -> None:
        # one table drives both pools: the identical full refresh or
        # dirty-row scatter lands on the draft state's device table, so
        # a clean tick skips both transfers and a delta tick ships only
        # the dirty rows twice (target + draft) instead of two full
        # tables
        super()._apply_table_update(idx, rows)
        if idx is None:
            self.draft_state["table"] = jnp.asarray(self._table)
        else:
            self.draft_state["table"] = self._table_scatter(
                self.draft_state["table"], jnp.asarray(idx),
                jnp.asarray(rows))

    # -- stats ----------------------------------------------------------------
    @property
    def acceptance_rate(self) -> float:
        """Accepted drafts / proposed drafts (quality as a statistic)."""
        return self.accepted / max(self.proposed, 1)

    @property
    def committed_per_tick(self) -> float:
        return self.tokens_out / max(self.spec_ticks, 1)

    @property
    def committed_per_slot_tick(self) -> float:
        """Mean tokens committed per participating (slot, tick) pair —
        the amortization factor of one verify call, robust to ragged
        occupancy (drain-phase ticks run partially occupied)."""
        return self.tokens_out / max(self.slot_ticks, 1)

    @property
    def draft_weight_read_bytes(self) -> int:
        return tree_bytes(self.draft_params)[0]

    @property
    def draft_kv_bytes_per_token(self) -> int:
        """Bytes one appended draft-KV row costs per token, at the
        draft's (narrower) packed width — summed per layer when the
        draft carries a mixed per-layer plan."""
        return self.draft_cfg.kv_bytes_per_token()

    def metrics_snapshot(self) -> Dict[str, Any]:
        """The base snapshot plus the draft stream. Note
        ``draft_fused_analytic_bytes_per_pass`` is the *lifetime mean*
        (cumulative analytic bytes / passes): under adaptive retuning
        the per-pass figure moves mid-run, and the mean is the number
        the byte-parity invariant holds against; with no retune it
        equals the static per-pass figure exactly."""
        snap = super().metrics_snapshot()
        passes = self._draft_weight_passes
        snap.update({
            "k": self.k,
            "initial_k": self._initial_k,
            "draft_bits": self.draft_bits,
            "draft_kv_bits": self.draft_kv_bits,
            "spec_ticks": self.spec_ticks,
            "slot_ticks": self.slot_ticks,
            "proposed": self.proposed,
            "accepted": self.accepted,
            "acceptance_rate": self.acceptance_rate,
            "acceptance_ewma": self._ewma,
            "post_retune_acceptance": self.post_retune_acceptance,
            "committed_per_tick": self.committed_per_tick,
            "committed_per_slot_tick": self.committed_per_slot_tick,
            "retunes": len(self.retune_events),
            "draft_weight_passes": passes,
            "draft_weight_read_bytes_fused": self._draft_bytes_fused,
            "draft_weight_read_bytes_dense": self._draft_bytes_dense,
            "draft_fused_bytes_per_pass": self._draft_pass_bytes["fused"],
            "draft_fused_analytic_bytes_per_pass": (
                self._draft_bytes_analytic / passes if passes
                else self._draft_pass_bytes["analytic"]),
            "draft_kv_bytes_appended": (
                self._draft_kv_rows_appended
                * self._draft_kv_bytes_per_row),
        })
        return snap

    def run_until_drained(self, max_ticks: int = 10000) -> Dict[str, Any]:
        stats = super().run_until_drained(max_ticks)
        if self.adaptive:
            stats.update(
                adaptive=True,
                retune_events=list(self.retune_events),
            )
        return stats

    @property
    def post_retune_acceptance(self) -> float:
        """Acceptance over the proposals made *after* the last retune —
        the controller's delivered operating point (equals the lifetime
        rate when no retune fired)."""
        if not self.retune_events:
            return self.acceptance_rate
        last = self.retune_events[-1]
        dp = self.proposed - last["proposed"]
        da = self.accepted - last["accepted"]
        return da / max(dp, 1)
