"""Model assembly: one ``LM`` facade over every assigned family.

Layers are *stacked* (leading L dim) and executed with ``lax.scan`` +
``jax.checkpoint`` so the HLO stays compact for 88-layer models and
activation memory is O(1) in depth. Decode carries per-layer state slices
through the same scan. The vocabulary projection and loss are chunked over
the sequence so (B, S, 257k) logits never materialize.

Public surface (used by train/serve/dryrun):
  * ``init(rng)``             -> params pytree (or eval_shape for specs)
  * ``loss(params, batch)``   -> scalar LM loss
  * ``prefill(params, batch)``-> (last-token logits, decode state)
  * ``decode_step(params, state, tokens)`` -> (logits, state)
  * ``verify_step(params, state, tokens)`` -> (all-position logits, state)
    — multi-token decode (KV-append per position, one call): the
    speculative-verify path, and prefill of the scanned families
  * ``prefill_step(params, state, tokens, n_valid)`` -> state — chunked
    prompt ingestion: one multi-token forward per chunk for the dense and
    vlm families, a scan of decode steps for the others
  * ``rollback_decode_state(state, lengths)`` -> state — roll the KV back
    to per-sequence lengths (speculation rejects; in paged mode the
    serving layer then frees the pages past the committed length)
  * ``init_decode_state(batch, seq_len)``  -> zeroed state (donated arg)
  * ``init_paged_decode_state(batch, seq_len, page_size, n_pages)`` ->
    pooled-page state (physical page pool + per-sequence page tables;
    bitwise-identical decode to the dense layout)
  * ``input_specs(shape)``    -> ShapeDtypeStructs for the dry-run
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

from repro.distributed.sharding import constrain
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.attention import gather_kv_pages, kv_row_width
from repro.models.config import ModelConfig, ShapeConfig


def _stack_init(init_fn, rng, n, *args):
    return jax.vmap(lambda r: init_fn(r, *args))(jax.random.split(rng, n))


def _layer_init(fn, cfg, key):
    return fn(key, cfg)


@dataclasses.dataclass(frozen=True)
class InitPart:
    """One top-level entry of the parameter tree, not yet evaluated:
    ``fn(key)`` builds it — one layer of a stack when ``stacked`` (the
    stack is ``fn`` over the (n, 2) ``keys``, which is exactly what
    ``_stack_init``'s vmap computes), the whole entry otherwise."""

    fn: Any
    keys: Any
    stacked: bool = False

    def build(self):
        if self.stacked:
            return jax.vmap(self.fn)(self.keys)
        return self.fn(self.keys)


def _is_part(x) -> bool:
    return isinstance(x, InitPart)


# ---------------------------------------------------------------------------
# Paged KV: page-table indirection over a pooled physical cache
# ---------------------------------------------------------------------------

def scatter_kv_row(pool: jnp.ndarray, view: jnp.ndarray,
                   table: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """Persist the row appended at position ``lens[b]`` back to the pool.

    ``view`` is the gathered (B, S, W) cache *after* the append path
    wrote one token's row at each sequence's length; everything below
    ``lens`` is already pool-resident, so only that single row needs to
    reach the physical page. Out-of-range lengths (a free slot whose
    length kept advancing) clamp onto the scrap page, mirroring the dense
    cache's clamp-at-the-last-row behaviour for dead slots.
    """
    page = pool.shape[1]
    mp = table.shape[1]
    pos = jnp.minimum(lens, view.shape[1] - 1)
    row = jax.vmap(
        lambda v, p: jax.lax.dynamic_slice_in_dim(v, p, 1, 0)[0]
    )(view, pos)                                # (B, W)
    pidx = jnp.minimum(pos // page, mp - 1)
    ids = jnp.take_along_axis(table, pidx[:, None], axis=1)[:, 0]
    phys = ids * page + pos % page
    flat = pool.reshape((-1,) + pool.shape[2:])
    flat = flat.at[phys].set(row.astype(flat.dtype))
    return flat.reshape(pool.shape)


@dataclasses.dataclass
class LM:
    cfg: ModelConfig
    # Paged decode routing: True (default) attends straight through the
    # page table with the fused kernel (kernels.paged_attention); False
    # demotes to the gather-materialize program (gather_kv_pages +
    # attention_decode + scatter_kv_row) — kept as the parity oracle.
    # Irrelevant to dense decode states.
    paged_attn: bool = True

    # ------------------------------------------------------------------ init
    def init_parts(self, rng) -> Dict:
        """The parameter tree with every top-level entry left unevaluated
        as an :class:`InitPart` — each layer stack as its one-layer init
        over the stack's keys. :meth:`init` builds them all at once;
        :meth:`init_packed` builds (and packs) one leaf at a time."""
        cfg = self.cfg
        dt = cfg.dtype
        r = jax.random.split(rng, 8)

        def stack(fn, key, n):
            return InitPart(functools.partial(_layer_init, fn, cfg),
                            jax.random.split(key, n), stacked=True)

        def zeros(shape):
            return InitPart(lambda _k: jnp.zeros(shape, dt), r[7])

        parts: Dict[str, Any] = {
            "embed": InitPart(functools.partial(
                L.init_dense, shape=(cfg.vocab_size, cfg.d_model),
                scale=0.02, dtype=dt), r[0]),
            "final_norm": zeros((cfg.d_model,)),
        }
        if not cfg.tie_embeddings:
            parts["lm_head"] = InitPart(functools.partial(
                L.init_dense, shape=(cfg.d_model, cfg.vocab_size),
                dtype=dt), r[1])
        fam = cfg.family
        if fam in ("dense", "vlm"):
            parts["blocks"] = {
                "attn": stack(B.init_attention, r[2], cfg.n_layers),
                "mlp": stack(B.init_mlp, r[3], cfg.n_layers),
            }
        elif fam == "moe":
            parts["blocks"] = {
                "attn": stack(B.init_attention, r[2], cfg.n_layers),
                "moe": stack(B.init_moe, r[3], cfg.n_layers),
            }
        elif fam == "ssm":
            parts["blocks"] = {
                "mamba": stack(B.init_mamba, r[2], cfg.n_layers),
            }
        elif fam == "hybrid":
            g = cfg.pattern_rec + cfg.pattern_attn
            groups = cfg.n_layers // g
            tail = cfg.n_layers - groups * g
            parts["blocks"] = {
                "rec": stack(
                    lambda rr, c: _stack_init(B.init_rglru, rr,
                                              cfg.pattern_rec, c),
                    r[2], groups),
                "attn": stack(B.init_attention, r[3], groups),
                "mlp": stack(
                    lambda rr, c: _stack_init(B.init_mlp, rr, g, c),
                    r[4], groups),
            }
            if tail:
                parts["tail"] = {
                    "rec": stack(B.init_rglru, r[5], tail),
                    "mlp": stack(B.init_mlp, r[6], tail),
                }
        elif fam == "encdec":
            parts["enc_blocks"] = {
                "attn": stack(B.init_attention, r[2], cfg.encoder_layers),
                "mlp": stack(B.init_mlp, r[3], cfg.encoder_layers),
            }
            parts["blocks"] = {
                "self": stack(B.init_attention, r[4], cfg.n_layers),
                "cross": stack(B.init_attention, r[5], cfg.n_layers),
                "mlp": stack(B.init_mlp, r[6], cfg.n_layers),
            }
            parts["enc_norm"] = zeros((cfg.d_model,))
        else:
            raise ValueError(fam)
        return parts

    def init(self, rng) -> Dict:
        return compat.tree_map(lambda part: part.build(),
                               self.init_parts(rng), is_leaf=_is_part)

    def init_packed(self, rng, plan=None) -> Dict:
        """:meth:`init`'s exact values, built leaf by leaf and packed at
        ``plan``'s widths on the way (``None``: nothing packs).

        Each leaf is initialised (and packed) inside one jitted call; a
        layer-stacked leaf loops over its layers (``lax.map``), so the
        largest unpacked intermediate is one layer of one leaf. Neither
        the whole unpacked tree nor an f32 copy of a stacked leaf ever
        exists — what lets a model whose dense tree exceeds device
        memory come up packed."""
        from repro.core.tensor_store import pack_tensor

        parts = self.init_parts(rng)
        shapes = jax.eval_shape(self.init, rng)
        flat, treedef = compat.tree_flatten_with_path(shapes)
        jobs = []
        for path, sds in flat:
            keys = [p.key for p in path]
            part, depth = parts, 0
            while not _is_part(part):
                part, depth = part[keys[depth]], depth + 1
            spec = plan.bits_of(path, sds) if plan is not None else None
            bits, signed = spec if isinstance(spec, tuple) else (spec, True)
            jobs.append((part, tuple(keys[depth:]), bits, signed))

        def build(part, sub, bits, signed):
            def one(key):
                leaf = part.fn(key)
                for k in sub:
                    leaf = leaf[k]
                if bits is None or bits >= 32:
                    return leaf
                return pack_tensor(leaf, bits, signed=signed)
            if part.stacked:
                return jax.jit(lambda ks: jax.lax.map(one, ks))(part.keys)
            return jax.jit(one)(part.keys)

        # whole-tensor leaves (embedding, head) first: their one-shot init
        # has the largest transient, best paid while the device is empty
        order = sorted(range(len(jobs)), key=lambda i: jobs[i][0].stacked)
        leaves = [None] * len(jobs)
        for i in order:
            leaves[i] = build(*jobs[i])
        return compat.tree_unflatten(treedef, leaves)

    # -------------------------------------------------------------- forward
    def _positions(self, batch_shape, s):
        return jnp.broadcast_to(jnp.arange(s)[None], (batch_shape, s))

    @staticmethod
    def _nested_scan(body, x, stacked, n_layers: int):
        """Two-level remat scan: outer scan over G groups of layers, the
        whole group body checkpointed. Backward memory = G carries +
        (L/G) carries during a group's recompute, i.e. O(sqrt L) residual
        -stream snapshots instead of O(L) — required to fit train_4k for
        the 40-88 layer archs (see DESIGN.md)."""
        g = max((d for d in range(1, 9) if n_layers % d == 0))
        if g <= 1 or g == n_layers:
            x, _ = jax.lax.scan(jax.checkpoint(body), x, stacked)
            return x
        inner = n_layers // g
        grouped = compat.tree_map(
            lambda a: a.reshape((g, inner) + a.shape[1:]), stacked)

        @jax.checkpoint
        def group_body(h, gp):
            h, _ = jax.lax.scan(jax.checkpoint(body), h, gp)
            return h, None

        x, _ = jax.lax.scan(group_body, x, grouped)
        return x

    def _backbone(self, params, x, positions, prefix: int = 0,
                  enc_out=None) -> jnp.ndarray:
        cfg = self.cfg
        fam = cfg.family

        if fam in ("dense", "vlm", "moe"):
            def body(h, lp):
                h = B.attention_apply(lp["attn"], h, cfg, positions,
                                      causal=True, prefix=prefix)
                if fam == "moe":
                    h = B.moe_apply(lp["moe"], h, cfg)
                else:
                    h = B.mlp_apply(lp["mlp"], h, cfg)
                h = constrain(h, ("data", None, None))
                return h, None
            x = self._nested_scan(body, x, params["blocks"], cfg.n_layers)
        elif fam == "ssm":
            def body(h, lp):
                h = B.mamba_apply(lp["mamba"], h, cfg)
                h = constrain(h, ("data", None, None))
                return h, None
            x = self._nested_scan(body, x, params["blocks"], cfg.n_layers)
        elif fam == "hybrid":
            def body(h, lp):
                for i in range(cfg.pattern_rec):
                    h = B.rglru_apply(
                        compat.tree_map(lambda a: a[i], lp["rec"]),
                        h, cfg)
                    h = B.mlp_apply(
                        compat.tree_map(lambda a: a[i], lp["mlp"]),
                        h, cfg)
                h = B.attention_apply(lp["attn"], h, cfg, positions,
                                      causal=True, window=cfg.attn_window)
                h = B.mlp_apply(
                    compat.tree_map(
                        lambda a: a[cfg.pattern_rec], lp["mlp"]),
                    h, cfg)
                h = constrain(h, ("data", None, None))
                return h, None
            x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
            if "tail" in params:
                def tail_body(h, lp):
                    h = B.rglru_apply(lp["rec"], h, cfg)
                    h = B.mlp_apply(lp["mlp"], h, cfg)
                    return h, None
                x, _ = jax.lax.scan(jax.checkpoint(tail_body), x,
                                    params["tail"])
        elif fam == "encdec":
            def body(h, lp):
                h = B.attention_apply(lp["self"], h, cfg, positions,
                                      causal=True)
                h = B.attention_apply(lp["cross"], h, cfg, positions,
                                      kv_source=enc_out, use_rope=False)
                h = B.mlp_apply(lp["mlp"], h, cfg)
                h = constrain(h, ("data", None, None))
                return h, None
            x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
        return x

    def _encode(self, params, frames) -> jnp.ndarray:
        """Whisper encoder over stub frame embeddings (B, Se, D)."""
        cfg = self.cfg
        pos = self._positions(frames.shape[0], frames.shape[1])

        def body(h, lp):
            h = B.attention_apply(lp["attn"], h, cfg, pos, causal=False)
            h = B.mlp_apply(lp["mlp"], h, cfg)
            return h, None

        x, _ = jax.lax.scan(jax.checkpoint(body), frames.astype(cfg.dtype),
                            params["enc_blocks"])
        return L.rms_norm(x, params["enc_norm"])

    def _embed_inputs(self, params, batch) -> Tuple[jnp.ndarray, int, Any]:
        """(hidden, prefix_len, enc_out) for any family's batch dict."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = L.embed(tokens, params["embed"]).astype(cfg.dtype)
        x = constrain(x, ("data", None, None))
        prefix = 0
        enc_out = None
        if cfg.family == "vlm":
            img = batch["patch_embeds"].astype(cfg.dtype)   # (B, P, D)
            x = jnp.concatenate([img, x], axis=1)
            prefix = cfg.num_image_tokens
        if cfg.family == "encdec":
            enc_out = self._encode(params, batch["frames"])
        return x, prefix, enc_out

    def logits_fn(self, params, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        x = L.rms_norm(x, params["final_norm"])
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return L.unembed(x, head, cfg.tie_embeddings)

    # ----------------------------------------------------------------- loss
    def loss(self, params, batch, s_chunk: int = 512) -> jnp.ndarray:
        cfg = self.cfg
        x, prefix, enc_out = self._embed_inputs(params, batch)
        positions = self._positions(x.shape[0], x.shape[1])
        h = self._backbone(params, x, positions, prefix, enc_out)
        if prefix:
            h = h[:, prefix:]
        labels = batch["labels"]
        b, s = labels.shape
        s_chunk = min(s_chunk, s)
        n_chunks = s // s_chunk

        def ce_chunk(tot, i):
            hs = jax.lax.dynamic_slice_in_dim(h, i * s_chunk, s_chunk, 1)
            ls = jax.lax.dynamic_slice_in_dim(labels, i * s_chunk,
                                              s_chunk, 1)
            logits = self.logits_fn(params, hs).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            # gold logit via one-hot contraction: with vocab sharded over
            # 'model', this reduces locally + tiny psum, where a gather
            # (take_along_axis) makes GSPMD all-gather the full logits
            # (~vocab/s_chunk x more collective bytes; see EXPERIMENTS.md
            # section Perf, iteration 1)
            onehot = jax.nn.one_hot(ls, logits.shape[-1],
                                    dtype=logits.dtype)
            gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
            return tot + (logz - gold).sum(), None

        tot, _ = jax.lax.scan(ce_chunk, jnp.float32(0.0),
                              jnp.arange(n_chunks))
        return tot / (b * s)

    # -------------------------------------------------------------- prefill
    def prefill(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Run the full prompt, return last-position logits. (The decode
        state produced here is rebuilt by the serving layer; the dry-run
        lowers prefill for throughput and decode_step for latency.)"""
        x, prefix, enc_out = self._embed_inputs(params, batch)
        positions = self._positions(x.shape[0], x.shape[1])
        h = self._backbone(params, x, positions, prefix, enc_out)
        return self.logits_fn(params, h[:, -1:]), {}

    # --------------------------------------------------------------- decode
    def _kv_segment_layout(self):
        """Validated ``kv_segments()`` when the config carries per-layer
        KV widths, else ``None`` (the uniform single-buffer layout).

        Pack widths must be compile-time constants (the bitpack shift
        networks are Python loops), so mixed per-layer plans execute as
        one buffer + one scan per contiguous equal-width layer run. Only
        row-cache families whose decode is a single stacked scan segment;
        recurrent and cross-attention families keep the uniform knob."""
        cfg = self.cfg
        klb = cfg.compression.kv_layer_bits
        if klb is None:
            return None
        if cfg.family not in ("dense", "vlm", "moe"):
            raise ValueError(
                f"kv_layer_bits is only supported for dense/vlm/moe "
                f"decode stacks, not family {cfg.family!r}"
            )
        if len(klb) != cfg.n_kv_layers:
            raise ValueError(
                f"kv_layer_bits has {len(klb)} entries for "
                f"{cfg.n_kv_layers} KV layers"
            )
        if not cfg.compression.kv_bits:
            raise ValueError(
                "kv_layer_bits requires kv_bits (set it to the max "
                "per-layer width; None means a dense, unpacked cache)"
            )
        if max(klb) != cfg.compression.kv_bits:
            raise ValueError(
                f"kv_bits ({cfg.compression.kv_bits}) must equal "
                f"max(kv_layer_bits) = {max(klb)}"
            )
        return cfg.kv_segments()

    def init_decode_state(self, batch_size: int, seq_len: int,
                          abstract: bool = False) -> Dict:
        """Zeroed per-layer decode state (stacked on L for the scan).

        With per-layer KV widths (``compression.kv_layer_bits``) the
        ``kv`` entry is a *tuple* of segment dicts — one ``{"k", "v"}``
        buffer per contiguous equal-width layer run, each packed at its
        own width — instead of the single stacked dict. A uniform config
        keeps the legacy single-dict layout (and the exact decode
        program), which is what makes mixed-width support a pure
        superset."""
        cfg = self.cfg
        kv_bits = cfg.compression.kv_bits
        hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
        dt = cfg.dtype
        mk = (jax.ShapeDtypeStruct if abstract
              else (lambda sh, d: jnp.zeros(sh, d)))

        def kv(layers, s, bits=kv_bits):
            shape = (layers, batch_size, s, kv_row_width(hkv, hd, bits))
            dtype = jnp.uint32 if bits else dt
            return {"k": mk(shape, dtype), "v": mk(shape, dtype)}

        segs = self._kv_segment_layout()
        state: Dict[str, Any] = {
            "len": mk((batch_size,), jnp.int32),
        }
        fam = cfg.family
        if fam in ("dense", "vlm", "moe"):
            if segs is not None:
                state["kv"] = tuple(
                    kv(hi - lo, seq_len, bits) for lo, hi, bits in segs)
            else:
                state["kv"] = kv(cfg.n_layers, seq_len)
        elif fam == "ssm":
            state["conv"] = mk(
                (cfg.n_layers, batch_size, cfg.d_conv - 1, cfg.d_inner), dt)
            state["ssm"] = mk(
                (cfg.n_layers, batch_size, cfg.d_inner, cfg.ssm_state),
                jnp.float32)
        elif fam == "hybrid":
            g = cfg.pattern_rec + cfg.pattern_attn
            groups = cfg.n_layers // g
            tail = cfg.n_layers - groups * g
            lw = cfg.lru_width or cfg.d_model
            win = min(cfg.attn_window or seq_len, seq_len)
            state["kv"] = kv(groups, win)
            state["rec"] = {
                "conv": mk((groups, cfg.pattern_rec, batch_size,
                            cfg.d_conv - 1, lw), dt),
                "h": mk((groups, cfg.pattern_rec, batch_size, lw),
                        jnp.float32),
            }
            if tail:
                state["tail_rec"] = {
                    "conv": mk((tail, batch_size, cfg.d_conv - 1, lw), dt),
                    "h": mk((tail, batch_size, lw), jnp.float32),
                }
        elif fam == "encdec":
            state["kv"] = kv(cfg.n_layers, seq_len)
            # cross K/V computed from the encoder at prefill time
            shape = (cfg.n_layers, batch_size, cfg.encoder_seq,
                     kv_row_width(hkv, hd, kv_bits))
            dtype = jnp.uint32 if kv_bits else dt
            state["cross"] = {"ck": mk(shape, dtype), "cv": mk(shape, dtype)}
            state["clen"] = mk((batch_size,), jnp.int32)
        return state

    def init_paged_decode_state(self, batch_size: int, seq_len: int,
                                page_size: int, n_pages: int,
                                abstract: bool = False) -> Dict:
        """Paged twin of :meth:`init_decode_state`: the KV cache is a
        pool of ``n_pages`` physical pages (plus the scrap page 0) shared
        by all sequences, plus a per-sequence page table. Every page
        holds ``page_size`` whole rows, each packed exactly as the dense
        cache packs them (one lane-tiled row of every KV head per token),
        so any gathered run of pages stays fused-decodable by
        ``kernels.kv_decode``. ``page_size`` must divide ``seq_len`` so
        the gathered view has the dense cache's exact shape — which is
        what makes paged decode bitwise identical to dense decode.

        Only KV-row families page; recurrent state (ssm / hybrid) is
        O(1) per sequence and has no rows to pool."""
        cfg = self.cfg
        if not self.supports_rollback:
            raise ValueError(
                f"family {cfg.family!r} carries recurrent decode state; "
                "paged KV needs a row-addressable cache (use dense mode)"
            )
        if seq_len % page_size:
            raise ValueError(
                f"kv_page_size {page_size} must divide max_seq_len "
                f"{seq_len} so gathered pages keep the dense cache shape"
            )
        max_pages = seq_len // page_size
        kv_bits = cfg.compression.kv_bits
        hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
        dt = cfg.dtype
        mk = (jax.ShapeDtypeStruct if abstract
              else (lambda sh, d: jnp.zeros(sh, d)))

        def kv_pool(layers, bits=kv_bits):
            shape = (layers, n_pages + 1,         # + scrap page 0
                     page_size, kv_row_width(hkv, hd, bits))
            dtype = jnp.uint32 if bits else dt
            return {"k": mk(shape, dtype), "v": mk(shape, dtype)}

        segs = self._kv_segment_layout()
        state: Dict[str, Any] = {
            "len": mk((batch_size,), jnp.int32),
            "table": mk((batch_size, max_pages), jnp.int32),
            "kv": (tuple(kv_pool(hi - lo, bits) for lo, hi, bits in segs)
                   if segs is not None else kv_pool(cfg.n_layers)),
        }
        if cfg.family == "encdec":
            # the cross cache is prompt-scoped and fixed-length — per-slot
            # dense regions are already exactly sized, so it stays dense
            shape = (cfg.n_layers, batch_size, cfg.encoder_seq,
                     kv_row_width(hkv, hd, kv_bits))
            dtype = jnp.uint32 if kv_bits else dt
            state["cross"] = {"ck": mk(shape, dtype), "cv": mk(shape, dtype)}
            state["clen"] = mk((batch_size,), jnp.int32)
        return state

    def decode_step(self, params, state: Dict,
                    tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
        """tokens: (B, 1) -> (logits (B, 1, V), updated state).

        Accepts both decode-state layouts: the dense per-slot cache of
        :meth:`init_decode_state` and the paged pool + page table of
        :meth:`init_paged_decode_state` (detected by the ``table`` key).
        Paged states dispatch straight into the fused paged-attention
        kernel by default (``paged_attn``): the new row persists directly
        to its physical page and attention walks the pool through the
        table, so only live pages are read. With ``paged_attn=False``
        the demoted gather path runs instead — gather each layer's pages
        into the dense view, run the dense attention/append program,
        scatter the appended row back. Both produce bitwise-identical
        outputs (same packed words in, same masked softmax), which is
        exactly what the parity tests pin."""
        cfg = self.cfg
        fam = cfg.family
        table = state.get("table")
        fused_paged = table is not None and self.paged_attn
        x = L.embed(tokens, params["embed"]).astype(cfg.dtype)
        x = constrain(x, ("data", None, None))
        positions = state["len"][:, None]

        def kv_view(kv):
            if table is None:
                return kv["k"], kv["v"]
            return (gather_kv_pages(kv["k"], table),
                    gather_kv_pages(kv["v"], table))

        def kv_persist(kv, st):
            if table is None:
                return {"k": st["k"], "v": st["v"]}
            return {
                "k": scatter_kv_row(kv["k"], st["k"], table, state["len"]),
                "v": scatter_kv_row(kv["v"], st["v"], table, state["len"]),
            }

        if fam in ("dense", "vlm", "moe"):
            def body_at(bits):
                def body(h, xs):
                    lp, kv = xs
                    if fused_paged:
                        h, new_kv = B.attention_decode_paged(
                            lp["attn"], h, cfg, kv, table, state["len"],
                            positions, kv_bits_override=bits)
                    else:
                        kc, vc = kv_view(kv)
                        st = {"k": kc, "v": vc, "len": state["len"]}
                        h, st = B.attention_decode(lp["attn"], h, cfg, st,
                                                   positions,
                                                   kv_bits_override=bits)
                        new_kv = kv_persist(kv, st)
                    if fam == "moe":
                        h = B.moe_apply(lp["moe"], h, cfg)
                    else:
                        h = B.mlp_apply(lp["mlp"], h, cfg)
                    return h, new_kv
                return body
            if isinstance(state["kv"], tuple):
                # width-segmented cache: one scan per contiguous
                # equal-width layer run, each at its own static pack
                # width (bitpack shift networks need Python-int widths)
                new_segs = []
                for (lo, hi, bits), kv_seg in zip(
                        cfg.kv_segments(), state["kv"]):
                    blocks = compat.tree_map(
                        lambda a, lo=lo, hi=hi: a[lo:hi], params["blocks"])
                    x, new_kv = jax.lax.scan(
                        body_at(bits), x, (blocks, kv_seg))
                    new_segs.append(new_kv)
                state = dict(state, kv=tuple(new_segs))
            else:
                x, new_kv = jax.lax.scan(body_at(None), x,
                                         (params["blocks"], state["kv"]))
                state = dict(state, kv=new_kv)
        elif fam == "ssm":
            def body(h, xs):
                lp, st = xs
                h, st = B.mamba_decode(lp["mamba"], h, cfg, st)
                return h, st
            x, new_st = jax.lax.scan(
                body, x,
                (params["blocks"],
                 {"conv": state["conv"], "ssm": state["ssm"]}),
            )
            state = dict(state, **new_st)
        elif fam == "hybrid":
            def body(h, xs):
                lp, kv, rec = xs
                new_rec = {"conv": [], "h": []}
                for i in range(cfg.pattern_rec):
                    st = {"conv": rec["conv"][i], "h": rec["h"][i]}
                    h, st = B.rglru_decode(
                        compat.tree_map(lambda a: a[i], lp["rec"]),
                        h, cfg, st)
                    h = B.mlp_apply(
                        compat.tree_map(lambda a: a[i], lp["mlp"]),
                        h, cfg)
                    new_rec["conv"].append(st["conv"])
                    new_rec["h"].append(st["h"])
                st = {"k": kv["k"], "v": kv["v"], "len": state["len"]}
                h, st = B.attention_decode(lp["attn"], h, cfg, st, positions,
                                           window=cfg.attn_window)
                h = B.mlp_apply(
                    compat.tree_map(
                        lambda a: a[cfg.pattern_rec], lp["mlp"]),
                    h, cfg)
                return h, (
                    {"k": st["k"], "v": st["v"]},
                    {"conv": jnp.stack(new_rec["conv"]),
                     "h": jnp.stack(new_rec["h"])},
                )
            x, (new_kv, new_rec) = jax.lax.scan(
                body, x, (params["blocks"], state["kv"], state["rec"]))
            state = dict(state, kv=new_kv, rec=new_rec)
            if "tail" in params:
                def tail_body(h, xs):
                    lp, st = xs
                    h, st = B.rglru_decode(lp["rec"], h, cfg, st)
                    h = B.mlp_apply(lp["mlp"], h, cfg)
                    return h, st
                x, new_tail = jax.lax.scan(
                    tail_body, x, (params["tail"], state["tail_rec"]))
                state = dict(state, tail_rec=new_tail)
        elif fam == "encdec":
            def body(h, xs):
                lp, kv, cross = xs
                if fused_paged:
                    h, new_kv = B.attention_decode_paged(
                        lp["self"], h, cfg, kv, table, state["len"],
                        positions)
                else:
                    kc, vc = kv_view(kv)
                    st = {"k": kc, "v": vc, "len": state["len"]}
                    h, st = B.attention_decode(lp["self"], h, cfg, st,
                                               positions)
                    new_kv = kv_persist(kv, st)
                # the cross cache is prompt-scoped, dense and fixed-size
                # per slot — nothing to page through
                cst = {"ck": cross["ck"], "cv": cross["cv"],
                       "clen": state["clen"]}
                h, _ = B.attention_decode(lp["cross"], h, cfg, cst,
                                          positions, cross=True)
                h = B.mlp_apply(lp["mlp"], h, cfg)
                return h, new_kv
            x, new_kv = jax.lax.scan(
                body, x, (params["blocks"], state["kv"], state["cross"]))
            state = dict(state, kv=new_kv)

        logits = self.logits_fn(params, x)
        state = dict(state, len=state["len"] + 1)
        return logits, state

    # ----------------------------------------------- multi-token decode path
    @property
    def supports_rollback(self) -> bool:
        """True when the decode state is entirely KV rows + a length (so a
        speculative reject is a pure length reset). Recurrent families
        (ssm / hybrid) fold every token into an O(1) state that cannot be
        un-folded, so they cannot serve as speculation targets."""
        return self.cfg.family in ("dense", "moe", "vlm", "encdec")

    @property
    def one_pass_prefill(self) -> bool:
        """True when :meth:`prefill_step` runs a chunk as one multi-token
        forward (attention + dense MLP: the dense and vlm families). The
        others scan the decode step: ``moe_ffn`` sizes expert capacity
        from the token count, so an idle slot's padding rows would
        compete with real tokens for it; encdec's cross attention has no
        multi-query form; ssm and hybrid never prefill in chunks."""
        return self.cfg.family in ("dense", "vlm")

    def weight_passes(self, positions: int, prefill: bool = False) -> int:
        """Passes over the weights that one ``verify_step`` call over
        ``positions`` token positions streams — it scans the single-token
        decode step once per position, so every position reads every
        weight — or, with ``prefill``, one ``prefill_step`` chunk that
        wide: one pass where :attr:`one_pass_prefill`, else the scan's
        one per position. The engines' byte counters multiply their
        per-pass bytes by this."""
        return 1 if prefill and self.one_pass_prefill else positions

    def verify_step(self, params, state: Dict,
                    tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
        """tokens: (B, T) -> (logits (B, T, V), state with len advanced T).

        Scores T positions in one call by scanning the single-token decode
        body over the token axis — numerically *identical* to T sequential
        ``decode_step`` calls (same program, same KV-append path), which is
        what makes greedy speculative decoding exactly lossless. The
        speculative scheduler rolls ``len`` back afterwards to the
        accepted prefix; rows past ``len`` are dead (attention masks by
        length, appends overwrite in place)."""
        def body(st, tok):
            logits, st = self.decode_step(params, st, tok[:, None])
            return st, logits[:, 0]

        state, per_pos = jax.lax.scan(
            body, state, jnp.moveaxis(tokens, 1, 0))
        return jnp.moveaxis(per_pos, 0, 1), state

    def prefill_step(self, params, state: Dict, tokens: jnp.ndarray,
                     n_valid: jnp.ndarray) -> Dict:
        """Ingest a prompt chunk: tokens (B, C), n_valid (B,) of them real
        per sequence; sets ``len = len_before + n_valid``. The padding
        rows land past the valid length, where they are masked out and
        later overwritten, so sequences with shorter chunks (or none:
        n_valid = 0) keep their valid rows byte for byte.

        Where :attr:`one_pass_prefill`, the chunk is one forward over all
        B·C rows (:meth:`_prefill_forward`): each weight is read once per
        chunk, and the K/V rows match the decode path's within float
        rounding, not bitwise. Other families scan :meth:`verify_step`,
        bitwise equal to C decode steps."""
        len0 = state["len"]
        if self.one_pass_prefill:
            state = self._prefill_forward(params, state, tokens)
        else:
            _, state = self.verify_step(params, state, tokens)
        return dict(state, len=len0 + jnp.asarray(n_valid, jnp.int32))

    def _prefill_forward(self, params, state: Dict,
                         tokens: jnp.ndarray) -> Dict:
        """One forward of a (B, C) chunk through the dense/vlm stack:
        write each layer's C K/V rows at ``len .. len + C - 1`` and attend
        them causally against the cache (``blocks.attention_prefill``).
        No head: only the cache is kept. ``len`` is left as it was."""
        cfg = self.cfg
        table = state.get("table")
        len0 = state["len"]
        x = L.embed(tokens, params["embed"]).astype(cfg.dtype)
        x = constrain(x, ("data", None, None))
        positions = len0[:, None] + jnp.arange(tokens.shape[1],
                                               dtype=jnp.int32)

        def body_at(bits):
            def body(h, xs):
                lp, kv = xs
                h, new_kv = B.attention_prefill(
                    lp["attn"], h, cfg, kv, table, len0, positions,
                    kv_bits_override=bits)
                return B.mlp_apply(lp["mlp"], h, cfg), new_kv
            return body

        if not isinstance(state["kv"], tuple):
            _, new_kv = jax.lax.scan(body_at(None), x,
                                     (params["blocks"], state["kv"]))
            return dict(state, kv=new_kv)
        # width-segmented cache: one scan per equal-width layer run, as
        # in decode_step
        new_segs = []
        for (lo, hi, bits), kv_seg in zip(cfg.kv_segments(), state["kv"]):
            blocks = compat.tree_map(lambda a, lo=lo, hi=hi: a[lo:hi],
                                     params["blocks"])
            x, new_kv = jax.lax.scan(body_at(bits), x, (blocks, kv_seg))
            new_segs.append(new_kv)
        return dict(state, kv=tuple(new_segs))

    def rollback_decode_state(self, state: Dict,
                              lengths: jnp.ndarray) -> Dict:
        """Roll the cache back to ``lengths`` valid rows per sequence.

        O(1): KV rows are only ever read below ``len`` and the append
        path writes at ``len``, so discarding speculated rows is a length
        reset — no data movement (the indirection-table free, Section 3.2
        style). Only valid for ``supports_rollback`` families."""
        if not self.supports_rollback:
            raise ValueError(
                f"family {self.cfg.family!r} carries recurrent decode "
                "state; KV-length rollback cannot undo folded tokens"
            )
        return dict(state, len=jnp.asarray(lengths, jnp.int32))

    # ---------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input (weak-type
        correct, shardable, no allocation)."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs: Dict[str, Any] = {}
        if shape.kind in ("train", "prefill"):
            specs["tokens"] = toks
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            if cfg.family == "vlm":
                specs["patch_embeds"] = jax.ShapeDtypeStruct(
                    (b, cfg.num_image_tokens, cfg.d_model), jnp.float32)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
        else:                                   # decode: one new token
            specs["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        return specs
