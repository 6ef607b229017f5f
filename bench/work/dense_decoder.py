"""Operations and bytes the algorithm needs, for a dense decoder served
from packed weights and a paged KV cache — whatever the program does to
compute it.

Conventions (one call of a jitted step over the slot array):

* weights are read once per call, at the configured width: every matmul
  weight (the projections, the MLP and the head) plus the embedding rows
  the call's tokens gather;
* keys and values are read as the distinct live pages the lengths need
  (one page holds ``kv_page_size`` rows of every layer's K and V), and
  each new token writes one row;
* a prefill chunk is one weight pass, however many tokens it holds;
* operations count a multiply-add as 2 and cover the matmuls and the
  attention scores and sums (``4 * layers * heads * head_dim`` per
  attended position); norms, rotary and softmax are left out.

Rows that hold no request (dead slots, padding of a chunk) are no work.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    weight_bytes_per_elem: float
    kv_bytes_per_elem: float
    page: int

    @property
    def layer_matmul_params(self) -> int:
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        mlp = (3 if self.gated else 2) * self.d * self.d_ff
        return attn + mlp

    @property
    def matmul_params(self) -> int:
        """Parameters every token multiplies: the blocks and the head."""
        return self.layers * self.layer_matmul_params + self.d * self.vocab

    @property
    def weight_bytes(self) -> float:
        """One pass over the matmul weights at the configured width."""
        return self.matmul_params * self.weight_bytes_per_elem

    @property
    def kv_bytes_per_token(self) -> float:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * self.kv_bytes_per_elem)

    @property
    def attn_flops_per_position(self) -> int:
        return 4 * self.layers * self.heads * self.head_dim

    def embed_bytes(self, tokens: int) -> float:
        return tokens * self.d * self.weight_bytes_per_elem


def sizes(conf: Dict[str, Any]) -> Sizes:
    m = conf["model"]
    return Sizes(
        layers=m["n_layers"], d=m["d_model"], heads=m["n_heads"],
        kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab_size"], gated=m["gated_mlp"],
        weight_bytes_per_elem=conf["weight_bits"] / 8,
        kv_bytes_per_elem=conf["kv_bits"] / 8, page=conf["kv_page_size"])


def _pages(ctx: int, page: int) -> int:
    return -(-ctx // page)


def call_work(s: Sizes, rows: Sequence[Tuple[int, int]]
              ) -> Dict[str, Tuple[float, float]]:
    """Work of one call, split by where it runs.

    ``rows`` holds, for each request the call serves, ``(new, ctx)``:
    the tokens it appends in this call and its cache length after them.
    Returns ``{"step", "packed_matmul", "paged_attention"}`` each as
    ``(flops, bytes)``; the step is their sum plus the embedding rows and
    the KV rows written.
    """
    tokens = sum(n for n, _ in rows)
    positions = sum(n * c - n * (n - 1) // 2 for n, c in rows)
    mm_flops = 2.0 * s.matmul_params * tokens
    mm_bytes = s.weight_bytes
    at_flops = float(s.attn_flops_per_position * positions)
    at_bytes = (sum(_pages(c, s.page) for _, c in rows) * s.page
                * s.kv_bytes_per_token)
    step_flops = mm_flops + at_flops
    step_bytes = (mm_bytes + at_bytes + s.embed_bytes(tokens)
                  + tokens * s.kv_bytes_per_token)
    return {"step": (step_flops, step_bytes),
            "packed_matmul": (mm_flops, mm_bytes),
            "paged_attention": (at_flops, at_bytes)}


def model_flops(s: Sizes, rows: Sequence[Tuple[int, int]]) -> float:
    """Model FLOPs of processing ``rows`` (as in :func:`call_work`):
    every token through the matmul parameters, plus attention."""
    return call_work(s, rows)["step"][0]
