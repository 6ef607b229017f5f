"""Whether what the timed path served is correct.

After the window has closed and the engine is gone, a sample of the
requests it served, drawn from the seed and always holding the longest
(prompt and served tokens), is run through the configuration's plain
reference (``bench/reference/<reference>.py``) over each prompt and its
served tokens. Every served token was the engine's greedy choice; the
number compared is the widest gap by which a served token's reference
logit lies below the reference's best logit at that position.

A request still decoding when the window closed is compared over the
tokens it had returned; in a mix whose answers outlast the window, that
is every request.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench import cell as cells
from bench import load


def sample(requests: List[load.Req], seed: int, k: int) -> List[load.Req]:
    served = [r for r in requests if r.token_times]
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.prompt) + len(r.token_times))
    rest = [r for r in served if r is not longest]
    pick = load.rng(seed, 5).permutation(len(rest))[:k - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(reqs: List[load.Req]) -> List[Dict[str, List[int]]]:
    return [{"prompt": r.prompt, "served": r.tokens[:len(r.token_times)]}
            for r in reqs]


def reference_gaps(conf: Dict[str, Any], seed: int,
                   seqs: List[Dict[str, List[int]]], control: bool = False
                   ) -> Dict[str, Any]:
    """Widest gap of the served tokens under the reference, and with
    ``control`` also that of the tokens the stepped-down precision puts
    first at the same positions."""
    ref = cells.load_module("reference", conf["reference"])
    # one shape for every run of a cell (sample rows by max_seq_len), so
    # that the reference's programs compile once and then load
    pad = [{"prompt": [0], "served": []}] * (conf["check"]["requests"]
                                             - len(seqs))
    inputs, served = ref.pack_sequences(list(seqs) + pad,
                                        conf["max_seq_len"])
    mask = served >= 0
    out: Dict[str, Any] = {"tokens": int(mask.sum())}
    scored = [served]
    if control:
        _, top = ref.forward_gaps(conf, seed, inputs, served[None],
                                  control=True)
        scored.append(top)
    gaps, _ = ref.forward_gaps(conf, seed, inputs, np.stack(scored))
    out["logit_gap"] = float(gaps[0][mask].max())
    if control:
        out["control_logit_gap"] = float(gaps[1][mask].max())
    return out


def _num(value, op: str, limit) -> Dict[str, Any]:
    return {"value": value, "op": op, "limit": limit}


def _judge(gap: float, tokens: int, bad_tokens: int, limit: float
           ) -> Dict[str, Any]:
    checked = {"tokens_outside_vocab": _num(bad_tokens, "<=", 0),
               "tokens_compared": _num(tokens, ">=", 1),
               "logit_gap": _num(gap, "<=", limit)}
    ok = bad_tokens == 0 and tokens >= 1 and gap <= limit
    return {"correct": bool(ok), "checked": checked}


def verdict(conf: Dict[str, Any], seed: int, requests: List[load.Req],
            vocab: int, control: bool = False) -> Dict[str, Any]:
    """``{"correct", "checked": {name: {"value", "op", "limit"}}}``; with
    ``control``, also ``"control"``: the same judgement of the tokens that
    the precision-stepped reference puts first at the same positions,
    which has to come out not correct."""
    limits = conf["check"]
    reqs = sample(requests, seed, limits["requests"])
    bad_tokens = sum(1 for r in requests for t in r.tokens
                     if not 0 <= t < vocab)
    if not reqs:
        return _judge(float("inf"), 0, bad_tokens, limits["logit_gap"])
    res = reference_gaps(conf, seed, sequences(reqs), control=control)
    out = _judge(res["logit_gap"], res["tokens"], bad_tokens,
                 limits["logit_gap"])
    if control:
        out["control"] = _judge(res["control_logit_gap"], res["tokens"], 0,
                                limits["logit_gap"])
    return out
