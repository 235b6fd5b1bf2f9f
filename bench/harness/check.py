"""The comparison that decides ``correct``.

After the window: a sample of the requests that served tokens, drawn
from the seed, with the longest among them, until ``SAMPLE_TOKENS``
served tokens are in it. Finished requests are drawn first; a request
still decoding when the window closed is compared over the tokens it had
served, since a reply of a few hundred tokens outlasts the window at the
cells' loads. The plain reference runs once over each prompt with its served
tokens. At every served token it reads how far that token's logit lies
below the reference's best at that position: zero when the reference
agrees, small where bf16 rounding swapped a near tie. The number compared
is the widest such gap over the sample.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

SAMPLE_TOKENS = 300          # served tokens to compare, at least
SAMPLE_POSITIONS = 24576     # prompt and served positions, at most


def sample(records, seed: int) -> List:
    """Requests to compare, drawn from ``seed``."""
    from repro.serving import RequestState
    bad = (RequestState.FAILED, RequestState.TIMED_OUT)
    done = [r for r in records if r.req.tokens and r.req.state not in bad]
    if not done:
        return []
    length = lambda r: r.req.prompt_len + len(r.req.tokens)   # noqa: E731
    picked = [max(done, key=length)]
    rng = np.random.default_rng([seed, 7])
    finished = [r.req.state is RequestState.FINISHED for r in done]
    order = sorted(rng.permutation(len(done)), key=lambda i: not finished[i])
    served = sum(len(r.req.tokens) for r in picked)
    positions = sum(length(r) for r in picked)
    for i in order:
        if served >= SAMPLE_TOKENS:
            break
        r = done[i]
        if any(r is p for p in picked):
            continue
        if positions + length(r) > SAMPLE_POSITIONS:
            continue
        picked.append(r)
        served += len(r.req.tokens)
        positions += length(r)
    return picked


def sequences(picked):
    """Token sequences (prompt + served) and, per position, the served
    token that follows it (0 where the prompt goes on)."""
    seqs, targets, spans = [], [], []
    for r in picked:
        p = np.asarray(r.req.prompt, np.int32)
        t = np.asarray(r.req.tokens, np.int32)
        seq = np.concatenate([p, t])
        tgt = np.zeros((len(seq), 1), np.int32)
        tgt[len(p) - 1:len(seq) - 1, 0] = t
        seqs.append(seq)
        targets.append(tgt)
        spans.append((len(p) - 1, len(seq) - 1))
    return seqs, targets, spans


def widest_gap(ref_out, spans, column: int = 0) -> Dict[str, float]:
    """Widest gap between the reference's best logit and its logit for the
    served token (candidate ``column`` of ``picked``), over every served
    position; with the count compared."""
    worst, n, agree = 0.0, 0, 0
    for o, (a, b) in zip(ref_out, spans):
        gap = o["best"][a:b] - o["picked"][a:b, column]
        worst = max(worst, float(gap.max(initial=0.0)))
        n += b - a
        agree += int((gap <= 0).sum())
    return {"served_logit_gap": worst, "served_tokens": n,
            "tokens_equal_to_reference": agree}
