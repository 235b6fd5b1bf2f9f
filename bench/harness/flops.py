"""Operations and bytes of the model's work, from its widths alone.

A matmul of an (m, k) by a (k, n) operand is 2 m k n operations. Model
operations count what the model needs, not what the program computes:
the tokens of a prefill chunk that carry prompt (not its padding), each
decoded token, and attention over the tokens resident at each position.
"""

from __future__ import annotations

from typing import Dict, Iterable


def widths(config: Dict) -> Dict[str, int]:
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return dict(d=d, hq=hq, hkv=config["num_key_value_heads"], dh=d // hq,
                f=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"])


def dense_flops_per_token(config: Dict) -> float:
    """Matmul operations per token outside attention's scores: the
    projections, the SwiGLU MLP and the output head."""
    w = widths(config)
    d, qd, kvd, f = w["d"], w["hq"] * w["dh"], w["hkv"] * w["dh"], w["f"]
    per_layer = d * qd + 2 * d * kvd + qd * d + d * 2 * f + f * d
    return 2.0 * (w["layers"] * per_layer + d * w["vocab"])


def attention_flops(config: Dict, context: int) -> float:
    """Scores and the weighted sum of one query over ``context`` keys, in
    every layer: 2 * 2 * n_heads * head_dim * context per layer."""
    w = widths(config)
    return 4.0 * w["layers"] * w["hq"] * w["dh"] * context


def prefill_flops(config: Dict, start: int, tokens: int) -> float:
    """A chunk of ``tokens`` prompt tokens after ``start`` resident ones:
    position p attends to p + 1 keys."""
    if tokens <= 0:
        return 0.0
    ctx = tokens * start + tokens * (tokens + 1) / 2
    return tokens * dense_flops_per_token(config) + attention_flops(
        config, ctx)


def decode_flops(config: Dict, contexts: Iterable[int]) -> float:
    """One decoded token per sequence, attending to ``contexts`` keys."""
    contexts = list(contexts)
    return len(contexts) * dense_flops_per_token(config) + attention_flops(
        config, sum(contexts))


def paged_decode_cost(config: Dict, contexts: Iterable[int],
                      kv_bytes: int = 2) -> Dict[str, float]:
    """One call of the paged decode attention kernel in one layer: the
    queries of every decoding sequence against its resident keys and
    values. Operations: 4 * n_heads * head_dim per key. Bytes: the
    resident keys and values once, the queries read and the outputs
    written, whatever the kernel itself reads."""
    w = widths(config)
    contexts = list(contexts)
    keys = sum(contexts)
    flops = 4.0 * w["hq"] * w["dh"] * keys
    nbytes = (2.0 * w["hkv"] * w["dh"] * keys * kv_bytes
              + 2.0 * len(contexts) * w["hq"] * w["dh"] * kv_bytes)
    return {"flops": flops, "bytes": nbytes}
