"""Operations and bytes of the model's work, from its widths alone.

A matmul of an (m, k) by a (k, n) operand is 2 m k n operations. Model
operations count what the model needs, not what the program computes:
the tokens of a prefill chunk that carry prompt (not its padding), each
decoded token, and attention over the tokens resident at each position.

The functions here count a dense RoPE/SwiGLU decoder. A configuration of
another architecture names its own counts module (``counts_for``).
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Tuple

from harness import spec


def head_dim(config: Dict) -> int:
    """The file's ``head_dim``; hidden_size / num_attention_heads where it
    gives none."""
    dh = config.get("head_dim")
    if dh is None:
        dh = config["hidden_size"] // config["num_attention_heads"]
    return dh


def widths(config: Dict) -> Dict[str, int]:
    return dict(d=config["hidden_size"], hq=config["num_attention_heads"],
                hkv=config["num_key_value_heads"], dh=head_dim(config),
                f=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"])


def counts_for(config: Dict, bench_dir: str = None):
    """The work counts of a configuration: the module
    ``bench/configs/<counts>.py`` where the file names ``"counts"``, else
    this module's dense counts. Either gives ``prefill_flops(config,
    start, tokens)``, ``decode_flops(config, contexts)`` and
    ``paged_decode_groups(config, contexts)``."""
    name = config.get("counts")
    if name is None:
        return sys.modules[__name__]
    return spec.load_module(
        os.path.join(bench_dir or spec.BENCH_DIR, "configs", name + ".py"),
        "bench_counts_" + name)


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


def paged_decode_groups(config: Dict, contexts: Iterable[int]
                        ) -> List[Tuple[int, Dict[str, float]]]:
    """(layers, one layer's ``paged_decode_cost``) for each group of
    layers whose paged attention reads alike: every layer, here."""
    return [(config["num_hidden_layers"], paged_decode_cost(config,
                                                            contexts))]
