"""Work counts of the tests' smoke decoder with sliding-window layers:
harness/flops.py's dense counts, with each windowed layer's keys capped
at ``sliding_window`` (``layer_types`` says which layers are windowed)."""

from harness import flops


def _groups(config):
    """(layers, window or None) for the full and the windowed layers."""
    kinds = config["layer_types"]
    return [(kinds.count("full_attention"), None),
            (kinds.count("sliding_attention"), config["sliding_window"])]


def _keys(context, window):
    return context if window is None else min(context, window)


def _attention(config, keys_per_group):
    w = flops.widths(config)
    return sum(4.0 * n * w["hq"] * w["dh"] * k
               for (n, _), k in zip(_groups(config), keys_per_group))


def prefill_flops(config, start, tokens):
    keys = [sum(_keys(start + p + 1, win) for p in range(tokens))
            for _, win in _groups(config)]
    return tokens * flops.dense_flops_per_token(config) + _attention(
        config, keys)


def decode_flops(config, contexts):
    contexts = list(contexts)
    keys = [sum(_keys(c, win) for c in contexts)
            for _, win in _groups(config)]
    return len(contexts) * flops.dense_flops_per_token(config) + _attention(
        config, keys)


def paged_decode_groups(config, contexts):
    contexts = list(contexts)
    return [(n, flops.paged_decode_cost(config,
                                        [_keys(c, win) for c in contexts]))
            for n, win in _groups(config) if n]
