"""Language-model composition: embeddings → scanned layer units → logits.

Layer stacking follows ``ModelConfig.scan_plan()``: maximal repeating unit
patterns are stacked and driven by ``lax.scan`` so the HLO stays compact for
80-layer models (essential for the 512-device dry-run), with non-repeating
prologue layers (e.g. deepseek's first dense layer) unrolled.

Entry points:
    lm_specs / init            — parameter trees (ParamSpec-based)
    forward / loss_fn          — training path (differentiable)
    encode                     — whisper encoder (stub frame embeddings in)
    prefill / decode_step      — serving path with stacked caches
All take a ``ForwardOpts`` bundle selecting attention impl, chunk sizes,
MoE dispatch, remat policy, and norm impl — these are the distribution-level
tunables swept by the §Perf hillclimbs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distribution.sharding import shard
from repro.models import attention as ATT
from repro.models import mamba2 as MAM
from repro.models import moe as MOE
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mlp, apply_norm, embed_specs, embed_tokens, logits_out, mlp_specs,
    norm_specs,
)
from repro.models.param import ParamSpec, init_params, stack_tree


@dataclasses.dataclass(frozen=True)
class ForwardOpts:
    attn_impl: str = "chunked"       # full | chunked | triangular | pallas
    decode_impl: str = "full"        # full | pallas (registry decode kernels)
    attn_chunk: int = 512
    moe_impl: str = "index"          # index | einsum
    remat: str = "none"              # none | full | dots
    norm_impl: str = "jnp"           # jnp | pallas
    ssd_chunk: Optional[int] = None  # None → cfg.ssm.chunk
    # Quantization policy (repro.quant): None | w8a8 | w8a16 | kv8. Weight
    # policies take effect through quant.quantize_params (QTensor leaves
    # dispatch the quantized GEMM wherever they appear); kv8 makes the
    # serving caches int8 (dense and paged). quant_impl picks the GEMM
    # backend: "sim" = exact integer-grid XLA math (host production path),
    # "pallas" = the autotuned matmul_w8a8 kernel (TPU / interpret mode).
    quant: Optional[str] = None
    quant_impl: str = "sim"          # sim | pallas

    def kv_dtype(self) -> Optional[str]:
        from repro.quant.policy import get_policy
        pol = get_policy(self.quant)
        return pol.kv_dtype if pol is not None else None


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def layer_specs(cfg: ModelConfig, kind: str):
    mixer, ffn = kind.split("_")
    specs: Dict[str, Any] = {"ln1": norm_specs(cfg)}
    if mixer in ("attn", "enc", "dec"):
        specs["mix"] = ATT.attn_specs(cfg)
    else:
        specs["mix"] = MAM.mamba_specs(cfg)
    if mixer == "dec":
        specs["lnx"] = norm_specs(cfg)
        specs["cross"] = ATT.cross_specs(cfg)
    if ffn == "mlp":
        specs["ln2"] = norm_specs(cfg)
        specs["ffn"] = mlp_specs(cfg, cfg.d_ff_dense or cfg.d_ff)
    elif ffn == "moe":
        specs["ln2"] = norm_specs(cfg)
        specs["ffn"] = MOE.moe_specs(cfg)
    return specs


def _unit_specs(cfg: ModelConfig, unit: Tuple[str, ...]):
    return {f"l{i}": layer_specs(cfg, kind) for i, kind in enumerate(unit)}


def lm_specs(cfg: ModelConfig):
    specs: Dict[str, Any] = {"embed": embed_specs(cfg),
                             "final_ln": norm_specs(cfg)}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        u = _unit_specs(cfg, unit)
        specs[f"u{ui}"] = stack_tree(u, reps) if reps > 1 else u
    if cfg.family == "encdec":
        enc_unit = _unit_specs(cfg, ("enc_mlp",))
        specs["enc"] = {
            "pos": ParamSpec((cfg.enc_seq, cfg.d_model), (None, "d_model"),
                             jnp.dtype(cfg.dtype), "normal", 0.02),
            "units": stack_tree(enc_unit, cfg.n_enc_layers),
            "final_ln": norm_specs(cfg),
        }
    return specs


def init(rng, cfg: ModelConfig):
    return init_params(rng, lm_specs(cfg))


# ---------------------------------------------------------------------------
# Train-path blocks
# ---------------------------------------------------------------------------

def _block_apply(p, h, kind, cfg: ModelConfig, opts: ForwardOpts,
                 cross_kv=None):
    mixer, ffn = kind.split("_")
    hn = apply_norm(p["ln1"], h, cfg, impl=opts.norm_impl)
    if mixer in ("attn", "enc", "dec"):
        mix = ATT.attn_forward(p["mix"], hn, cfg, impl=opts.attn_impl,
                               chunk=opts.attn_chunk,
                               causal=(mixer != "enc"))
    else:
        mix = MAM.mamba_forward(p["mix"], hn, cfg, chunk=opts.ssd_chunk)
    h = h + mix
    if mixer == "dec":
        hx = apply_norm(p["lnx"], h, cfg, impl=opts.norm_impl)
        h = h + ATT.cross_forward(p["cross"], hx, cfg, cross_kv,
                                  impl="chunked", chunk=opts.attn_chunk)
    aux = jnp.zeros((), jnp.float32)
    if ffn == "mlp":
        dense_cfg = (dataclasses.replace(cfg, d_ff=cfg.d_ff_dense)
                     if cfg.d_ff_dense else cfg)
        h = h + apply_mlp(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                               impl=opts.norm_impl), dense_cfg,
                          quant_impl=opts.quant_impl)
    elif ffn == "moe":
        fn = _moe_fn(opts)
        mo, aux = fn(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                          impl=opts.norm_impl), cfg)
        h = h + mo
    return h, aux


def _moe_fn(opts: ForwardOpts):
    return {"index": MOE.apply_moe, "einsum": MOE.apply_moe_einsum,
            "shmap": MOE.apply_moe_shmap}[opts.moe_impl]


def _unit_apply(pu, h, unit, cfg, opts, cross_kv=None):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(unit):
        h, a = _block_apply(pu[f"l{i}"], h, kind, cfg, opts,
                            cross_kv=cross_kv)
        aux = aux + a
    return h, aux


def _maybe_remat(fn, opts: ForwardOpts):
    if opts.remat == "none":
        return fn
    if opts.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _run_units(params, h, cfg: ModelConfig, opts: ForwardOpts, cross_kv=None):
    aux_total = jnp.zeros((), jnp.float32)
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        pu = params[f"u{ui}"]
        if reps == 1:
            fn = _maybe_remat(
                lambda p_, h_: _unit_apply(p_, h_, unit, cfg, opts, cross_kv),
                opts)
            h, aux = fn(pu, h)
            aux_total = aux_total + aux
        else:
            def body(h_, pl, unit=unit):
                h2, aux = _unit_apply(pl, h_, unit, cfg, opts, cross_kv)
                return h2, aux
            h, auxs = jax.lax.scan(_maybe_remat(
                lambda c, x: body(c, x), opts), h, pu)
            aux_total = aux_total + jnp.sum(auxs)
    return h, aux_total


# ---------------------------------------------------------------------------
# Public: encode / forward / loss
# ---------------------------------------------------------------------------

def encode(params, cfg: ModelConfig, enc_embeds,
           opts: ForwardOpts = ForwardOpts()):
    """Whisper encoder over stub frame embeddings (B, enc_seq, d)."""
    pe = params["enc"]
    h = enc_embeds + pe["pos"].astype(enc_embeds.dtype)
    h = shard(h, "batch", "seq", None)

    def body(h_, pl):
        h2, _ = _unit_apply(pl, h_, ("enc_mlp",), cfg, opts)
        return h2, None

    h, _ = jax.lax.scan(_maybe_remat(body, opts), h, pe["units"])
    return apply_norm(pe["final_ln"], h, cfg, impl=opts.norm_impl)


def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            enc_embeds=None, opts: ForwardOpts = ForwardOpts()):
    """tokens (B, S) → logits (B, S_total, vocab) fp32. ``prefix_embeds``
    (VLM stub patches) are prepended; ``enc_embeds`` feed the encoder."""
    h = embed_tokens(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        h = shard(h, "batch", "seq", None)
    cross = None
    if cfg.family == "encdec":
        assert enc_embeds is not None
        enc_h = encode(params, cfg, enc_embeds, opts)
        # Cross-KV shared across decoder layers is wrong — each layer has
        # its own projections; pass enc states and project per layer.
        cross = enc_h
    h, aux = _run_units_with_cross(params, h, cfg, opts, cross)
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    return logits_out(params["embed"], h, cfg), aux


def _run_units_with_cross(params, h, cfg, opts, enc_h):
    if enc_h is None:
        return _run_units(params, h, cfg, opts)
    # Decoder units project their own cross-KV from enc_h inside the layer.
    aux_total = jnp.zeros((), jnp.float32)
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        pu = params[f"u{ui}"]

        def body(h_, pl, unit=unit):
            hh = h_
            aux = jnp.zeros((), jnp.float32)
            for i, kind in enumerate(unit):
                p = pl[f"l{i}"]
                kv = ATT.cross_kv(p["cross"], enc_h, cfg) \
                    if kind.startswith("dec") else None
                hh, a = _block_apply(p, hh, kind, cfg, opts, cross_kv=kv)
                aux = aux + a
            return hh, aux

        if reps == 1:
            h, aux = _maybe_remat(body, opts)(h, pu)
            aux_total += aux
        else:
            h, auxs = jax.lax.scan(_maybe_remat(body, opts), h, pu)
            aux_total += jnp.sum(auxs)
    return h, aux_total


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            opts: ForwardOpts = ForwardOpts()):
    """batch: tokens (B,S) int32, labels (B,S) int32 (−1 = masked), plus
    optional prefix_embeds / enc_embeds. Returns (loss, metrics)."""
    logits, aux = forward(
        params, cfg, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        enc_embeds=batch.get("enc_embeds"), opts=opts)
    labels = batch["labels"]
    if batch.get("prefix_embeds") is not None:
        logits = logits[:, batch["prefix_embeds"].shape[1]:]
    valid = labels >= 0
    labels_safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # One-hot contraction instead of take_along_axis: stays sharded over a
    # tensor-parallel vocab axis (a gather would all-gather the logits —
    # tens of GB/device at 200k vocab).
    onehot = jax.nn.one_hot(labels_safe, logits.shape[-1],
                            dtype=logits.dtype)
    ll = jnp.sum(logits * onehot, axis=-1)
    ce = jnp.where(valid, lse - ll, 0.0)
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    ce_mean = jnp.sum(ce) / n_valid
    aux_coef = cfg.moe.aux_loss_coef if cfg.moe is not None else 0.0
    loss = ce_mean + aux_coef * aux
    acc = jnp.sum(
        jnp.where(valid, (jnp.argmax(logits, -1) == labels_safe), 0)
    ) / n_valid
    return loss, {"ce": ce_mean, "aux": aux, "acc": acc,
                  "tokens": n_valid.astype(jnp.float32)}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with stacked caches
# ---------------------------------------------------------------------------

def _block_prefill(p, h, kind, cfg, opts, max_len, enc_h=None):
    mixer, ffn = kind.split("_")
    cache: Dict[str, Any] = {}
    hn = apply_norm(p["ln1"], h, cfg, impl=opts.norm_impl)
    if mixer in ("attn", "dec"):
        mix, c = ATT.attn_prefill(p["mix"], hn, cfg, max_len=max_len,
                                  impl=opts.attn_impl, chunk=opts.attn_chunk,
                                  kv_dtype=opts.kv_dtype())
        cache["self"] = c
    else:
        mix, c = MAM.mamba_prefill(p["mix"], hn, cfg, chunk=opts.ssd_chunk)
        cache["ssm"] = c
    h = h + mix
    if mixer == "dec":
        kv = ATT.cross_kv(p["cross"], enc_h, cfg)
        hx = apply_norm(p["lnx"], h, cfg, impl=opts.norm_impl)
        h = h + ATT.cross_forward(p["cross"], hx, cfg, kv,
                                  chunk=opts.attn_chunk)
        cache["cross"] = kv
    if ffn == "mlp":
        dense_cfg = (dataclasses.replace(cfg, d_ff=cfg.d_ff_dense)
                     if cfg.d_ff_dense else cfg)
        h = h + apply_mlp(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                               impl=opts.norm_impl), dense_cfg,
                          quant_impl=opts.quant_impl)
    elif ffn == "moe":
        mo, _ = _moe_fn(opts)(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                                   impl=opts.norm_impl), cfg)
        h = h + mo
    return h, cache


def _block_decode(p, h, kind, cfg, opts, cache, pos):
    mixer, ffn = kind.split("_")
    new: Dict[str, Any] = dict(cache)
    hn = apply_norm(p["ln1"], h, cfg, impl=opts.norm_impl)
    if mixer in ("attn", "dec"):
        mix, c = ATT.attn_decode(p["mix"], hn, cfg, cache["self"], pos,
                                 impl=opts.decode_impl)
        new["self"] = c
    else:
        mix, c = MAM.mamba_decode(p["mix"], hn, cfg, cache["ssm"])
        new["ssm"] = c
    h = h + mix
    if mixer == "dec":
        hx = apply_norm(p["lnx"], h, cfg, impl=opts.norm_impl)
        h = h + ATT.cross_forward(p["cross"], hx, cfg, cache["cross"],
                                  impl="full")
    if ffn == "mlp":
        dense_cfg = (dataclasses.replace(cfg, d_ff=cfg.d_ff_dense)
                     if cfg.d_ff_dense else cfg)
        h = h + apply_mlp(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                               impl=opts.norm_impl), dense_cfg,
                          quant_impl=opts.quant_impl)
    elif ffn == "moe":
        mo, _ = _moe_fn(opts)(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                                   impl=opts.norm_impl), cfg)
        h = h + mo
    return h, new


def prefill(params, cfg: ModelConfig, tokens, *, max_len: int,
            enc_embeds=None, prefix_embeds=None,
            opts: ForwardOpts = ForwardOpts()):
    """Run the prompt, return (last-position logits, cache)."""
    h = embed_tokens(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    enc_h = encode(params, cfg, enc_embeds, opts) if cfg.family == "encdec" \
        else None
    caches = {}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        pu = params[f"u{ui}"]

        def body(h_, pl, unit=unit):
            hh = h_
            cs = {}
            for i, kind in enumerate(unit):
                hh, c = _block_prefill(pl[f"l{i}"], hh, kind, cfg, opts,
                                       max_len, enc_h=enc_h)
                cs[f"l{i}"] = c
            return hh, cs

        if reps == 1:
            h, cs = body(h, pu)
        else:
            h, cs = jax.lax.scan(body, h, pu)
        caches[f"u{ui}"] = cs
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    logits = logits_out(params["embed"], h[:, -1:], cfg)
    return logits[:, 0], caches


def decode_step(params, cfg: ModelConfig, token, cache, pos,
                opts: ForwardOpts = ForwardOpts()):
    """token (B, 1) int32; pos scalar int32. → (logits (B, vocab), cache)."""
    if cfg.learned_pos:
        h = (jnp.take(params["embed"]["tok"], token, axis=0) +
             params["embed"]["pos"][pos][None, None, :].astype(
                 jnp.dtype(cfg.dtype)))
    else:
        h = embed_tokens(params["embed"], token, cfg)
    new_cache = {}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        pu = params[f"u{ui}"]
        cu = cache[f"u{ui}"]

        def body(h_, xs, unit=unit):
            pl, cl = xs
            hh = h_
            ncs = {}
            for i, kind in enumerate(unit):
                hh, nc = _block_decode(pl[f"l{i}"], hh, kind, cfg, opts,
                                       cl[f"l{i}"], pos)
                ncs[f"l{i}"] = nc
            return hh, ncs

        if reps == 1:
            h, ncs = body(h, (pu, cu))
        else:
            h, ncs = jax.lax.scan(body, h, (pu, cu))
        new_cache[f"u{ui}"] = ncs
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    logits = logits_out(params["embed"], h, cfg)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Paged serving: chunked prefill + decode over a shared page pool
# (block tables / lengths are scheduler state, repro/serving/)
# ---------------------------------------------------------------------------

def _check_paged(cfg: ModelConfig) -> None:
    if cfg.family != "dense" or cfg.mla is not None or cfg.window is not None \
            or cfg.learned_pos or cfg.n_prefix:
        raise NotImplementedError(
            f"paged serving supports dense RoPE attention archs; "
            f"{cfg.name!r} needs MLA/SWA/enc-dec/prefix paging")


def _apply_ffn(p, h, ffn, cfg: ModelConfig, opts: ForwardOpts):
    if ffn == "mlp":
        dense_cfg = (dataclasses.replace(cfg, d_ff=cfg.d_ff_dense)
                     if cfg.d_ff_dense else cfg)
        return h + apply_mlp(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                                  impl=opts.norm_impl),
                             dense_cfg, quant_impl=opts.quant_impl)
    if ffn == "moe":
        mo, _ = _moe_fn(opts)(p["ffn"], apply_norm(p["ln2"], h, cfg,
                                                   impl=opts.norm_impl), cfg)
        return h + mo
    return h


_PAGED_ATTN = {
    "prefill": ATT.attn_prefill_paged,
    "decode": ATT.attn_decode_paged,
    "verify": ATT.attn_verify_paged,
}


def _block_paged(p, h, kind, cfg, opts, cache, tables, start, layer, *,
                 mode):
    mixer, ffn = kind.split("_")
    assert mixer == "attn", f"paged serving: unsupported mixer {mixer!r}"
    hn = apply_norm(p["ln1"], h, cfg, impl=opts.norm_impl)
    mix, c = _PAGED_ATTN[mode](p["mix"], hn, cfg, cache["self"],
                               tables, start, layer)
    h = _apply_ffn(p, h + mix, ffn, cfg, opts)
    return h, {"self": c}


def _run_units_paged(params, h, cfg, opts, cache, tables, start, *, mode):
    """Every unit's pools (stacked along the unit's repeats) ride in the
    layer scan's carry with the activations; the layer index comes in as
    ``xs`` beside the layer's weights. Each layer writes its new tokens and
    reads its pages in place at that index, so no layer's pool is sliced
    out of the stack or written back whole."""
    new_cache = {}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        pu = params[f"u{ui}"]

        def body(carry, xs, unit=unit):
            hh, cu = carry
            pl, layer = xs
            cu = dict(cu)
            for i, kind in enumerate(unit):
                hh, cu[f"l{i}"] = _block_paged(
                    pl[f"l{i}"], hh, kind, cfg, opts, cu[f"l{i}"], tables,
                    start, layer, mode=mode)
            return (hh, cu), None

        carry = (h, cache[f"u{ui}"])
        if reps == 1:
            carry, _ = body(carry, (pu, 0))
        else:
            carry, _ = jax.lax.scan(body, carry,
                                    (pu, jnp.arange(reps, dtype=jnp.int32)))
        h, new_cache[f"u{ui}"] = carry
    return h, new_cache


def prefill_paged(params, cfg: ModelConfig, tokens, cache, block_tables,
                  start, opts: ForwardOpts = ForwardOpts()):
    """One chunked-prefill step: tokens (B, S) land at positions
    start[b]..start[b]+S-1, KV written through the block tables. Returns
    (all-position logits (B, S, vocab), new cache) — chunks are padded to a
    fixed width by the scheduler, so the caller picks the logit at its last
    *valid* position, not position -1."""
    _check_paged(cfg)
    h = embed_tokens(params["embed"], tokens, cfg)
    h, new_cache = _run_units_paged(params, h, cfg, opts, cache,
                                    block_tables, start, mode="prefill")
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    logits = logits_out(params["embed"], h, cfg)
    return logits, new_cache


def decode_step_paged(params, cfg: ModelConfig, token, cache, block_tables,
                      lens, opts: ForwardOpts = ForwardOpts()):
    """One-token paged decode across the continuous batch. token (B, 1);
    lens (B,) int32 resident lengths (0 = inactive slot). Returns
    (logits (B, vocab), new cache)."""
    _check_paged(cfg)
    h = embed_tokens(params["embed"], token, cfg)
    h, new_cache = _run_units_paged(params, h, cfg, opts, cache,
                                    block_tables, lens, mode="decode")
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    logits = logits_out(params["embed"], h, cfg)
    return logits[:, 0], new_cache


def verify_step_paged(params, cfg: ModelConfig, tokens, cache, block_tables,
                      lens, opts: ForwardOpts = ForwardOpts()):
    """Speculative verify across the continuous batch: score K consecutive
    positions per sequence in one pass. tokens (B, K) — the last committed
    token plus K-1 drafts, landing at positions lens[b]..lens[b]+K-1;
    lens (B,) int32 resident lengths (0 = inactive slot). Returns
    (logits (B, K, vocab), new cache): logits[:, t] predicts the token
    after draft position t, exactly what K sequential ``decode_step_paged``
    calls would produce when every draft matches."""
    _check_paged(cfg)
    h = embed_tokens(params["embed"], tokens, cfg)
    h, new_cache = _run_units_paged(params, h, cfg, opts, cache,
                                    block_tables, lens, mode="verify")
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    logits = logits_out(params["embed"], h, cfg)
    return logits, new_cache


def paged_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int,
                      kv_dtype: Optional[str] = None):
    """ShapeDtypeStruct tree matching the paged cache: per unit and layer
    of the unit, the pools of its ``reps`` repeats stacked in one array
    (reps, Hkv, P, page_size, D) — a unit that does not repeat stacks
    one. ``kv_dtype="int8"`` (the kv8 policy) makes the pools int8 with
    parallel per-token scale pools."""
    _check_paged(cfg)
    caches = {}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        cs = {f"l{i}": {"self": ATT.paged_cache_spec(cfg, num_pages,
                                                     page_size,
                                                     kv_dtype=kv_dtype)}
              for i, kind in enumerate(unit)}
        caches[f"u{ui}"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((reps,) + s.shape, s.dtype), cs)
    return caches


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     kv_dtype: Optional[str] = None):
    """Zero-filled page pools for every layer (int8 + scale pools under
    ``kv_dtype="int8"``)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_specs(cfg, num_pages, page_size,
                                          kv_dtype=kv_dtype))


def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                kv_dtype: Optional[str] = None):
    """ShapeDtypeStruct tree matching prefill's cache (for the dry-run)."""
    caches = {}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):
        cs = {}
        for i, kind in enumerate(unit):
            mixer = kind.split("_")[0]
            c: Dict[str, Any] = {}
            if mixer in ("attn", "dec"):
                c["self"] = ATT.attn_cache_spec(cfg, batch, max_len,
                                                kv_dtype=kv_dtype)
            else:
                c["ssm"] = MAM.mamba_cache_spec(cfg, batch)
            if mixer == "dec":
                dt = jnp.dtype(cfg.dtype)
                kvs = (batch, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim)
                c["cross"] = {"ck": jax.ShapeDtypeStruct(kvs, dt),
                              "cv": jax.ShapeDtypeStruct(kvs, dt)}
            cs[f"l{i}"] = c
        if reps > 1:
            cs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((reps,) + s.shape, s.dtype), cs)
        caches[f"u{ui}"] = cs
    return caches
