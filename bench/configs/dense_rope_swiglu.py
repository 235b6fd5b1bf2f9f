"""Plain float32 reference of a dense decoder: RMS norm, RoPE attention
(grouped KV heads), SwiGLU MLP, optionally tied embeddings.

Written from the published description alone and computed with
``jax.numpy`` at the highest matmul precision. It imports nothing of the
program and takes nothing the program made: it draws its own weights, one
layer at a time, from the seed (``harness/weights.py``), and runs every
sequence layer by layer, so a 3.8 B model fits beside nothing else.

Per layer, with x the residual stream:

  a = rms(x) * ln1;  q, k, v = a Wq, a Wk, a Wv  (heads of head_dim)
  q, k rotated by RoPE (theta, halves of head_dim rotated as pairs)
  x += softmax(q k^T / sqrt(head_dim), causal) v Wo   (query head h reads
       KV head h // (n_heads / n_kv_heads))
  b = rms(x) * ln2;  g | u = b Wi;  x += (silu(g) * u) Wo2
  logits = rms(x) * final_ln  @  (embed^T if tied, else unembed)

``precision="fp8"`` is the control: every matmul operand is rounded to
float8_e4m3fn (weights per output column, activations per row, attention
operands per head and row), the step below the bfloat16 the configuration
states.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops, weights

HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 1024         # sequences are padded to a multiple of this
Q_BLOCK = 512         # attention queries per block
LOGIT_ROWS = 256      # rows of logits formed at once
NORMS = ("ln1/w", "ln2/w")   # a layer's norm weights, drawn as ones


class Dims(NamedTuple):
    d: int
    hq: int
    hkv: int
    dh: int
    f: int
    vocab: int
    layers: int
    eps: float
    theta: float
    tied: bool


def dims(config: Dict) -> Dims:
    d = config["hidden_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return Dims(d=d, hq=hq, hkv=hkv, dh=flops.head_dim(config),
                f=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"],
                eps=config["rms_norm_eps"], theta=config["rope_theta"],
                tied=bool(config["tie_word_embeddings"]))


def _fp8(x, axis):
    """Round ``x`` to float8_e4m3fn with one scale per slice along
    ``axis`` (the contracted one), then back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, low):
    if low:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (T, H, D): rotate halves [:D/2] and [D/2:] as pairs by position."""
    T, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dm", "low"))
def _layer(h, w, *, dm, low):
    """One decoder layer over one sequence h (T, d)."""
    T = h.shape[0]
    hq, hkv, dh = dm.hq, dm.hkv, dm.dh
    g = hq // hkv
    a = _rms(h, w["ln1"], dm.eps)
    q = _rope(_mm(a, w["wq"], low).reshape(T, hq, dh), dm.theta)
    k = _rope(_mm(a, w["wk"], low).reshape(T, hkv, dh), dm.theta)
    v = _mm(a, w["wv"], low).reshape(T, hkv, dh)
    if low:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    q = q.reshape(T // Q_BLOCK, Q_BLOCK, hkv, g, dh)
    kpos = jnp.arange(T)

    def block(args):
        qb, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST)
        s = s * dh ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = _fp8(p, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (q, jnp.arange(T // Q_BLOCK)))
    h = h + _mm(o.reshape(T, hq * dh), w["wo"], low)
    b = _rms(h, w["ln2"], dm.eps)
    gu = _mm(b, w["wi"], low)
    gate, up = jnp.split(gu, 2, axis=-1)
    return h + _mm(jax.nn.silu(gate) * up, w["wo2"], low)


def _layer_weights(key, dm: Dims, layer, dtype):
    d, hq, hkv, dh, f = dm.d, dm.hq, dm.hkv, dm.dh, dm.f
    shapes = {"ln1/w": (d,), "mix/wq": (d, hq * dh), "mix/wk": (d, hkv * dh),
              "mix/wv": (d, hkv * dh), "mix/wo": (hq * dh, d),
              "ln2/w": (d,), "ffn/wi": (d, 2 * f), "ffn/wo": (f, d)}
    w = {name: weights.draw(key, name, layer, shape, dtype,
                            init="ones" if name in NORMS else "normal")
         .astype(jnp.float32) for name, shape in shapes.items()}
    return {"ln1": w["ln1/w"], "wq": w["mix/wq"], "wk": w["mix/wk"],
            "wv": w["mix/wv"], "wo": w["mix/wo"], "ln2": w["ln2/w"],
            "wi": w["ffn/wi"], "wo2": w["ffn/wo"]}


_layer_weights_jit = jax.jit(_layer_weights, static_argnames=("dm", "dtype"))


@functools.partial(jax.jit, static_argnames=("dm", "dtype"))
def _tables(key, *, dm, dtype):
    """The token embedding and the output head, as served, in float32."""
    tok = weights.draw(key, "embed/tok", 0, (dm.vocab, dm.d), dtype,
                       tied=dm.tied).astype(jnp.float32)
    if dm.tied:
        return tok, tok.T
    out = weights.draw(key, "embed/unembed", 0, (dm.d, dm.vocab), dtype)
    return tok, out.astype(jnp.float32)


@jax.jit
def _embed(tok, ids):
    return tok[ids]


@functools.partial(jax.jit, static_argnames=("dm", "low"))
def _head(key, out, h, targets, *, dm, low):
    """For each row of h: the best logit, the logits of ``targets`` (K
    candidate tokens per row) and the argmax."""
    ln = weights.draw(key, "final_ln/w", 0, (dm.d,), jnp.float32,
                      init="ones")
    x = _rms(h, ln, dm.eps).reshape(-1, LOGIT_ROWS, dm.d)

    def rows(args):
        xb, tb = args
        lg = _mm(xb, out, low)
        return (lg.max(-1), jnp.take_along_axis(lg, tb, -1),
                jnp.argmax(lg, -1).astype(jnp.int32))

    t = targets.reshape(-1, LOGIT_ROWS, targets.shape[-1])
    best, picked, top = jax.lax.map(rows, (x, t))
    return best.reshape(-1), picked.reshape(-1, targets.shape[-1]), \
        top.reshape(-1)


def forward(config: Dict, seed: int, seqs: Sequence[np.ndarray],
            targets: Sequence[np.ndarray], precision: str = "float32"
            ) -> List[Dict[str, np.ndarray]]:
    """Run every sequence through the model. ``targets[i]`` is (len, K)
    token ids: row p holds candidates for the token after position p.
    Returns per sequence ``best`` (len,), ``picked`` (len, K) logits and
    ``top`` (len,) argmax ids."""
    if precision not in ("float32", "fp8"):
        raise ValueError(precision)
    low = precision == "fp8"
    dm = dims(config)
    key = weights.base_key(seed)
    dtype = config["deployment"]["dtype"]      # the weights as served
    lens = [len(s) for s in seqs]
    pad = [-(-n // BUCKET) * BUCKET for n in lens]
    tok, head = _tables(key, dm=dm, dtype=dtype)
    hs = [_embed(tok, jnp.asarray(np.pad(s, (0, p - n))))
          for s, n, p in zip(seqs, lens, pad)]
    del tok
    for layer in range(dm.layers):
        w = _layer_weights_jit(key, dm, layer, dtype)
        hs = [_layer(h, w, dm=dm, low=low) for h in hs]
        del w
    out = []
    for h, t, n, p in zip(hs, targets, lens, pad):
        t = np.pad(np.asarray(t, np.int32), ((0, p - n), (0, 0)))
        best, picked, top = _head(key, head, h, jnp.asarray(t), dm=dm,
                                  low=low)
        out.append({"best": np.asarray(best)[:n],
                    "picked": np.asarray(picked)[:n],
                    "top": np.asarray(top)[:n]})
    return out
