"""Paged-KV continuous-batching serving: pool/scheduler invariants
(property-based where hypothesis is available, seeded otherwise), block
tables vs a dense reference cache, and the end-to-end guarantee that paged
continuous batching generates token-for-token what the dense static path
generates."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # container may lack hypothesis — skip properties
    from conftest import hypothesis_fallback
    given, settings, st = hypothesis_fallback()

from repro.serving import PagePool, PrefixCache, Request, Scheduler
from repro.serving.page_pool import SCRATCH_PAGE


# ---------------------------------------------------------------------------
# PagePool: ref-counted free list
# ---------------------------------------------------------------------------

def test_pool_alloc_free_roundtrip():
    pool = PagePool(num_pages=8, page_size=16)
    assert pool.num_free == 7                       # page 0 reserved
    pages = pool.alloc(3)
    assert len(pages) == 3 and SCRATCH_PAGE not in pages
    assert pool.num_free == 4 and pool.num_allocated == 3
    assert pool.alloc(5) is None                    # admission control
    pool.free(pages)
    assert pool.num_free == 7 and pool.num_allocated == 0
    pool.check_invariants()


def test_pool_refcount_sharing():
    pool = PagePool(num_pages=4, page_size=8)
    pages = pool.alloc(2)
    pool.share(pages)                               # second owner (fork)
    pool.free(pages)                                # first owner releases
    assert pool.num_allocated == 2                  # still held
    pool.check_invariants()
    pool.free(pages)                                # last owner releases
    assert pool.num_free == 3
    pool.check_invariants()


def test_pool_double_free_raises():
    pool = PagePool(num_pages=4, page_size=8)
    pages = pool.alloc(1)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free(pages)
    with pytest.raises(ValueError, match="unallocated"):
        pool.share([2])
    pool.check_invariants()


def test_pool_pages_for():
    pool = PagePool(num_pages=4, page_size=16)
    assert pool.pages_for(0) == 0
    assert pool.pages_for(1) == 1
    assert pool.pages_for(16) == 1
    assert pool.pages_for(17) == 2


# ---------------------------------------------------------------------------
# Scheduler traces: no page leaked or double-freed, tables consistent
# ---------------------------------------------------------------------------

def drive_trace(reqs, num_pages=16, page_size=8, max_batch=3,
                prefill_chunk=4, check_every_step=True,
                prefix_cache=False):
    """Run a full admit/prefill/decode/retire trace without a model:
    generation is faked by appending dummy token ids. Returns the
    scheduler after the trace drains. With ``prefix_cache`` retirement
    parks pages in a radix trie instead of freeing them."""
    pool = PagePool(num_pages, page_size)
    sched = Scheduler(pool, max_batch=max_batch,
                      max_pages=pool.pages_for(64),
                      prefill_chunk=prefill_chunk,
                      prefix_cache=PrefixCache(pool) if prefix_cache
                      else None)
    for r in reqs:
        sched.submit(r)
    guard = 0
    while sched.has_work():
        guard += 1
        assert guard < 10_000, "trace did not drain"
        sched.retire_finished()
        sched.admit()
        chunk = sched.next_prefill()
        if chunk is not None:
            b, tokens, start, valid = chunk
            sched.mark_prefilled(b, valid)
            seq = sched.slots[b]
            if seq.prompt_done:
                seq.req.tokens.append(1)
        mask = sched.decode_mask()
        for b in np.nonzero(mask)[0]:
            sched.slots[int(b)].req.tokens.append(1)
        sched.advance_decoded(mask)
        if check_every_step:
            sched.check_invariants()
    sched.retire_finished()
    sched.check_invariants()
    return sched


def _mk_reqs(spec):
    return [Request(rid=i, prompt=np.arange(1, p + 1, dtype=np.int32),
                    max_new_tokens=g) for i, (p, g) in enumerate(spec)]


def test_trace_drains_and_recycles_pages():
    sched = drive_trace(_mk_reqs([(5, 3), (12, 1), (1, 6), (20, 4),
                                  (7, 2), (3, 3)]))
    assert len(sched.finished) == 6
    assert sched.pool.num_allocated == 0            # everything recycled
    for r in sched.finished:
        assert len(r.tokens) == r.max_new_tokens


def test_admission_blocks_under_pool_pressure_then_recovers():
    # Pool fits ~one big request at a time: admission must serialize
    # without leaking or deadlocking.
    sched = drive_trace(_mk_reqs([(30, 4), (30, 4), (30, 4)]),
                        num_pages=7, page_size=8, max_batch=3)
    assert len(sched.finished) == 3
    assert sched.pool.num_allocated == 0


def test_oversized_request_rejected():
    # Oversized submissions complete as FAILED results, never exceptions:
    # one bad request in a replayed trace must not abort the whole run.
    from repro.serving import RequestState

    pool = PagePool(8, 8)
    sched = Scheduler(pool, max_batch=2, max_pages=2, prefill_chunk=4)
    req = Request(rid=0, prompt=np.ones(30, np.int32), max_new_tokens=8)
    sched.submit(req)
    assert req.state is RequestState.FAILED
    assert "pages > table width" in req.failure_reason
    assert req in sched.finished
    assert not sched.waiting and not sched.has_work()


@given(st.lists(st.tuples(st.integers(1, 24), st.integers(1, 6)),
                min_size=1, max_size=12),
       st.integers(1, 4), st.sampled_from([4, 8]), st.sampled_from([2, 4, 8]))
@settings(max_examples=25, deadline=None)
def test_property_no_leak_no_double_free(spec, max_batch, page_size, chunk):
    """Random admit/finish traces: every page is either free or owned by
    exactly one live sequence at every step, and the pool is whole after
    the trace drains (checked inside drive_trace each step)."""
    sched = drive_trace(_mk_reqs(spec), num_pages=16, page_size=page_size,
                        max_batch=max_batch, prefill_chunk=chunk)
    assert len(sched.finished) == len(spec)
    assert sched.pool.num_allocated == 0
    assert sched.pool.num_free == sched.pool.num_pages - 1


# ---------------------------------------------------------------------------
# Share/free/fork traces interleaved with admission bursts: the refcount
# machinery (prefix caching / beam forks) must keep the pool whole under
# arbitrary interleavings, not just the scheduler's own alloc/free pattern.
# ---------------------------------------------------------------------------

def drive_fork_trace(ops, num_pages=16, page_size=8, max_batch=3):
    """Interpret a trace of (op, arg) steps against a PagePool plus a
    shadow ownership model, checking ``check_invariants`` AND shadow
    agreement after every step.

    Ops: ("burst", n)  — admission burst: up to n allocations of 1-3 pages
         ("fork", i)   — share() handle i's pages (new owner, beam fork)
         ("free", i)   — release handle i (indices wrap over live handles)
    Returns the pool and the live-handle list (caller drains + re-checks).
    """
    pool = PagePool(num_pages, page_size)
    handles = []                       # each: list of pages owned once

    def check():
        pool.check_invariants()
        want = {}
        for h in handles:
            for p in h:
                want[p] = want.get(p, 0) + 1
        for p in range(1, num_pages):
            assert pool.refcount(p) == want.get(p, 0), \
                f"page {p}: pool says {pool.refcount(p)}, shadow {want.get(p, 0)}"

    for op, arg in ops:
        if op == "burst":
            for k in range(arg):
                pages = pool.alloc(1 + (k % 3))
                if pages is None:
                    break              # admission control, not an error
                handles.append(pages)
        elif op == "fork" and handles:
            src = handles[arg % len(handles)]
            pool.share(src)
            handles.append(list(src))
        elif op == "free" and handles:
            pool.free(handles.pop(arg % len(handles)))
        check()
    return pool, handles


def _drain(pool, handles):
    while handles:
        pool.free(handles.pop())
        pool.check_invariants()
    assert pool.num_allocated == 0
    assert pool.num_free == pool.num_pages - 1


def test_fork_trace_seeded():
    rng = np.random.default_rng(7)
    for _ in range(20):
        n = int(rng.integers(1, 30))
        ops = [(("burst", "fork", "free")[int(rng.integers(0, 3))],
                int(rng.integers(0, 6))) for _ in range(n)]
        pool, handles = drive_fork_trace(
            ops, num_pages=int(rng.integers(4, 24)),
            page_size=int(rng.choice([4, 8])))
        _drain(pool, handles)


@given(st.lists(st.tuples(st.sampled_from(["burst", "fork", "free"]),
                          st.integers(0, 6)), min_size=1, max_size=40),
       st.integers(4, 24))
@settings(max_examples=50, deadline=None)
def test_property_fork_traces_keep_pool_whole(ops, num_pages):
    """Every interleaving of admission bursts, prefix forks, and frees
    keeps refcounts exact and the pool leak-free at every step."""
    pool, handles = drive_fork_trace(ops, num_pages=num_pages)
    _drain(pool, handles)


def test_scheduler_trace_with_shared_prefix_pages():
    """A scheduler trace runs to completion while an external owner holds
    share()d references to admitted sequences' pages (prefix cache): the
    scheduler's frees release its ownership only, the pages survive until
    the external owner lets go, and invariants hold at every step."""
    pool = PagePool(24, 8)
    sched = Scheduler(pool, max_batch=2, max_pages=pool.pages_for(64),
                      prefill_chunk=4)
    for r in _mk_reqs([(6, 3), (10, 2), (4, 4), (9, 1)]):
        sched.submit(r)
    forked = []
    guard = 0
    while sched.has_work():
        guard += 1
        assert guard < 10_000
        sched.retire_finished()
        for b in sched.admit():
            # fork every admitted sequence's pages (prefix cache holds on)
            pages = sched.slots[b].pages
            pool.share(pages)
            forked.append(list(pages))
        chunk = sched.next_prefill()
        if chunk is not None:
            b, tokens, start, valid = chunk
            sched.mark_prefilled(b, valid)
            if sched.slots[b].prompt_done:
                sched.slots[b].req.tokens.append(1)
        mask = sched.decode_mask()
        for b in np.nonzero(mask)[0]:
            sched.slots[int(b)].req.tokens.append(1)
        sched.advance_decoded(mask)
        sched.check_invariants()
    sched.retire_finished()
    sched.check_invariants()
    # Scheduler released its ownerships; the forked prefixes still pin
    # every page they reference (held pages are never recycled, so each
    # admission got fresh pages and the forked sets are disjoint).
    assert len(sched.finished) == 4
    assert pool.num_allocated == len({p for f in forked for p in f})
    for f in forked:
        pool.free(f)
        pool.check_invariants()
    assert pool.num_allocated == 0
    assert pool.num_free == pool.num_pages - 1


# ---------------------------------------------------------------------------
# Prefix-cached scheduler traces: marginal admission accounting, eviction
# under pressure, and forks composing with cache hits (PR 5 share/free
# machinery under the radix trie). Trie-level unit and shadow-model tests
# live in tests/test_prefix_cache.py.
# ---------------------------------------------------------------------------

def test_cached_trace_prefill_accounting_is_exact():
    """Every prompt token is either computed by a prefill chunk or served
    from a cached page — never both, never neither: over a whole trace
    ``total_prefill_tokens + total_cached_tokens == sum(prompt lens)``,
    and only the parked pages survive the drain."""
    specs = [([(5, 3), (12, 1), (1, 6), (20, 4), (7, 2), (3, 3)], 3),
             ([(16, 2), (16, 2), (16, 2)], 1),     # identical, serialized
             ([(24, 1), (8, 5), (24, 1), (9, 2)], 2)]
    for spec, max_batch in specs:      # _mk_reqs prompts share prefixes
        sched = drive_trace(_mk_reqs(spec), prefix_cache=True,
                            max_batch=max_batch, page_size=4)
        cache = sched.prefix_cache
        assert len(sched.finished) == len(spec)
        assert sched.total_prefill_tokens + sched.total_cached_tokens \
            == sum(p for p, _ in spec)
        assert sched.total_cached_tokens > 0       # sharing happened
        assert sched.pool.num_allocated == cache.num_pages
        cache.drop()
        assert sched.pool.num_allocated == 0


def test_cached_trace_marginal_admission_only():
    """The second of two identical requests is charged only its marginal
    pages: the free-list drop at admission is total-need minus the cached
    full pages of its prompt."""
    pool = PagePool(32, 8)
    cache = PrefixCache(pool)
    sched = Scheduler(pool, max_batch=1, max_pages=pool.pages_for(64),
                      prefill_chunk=4, prefix_cache=cache)
    prompt = np.arange(1, 25, dtype=np.int32)      # 24 tokens, 3 pages
    for r in _mk_reqs([(24, 3)]):
        sched.submit(r)
    _drain_sched(sched)
    free_before = pool.num_free
    req = Request(rid=9, prompt=prompt, max_new_tokens=3)
    sched.submit(req)
    sched.admit()
    seq = sched.slots[0]
    # limit = 23 caps the hit at 2 full pages (16 tokens)
    assert seq.cached_tokens == 16
    # Optimistic admission charges the chunk-padded PREFILL view only
    # (decode grows pages on demand), minus the cached full pages.
    need = pool.pages_for(-(-req.prompt_len // 4) * 4)
    assert free_before - pool.num_free == need - 2
    _drain_sched(sched)


def test_cached_trace_evicts_under_pressure():
    """Disjoint-prefix requests through a pool that can't hold a request
    plus the previous request's parked pages: admission must evict LRU
    trie pages (never deadlock), with invariants held at every step."""
    reqs = [Request(rid=i,
                    prompt=np.arange(100 * i, 100 * i + 24,
                                     dtype=np.int32),
                    max_new_tokens=2) for i in range(4)]
    pool = PagePool(6, 8)              # 5 usable; each request needs 4
    cache = PrefixCache(pool)
    sched = Scheduler(pool, max_batch=1, max_pages=pool.pages_for(64),
                      prefill_chunk=4, prefix_cache=cache)
    for r in reqs:
        sched.submit(r)
    _drain_sched(sched)
    assert len(sched.finished) == 4
    assert cache.stats()["evicted_pages"] >= 6     # 2 per later admission
    assert pool.num_allocated == cache.num_pages


def test_fork_after_hit_outlives_eviction():
    """A fork taken on a cache hit (beam fork / a second live request)
    pins the pages: the trie cannot evict them while the fork holds its
    ownership, and they return to the free list only after BOTH the trie
    and the fork let go."""
    sched = drive_trace(_mk_reqs([(16, 2)]), prefix_cache=True)
    cache, pool = sched.prefix_cache, sched.pool
    assert cache.num_pages == 2                    # 17 resident tokens
    pages, n = cache.match(np.arange(1, 17, dtype=np.int32))
    assert n == 16
    pool.share(pages)                              # fork after the hit
    assert cache.drop() == 0                       # pinned: nothing evicts
    assert cache.num_pages == 2
    pool.free([pages[1]])                          # fork releases the tail
    assert cache.drop() == 1                       # tail leaf now evicts
    sched.check_invariants()
    pool.free([pages[0]])
    assert cache.drop() == 1
    assert pool.num_allocated == 0
    pool.check_invariants()


def _drain_sched(sched):
    guard = 0
    while sched.has_work():
        guard += 1
        assert guard < 10_000, "trace did not drain"
        sched.retire_finished()
        sched.admit()
        chunk = sched.next_prefill()
        if chunk is not None:
            b, tokens, start, valid = chunk
            sched.mark_prefilled(b, valid)
            if sched.slots[b].prompt_done:
                sched.slots[b].req.tokens.append(1)
        mask = sched.decode_mask()
        for b in np.nonzero(mask)[0]:
            sched.slots[int(b)].req.tokens.append(1)
        sched.advance_decoded(mask)
        sched.check_invariants()


# ---------------------------------------------------------------------------
# Block tables vs a dense reference cache (scatter/gather consistency)
# ---------------------------------------------------------------------------

def _scatter_gather_roundtrip(B, lens_np, page_size, seed):
    import jax.numpy as jnp

    from repro.kernels.paged_decode import pool_head_dim
    from repro.kernels.ref import gather_pages
    from repro.models.attention import _gather_pages_bthd, _write_pages

    rng = np.random.default_rng(seed)
    Hkv, D = 2, 8
    max_len = int(max(lens_np))
    NB = -(-max_len // page_size)
    pool_pages = 1 + B * NB
    tables = np.zeros((B, NB), np.int32)
    nxt = 1
    for b in range(B):                       # ragged ownership, page 0 scratch
        need = -(-int(lens_np[b]) // page_size)
        tables[b, :need] = np.arange(nxt, nxt + need)
        nxt += need
    # Two layers stacked, head dim padded to whole lanes; every write goes
    # to layer 1, and layer 0 and the padding stay zero.
    pool = jnp.zeros((2, Hkv, pool_pages, page_size, pool_head_dim(D)),
                     jnp.float32)
    dense = np.zeros((B, max_len, Hkv, D), np.float32)
    # Write each sequence in two ragged chunks, like chunked prefill.
    tbl = jnp.asarray(tables)
    for b in range(B):
        L = int(lens_np[b])
        split = rng.integers(0, L + 1)
        for lo, hi in ((0, split), (split, L)):
            if hi == lo:
                continue
            vals = rng.standard_normal((1, hi - lo, Hkv, D)).astype(np.float32)
            pool = _write_pages(pool, jnp.asarray(vals), tbl[b:b + 1],
                                jnp.asarray([lo], jnp.int32), 1)
            dense[b, lo:hi] = vals[0]
    assert not np.asarray(pool[0]).any()
    assert not np.asarray(pool[..., D:]).any()
    got = np.asarray(_gather_pages_bthd(pool, tbl, 1, D))  # (B, NB*ps, Hkv, D)
    for b in range(B):
        L = int(lens_np[b])
        np.testing.assert_array_equal(got[b, :L], dense[b, :L])
    # ref.gather_pages agrees with the model-side gather (kernel layout).
    got2 = np.asarray(gather_pages(pool[1, ..., :D], tbl))  # (B, Hkv, T, D)
    np.testing.assert_array_equal(np.moveaxis(got2, 1, 2), got)


def test_block_tables_match_dense_cache_seeded():
    _scatter_gather_roundtrip(3, np.array([5, 17, 24]), page_size=8, seed=0)
    _scatter_gather_roundtrip(2, np.array([1, 31]), page_size=16, seed=1)


@given(st.lists(st.integers(1, 40), min_size=1, max_size=4),
       st.sampled_from([4, 8, 16]), st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_property_block_tables_match_dense_cache(lens, page_size, seed):
    _scatter_gather_roundtrip(len(lens), np.array(lens), page_size, seed)


# ---------------------------------------------------------------------------
# End-to-end: paged continuous batching == dense static decode, token for
# token, on seeded random traffic
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(name="paged-t", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, vocab_size=128, dtype="float32")


def _dense_greedy(params, cfg, prompt, gen):
    import jax
    import jax.numpy as jnp

    from repro.models import lm
    toks = jnp.asarray(prompt[None], jnp.int32)
    P = len(prompt)
    lg, cache = lm.prefill(params, cfg, toks, max_len=P + gen,
                           opts=lm.ForwardOpts(attn_impl="full"))
    out = [int(jnp.argmax(lg[0]))]
    for i in range(gen - 1):
        lg, cache = lm.decode_step(
            params, cfg, jnp.asarray([[out[-1]]], jnp.int32), cache,
            jnp.int32(P + i), opts=lm.ForwardOpts(decode_impl="full"))
        out.append(int(jnp.argmax(lg[0])))
    return out


def test_paged_engine_matches_dense_reference():
    import jax

    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import ServingEngine

    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    rng = np.random.default_rng(42)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, int(p))
                    .astype(np.int32),
                    max_new_tokens=int(g))
            for i, (p, g) in enumerate(
                zip(rng.integers(2, 10, 5), rng.integers(1, 5, 5)))]
    engine = ServingEngine(cfg, params, num_pages=24, page_size=8,
                           max_batch=3, max_seq_len=24, prefill_chunk=4)
    res = engine.run(reqs)
    assert res["requests"] == len(reqs)
    engine.scheduler.check_invariants()
    assert engine.pool.num_allocated == 0
    for r in sorted(engine.scheduler.finished, key=lambda r: r.rid):
        want = _dense_greedy(params, cfg, r.prompt, r.max_new_tokens)
        assert r.tokens == want, \
            f"req {r.rid}: paged {r.tokens} != dense {want}"


def test_paged_engine_requires_supported_arch():
    import dataclasses

    import jax

    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(_tiny_cfg(), window=8)
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    with pytest.raises(NotImplementedError, match="paged serving"):
        ServingEngine(cfg, params, num_pages=8, page_size=8,
                      max_batch=1, max_seq_len=16, prefill_chunk=4)


# ---------------------------------------------------------------------------
# In-place pools: one step of the model against the formulation it
# replaced, which sliced each layer's pool out of its stack, wrote the new
# tokens with a scatter (``.at[].set``) and set the layer back.
# ---------------------------------------------------------------------------

def _scatter_ref(pages, vals, tables, start):
    """The replaced write: vals (B, S, Hkv[, D]) at positions start[b] + s
    of one layer's pool (Hkv, P, page_size[, D]) through the tables."""
    import jax.numpy as jnp
    S, ps = vals.shape[1], pages.shape[2]
    pos = start[:, None] + jnp.arange(S)[None, :]
    blocks = jnp.clip(pos // ps, 0, tables.shape[1] - 1)
    page_ids = jnp.take_along_axis(tables, blocks, axis=1)
    return pages.at[:, page_ids, pos % ps].set(jnp.moveaxis(vals, 2, 0))


def _reference_layer(p, h, kind, cfg, opts, pool, tables, start, mode):
    """One layer over its own pool (Hkv, P, page_size, D), as the model did
    before: new tokens written by ``_scatter_ref``, the pool read whole
    (the kernels on a 4-D pool, prefill through an XLA gather)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels.ref import gather_pages
    from repro.models import attention as ATT
    from repro.models import lm
    from repro.models.layers import apply_norm

    B, S = h.shape[:2]
    positions = start[:, None] + jnp.arange(S)[None, :]
    pool = dict(pool)
    hn = apply_norm(p["ln1"], h, cfg, impl=opts.norm_impl)
    q, k, v = ATT._qkv(p["mix"], hn, cfg, positions)
    scales = {}
    if "k_scales" in pool:
        k, ks, v, vs = ATT._quant_kv_token(k, v)
        for name, val in (("k_scales", ks), ("v_scales", vs)):
            scales[name] = _scatter_ref(pool[name][:, :, 0], val, tables,
                                        start)
            pool[name] = scales[name][:, :, None]
    kp = pool["k_pages"] = _scatter_ref(pool["k_pages"], k, tables, start)
    vp = pool["v_pages"] = _scatter_ref(pool["v_pages"], v, tables, start)
    if mode == "decode":
        o = kops.paged_decode(q[:, 0], kp, vp, tables, start + 1,
                              **scales)[:, None]
    elif mode == "verify":
        o = kops.paged_verify(q, kp, vp, tables, start + S, **scales)
    else:
        kd = jnp.moveaxis(gather_pages(kp, tables), 1, 2)
        vd = jnp.moveaxis(gather_pages(vp, tables), 1, 2)
        if scales:
            ksd, vsd = (jnp.moveaxis(sc[:, tables].reshape(sc.shape[0], B, -1),
                                     0, 2)
                        for sc in (scales["k_scales"], scales["v_scales"]))
            kd = kd.astype(jnp.float32) * ksd[..., None]
            vd = vd.astype(jnp.float32) * vsd[..., None]
        valid = jnp.arange(kd.shape[1])[None, None, :] <= positions[:, :, None]
        s = jnp.einsum("bskgd,btkd->bkgst", ATT._group(q, cfg.n_kv_heads), kd,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, None], s * cfg.head_dim ** -0.5, -1e30)
        prob = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkv->bskgv", prob.astype(vd.dtype), vd,
                       preferred_element_type=jnp.float32)
        o = o.reshape(B, S, cfg.n_heads, cfg.head_dim).astype(h.dtype)
    h = lm._apply_ffn(p, h + ATT._proj_out(p["mix"], o, cfg),
                      kind.split("_")[1], cfg, opts)
    return h, pool


def _reference_step(params, cfg, opts, tokens, cache, tables, start, mode):
    """Logits and cache of one paged step in the replaced formulation: the
    stacked pools go through the layer scan as ``xs`` and come back as
    ``ys``, each layer's pool sliced out and written back whole."""
    import jax

    from repro.models.layers import apply_norm, embed_tokens, logits_out

    h = embed_tokens(params["embed"], tokens, cfg)
    new_cache = {}
    for ui, (unit, reps) in enumerate(cfg.scan_plan()):

        def body(h_, xs, unit=unit, reps=reps):
            pl, cl = xs
            ncs = {}
            for i, kind in enumerate(unit):
                self_ = cl[f"l{i}"]["self"]
                if reps == 1:
                    self_ = {n: a[0] for n, a in self_.items()}
                h_, pool = _reference_layer(pl[f"l{i}"], h_, kind, cfg, opts,
                                            self_, tables, start, mode)
                if reps == 1:
                    pool = {n: a[None] for n, a in pool.items()}
                ncs[f"l{i}"] = {"self": pool}
            return h_, ncs

        xs = (params[f"u{ui}"], cache[f"u{ui}"])
        if reps == 1:
            h, new_cache[f"u{ui}"] = body(h, xs)
        else:
            h, new_cache[f"u{ui}"] = jax.lax.scan(body, h, xs)
    h = apply_norm(params["final_ln"], h, cfg, impl=opts.norm_impl)
    logits = logits_out(params["embed"], h, cfg)
    return (logits[:, 0] if mode == "decode" else logits), new_cache


# (tables, resident lengths, tokens per row) of each step; page size 4,
# page 0 is scratch, page 7 belongs to nobody. Decode lands one token
# mid-page and one on a page's first slot, verify's 3 tokens cross a page
# in both rows, and the prefill chunk starts mid-page (as after a prefix
# hit with small pages) and covers three pages. The last decode and
# verify rows are inactive (scratch table, length 0).
_STEPS = {
    "decode": ([[1, 2, 3, 0], [4, 5, 6, 0], [0, 0, 0, 0]], [5, 8, 0], 1),
    "verify": ([[1, 2, 3, 0], [4, 5, 6, 0], [0, 0, 0, 0]], [2, 7, 0], 3),
    "prefill": ([[1, 2, 3, 4]], [3], 6),
}


@pytest.mark.parametrize("reps", [2, 1], ids=["scanned", "one_layer"])
@pytest.mark.parametrize("mode", ["decode", "prefill", "verify"])
@pytest.mark.parametrize("kv", [None, "kv8"], ids=["bf16", "kv8"])
def test_in_place_pool_step_matches_layer_slice_scatter(kv, mode, reps):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import lm
    from repro.models.param import init_params

    cfg = dataclasses.replace(_tiny_cfg(), n_layers=reps, dtype="bfloat16")
    assert cfg.scan_plan() == [(("attn_mlp",), reps)]
    opts = lm.ForwardOpts(decode_impl="paged", quant=kv)
    params = init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))
    ps, n_pages = 4, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def fill(spec):            # resident tokens everywhere, not zeros
        key = next(keys)
        if spec.dtype == jnp.int8:
            return jax.random.randint(key, spec.shape, -127, 128, jnp.int8)
        if spec.dtype == jnp.float32:                   # kv8 scales
            return jax.random.uniform(key, spec.shape, jnp.float32,
                                      0.005, 0.02)
        return jax.random.normal(key, spec.shape, spec.dtype)

    cache0 = jax.tree.map(fill, lm.paged_cache_specs(
        cfg, n_pages, ps, kv_dtype=opts.kv_dtype()))
    rows, lens, S = _STEPS[mode]
    tables = jnp.asarray(rows, jnp.int32)
    start = jnp.asarray(lens, jnp.int32)
    tokens = jax.random.randint(next(keys), (len(rows), S), 1,
                                cfg.vocab_size)
    step = {"decode": lm.decode_step_paged, "verify": lm.verify_step_paged,
            "prefill": lm.prefill_paged}[mode]
    logits, cache = step(params, cfg, tokens, cache0, tables, start, opts)
    # The replaced formulation's pools had no lanes past the head dim.
    unpadded = jax.tree.map(
        lambda a: a if a.shape[-2] == 1 else a[..., :cfg.head_dim], cache0)
    want_logits, want_cache = _reference_step(params, cfg, opts, tokens,
                                              unpadded, tables, start, mode)
    # Active rows only: an inactive row (length 0) writes and reads the
    # scratch page, which writes with nothing to write also use, and its
    # tokens are discarded.
    active = np.asarray(lens) > 0
    logits, want_logits = (np.asarray(a, np.float32)[active]
                           for a in (logits, want_logits))
    np.testing.assert_array_equal(logits.argmax(-1), want_logits.argmax(-1))
    np.testing.assert_array_equal(logits, want_logits)
    # Every resident and new token, by its table, in every layer: the same.
    # Slots past a row's new length may differ (the in-place write fills
    # them with copies; nothing reads them), and so may the scratch page.
    owned = {int(p) for row, n in zip(rows, lens) if n for p in row if p}
    for got, want, old in zip(jax.tree.leaves(cache),
                              jax.tree.leaves(want_cache),
                              jax.tree.leaves(cache0)):
        got, want, old = (np.asarray(a) for a in (got, want, old))
        if got.shape[-2] == 1:                          # scale pools
            got, want, old = got[..., 0, :], want[..., 0, :], old[..., 0, :]
        else:
            got, old = got[..., :cfg.head_dim], old[..., :cfg.head_dim]
        for row, n in zip(rows, lens):
            for pos in range(n + S if n else 0):
                page, slot = row[pos // ps], pos % ps
                np.testing.assert_array_equal(got[:, :, page, slot],
                                              want[:, :, page, slot])
        for page in set(range(1, n_pages)) - owned:
            np.testing.assert_array_equal(got[:, :, page], old[:, :, page])
            np.testing.assert_array_equal(want[:, :, page], old[:, :, page])
