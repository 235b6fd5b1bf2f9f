"""The serving engine's spans and the scheduler's per-request stamps.

``ServingEngine.step`` writes its host phases into the profiler's own
trace as ``serve.*`` annotations under one ``serve.step``, whose
``perf_ns`` argument maps the program's ``time.perf_counter`` onto the
profile. ``Request`` carries the scheduler's spans (submitted, admitted,
prefilled) and the prefill counters that the benchmark's scheduler
metrics read.
"""

import glob
import math
import os

import numpy as np
import pytest

from repro.serving import PagePool, Request, Scheduler

# Phases of one step, in the order they run.
PHASES = ["serve.retire", "serve.admit", "serve.prefill.launch",
          "serve.prefill.wait", "serve.decode.prepare",
          "serve.decode.launch", "serve.decode.wait", "serve.decode.commit"]
CHUNK = 4


def _tiny_cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(name="spans-t", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, vocab_size=128, dtype="float32")


@pytest.fixture(scope="module")
def engine_parts():
    import jax

    from repro.models import lm
    from repro.models.param import init_params
    cfg = _tiny_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), lm.lm_specs(cfg))


def _engine(engine_parts, max_batch=3):
    from repro.serving import ServingEngine
    cfg, params = engine_parts
    return ServingEngine(cfg, params, num_pages=24, page_size=8,
                         max_batch=max_batch, max_seq_len=32,
                         prefill_chunk=CHUNK)


def _requests(seed, n=5, first_rid=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=first_rid + i,
                    prompt=rng.integers(1, 128, int(p)).astype(np.int32),
                    max_new_tokens=int(g))
            for i, (p, g) in enumerate(zip(rng.integers(2, 11, n),
                                           rng.integers(2, 5, n)))]


def _serve(engine, reqs):
    """Submit ``reqs`` and step until they are done; the stats of each
    step, keyed by its ``step_num``."""
    for r in reqs:
        engine.scheduler.submit(r)
    out = {}
    while engine.scheduler.has_work():
        stats = engine.step()
        out[engine.step_num] = stats
    return out


@pytest.fixture(scope="module")
def profiled(engine_parts, tmp_path_factory):
    """Serve five requests under ``jax.profiler.trace`` (after a warm-up
    run, so nothing compiles inside it) and read the host events back."""
    import jax
    from jax.profiler import ProfileData
    engine = _engine(engine_parts)
    _serve(engine, _requests(1))
    reqs = _requests(2, first_rid=100)
    d = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(d):
        stats = _serve(engine, reqs)
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [(e.name, int(e.start_ns), int(e.end_ns),
                        {k: v for k, v in e.stats}) for e in line.events
                       if e.name.startswith("serve.")]
    steps = sorted((e for e in events if e[0] == "serve.step"),
                   key=lambda e: e[1])
    phases = {e[3]["step_num"]: [] for e in steps}
    for ev in sorted(events, key=lambda e: e[1]):
        if ev[0] == "serve.step":
            continue
        owner = [s for s in steps if s[1] <= ev[1] and ev[2] <= s[2]]
        assert len(owner) == 1, f"{ev[0]} outside any serve.step"
        phases[owner[0][3]["step_num"]].append(ev)
    return {"steps": steps, "phases": phases, "stats": stats,
            "reqs": reqs}


def test_each_step_encloses_its_phases_in_order(profiled):
    assert [s[3]["step_num"] for s in profiled["steps"]] == \
        sorted(profiled["stats"])
    for num, evs in profiled["phases"].items():
        names = [e[0] for e in evs]
        assert all(n in PHASES for n in names), names
        order = [PHASES.index(n) for n in names]
        assert order == sorted(order) and len(set(order)) == len(order), \
            f"step {num}: {names}"
        assert names[:2] == ["serve.retire", "serve.admit"], names
        assert "serve.decode.prepare" in names


def test_every_decode_step_has_one_wait(profiled):
    for num, evs in profiled["phases"].items():
        names = [e[0] for e in evs]
        decoded = profiled["stats"][num].decode_tokens > 0
        assert names.count("serve.decode.launch") == int(decoded)
        assert names.count("serve.decode.wait") == int(decoded)
        assert names.count("serve.decode.commit") == int(decoded)
        prefilled = profiled["stats"][num].prefill_tokens > 0
        assert names.count("serve.prefill.launch") == int(prefilled)


def test_prefill_launch_carries_the_request(profiled):
    launches = [e for evs in profiled["phases"].values() for e in evs
                if e[0] == "serve.prefill.launch"]
    reqs = {r.rid: r for r in profiled["reqs"]}
    assert {e[3]["rid"] for e in launches} == set(reqs)
    for r in reqs.values():
        mine = [e[3] for e in launches if e[3]["rid"] == r.rid]
        assert len(mine) == math.ceil(r.prompt_len / CHUNK)
        assert [a["start"] for a in mine] == \
            list(range(0, r.prompt_len, CHUNK))
        assert sum(a["tokens"] for a in mine) == r.prompt_len
    # only the chunk that completes a prompt is read back
    waits = [e for evs in profiled["phases"].values() for e in evs
             if e[0] == "serve.prefill.wait"]
    assert sorted(e[3]["rid"] for e in waits) == sorted(reqs)


def test_perf_counter_anchor_maps_with_one_offset(profiled):
    offsets = [s[1] - s[3]["perf_ns"] for s in profiled["steps"]]
    assert len(offsets) > 5
    assert max(offsets) - min(offsets) < 50_000, offsets


def test_request_stamps_are_ordered(engine_parts):
    engine = _engine(engine_parts)
    reqs = _requests(3, n=6)
    _serve(engine, reqs)
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.prefilled_at \
            <= r.token_times[0], r.rid
        assert r.prefill_chunks == math.ceil(r.prompt_len / CHUNK)
        assert r.prefill_steps >= r.prefill_chunks


def test_two_prompts_prefilling_at_once_pass_each_other(engine_parts):
    """Two two-chunk prompts admitted in one step take turns: A's chunks
    run in steps 1 and 3, B's in 2 and 4. A waited one step while B's
    chunk ran, B two."""
    engine = _engine(engine_parts, max_batch=2)
    a, b = (Request(rid=i, prompt=np.arange(1, 2 * CHUNK + 1, dtype=np.int32),
                    max_new_tokens=2) for i in range(2))
    _serve(engine, [a, b])
    assert (a.prefill_chunks, a.prefill_steps) == (2, 3)
    assert (b.prefill_chunks, b.prefill_steps) == (2, 4)
    assert a.admitted_at == pytest.approx(b.admitted_at, abs=0.05)


def test_resumed_request_keeps_its_first_stamps():
    """Preempted mid-prompt and admitted again: the first admission stamp
    and the counts of the first occupancy stay as they were."""
    pool = PagePool(num_pages=16, page_size=4)
    sched = Scheduler(pool, max_batch=1, max_pages=pool.pages_for(32),
                      prefill_chunk=CHUNK)
    req = Request(rid=0, prompt=np.arange(1, 13, dtype=np.int32),
                  max_new_tokens=2)
    sched.submit(req)
    sched.admit()
    b, _, _, valid = sched.next_prefill()
    sched.mark_prefilled(b, valid)
    first = (req.admitted_at, req.prefill_steps, req.prefill_chunks)
    assert first[1:] == (1, 1)
    sched.preempt(b)
    while not sched.admit():
        pass
    while (chunk := sched.next_prefill()) is not None:
        sched.mark_prefilled(chunk[0], chunk[3])
    assert (req.admitted_at, req.prefill_steps, req.prefill_chunks) == first
    assert req.prefilled_at is None
    assert sched.resumes == 1
