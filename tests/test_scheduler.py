"""Chunked-prefill scheduler: policy math (pure, no model), scheduler
invariants as hypothesis-ready property bodies (budget conservation, class
ordering, decode floor, eviction-victim class safety), engine-level
bit-equality against monolithic prefill, prefix-skip correctness, and the
preempt/requeue interaction with in-flight chunks."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.layers import ModelOptions
from repro.serving import Request, ServingEngine
from repro.serving.scheduler import (BEST_EFFORT, REALTIME, ChunkedScheduler,
                                     PrefillTask, SLOController, SLOTick,
                                     eviction_victims, insert_by_class,
                                     is_realtime, req_deadline)
from conftest import reduced_params


def _streams(cfg, opts, params, reqs, *, n_slots=2, max_seq=64, **kw):
    eng = ServingEngine(cfg, opts, params, n_slots=n_slots, max_seq=max_seq,
                        eos=-999, fused=True, tick_tokens=4, **kw)
    for i, (prompt, max_tokens) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=prompt.copy(),
                           max_tokens=max_tokens))
    done = eng.run(max_ticks=2_000)
    assert len(done) == len(reqs)
    return {r.uid: r.out_tokens for r in done}, eng


# ---------------------------------------------------------------------------
# policy unit tests (no model)
# ---------------------------------------------------------------------------

def _task(slot, total, n_skip=0):
    return PrefillTask(req=None, slot=slot, total=total, n_skip=n_skip)


def test_plan_decode_reserved_before_prefill():
    """Starvation guarantee: active decoders get their reservation first and
    a long prompt can never take more than the leftover budget per tick."""
    sched = ChunkedScheduler(chunk_size=16, token_budget=48)
    sched.start_task(_task(slot=0, total=400))
    plan = sched.plan_tick(n_active=2, tick_tokens=8)
    assert plan.decode_steps == 8              # min(tick_tokens, 48 // 2)
    chunk_tokens = sum(c.n_tok for c in plan.chunks)
    assert chunk_tokens == 48 - 2 * 8          # prefill only gets the rest
    assert plan.budget_used <= 48


def test_plan_decode_always_advances():
    """Even a budget smaller than the active batch decodes one step."""
    sched = ChunkedScheduler(chunk_size=16, token_budget=4)
    sched.start_task(_task(slot=0, total=100))
    plan = sched.plan_tick(n_active=6, tick_tokens=8)
    assert plan.decode_steps == 1
    assert not plan.chunks                     # nothing left for prefill


def test_plan_progress_floor_without_decoders():
    """token_budget < chunk_size on an idle engine still prefills."""
    sched = ChunkedScheduler(chunk_size=32, token_budget=8)
    sched.start_task(_task(slot=0, total=100))
    plan = sched.plan_tick(n_active=0, tick_tokens=8)
    assert len(plan.chunks) == 1 and plan.chunks[0].n_tok == 8


def test_plan_fcfs_and_partial_final_chunk():
    sched = ChunkedScheduler(chunk_size=16, token_budget=64)
    a = sched.start_task(_task(slot=1, total=21))    # admitted first
    b = sched.start_task(_task(slot=0, total=40))
    plan = sched.plan_tick(n_active=0, tick_tokens=8)
    # task a: 16 + 5 (partial), then task b with what's left (64-21=43)
    assert [(c.task.slot, c.start, c.n_tok) for c in plan.chunks[:2]] == \
        [(1, 0, 16), (1, 16, 5)]
    assert plan.chunks[2].task is b
    assert sum(c.n_tok for c in plan.chunks) <= 64
    # planning must not mutate task positions
    assert a.pos == 0 and b.pos == 0


def test_plan_deprioritizes_stalled_tasks():
    """A stalled task still retries every tick, but healthy tasks get the
    budget first (evicting a progressing task would restart guaranteed
    work, so stalled ones wait their turn instead)."""
    sched = ChunkedScheduler(chunk_size=16, token_budget=32)
    a = sched.start_task(_task(slot=0, total=64))
    b = sched.start_task(_task(slot=1, total=64))
    a.stalled = True
    plan = sched.plan_tick(n_active=0, tick_tokens=8)
    assert plan.chunks and all(c.task is b for c in plan.chunks)
    b.stalled = True                 # both stalled: FCFS retry order
    plan = sched.plan_tick(n_active=0, tick_tokens=8)
    assert plan.chunks[0].task is a


def test_requeue_task_goes_to_front():
    sched = ChunkedScheduler(chunk_size=16, token_budget=32)
    sched.submit("r1")
    task = sched.start_task(PrefillTask(req="r0", slot=0, total=32))
    task.pos = 16                               # chunks already in flight
    sched.requeue_task(0)
    assert sched.waiting == ["r0", "r1"]        # seniority preserved
    assert 0 not in sched.tasks


def test_prefix_skip_starts_at_first_nonshared_token():
    t = _task(slot=0, total=64, n_skip=48)
    sched = ChunkedScheduler(chunk_size=16, token_budget=64)
    sched.start_task(t)
    assert t.pos == 48 and t.remaining == 16
    plan = sched.plan_tick(n_active=0, tick_tokens=8)
    assert plan.chunks[0].start == 48 and plan.chunks[0].n_tok == 16


# ---------------------------------------------------------------------------
# scheduler invariants: hypothesis-ready property bodies
#
# Each ``check_*`` body is a pure function of its drawn inputs, exercised
# here by fixed-draw smokes (so the invariants stay covered without
# hypothesis) and by the ``@given`` wrappers in test_property.py with
# random draws. No model, no jax — plan_tick is host-side policy.
# ---------------------------------------------------------------------------

class _Req:
    """Request double carrying only what the policy layer reads."""

    def __init__(self, uid, priority=BEST_EFFORT, t_deadline=math.inf):
        self.uid = uid
        self.priority = priority
        self.t_deadline = t_deadline

    def __repr__(self):
        return f"_Req({self.uid}, {self.priority}, {self.t_deadline})"


def check_budget_conservation(chunk_size, token_budget, n_active,
                              tick_tokens, totals):
    """One tick never plans more work than the budget allows: chunks fit
    in what the decode reservation leaves, and — whenever the budget can
    cover one decode step per active slot — the whole tick fits inside
    ``token_budget``. The only overdraw the policy permits is the >= 1
    decode-step progress floor when ``token_budget < n_active``."""
    sched = ChunkedScheduler(chunk_size=chunk_size, token_budget=token_budget)
    for i, total in enumerate(totals):
        sched.start_task(_task(slot=i, total=total))
    plan = sched.plan_tick(n_active=n_active, tick_tokens=tick_tokens)
    chunk_tok = sum(c.n_tok for c in plan.chunks)
    assert chunk_tok <= max(0, token_budget - n_active * plan.decode_steps)
    if n_active and token_budget >= n_active:
        assert n_active * plan.decode_steps + chunk_tok <= token_budget
    assert plan.budget_used == n_active * plan.decode_steps + chunk_tok
    # chunks are well-formed: contiguous from each task's position, sized
    # within chunk_size, never past the prompt end
    pos = {}
    for c in plan.chunks:
        assert 1 <= c.n_tok <= chunk_size
        assert c.start == pos.get(c.task.slot, c.task.pos)
        pos[c.task.slot] = c.start + c.n_tok
        assert pos[c.task.slot] <= c.task.total


def check_decode_floor(token_budget, n_active, tick_tokens):
    """Active decoders always advance: >= 1 step regardless of pressure,
    <= tick_tokens regardless of slack (with no SLO boost in play)."""
    sched = ChunkedScheduler(chunk_size=8, token_budget=token_budget)
    plan = sched.plan_tick(n_active=n_active, tick_tokens=tick_tokens)
    if n_active:
        assert 1 <= plan.decode_steps <= tick_tokens
    else:
        assert plan.decode_steps == 0


def check_insert_by_class(specs):
    """Queue shape after arbitrary class-ordered inserts: one realtime
    segment (deadlines non-decreasing) strictly ahead of the best-effort
    segment, and FCFS seniority within each class for plain (front=False)
    arrivals — equal-deadline realtime peers and all best-effort requests
    keep arrival order. ``specs``: (is_rt, deadline, front) per arrival."""
    queue = []
    for i, (rt, dl, front) in enumerate(specs):
        req = _Req(i, REALTIME if rt else BEST_EFFORT,
                   float(dl) if rt else math.inf)
        req.front = front
        insert_by_class(queue, req, front=front)
    k = 0
    while k < len(queue) and is_realtime(queue[k]):
        k += 1
    assert all(not is_realtime(r) for r in queue[k:]), \
        "a best-effort request sits inside the realtime segment"
    dls = [req_deadline(r) for r in queue[:k]]
    assert dls == sorted(dls), f"realtime segment not EDF: {dls}"
    plain_rt = [r.uid for r in queue[:k] if not r.front]
    by_dl = {}
    for r in queue[:k]:
        if not r.front:
            by_dl.setdefault(req_deadline(r), []).append(r.uid)
    for dl, uids in by_dl.items():
        assert uids == sorted(uids), \
            f"equal-deadline realtime arrivals reordered at dl={dl}: {uids}"
    plain_be = [r.uid for r in queue[k:] if not r.front]
    assert plain_be == sorted(plain_be), \
        f"best-effort arrivals reordered: {plain_be}"
    assert len(queue) == len(specs)
    del plain_rt


def check_all_best_effort_degeneracy(fronts):
    """With no realtime requests anywhere, insert_by_class must be
    *bit-identical* to the static policy: append, or insert(0) for
    front=True. This is the anchor for the engine-level guarantee that
    an all-best-effort workload schedules exactly as before the SLO
    scheduler existed."""
    queue, ref = [], []
    for i, front in enumerate(fronts):
        req = _Req(i)
        insert_by_class(queue, req, front=front)
        ref.insert(0, req) if front else ref.append(req)
    assert queue == ref


def check_eviction_victim_class(specs, exclude):
    """Realtime is never an eviction victim, and every stalled best-effort
    task (other than the protected slot) is offered — the policy may not
    silently shrink the victim set either. ``specs``: (is_rt, stalled)."""
    tasks = {}
    for s, (rt, stalled) in enumerate(specs):
        t = _task(slot=s, total=32)
        t.req = _Req(s, REALTIME if rt else BEST_EFFORT)
        t.stalled = stalled
        tasks[s] = t
    victims = eviction_victims(tasks, exclude=exclude)
    assert set(victims) == {
        s for s, t in tasks.items()
        if s != exclude and t.stalled and not is_realtime(t.req)}
    for s in victims:
        assert not is_realtime(tasks[s].req)


def check_slo_quota_and_boost(token_budget, chunk_size, rt_total, be_total,
                              quota, need, n_active, tick_tokens):
    """Under an SLO tick: best-effort chunk tokens never exceed the quota,
    realtime chunks are never quota'd (only budget-bound), and the decode
    reservation honours ``decode_need`` up to ``tick_tokens``. A default
    SLOTick (no pressure) must plan bit-identically to slo=None."""
    def build():
        sched = ChunkedScheduler(chunk_size=chunk_size,
                                 token_budget=token_budget)
        t_rt = _task(slot=0, total=rt_total)
        t_rt.req = _Req(0, REALTIME, t_deadline=1.0)
        sched.start_task(t_rt)
        t_be = _task(slot=1, total=be_total)
        t_be.req = _Req(1)
        sched.start_task(t_be)
        return sched

    plan = build().plan_tick(n_active, tick_tokens,
                             slo=SLOTick(decode_need=need,
                                         be_chunk_quota=quota))
    be_tok = sum(c.n_tok for c in plan.chunks
                 if not is_realtime(c.task.req))
    rt_tok = sum(c.n_tok for c in plan.chunks if is_realtime(c.task.req))
    assert be_tok <= quota
    assert plan.decode_steps <= tick_tokens
    if n_active:
        base = max(1, min(tick_tokens, token_budget // n_active))
        expect = min(tick_tokens, need) if need > base else base
        assert plan.decode_steps == expect
    reserved = n_active * plan.decode_steps
    assert rt_tok + be_tok <= max(0, token_budget - reserved)
    # realtime chunks saw the full leftover, not the best-effort quota
    if quota == 0 and rt_total > 0 and token_budget - reserved > 0:
        assert rt_tok > 0, "quota starved a realtime chunk"
    # no-pressure SLO tick == static plan, field for field
    a = build().plan_tick(n_active, tick_tokens)
    b = build().plan_tick(n_active, tick_tokens, slo=SLOTick())
    assert ([(c.task.slot, c.start, c.n_tok) for c in a.chunks],
            a.decode_steps, a.budget_used) == \
           ([(c.task.slot, c.start, c.n_tok) for c in b.chunks],
            b.decode_steps, b.budget_used)


def test_budget_conservation_fixed_draws():
    check_budget_conservation(16, 48, 2, 8, [400, 37])
    check_budget_conservation(8, 4, 6, 8, [100])       # floor overdraw
    check_budget_conservation(32, 8, 0, 8, [100, 3, 17])


def test_decode_floor_fixed_draws():
    check_decode_floor(4, 6, 8)
    check_decode_floor(64, 1, 4)
    check_decode_floor(16, 0, 8)


def test_insert_by_class_fixed_draws():
    check_insert_by_class([(False, None, False), (True, 3.0, False),
                           (True, 1.0, False), (False, None, True),
                           (True, 3.0, False), (True, 2.0, True),
                           (False, None, False)])
    check_all_best_effort_degeneracy([False, True, False, False, True])


def test_eviction_victim_class_fixed_draws():
    check_eviction_victim_class([(True, True), (False, True),
                                 (False, False), (True, False)], exclude=-1)
    check_eviction_victim_class([(False, True), (False, True)], exclude=0)


def test_slo_quota_and_boost_fixed_draws():
    check_slo_quota_and_boost(32, 16, 40, 40, 0, 6, 2, 8)
    check_slo_quota_and_boost(48, 16, 64, 64, 8, 0, 1, 4)


def test_slo_controller_math():
    """need = max over slots of ceil(remaining / floor(slack/ewma));
    pressure when slack < safety * remaining * ewma or realtime prefill
    is pending; finished / undeadlined slots are ignored."""
    ctl = SLOController(slo_hz=10.0, safety=2.0)
    tick = ctl.plan(now=0.0, tick_ewma_s=0.01,
                    rt_decode=[(12, 0.04), (3, 0.10)],
                    rt_prefill_pending=False)
    # slot 1: slack 0.04 -> 4 ticks -> ceil(12/4) = 3/tick; pressure
    # (0.04 < 2 * 12 * 0.01); slot 2 comfortable (ceil(3/10) = 1)
    assert tick.decode_need == 3 and tick.be_chunk_quota == 0
    tick = ctl.plan(0.0, 0.01, [(4, 1.0)], rt_prefill_pending=False)
    assert tick.decode_need == 1 and tick.be_chunk_quota is None
    tick = ctl.plan(0.0, 0.01, [(0, 0.001), (5, math.inf)],
                    rt_prefill_pending=False)
    assert tick.decode_need == 0 and tick.be_chunk_quota is None
    tick = ctl.plan(0.0, 0.01, [], rt_prefill_pending=True)
    assert tick.be_chunk_quota == 0
    with pytest.raises(ValueError, match="slo_hz"):
        SLOController(slo_hz=0.0)


# ---------------------------------------------------------------------------
# engine-level: bit-equality and edge cases
# ---------------------------------------------------------------------------

def check_chunk_invariance(chunk, paged, lens):
    """Greedy streams from a chunked engine must equal the monolithic dense
    baseline bit-for-bit, for any chunk size and prompt lengths. Driven by
    the fixed-draw smoke below, and by the hypothesis property in
    test_property.py with random draws (this module stays importable
    without hypothesis, so the body is usable in both environments)."""
    cfg, params = reduced_params("qwen1.5-0.5b")
    opts = ModelOptions(remat=False)
    page_size = 4
    kw = dict(paged=True, page_size=page_size) if paged else {}
    if paged:                       # chunk writes must start page-aligned
        chunk = max(page_size, chunk - chunk % page_size)
    rng = np.random.default_rng(chunk * 101 + len(lens))
    reqs = [(rng.integers(0, cfg.vocab_size, n, dtype=np.int32), 4)
            for n in lens]
    base, _ = _streams(cfg, opts, params, reqs)
    chunked, _ = _streams(cfg, opts, params, reqs, chunked_prefill=True,
                          chunk_size=chunk, token_budget=max(16, chunk),
                          **kw)
    assert chunked == base, \
        f"chunk={chunk} paged={paged} lens={lens}: streams diverged"


@pytest.mark.parametrize("chunk,paged,lens", [
    (7, False, [13, 37]),           # odd chunk, non-aligned prompts
    (10, True, [9, 40, 1]),         # paged, chunk snapped to page multiple
])
def test_chunk_invariance_fixed_draws(chunk, paged, lens):
    check_chunk_invariance(chunk, paged, lens)


def test_chunked_matches_monolithic_dense_and_paged(opts):
    """Chunk size that divides nothing (5 into prompts of 13/9/21) must
    still produce greedy streams bit-identical to the admit-stall
    monolithic baseline, on both layouts."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, l, dtype=np.int32), m)
            for l, m in [(13, 7), (9, 5), (21, 8), (5, 6)]]
    base, _ = _streams(cfg, opts, params, reqs)
    dense, e_d = _streams(cfg, opts, params, reqs, chunked_prefill=True,
                          chunk_size=5, token_budget=20)
    assert dense == base
    paged, e_p = _streams(cfg, opts, params, reqs, chunked_prefill=True,
                          chunk_size=8, token_budget=20, paged=True,
                          page_size=8)
    assert paged == base
    total = sum(len(p) for p, _ in reqs)
    for e in (e_d, e_p):
        assert e.stats.prefill_tokens + e.stats.prefill_skipped == total
        assert len(e.stats.ttft_s) == len(reqs)
        assert len(e.stats.queue_s) == len(reqs)


def test_chunk_larger_than_prompt_single_dispatch(opts):
    """chunk_size > prompt: one padded chunk, still bit-identical."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg.vocab_size, 7, dtype=np.int32), 5)]
    base, _ = _streams(cfg, opts, params, reqs, n_slots=1)
    ch, eng = _streams(cfg, opts, params, reqs, n_slots=1,
                       chunked_prefill=True, chunk_size=32, token_budget=32)
    assert ch == base and eng.stats.prefill_tokens == 7


def test_prefix_hit_covering_entire_prompt(opts):
    """A full-prompt prefix hit skips everything except the final page
    (whose last-position logits seed decoding) and still emits the same
    stream as the first run."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)  # 2 pages
    eng = ServingEngine(cfg, opts, params, n_slots=2, max_seq=64, eos=-999,
                        fused=True, tick_tokens=4, chunked_prefill=True,
                        chunk_size=8, token_budget=24, paged=True,
                        page_size=8)
    eng.submit(Request(uid=0, prompt=prompt.copy(), max_tokens=5))
    eng.run()
    run1 = eng.stats.prefill_tokens
    eng.submit(Request(uid=1, prompt=prompt.copy(), max_tokens=5))
    done = eng.run()
    r0, r1 = sorted(done, key=lambda r: r.uid)
    assert r1.out_tokens == r0.out_tokens
    assert run1 == 16                          # first run computed all of it
    assert r1.prefill_skipped == 8             # all but the final page
    assert eng.stats.prefill_tokens == 16 + 8  # repeat ran only 8 tokens
    assert r1.pages_shared >= 1


def test_preempt_requeue_with_inflight_chunks(opts):
    """A pool too small for everyone forces mid-prefill preemption; the
    requeued request restarts (possibly prefix-skipping its own first
    attempt's pages) and every stream still matches the ample-pool run."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, cfg.vocab_size, 20, dtype=np.int32), 8),
            (rng.integers(0, cfg.vocab_size, 24, dtype=np.int32), 6),
            (rng.integers(0, cfg.vocab_size, 12, dtype=np.int32), 5)]
    base, _ = _streams(cfg, opts, params, reqs, n_slots=3)
    tiny, eng = _streams(cfg, opts, params, reqs, n_slots=3,
                         chunked_prefill=True, chunk_size=8, token_budget=16,
                         paged=True, page_size=8, num_pages=9,
                         reserve_pages=1)
    assert tiny == base
    assert eng.pool.pages_in_use == 0          # all pages returned


def test_decode_tick_does_not_clobber_inflight_prefill(opts):
    """Regression: the fused tick writes KV for every slot row, done or
    not; a mid-prefill slot's page-table row must be nulled in the decode
    snapshot or stale decode indices overwrite freshly-written chunk KV."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(4)
    # one decoding request, then a second arrives so its chunks interleave
    # with the first one's decode ticks
    reqs = [(rng.integers(0, cfg.vocab_size, 6, dtype=np.int32), 12),
            (rng.integers(0, cfg.vocab_size, 24, dtype=np.int32), 5)]
    base, _ = _streams(cfg, opts, params, reqs)
    ch, _ = _streams(cfg, opts, params, reqs, chunked_prefill=True,
                     chunk_size=8, token_budget=10, paged=True, page_size=8)
    assert ch == base


def test_chunked_engine_validations(opts):
    cfg, params = reduced_params("smollm-135m")
    with pytest.raises(ValueError, match="fused"):
        ServingEngine(cfg, opts, params, fused=False, chunked_prefill=True)
    with pytest.raises(ValueError, match="page_size"):
        ServingEngine(cfg, opts, params, chunked_prefill=True, paged=True,
                      page_size=16, chunk_size=24, max_seq=64)
    ring = ModelOptions(remat=False, window_cache=True)
    with pytest.raises(ValueError, match="window_cache"):
        ServingEngine(cfg, ring, params, chunked_prefill=True)
    # kernel path: the paged chunk kernel partitions the key axis per page,
    # so bit-equality vs the dense kernel's bands needs the two to match
    pallas = ModelOptions(remat=False, use_pallas=True, prefill_band=32)
    with pytest.raises(ValueError, match="prefill_band"):
        ServingEngine(cfg, pallas, params, chunked_prefill=True, paged=True,
                      page_size=16, chunk_size=16, max_seq=64)
    ServingEngine(cfg, pallas, params, chunked_prefill=True, paged=True,
                  page_size=32, chunk_size=32, max_seq=64)  # aligned: fine
    cfg_ssm, params_ssm = reduced_params("mamba2-780m")
    with pytest.raises(ValueError, match="attention-only"):
        ServingEngine(cfg_ssm, opts, params_ssm, chunked_prefill=True)


def test_phase_report_percentiles_and_ttft(opts):
    """EngineStats: per-request ttft/queue populated and phase_report
    carries decode-tick percentiles on legacy engines too."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, cfg.vocab_size, 6, dtype=np.int32), 6)
            for _ in range(3)]
    _, eng = _streams(cfg, opts, params, reqs)
    rep = eng.stats.phase_report()
    assert {"decode_tick_p50", "decode_tick_p99"} <= rep.keys()
    assert rep["decode_tick_p99"] >= rep["decode_tick_p50"] > 0
    assert len(eng.stats.ttft_s) == 3
    for r in eng.finished:
        assert r.ttft_s >= r.queue_s >= 0


def test_decode_kv_pages_count_live_pages(opts):
    """EngineStats.decode_kv_pages / decode_kv_pages_full on a small paged
    engine equal the live pages worked out by hand from the positions."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 13, dtype=np.int32)
    _, eng = _streams(cfg, opts, params, [(prompt, 10)], paged=True,
                      page_size=8)
    # 2 slots of 64 positions, 8 pages each. The request decodes 9 tokens
    # at positions 13..21: pages 0-1 are live at 13-15 (3 steps x 2),
    # pages 0-2 at 16-21 (6 steps x 3). The idle slot attends position 0,
    # one page a step.
    st = eng.stats
    assert st.decode_kv_pages == 3 * 2 + 6 * 3 + 9 * 1
    assert st.decode_kv_pages_full == 9 * 2 * 8
    assert st.phase_report()["decode_page_ratio"] == pytest.approx(33 / 144)


def test_realtime_jumps_best_effort_backlog(opts):
    """A realtime control request submitted behind a best-effort backlog is
    admitted class-first, finishes first, and scores its deadline in the
    per-class scoreboard."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, cfg.vocab_size, 48, dtype=np.int32), 6)
            for _ in range(3)]
    eng = ServingEngine(cfg, opts, params, n_slots=2, max_seq=64, eos=-999,
                        fused=True, tick_tokens=4, paged=True, page_size=8,
                        chunked_prefill=True, chunk_size=16, token_budget=16,
                        slo_hz=20.0)
    for i, (p, m) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=p.copy(), max_tokens=m))
    eng.submit(Request(uid=99,
                       prompt=rng.integers(0, cfg.vocab_size, 8,
                                           dtype=np.int32),
                       max_tokens=4, priority="realtime", deadline_s=60.0))
    done = eng.run(max_ticks=2_000)
    assert len(done) == 4
    assert done[0].uid == 99, \
        f"realtime request finished {[r.uid for r in done].index(99) + 1}th"
    rep = eng.stats.phase_report()
    assert rep["deadline_total_realtime"] == 1.0
    assert rep["deadline_attainment_realtime"] == 1.0
    assert rep["tick_ewma_s"] > 0


def test_slo_engine_bit_equal_on_best_effort_workload(opts):
    """With no realtime traffic and no deadlines, an slo_hz engine must
    generate bit-identically to the static scheduler — the SLO controller
    is a strict no-op without deadline pressure."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(10)
    reqs = [(rng.integers(0, cfg.vocab_size, l, dtype=np.int32), m)
            for l, m in [(13, 6), (29, 4), (7, 7)]]
    kw = dict(chunked_prefill=True, chunk_size=16, token_budget=16,
              paged=True, page_size=8)
    base, _ = _streams(cfg, opts, params, reqs, **kw)
    slo, _ = _streams(cfg, opts, params, reqs, slo_hz=10.0, **kw)
    assert slo == base


def test_slo_hz_engine_validation(opts):
    cfg, params = reduced_params("smollm-135m")
    with pytest.raises(ValueError, match="slo_hz"):
        ServingEngine(cfg, opts, params, n_slots=2, max_seq=64, eos=-999,
                      chunked_prefill=True, slo_hz=-1.0)
    with pytest.raises(ValueError, match="chunked_prefill"):
        ServingEngine(cfg, opts, params, n_slots=2, max_seq=64, eos=-999,
                      slo_hz=10.0)


def test_positioned_prefill_model_api(opts):
    """model.prefill(cache_index>0): suffix prefill over existing caches is
    bit-identical to one monolithic call."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
    lg_m, _ = M.prefill(cfg, opts, params,
                        {"tokens": jnp.asarray(prompt[None])}, 32,
                        cache_dtype=jnp.float32)
    lg_a, caches = M.prefill(cfg, opts, params,
                             {"tokens": jnp.asarray(prompt[None, :5])}, 32,
                             cache_dtype=jnp.float32)
    lg_b, _ = M.prefill(cfg, opts, params,
                        {"tokens": jnp.asarray(prompt[None, 5:])}, 32,
                        caches=caches, cache_index=5)
    assert (jnp.asarray(lg_b) == jnp.asarray(lg_m)).all()
    with pytest.raises(ValueError, match="existing caches"):
        M.prefill(cfg, opts, params, {"tokens": jnp.asarray(prompt[None])},
                  32, cache_index=5)
