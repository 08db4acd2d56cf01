"""Continuous-batching serving engine with a device-resident decode loop.

Decode runs over a fixed slot batch [B_slots]; each slot carries its own
cache position (per-slot `index` vector — see layers.update_cache /
attention_decode). Finished slots are refilled from the request queue via a
jitted prefill whose cache slice is scattered into the slot cache. This is
vLLM-style continuous batching re-expressed in fixed shapes (the
XLA-friendly formulation): no recompilation on admit/evict.

Two decode paths:

- **fused** (default): one jitted multi-token tick — a ``lax.while_loop``
  over up to ``tick_tokens`` decode steps that carries per-slot
  index/budget/done state as device arrays and fuses sampling into the step.
  The host is consulted only when a slot finishes or the tick's token budget
  is exhausted, so an N-token decode costs ~ceil(N/K) host syncs instead of
  N. This attacks exactly the launch/sync overhead the paper identifies as
  first-order for the memory-bound action-generation phase.
- **reference**: the original one-token-per-tick path (``step()``), kept for
  equivalence testing and as the bit-exactness oracle under greedy sampling.
- **speculative** (``spec_decode=True``; fused-only, greedy-only): each
  round of the fused tick drafts ``spec_k - 1`` tokens with a
  layer-truncated (optionally int8/fp8 weight-quantized) draft of the
  *same* model, verifies all ``spec_k`` positions through the full model in
  one banded chunk-prefill dispatch against the live cache, and emits the
  greedy longest-prefix-accepted run plus one bonus token — bit-equal to
  the reference stream at up to ``spec_k`` accepted tokens per full
  weight+cache HBM pass, which is exactly the memory-bound
  action-generation pass the paper measures as the bottleneck. Rejected
  speculative KV needs no undo: the next round's full-model chunk rewrites
  those positions before any read (causal masking never looks past a
  slot's live position), and rows past the cache capacity sink into the
  paged null page / dense scatter drop. See docs/speculative.md.

Two cache layouts (``paged=``):

- **dense** (default, and the equivalence oracle): per-slot head-major
  ``[n_slots, K, max_seq, h]`` buffers, over-allocated at ``max_seq``;
  admission scatters the batch-1 prefill cache into the slot's batch row.
- **paged**: attention K/V lives in shared head-major
  ``[num_pages, K, page_size, h]`` pools addressed through a per-slot page
  table (``serving.kv_pool``).
  Admission allocates pages (sharing full prompt-prefix pages with the
  pool's prefix cache — repeated robot observations are not re-stored) and
  scatters prefill KV page-wise; finish frees pages back to the pool. Cache
  memory scales with pages actually used, not ``max_seq`` per slot, and
  ``EngineStats`` tracks pages-in-use / cache-bytes high-water / prefix
  hits. ``kv_dtype="int8"``/``"fp8"`` stores the pools as 1-byte codes
  with per-page-per-head f32 scale siblings (quantize on scatter and on
  decode write, dequantize in the decode read — see models.kv_quant and
  docs/kv-cache.md), shrinking cache bytes and decode HBM traffic to
  ~0.52x the bf16-equivalent at int8.

Two admission policies:

- **admit-stall** (default): a popped request runs its whole prompt through
  one monolithic prefill dispatch before anything else proceeds.
- **chunked** (``chunked_prefill=True``): a Sarathi-style token-budget
  scheduler (``serving.scheduler``) splits prompts into fixed-size chunks
  and packs them with decode into each tick, so a long prompt never stalls
  an active decoder beyond the budget. Prefill-from-position makes a
  prefix-cache hit *skip* the shared compute (chunking starts at the first
  non-shared token), and pool admission becomes chunk-granular with a
  decode-headroom reserve + longest-idle eviction. See docs/scheduler.md.

Phase latency accounting (vision / prefill / decode) comes from the
engine's spans (``serving.tracing``; ``EngineStats.spans``): every tick,
admission, vision run, chunk, first-token read-back, decode dispatch,
read-back and emit is a named span on the host clock and, with a profiler
trace on, on the device trace's. ``phase_report()`` sums them — the
serving-side counterpart of the paper's Nsight phase decomposition — and
survives the fusion: vision runs as its own jitted stage
(``M.encode_vision`` feeding ``batch['prefix']``). Per-request
``queue_s``/``ttft_s``, per-tick ``tick_s`` and the decode-tick p50/p99 in
``phase_report()`` make scheduler jitter observable. The stage programs are
named ``jit_serve_tick``, ``jit_serve_spec_tick``, ``jit_serve_vision`` and
``jit_serve_chunk`` in profiles and HLO dumps.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import serving_rules, spec_for
from repro.kernels.decode_attention.decode_attention import live_pages
from repro.models import kv_quant
from repro.models import model as M
from repro.models.layers import ModelOptions, band_len, on_tpu, pallas_on
from repro.models.params import is_pspec
from repro.models.stacks import (cache_batch_axis, cache_template,
                                 is_paged_leaf, is_scale_leaf, stack_plan)
from repro.serving import sampler as S
from repro.serving.kv_pool import KVPool, PoolExhausted
from repro.serving.scheduler import (BEST_EFFORT, ChunkedScheduler, ChunkPlan,
                                     PrefillTask, SLOController,
                                     eviction_victims, insert_by_class,
                                     is_realtime, req_deadline)
from repro.serving.tracing import SpanRecorder, span


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # [S] int32
    max_tokens: int
    patches: Optional[np.ndarray] = None
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    cancelled: bool = False            # aborted via ServingEngine.cancel()
    t_submit: float = 0.0
    t_prefill: float = 0.0
    t_done: float = 0.0
    queue_s: float = 0.0               # submit -> prefill start (queue wait)
    ttft_s: float = 0.0                # submit -> first token
    pages_used: int = 0                # paged engine: pages held at finish
    pages_shared: int = 0              # paged engine: prefix-cache hits
    prefill_skipped: int = 0           # prompt positions skipped (prefix hit)
    priority: str = BEST_EFFORT        # scheduling class ("realtime" jumps
    #                                    the queue, EDF within class)
    deadline_s: float = 0.0            # relative SLO (0 = none); the
    #                                    absolute deadline is stamped at
    #                                    submit time
    t_deadline: float = math.inf       # t_submit + deadline_s (set by
    #                                    ServingEngine.submit; inf = none)
    trace: Optional[SpanRecorder] = None  # where this request's spans go
    #                                    (set by ServingEngine.submit)


@dataclass
class EngineStats:
    """Host-sync contract + phase + cache accounting for one engine lifetime.

    A "sync" is a device->host readback that blocks the Python loop (the
    per-token ``np.asarray``/``int()`` the paper's launch-overhead term maps
    to). The fused path pays one per tick; the reference path one per token.

    The cache fields are live only on the paged engine: ``pages_in_use`` /
    ``pages_hwm`` count pool pages referenced by live slots,
    ``cache_bytes_hwm`` is the high-water of their device bytes (summed over
    every attention layer's K+V pools *at the pool's storage dtype* — a
    quantized engine's figure reflects the 1-byte codes plus their f32
    scale rows, not the bf16/f32 equivalent), and ``prefix_hits`` counts
    pages served from the prefix cache instead of being re-stored.
    """
    decode_syncs: int = 0       # blocking readbacks on the decode path
    ticks: int = 0              # engine ticks (fused or reference)
    device_steps: int = 0       # decode steps executed on device
    tokens_decoded: int = 0     # tokens emitted by the decode path
    # the engine's spans (serving.tracing): a bounded ring plus running
    # totals per span name; the phase split below is read from them
    spans: SpanRecorder = field(default_factory=SpanRecorder)
    prefill_tokens: int = 0     # prompt positions actually run through prefill
    prefill_skipped: int = 0    # prompt positions skipped via prefix-cache hit
    # key-lane accounting for the banded prefill-with-cache core: per prefill
    # dispatch, every query row attends a key axis of the banded live-prefix
    # length instead of the full max_seq view the pre-dispatcher core used.
    # prefill_key_lanes sums rows x attended lanes; *_full sums the same
    # rows x max_seq — their ratio is the structurally recovered key-axis
    # factor (phase_report()["prefill_key_lane_ratio"]).
    prefill_key_lanes: int = 0       # sum of rows x banded key length
    prefill_key_lanes_full: int = 0  # rows x max_seq (old full-view core)
    # page accounting for the paged decode-attention kernel, per decode
    # step of the fused and reference paths: decode_kv_pages sums the live
    # pages the kernel visits for every slot (idle slots attend position 0,
    # one page), once per entry of the window pattern; *_full sums slots x
    # pages per slot over the same steps, the whole page grid. Their ratio
    # is phase_report()["decode_page_ratio"].
    decode_kv_pages: int = 0
    decode_kv_pages_full: int = 0
    pages_in_use: int = 0       # paged: current pool pages held by live slots
    pages_hwm: int = 0          # paged: high-water pages in use
    cache_bytes_hwm: int = 0    # paged: high-water KV bytes actually held
    prefix_hits: int = 0        # paged: pages reused via the prefix cache
    # sharded serving (ServingEngine mesh=...): mesh_shape names the mesh
    # axes, e.g. (("model", 4),), and cache_bytes_hwm_shard is the
    # *per-device* byte high-water — each shard stores its own heads' slice
    # of every page, so the honest per-device figure is ~1/N of the summed
    # cache_bytes_hwm (replicated leaves, e.g. a head-replication fallback,
    # keep it higher). Without a mesh, shard == total.
    mesh_shape: Optional[Tuple] = None
    cache_bytes_hwm_shard: int = 0
    # queue_s / ttft_s are per-*event* samples: one entry per admission
    # (submit -> prefill start) and per prefill completion (submit -> first
    # token). Without preemption that is exactly one entry per request; a
    # preempted-and-retried request contributes an entry per attempt that
    # reached the boundary (the Request's own fields hold the final values).
    # prefill_tokens likewise counts prompt positions actually *executed* —
    # a preempted prefill's re-run is real work and is counted again.
    queue_s: List[float] = field(default_factory=list)
    ttft_s: List[float] = field(default_factory=list)
    tick_s: List[float] = field(default_factory=list)    # whole-tick wall
    tick_prefill_tokens: List[int] = field(default_factory=list)  # per tick:
    # prompt positions prefilled inside that tick — the head-of-line metric
    # (admit-stall pays a whole prompt in one tick; the scheduler's entry
    # never exceeds its token budget)
    tick_key_lanes: List[int] = field(default_factory=list)  # per tick: key
    # lanes (rows x banded length) the tick's prefill dispatches attended
    # speculative decode (spec_decode=True). A verify "pass" is one
    # full-model chunk dispatch over spec_k positions for one live slot —
    # the weight+cache HBM pass speculation amortizes. accept_hist[n]
    # counts passes that emitted n tokens (accepted prefix + bonus), so
    # emitted / passes — phase_report()["spec_accept_per_pass"] — is the
    # tokens-per-HBM-pass factor the spec_decode bench gates >= 2x.
    # Draft cost is tracked both as raw truncated steps and as full-model
    # pass equivalents (steps x draft_layers / num_layers), giving the
    # draft/verify phase split. spec_key_lanes uses the *per-slot* banded
    # bound (satellite: per-slot live bounds) vs the max_seq view.
    spec_verify_passes: int = 0
    spec_draft_steps: int = 0            # truncated draft steps executed
    spec_draft_pass_equiv: float = 0.0   # draft cost in full-model passes
    spec_accept_hist: List[int] = field(default_factory=list)
    spec_key_lanes: int = 0              # verify rows x per-slot band bound
    spec_key_lanes_full: int = 0         # verify rows x max_seq
    # deadline + preemption accounting (SLO-aware scheduling). Only
    # requests carrying a deadline (deadline_s > 0) count toward
    # attainment — an undeadlined request can neither hit nor miss.
    # Preemptions are keyed by the *victim's* class; the policy invariant
    # (realtime is never an admission-side victim) makes
    # preemptions["realtime"] > 0 on that path a bug, not a statistic.
    deadline_hit: Dict[str, int] = field(default_factory=dict)
    deadline_miss: Dict[str, int] = field(default_factory=dict)
    preemptions: Dict[str, int] = field(default_factory=dict)
    tick_ewma_s: float = 0.0    # EWMA whole-tick wall (alpha 0.2) — the
    #                             live tick-cost estimate the SLO
    #                             controller and Backpressure quote from

    def record_tick_wall(self, wall_s: float):
        """Fold one tick's wall time into the EWMA (first sample seeds)."""
        self.tick_ewma_s = (wall_s if self.tick_ewma_s == 0.0
                            else 0.8 * self.tick_ewma_s + 0.2 * wall_s)

    def record_deadline(self, req) -> None:
        """Score a finishing request against its absolute deadline."""
        if not (req.deadline_s > 0):
            return
        cls = req.priority
        bucket = (self.deadline_hit if req.t_done <= req.t_deadline
                  else self.deadline_miss)
        bucket[cls] = bucket.get(cls, 0) + 1

    def record_preemption(self, req) -> None:
        self.preemptions[req.priority] = \
            self.preemptions.get(req.priority, 0) + 1

    def decode_tick_s(self) -> List[float]:
        """Per tick, seconds from its decode dispatch (``engine.decode``,
        page growth included) to the end of its read-back
        (``engine.readback``), over the ticks the span ring still holds."""
        ring = self.spans.spans()
        start = {s.parent: s.t0 for s in ring if s.name == "engine.decode"}
        return [s.t1 - start[s.parent] for s in ring
                if s.name == "engine.readback" and s.parent in start]

    def phase_report(self) -> Dict[str, float]:
        """Figure-2-style wall-time decomposition, plus decode-tick latency
        percentiles (p50/p99 over the per-tick decode stage) so scheduler
        jitter — a prefill chunk crowding the tick a decoder needed — is
        observable, not just the aggregate mean. The phases are span
        totals (host seconds): ``vision`` is ``engine.vision`` (the tower
        through its ``block_until_ready``); ``prefill`` is every admission
        less its vision plus every chunk, so it ends at each request's
        first-token read-back (``engine.first_token``, inside the last
        chunk); ``decode`` is ``engine.decode`` (page growth and dispatch)
        plus ``engine.readback``. When prefill ran,
        ``prefill_key_lane_ratio`` is the banded core's key-axis work over
        the old full-``max_seq``-view equivalent — the paper-style phase
        accounting for the recovered ~max_seq/S prefill factor. On a paged
        engine that decoded, ``decode_page_ratio`` is the share of the
        decode kernel's page grid that held live pages. Per-request
        queue-wait and TTFT percentiles (``queue_p50/p99``, ``ttft_p50/p99``,
        seconds, present once any request reached the respective boundary)
        make a stalled fleet diagnosable from a front-end log line: a
        growing queue_p99 with flat decode percentiles means admission is
        starved (pool pressure / backlog), not that decode got slower."""
        tot = self.spans.totals
        vision = tot.get("engine.vision", 0.0)
        rep = {"vision": vision,
               "prefill": (tot.get("engine.admit", 0.0) - vision
                           + tot.get("engine.chunk", 0.0)),
               "decode": (tot.get("engine.decode", 0.0)
                          + tot.get("engine.readback", 0.0))}
        ticks = self.decode_tick_s()
        if ticks:
            rep["decode_tick_p50"] = float(np.percentile(ticks, 50))
            rep["decode_tick_p99"] = float(np.percentile(ticks, 99))
        for name, samples in (("queue", self.queue_s), ("ttft", self.ttft_s)):
            if samples:
                rep[f"{name}_p50"] = float(np.percentile(samples, 50))
                rep[f"{name}_p99"] = float(np.percentile(samples, 99))
        if self.prefill_key_lanes_full:
            rep["prefill_key_lane_ratio"] = (self.prefill_key_lanes
                                             / self.prefill_key_lanes_full)
        if self.decode_kv_pages_full:
            rep["decode_page_ratio"] = (self.decode_kv_pages
                                        / self.decode_kv_pages_full)
        # per-class deadline attainment (requests with deadline_s > 0
        # only) and preemption counts, keyed by class suffix — the SLO
        # scheduler's scoreboard and the `slo` bench gate's input
        for cls in sorted(set(self.deadline_hit) | set(self.deadline_miss)):
            hit = self.deadline_hit.get(cls, 0)
            miss = self.deadline_miss.get(cls, 0)
            rep[f"deadline_attainment_{cls}"] = hit / (hit + miss)
            rep[f"deadline_total_{cls}"] = float(hit + miss)
        for cls, n in sorted(self.preemptions.items()):
            rep[f"preemptions_{cls}"] = float(n)
        if self.tick_ewma_s:
            rep["tick_ewma_s"] = float(self.tick_ewma_s)
        # paged cache accounting (and, under a mesh, the per-device view:
        # scrapers must not read the summed figure as a per-device one)
        if self.pages_hwm:
            rep["pages_in_use"] = float(self.pages_in_use)
            rep["pages_hwm"] = float(self.pages_hwm)
            rep["cache_bytes_hwm"] = float(self.cache_bytes_hwm)
            rep["prefix_hits"] = float(self.prefix_hits)
        if self.mesh_shape:
            for ax, sz in self.mesh_shape:
                rep[f"mesh_{ax}"] = float(sz)
            if self.pages_hwm:
                rep["cache_bytes_hwm_shard"] = float(self.cache_bytes_hwm_shard)
                # every shard references the same page set (it owns a head
                # slice of each page), so the count is per-device as-is
                rep["pages_in_use_shard"] = float(self.pages_in_use)
        if self.spec_verify_passes:
            emitted = sum(n * c for n, c in enumerate(self.spec_accept_hist))
            rep["spec_verify_passes"] = float(self.spec_verify_passes)
            rep["spec_accept_per_pass"] = emitted / self.spec_verify_passes
            rep["spec_accept_hist"] = [int(c) for c in self.spec_accept_hist]
            rep["spec_draft_steps"] = float(self.spec_draft_steps)
            rep["spec_draft_pass_equiv"] = float(self.spec_draft_pass_equiv)
            # draft/verify phase split, in full-model-pass equivalents:
            # what fraction of the tick's model work went to drafting
            tot = self.spec_draft_pass_equiv + self.spec_verify_passes
            rep["spec_draft_frac"] = float(self.spec_draft_pass_equiv / tot)
            if self.spec_key_lanes_full:
                rep["spec_key_lane_ratio"] = (self.spec_key_lanes
                                              / self.spec_key_lanes_full)
        return rep


def prefix_page_keys(cfg_name: str, page_size: int, kv_dtype: str,
                     prompt: np.ndarray, patches: Optional[np.ndarray] = None,
                     n_prefix: int = 0) -> List[bytes]:
    """Prefix-closed digests, one per *full* page of a request's prompt
    prefix — the content address a ``KVPool`` shares pages under.

    Key ``i`` covers every input that determines KV for positions
    ``[0, (i+1)*page_size)``: the vision patches (one digest, repeated over
    the ``n_prefix`` positions they fill) and the prompt tokens so far. The
    seed also covers the model name, page size, and pool storage dtype, so
    two pools can only ever share pages when their page contents would be
    bit-identical for identical prompts.

    Module-level (not an engine method) because the digest is also the
    *routing key*: ``serving.frontend`` computes it per candidate replica
    before any engine owns the request, and routes repeat observations to
    the replica whose pool already holds the prefix pages.
    """
    h = hashlib.sha1(f"{cfg_name}:{page_size}:{kv_dtype}".encode())
    items: List[bytes] = []
    if n_prefix:
        pd = hashlib.sha1(np.ascontiguousarray(patches).tobytes()).digest()
        items.extend([pd] * n_prefix)
    items.extend(int(t).to_bytes(8, "little", signed=True) for t in prompt)
    keys = []
    for i, item in enumerate(items):
        h.update(item)
        if (i + 1) % page_size == 0:
            keys.append(h.digest())
    return keys


def _fused_tick(cfg: ModelConfig, opts: ModelOptions, K: int, eos: int,
                temperature: float, top_k: int, stop_on_finish: bool,
                params, tokens, caches, index, budget, done, key,
                max_steps, page_table=None):
    """Up to K decode steps on device. Per-slot carry: current token [B,1],
    cache position index [B], remaining budget [B], done [B]. Emitted tokens
    land in out [B,K] (each live slot fills a prefix of its row, length
    n_emit[s]). Exits early when every slot is done or — with
    ``stop_on_finish`` — as soon as any slot newly finishes, so the host can
    refill it. ``max_steps`` (dynamic scalar <= K) lets the chunked
    scheduler cap the tick's decode depth to its token budget without
    recompiling; K stays the compiled loop bound. ``page_table`` [B,npg]
    selects the paged cache layout (pages for index..index+K-1 are
    pre-allocated by the host)."""
    B = tokens.shape[0]
    out0 = jnp.full((B, K), -1, jnp.int32)
    n_emit0 = jnp.zeros((B,), jnp.int32)
    entry_done = done
    cap = jnp.minimum(jnp.asarray(K, jnp.int32),
                      jnp.asarray(max_steps, jnp.int32))

    def cond(c):
        step, _, _, _, _, done, _, _, _ = c
        go = (step < cap) & ~jnp.all(done)
        if stop_on_finish:
            go &= ~jnp.any(done & ~entry_done)
        return go

    def body(c):
        step, tokens, caches, index, budget, done, key, out, n_emit = c
        logits, caches = M.decode_step(cfg, opts, params, tokens, caches,
                                       index, page_table=page_table)
        key, sub = jax.random.split(key)
        nxt = S.sample_token(logits, sub, temperature, top_k)   # [B]
        live = ~done
        col = jnp.where(live, nxt, -1)[:, None]
        out = jax.lax.dynamic_update_slice(out, col, (0, step))
        n_emit = n_emit + live.astype(jnp.int32)
        budget = jnp.where(live, budget - 1, budget)
        newly = live & ((nxt == eos) | (budget <= 0))
        index = jnp.where(live, index + 1, index)
        tokens = jnp.where(live[:, None], nxt[:, None], tokens)
        return (step + 1, tokens, caches, index, budget, done | newly, key,
                out, n_emit)

    init = (jnp.asarray(0, jnp.int32), tokens, caches, index, budget, done,
            key, out0, n_emit0)
    (steps, tokens, caches, index, budget, done, key, out, n_emit) = \
        jax.lax.while_loop(cond, body, init)
    return tokens, caches, index, budget, done, key, out, n_emit, steps


def _fused_spec_tick(cfg: ModelConfig, opts: ModelOptions, T: int, K: int,
                     draft_blocks: int, eos: int, stop_on_finish: bool,
                     max_seq: int, live_len: int, params, draft_params,
                     tokens, caches, index, budget, done, max_steps,
                     page_table=None):
    """Self-speculative fused tick: each while-loop round is
    draft -> verify -> accept instead of one decode step.

    Round anatomy (per live slot at position ``index``, current token
    ``tokens`` whose KV is not yet written — decode writes-then-attends):

    1. **Draft**: ``K - 1`` layer-truncated greedy steps
       (``M.draft_step`` over the leading ``draft_blocks`` blocks of
       ``draft_params``) roll out candidates; together with the current
       token they form the chunk [B, K] at positions ``index..index+K-1``.
       The draft's leading-layer KV lands in the shared cache — stale
       after this round, rewritten below.
    2. **Verify**: one full-model banded chunk dispatch
       (``M.verify_chunk``) runs all K positions, writing *all* layers'
       KV at those positions (which erases the draft's partial writes and
       any previous round's rejected rows before anything reads them) and
       returning every position's logits.
    3. **Accept** (``S.spec_accept``): greedy longest-prefix acceptance +
       one bonus token; ``n_emit in 1..K`` tokens per live slot, capped by
       the slot's remaining budget, the tick quota ``cap - e``, and a
       first emitted EOS. The emitted tokens are the *verifier's* argmaxes,
       so streams are bit-equal to the per-token reference; the carry
       token is the last emitted one and ``index += n_emit``.

    Rejected rows (``index + n_emit .. index + K - 1``) hold stale KV but
    are never read: causal masking hides positions past a slot's query,
    and the next round's verify rewrites them first (position re-write
    rollback). Rows at or past ``max_seq`` are masked out of the write
    path entirely (``n_valid``) — dense scatter drop / paged null-page
    sink — so speculation never corrupts the last page. ``live_len`` is
    the static banded key bound covering the oldest slot through this
    tick's deepest verify row (the host collapses the per-slot bounds to
    their max — a per-slot tuple as a static jit argument would retrace
    per batch age mix).

    Extra carry vs ``_fused_tick``: ``hist`` [K+1] counts verify passes
    by tokens emitted (the accepted-per-pass histogram) and ``passes``
    [B] counts per-slot verify passes (its denominator). Greedy-only, so
    no RNG key rides the carry."""
    B = tokens.shape[0]
    out0 = jnp.full((B, T), -1, jnp.int32)
    e0 = jnp.zeros((B,), jnp.int32)
    hist0 = jnp.zeros((K + 1,), jnp.int32)
    passes0 = jnp.zeros((B,), jnp.int32)
    entry_done = done
    cap = jnp.minimum(jnp.asarray(T, jnp.int32),
                      jnp.asarray(max_steps, jnp.int32))
    kcol = jnp.arange(K, dtype=jnp.int32)

    def cond(c):
        _, _, _, _, done, _, e, _, _, _ = c
        go = jnp.any(~done & (e < cap))
        if stop_on_finish:
            go &= ~jnp.any(done & ~entry_done)
        return go

    def body(c):
        tokens, caches, index, budget, done, out, e, hist, passes, iters = c
        live = ~done & (e < cap)
        # -- draft: K-1 truncated steps, chunk[0] is the current token -----
        cur = tokens
        chunk = [cur]
        for j in range(K - 1):
            pos = index + j
            nv = (live & (pos < max_seq)).astype(jnp.int32)
            dlogits, caches = M.draft_step(cfg, opts, draft_params, cur,
                                           caches, pos, draft_blocks,
                                           page_table=page_table, n_valid=nv)
            cur = jnp.argmax(dlogits[:, -1], -1).astype(jnp.int32)[:, None]
            chunk.append(cur)
        chunk = jnp.concatenate(chunk, axis=1)                       # [B,K]
        # -- verify: all K positions through the full model in one chunk --
        nv = jnp.where(live, jnp.clip(max_seq - index, 0, K), 0)
        vlogits, caches = M.verify_chunk(cfg, opts, params, chunk, caches,
                                         index, n_valid=nv,
                                         page_table=page_table,
                                         live_len=live_len)
        verify = jnp.argmax(vlogits, -1).astype(jnp.int32)           # [B,K]
        # -- accept: longest prefix + bonus, budget/quota/EOS capped ------
        n_emit, newly = S.spec_accept(chunk, verify, eos=eos,
                                      budget=budget, room=cap - e,
                                      live=live)
        cols = jnp.where(live[:, None] & (kcol[None] < n_emit[:, None]),
                         e[:, None] + kcol[None], T)    # T = dropped
        out = out.at[jnp.arange(B)[:, None], cols].set(verify, mode="drop")
        nxt = jnp.take_along_axis(
            verify, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)
        tokens = jnp.where(live[:, None], nxt, tokens)
        index = index + jnp.where(live, n_emit, 0)
        budget = budget - jnp.where(live, n_emit, 0)
        e = e + jnp.where(live, n_emit, 0)
        hist = hist.at[jnp.where(live, n_emit, K + 1)].add(1, mode="drop")
        passes = passes + live.astype(jnp.int32)
        return (tokens, caches, index, budget, done | newly, out, e, hist,
                passes, iters + 1)

    init = (tokens, caches, index, budget, done, out0, e0, hist0, passes0,
            jnp.asarray(0, jnp.int32))
    return jax.lax.while_loop(cond, body, init)


# Jitted stages are cached per static signature (configs are frozen
# dataclasses, hence hashable), so constructing many engines — tests, sweeps,
# one engine per model replica — shares compiled code instead of re-tracing.
@functools.lru_cache(maxsize=None)
def _jit_decode(cfg: ModelConfig, opts: ModelOptions):
    return jax.jit(lambda p, t, c, i, pt=None: M.decode_step(
        cfg, opts, p, t, c, i, page_table=pt))


@functools.lru_cache(maxsize=None)
def _jit_prefill(cfg: ModelConfig, opts: ModelOptions, max_seq: int,
                 cache_dtype):
    return jax.jit(lambda p, b: M.prefill(cfg, opts, p, b, max_seq,
                                          cache_dtype=cache_dtype))


# The stage programs are named functions, so a profile or an HLO dump shows
# them as jit_serve_vision / jit_serve_chunk / jit_serve_tick /
# jit_serve_spec_tick. The vision program's first argument stays ``p``: XLA
# names the weight leaves after it (``p__vision__...``).
@functools.lru_cache(maxsize=None)
def _jit_vision(cfg: ModelConfig, opts: ModelOptions):
    def serve_vision(p, px):
        return M.encode_vision(cfg, opts, p, px)
    return jax.jit(serve_vision)


@functools.lru_cache(maxsize=None)
def _jit_prefill_chunk(cfg: ModelConfig, opts: ModelOptions, paged: bool):
    """Chunked-prefill stage: one fixed-shape dispatch per chunk. The chunk
    length is baked in by the embeds shape (jit retraces per shape, and the
    scheduler always pads to ``chunk_size``); ``cache_index``/``n_valid``
    are dynamic scalars so chunk *position* never recompiles. ``live``
    (static, last arg) is the banded attention core's key-axis bound — the
    engine rounds it up to whole bands, so it takes at most
    ``max_seq / prefill_band`` distinct values per chunk shape. Caches are
    donated — the engine rebinds the returned tree."""
    if paged:
        def serve_chunk(p, e, c, i, nv, pt, live):
            return M.prefill_chunk(cfg, opts, p, e, c, i, n_valid=nv,
                                   page_table=pt, live_len=live)
        return jax.jit(serve_chunk, donate_argnums=2, static_argnums=6)

    def serve_chunk(p, e, c, i, nv, live):
        return M.prefill_chunk(cfg, opts, p, e, c, i, n_valid=nv,
                               live_len=live)
    return jax.jit(serve_chunk, donate_argnums=2, static_argnums=5)


@functools.lru_cache(maxsize=None)
def _jit_tick(cfg: ModelConfig, opts: ModelOptions, tick_tokens: int,
              eos: int, temperature: float, top_k: int,
              stop_on_finish: bool):
    def serve_tick(params, tokens, caches, index, budget, done, key,
                   max_steps, page_table=None):
        return _fused_tick(cfg, opts, tick_tokens, eos, temperature, top_k,
                           stop_on_finish, params, tokens, caches, index,
                           budget, done, key, max_steps, page_table)
    return jax.jit(serve_tick)


@functools.lru_cache(maxsize=None)
def _jit_spec_tick(cfg: ModelConfig, opts: ModelOptions, tick_tokens: int,
                   spec_k: int, draft_blocks: int, eos: int,
                   stop_on_finish: bool, max_seq: int):
    """Speculative fused tick, jitted per engine signature. ``live_len``
    (first arg, static) is the banded verify key bound — the engine rounds
    it to whole bands, so it takes at most ``max_seq / prefill_band``
    distinct values. Dense engines pass ``page_table=None`` (an empty
    pytree, not a trace problem)."""
    def serve_spec_tick(live_len, *args):
        return _fused_spec_tick(cfg, opts, tick_tokens, spec_k, draft_blocks,
                                eos, stop_on_finish, max_seq, live_len, *args)
    return jax.jit(serve_spec_tick, static_argnums=0)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, opts: ModelOptions, params,
                 n_slots: int = 4, max_seq: int = 512, eos: int = 1,
                 prompt_len: int = 64, fused: bool = True,
                 tick_tokens: int = 8, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, stop_on_finish: bool = True,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None, prefix_cache: bool = True,
                 kv_dtype: str = "bf16", chunked_prefill: bool = False,
                 chunk_size: int = 32, token_budget: int = 64,
                 reserve_pages: Optional[int] = None,
                 spec_decode: bool = False, spec_k: int = 4,
                 draft_layers: Optional[int] = None,
                 draft_quant: Optional[str] = None,
                 scale_granularity: Optional[str] = None,
                 slo_hz: float = 0.0, mesh: Optional[Mesh] = None):
        if tick_tokens < 1:
            raise ValueError(f"tick_tokens must be >= 1, got {tick_tokens}")
        if on_tpu() and not pallas_on(opts):
            raise ValueError("on the TPU the served path runs the compiled "
                             "Pallas kernels: ModelOptions(use_pallas=False) "
                             "selects the jnp reference cores, which are "
                             "not served")
        if mesh is not None:
            # sharded serving: every device stage becomes one shard_map-ped
            # program over the 'model' axis (see _init_mesh_stages). The
            # host-side scheduler/pool layer never sees the mesh.
            if "model" not in mesh.axis_names:
                raise ValueError("ServingEngine mesh needs a 'model' axis "
                                 "(launch.mesh.make_serving_mesh)")
            if any(mesh.shape[a] != 1 for a in mesh.axis_names
                   if a != "model"):
                raise ValueError("ServingEngine shards over 'model' only; "
                                 "every other mesh axis must have size 1")
            if cfg.encoder is not None:
                raise ValueError("mesh serving does not support "
                                 "encoder-decoder models (cross-attention "
                                 "context has no serving shard rule)")
            if not all(cfg.is_attn_layer(i) for i in range(cfg.num_layers)):
                raise ValueError("mesh serving requires attention-only "
                                 "decoders (SSM state has no head axis to "
                                 "partition the cache on)")
            if cfg.num_experts:
                raise ValueError("mesh serving does not support MoE layers "
                                 "(expert-parallel serving is not wired "
                                 "into the shard_map program)")
        if slo_hz < 0:
            raise ValueError(f"slo_hz must be >= 0, got {slo_hz}")
        if slo_hz > 0 and not chunked_prefill:
            raise ValueError("slo_hz requires chunked_prefill=True: the SLO "
                             "controller steers the per-tick decode depth "
                             "and chunk quota, which only exist under the "
                             "token-budget scheduler")
        if kv_quant.quant_dtype(kv_dtype) is not None and not paged:
            raise ValueError("kv_dtype quantization requires paged=True "
                             "(the page pool is the quantization boundary)")
        if chunked_prefill:
            if not fused:
                raise ValueError("chunked_prefill requires the fused decode "
                                 "path (fused=True)")
            if opts.window_cache:
                raise ValueError("chunked_prefill and window_cache ring "
                                 "buffers are mutually exclusive (rings "
                                 "don't support positioned prefill)")
            if cfg.encoder is not None:
                raise ValueError("chunked_prefill does not support "
                                 "encoder-decoder models")
            if not all(cfg.is_attn_layer(i) for i in range(cfg.num_layers)):
                raise ValueError("chunked_prefill requires attention-only "
                                 "decoders (SSM prefill state is not "
                                 "chunk-resumable yet)")
            if paged and chunk_size % page_size:
                raise ValueError(f"chunk_size {chunk_size} must divide by "
                                 f"page_size {page_size} so chunk writes "
                                 f"start page-aligned")
            if paged and pallas_on(opts) and page_size != opts.prefill_band:
                # the paged chunk kernel partitions the key axis per page
                # while the dense kernel (monolithic prefill) partitions per
                # prefill_band; the chunked==monolithic bit-equality
                # contract needs one absolute partition on the kernel path
                raise ValueError(
                    f"chunked_prefill with paged=True on the kernel path "
                    f"requires page_size ({page_size}) == "
                    f"ModelOptions.prefill_band ({opts.prefill_band}): the "
                    f"paged chunk-prefill kernel blocks the key axis per "
                    f"page, and bit-equality across chunkings needs the "
                    f"same partition as the dense kernel's bands")
        self.spec_decode, self.spec_k = spec_decode, spec_k
        self.draft_blocks = self.draft_layers = 0
        self.draft_quant = draft_quant
        if spec_decode:
            if not fused:
                raise ValueError("spec_decode requires the fused decode "
                                 "path (fused=True)")
            if temperature > 0:
                raise ValueError("spec_decode is greedy-only: longest-"
                                 "prefix acceptance re-emits the verifier's "
                                 "argmax, which only matches the reference "
                                 "stream at temperature 0")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if opts.window_cache:
                raise ValueError("spec_decode and window_cache ring buffers "
                                 "are mutually exclusive (rings don't "
                                 "support positioned chunk writes)")
            if cfg.encoder is not None:
                raise ValueError("spec_decode does not support "
                                 "encoder-decoder models")
            if not all(cfg.is_attn_layer(i) for i in range(cfg.num_layers)):
                raise ValueError("spec_decode requires attention-only "
                                 "decoders (SSM state cannot roll back "
                                 "rejected drafts by position re-write)")
            if paged and pallas_on(opts) and page_size != opts.prefill_band:
                raise ValueError(
                    f"spec_decode with paged=True on the kernel path requires "
                    f"page_size ({page_size}) == ModelOptions.prefill_band "
                    f"({opts.prefill_band}): the verify pass runs the paged "
                    f"chunk-prefill kernel, whose key-axis partition must "
                    f"match the dense kernel's bands for the bit-equality "
                    f"contract (same constraint as chunked_prefill)")
            period, nblocks, _ = stack_plan(cfg)
            if draft_layers is None:
                self.draft_blocks = max(1, nblocks // 2)
            else:
                if draft_layers % period or not (
                        0 < draft_layers <= nblocks * period):
                    raise ValueError(
                        f"draft_layers must be a multiple of the stack "
                        f"period ({period}) in 1..{nblocks * period}, "
                        f"got {draft_layers}")
                self.draft_blocks = draft_layers // period
            self.draft_layers = self.draft_blocks * period
            if draft_quant not in (None, "none", "int8", "fp8"):
                raise ValueError(f"draft_quant must be None/'none'/'int8'/"
                                 f"'fp8', got {draft_quant!r}")
        quantized = kv_quant.quant_dtype(kv_dtype) is not None
        if scale_granularity is not None and not quantized:
            raise ValueError("scale_granularity applies only to quantized "
                             "pools (kv_dtype int8/fp8)")
        if quantized:
            if scale_granularity is None:
                scale_granularity = "token" if spec_decode else "head"
            if scale_granularity not in kv_quant.SCALE_GRANULARITIES:
                raise ValueError(
                    f"scale_granularity must be one of "
                    f"{kv_quant.SCALE_GRANULARITIES}, "
                    f"got {scale_granularity!r}")
            if spec_decode and scale_granularity == "head":
                raise ValueError(
                    "spec_decode on a quantized pool requires "
                    "scale_granularity='token': shared per-(page, head) "
                    "scales let a rejected draft row's amax requantize "
                    "accepted rows on the same page, so speculative streams "
                    "cannot stay bit-equal to the per-token reference "
                    "(see docs/speculative.md)")
        self.scale_granularity = scale_granularity    # None when unquantized
        self.cfg, self.opts, self.params = cfg, opts, params
        # caches follow the params' dtype: bf16 weights serve a bf16 cache,
        # float32 weights (the CPU equivalence tests) a float32 one
        self.cache_dtype = jnp.dtype(params["embed"].dtype)
        self.mesh = mesh
        self._c1specs = None               # set by _init_mesh_stages
        self.n_slots, self.max_seq, self.eos = n_slots, max_seq, eos
        self.prompt_len = prompt_len
        self.fused, self.tick_tokens = fused, tick_tokens
        self.temperature, self.top_k = temperature, top_k
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.index = np.zeros(n_slots, np.int32)       # per-slot position
        self.budget = np.zeros(n_slots, np.int32)
        self.tokens = np.zeros((n_slots, 1), np.int32)
        self.paged, self.page_size = paged, page_size
        self.kv_dtype = kv_dtype
        self.prefix_cache = prefix_cache
        self.pool: Optional[KVPool] = None
        if paged:
            if max_seq % page_size:
                raise ValueError(f"max_seq {max_seq} must divide by "
                                 f"page_size {page_size}")
            pages_per_slot = max_seq // page_size
            if num_pages is None:
                # worst case every slot fills up, +1 for the null page
                num_pages = 1 + n_slots * pages_per_slot
            self.pool = KVPool(num_pages, page_size, n_slots, pages_per_slot)
            self.caches = M.init_caches(cfg, n_slots, max_seq,
                                        self.cache_dtype, opts, paged=True,
                                        num_pages=num_pages,
                                        page_size=page_size,
                                        kv_dtype=kv_dtype,
                                        scale_granularity=(scale_granularity
                                                           or "head"))
            self._bytes_per_page = sum(
                leaf.nbytes // num_pages for path, leaf in
                jax.tree_util.tree_leaves_with_path(self.caches)
                if is_paged_leaf(path))
        else:
            self.caches = M.init_caches(cfg, n_slots, max_seq,
                                        self.cache_dtype, opts)
            self._bytes_per_page = 0
        # per-device bytes per page: equals the summed figure on one device,
        # recomputed from actual shard buffers under a mesh
        self._bytes_per_page_shard = self._bytes_per_page
        self.stats = EngineStats()
        self.key = jax.random.PRNGKey(seed)
        self.scheduler: Optional[ChunkedScheduler] = None
        self.chunk_size, self.token_budget = chunk_size, token_budget
        # slot -> last time it made progress (chunk ran / tokens emitted);
        # the pool-aware admission policy evicts the longest-idle slot
        self._last_active = np.zeros(n_slots, np.float64)
        self.slo_hz = slo_hz
        self._slo = SLOController(slo_hz) if slo_hz > 0 else None
        if chunked_prefill:
            self.scheduler = ChunkedScheduler(chunk_size, token_budget)
            self._prefill_chunk = _jit_prefill_chunk(cfg, opts, paged)
        if paged:
            # decode headroom: admission never grabs the last pages an
            # in-flight decode needs to grow into (pool-aware policy)
            if reserve_pages is None:
                reserve_pages = n_slots if chunked_prefill else 0
            self.pool.set_reserve(min(reserve_pages,
                                      max(0, self.pool.num_pages - 2)))

        self._decode = _jit_decode(cfg, opts)
        self._prefill = _jit_prefill(cfg, opts, max_seq, self.cache_dtype)
        self._vision = (_jit_vision(cfg, opts)
                        if cfg.vision is not None else None)
        self._tick = _jit_tick(cfg, opts, tick_tokens, eos, temperature,
                               top_k, stop_on_finish)
        # cache-maintenance stages behind instance indirection so every call
        # site (admission, COW, scale resets) is layout-agnostic; a mesh
        # swaps these for shard_map-ped equivalents below
        self._scatter_slot_fn = _scatter_slot
        self._scatter_pages_fn = (
            lambda c, c1, d: _scatter_pages(c, c1, d, self.page_size))
        self._copy_pages_fn = _copy_pages
        self._reset_scales_fn = _reset_page_scales
        self._spec_tick = None
        if spec_decode:
            # the weight-quantized draft shares the tree structure (and
            # dtypes) of params — fake quantization round-trips values only
            self.draft_params = (
                kv_quant.fake_quantize_tree(params, draft_quant)
                if draft_quant in ("int8", "fp8") else params)
            self._spec_tick = _jit_spec_tick(cfg, opts, tick_tokens, spec_k,
                                             self.draft_blocks, eos,
                                             stop_on_finish, max_seq)
        if mesh is not None:
            self._init_mesh_stages(mesh, stop_on_finish)

    # -- sharded serving (mesh) -------------------------------------------
    def _init_mesh_stages(self, mesh: Mesh, stop_on_finish: bool):
        """Rebind every device stage as a single shard_map-ped program over
        the mesh's ``model`` axis, and partition params + KV pool across it.

        Layout (Megatron-style tensor parallelism, serving_rules):

        - attention heads and KV-cache pages shard on the head axis: each
          device owns ``[num_pages, K/n, page_size, h]`` slices of every
          page, so the paged kernels run *unchanged* per shard and the
          host-side page tables stay global (replicated operands). GQA
          divisibility is atomic — smollm's 9/3 heads replicate over
          model=2/4 and the program is collective-free for them.
        - MLP width and vocab shard per-leaf; partial attention/MLP outputs
          psum inside the layer (layers.attention / layers.mlp) and the
          *only* all-gather sits at the lm head, right before sampling
          (model._logits) — the activation wire cost per decoded token is
          2 psums/layer + one [V] gather.
        - everything the host scheduler/pool touches (page tables, token
          state, budgets) is replicated, so scheduler/kv_pool code observes
          no mesh at all.

        ``check_vma=False`` everywhere: the sharded-vs-single-device stream
        tests, not the tracer, check that outputs declared replicated are
        replicated."""
        cfg, opts = self.cfg, self.opts
        self._serving_rule_table, specs_of = _serving_specs(cfg, mesh)
        shopts = dataclasses.replace(opts, shard_axis="model")
        pspecs = serving_param_specs(cfg, mesh)
        if self.paged:
            cspecs = specs_of(cache_template(
                cfg, self.n_slots, self.max_seq, self.cache_dtype, opts,
                paged=True, num_pages=self.pool.num_pages,
                page_size=self.page_size, kv_dtype=self.kv_dtype,
                scale_granularity=(self.scale_granularity or "head")))
        else:
            cspecs = specs_of(cache_template(cfg, self.n_slots, self.max_seq,
                                             self.cache_dtype, opts))
        # batch-1 dense cache (prefill output / chunked-prefill carry)
        c1specs = specs_of(cache_template(cfg, 1, self.max_seq,
                                          self.cache_dtype, opts))
        self._c1specs = c1specs

        def place(tree, specs):
            return jax.tree_util.tree_map(
                lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                tree, specs)

        self.params = place(self.params, pspecs)
        self.caches = place(self.caches, cspecs)
        if self.spec_decode:
            self.draft_params = place(self.draft_params, pspecs)
        if self.paged:
            # honest per-device accounting: measure the shard buffers, so a
            # head-replication fallback (or replicated scale rows) reports
            # its true per-device cost instead of an assumed 1/N
            self._bytes_per_page_shard = sum(
                leaf.addressable_shards[0].data.nbytes // self.pool.num_pages
                for path, leaf in
                jax.tree_util.tree_leaves_with_path(self.caches)
                if is_paged_leaf(path))
        self.stats.mesh_shape = tuple(
            (a, int(mesh.shape[a])) for a in mesh.axis_names)

        R = P()

        def smap(f, in_specs, out_specs):
            return shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

        self._decode = jax.jit(smap(
            lambda p, t, c, i, pt: M.decode_step(cfg, shopts, p, t, c, i,
                                                 page_table=pt),
            (pspecs, R, cspecs, R, R), (R, cspecs)))
        # monolithic prefill: the prefill-from-zero path must not allocate
        # its own caches inside the shard trace (init_caches would build the
        # global head count), so a reusable sharded zero tree rides along
        self._cache1_zeros = place(
            M.init_caches(cfg, 1, self.max_seq, self.cache_dtype, opts),
            c1specs)
        prefill_sharded = jax.jit(smap(
            lambda p, b, c0: M.prefill(cfg, shopts, p, b, self.max_seq,
                                       cache_dtype=self.cache_dtype,
                                       fresh_caches=c0),
            (pspecs, R, c1specs), (R, c1specs)))
        self._prefill = lambda p, b: prefill_sharded(p, b,
                                                     self._cache1_zeros)
        tick_tokens, eos = self.tick_tokens, self.eos
        temperature, top_k = self.temperature, self.top_k

        def serve_tick(params, tokens, caches, index, budget, done, key,
                       max_steps, page_table):
            return _fused_tick(cfg, shopts, tick_tokens, eos, temperature,
                               top_k, stop_on_finish, params, tokens, caches,
                               index, budget, done, key, max_steps,
                               page_table)
        self._tick = jax.jit(smap(serve_tick,
                                  (pspecs, R, cspecs, R, R, R, R, R, R),
                                  (R, cspecs, R, R, R, R, R, R, R)))
        if self.spec_decode:
            spec_k, draft_blocks = self.spec_k, self.draft_blocks
            max_seq = self.max_seq

            def serve_spec_tick(live_len, p, dp, t, c, i, b, d, ms, pt):
                def f(*args):
                    return _fused_spec_tick(cfg, shopts, tick_tokens, spec_k,
                                            draft_blocks, eos, stop_on_finish,
                                            max_seq, live_len, *args)
                return smap(f, (pspecs, pspecs, R, cspecs, R, R, R, R, R),
                            (R, cspecs, R, R, R, R, R, R, R, R))(
                    p, dp, t, c, i, b, d, ms, pt)
            self._spec_tick = jax.jit(serve_spec_tick, static_argnums=0)
        if self.scheduler is not None:
            if self.paged:
                def serve_chunk(p, e, c, i, nv, pt, live):
                    def f(p, e, c, i, nv, pt):
                        return M.prefill_chunk(cfg, shopts, p, e, c, i,
                                               n_valid=nv, page_table=pt,
                                               live_len=live)
                    return smap(f, (pspecs, R, cspecs, R, R, R),
                                (R, cspecs))(p, e, c, i, nv, pt)
                self._prefill_chunk = jax.jit(serve_chunk,
                                              donate_argnums=2,
                                              static_argnums=6)
            else:
                def serve_chunk(p, e, c, i, nv, live):
                    def f(p, e, c, i, nv):
                        return M.prefill_chunk(cfg, shopts, p, e, c, i,
                                               n_valid=nv, live_len=live)
                    return smap(f, (pspecs, R, c1specs, R, R),
                                (R, c1specs))(p, e, c, i, nv)
                self._prefill_chunk = jax.jit(serve_chunk,
                                              donate_argnums=2,
                                              static_argnums=5)

        def scatter_slot(c, c1, slot, skip_paged):
            return smap(lambda a, b: _scatter_slot(a, b, slot, skip_paged),
                        (cspecs, c1specs), cspecs)(c, c1)
        self._scatter_slot_fn = jax.jit(scatter_slot, static_argnums=(2, 3))
        if self.paged:
            page_size = self.page_size
            self._scatter_pages_fn = jax.jit(smap(
                lambda c, c1, d: _scatter_pages_impl(c, c1, d, page_size),
                (cspecs, c1specs, R), cspecs), donate_argnums=0)
            self._copy_pages_fn = jax.jit(
                smap(_copy_pages_impl, (cspecs, R, R), cspecs),
                donate_argnums=0)
            self._reset_scales_fn = jax.jit(
                smap(_reset_page_scales_impl, (cspecs, R), cspecs),
                donate_argnums=0)

    def stage_programs(self, opts: Optional[ModelOptions] = None):
        """The engine's vision, chunked-prefill and single-step decode
        programs, as ``(vision, prefill_chunk, decode)``. With ``opts``
        (single-device engines only) the prefill and decode programs are
        traced under those options instead — e.g. ``use_pallas=False``,
        the jnp reference cores, for checking the kernels against."""
        if opts is None or opts == self.opts:
            return (self._vision, getattr(self, "_prefill_chunk", None),
                    self._decode)
        if self.mesh is not None:
            raise ValueError("stage_programs(opts) re-traces single-device "
                             "programs; this engine is sharded")
        return (self._vision, _jit_prefill_chunk(self.cfg, opts, self.paged),
                _jit_decode(self.cfg, opts))

    def _fresh_cache1(self):
        """Zeroed batch-1 dense cache for one chunked-prefill admission.
        Dense chunks donate their cache carry, so each admission needs its
        own tree (the monolithic path's zeros are reusable — prefill there
        is non-donating)."""
        c = M.init_caches(self.cfg, 1, self.max_seq, self.cache_dtype,
                          self.opts)
        if self.mesh is not None:
            c = jax.tree_util.tree_map(
                lambda x, sp: jax.device_put(
                    x, NamedSharding(self.mesh, sp)), c, self._c1specs)
        return c

    def _sample_host(self, logits):
        """Host-path sampling (admission + reference step) with the same
        temperature/top_k config the fused tick uses; greedy by default."""
        if self.temperature <= 0:
            return S.greedy(logits)
        self.key, sub = jax.random.split(self.key)
        return S.sample_token(logits, sub, self.temperature, self.top_k)

    # -- queue -----------------------------------------------------------
    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        req.trace = self.stats.spans
        # the relative SLO becomes absolute at submit: everything
        # downstream (EDF ordering, the SLO controller, attainment
        # scoring) compares wall clock against this one stamp
        req.t_deadline = (req.t_submit + req.deadline_s
                          if req.deadline_s > 0 else math.inf)
        if self.scheduler is not None:
            self.scheduler.submit(req)
        else:
            # legacy queue shares the class-ordered insert, so realtime
            # requests get admission priority in _admit too
            insert_by_class(self.queue, req)

    @property
    def pending(self) -> int:
        """Requests not yet finished: queued + mid-prefill + in slots."""
        n = len(self.queue) + sum(r is not None for r in self.slots)
        if self.scheduler is not None:
            n += self.scheduler.pending
        return n

    def cancel(self, uid: int) -> bool:
        """Abort request ``uid`` mid-flight, wherever it is in the pipeline.

        Call between ticks (the front-end stages cancellations and drains
        them at tick boundaries — never while a tick is in flight in another
        thread). Three cases, each leaving the engine in the same state as
        if the request had never been admitted past that point:

        - **queued** (waiting list / legacy queue): removed, nothing else
          held.
        - **mid-prefill** (chunked mode, a live ``PrefillTask``): the task
          is dropped without requeue and the slot's pool pages are freed.
          Full prompt pages the aborted chunks already registered in the
          prefix cache are *retained* (refcount 0, LRU) — their KV is
          written and correct, so a later identical observation still hits.
        - **mid-decode** (live slot): the slot is cleared and its pages are
          freed. The slot's table row resets to the null page, so if a
          fused tick is already compiled against the old snapshot the stale
          writes sink harmlessly (same mechanism as a finished slot riding
          through a tick).

        The request is marked ``cancelled`` and is *not* appended to
        ``finished``; pool accounting (``pages_in_use``) returns to what it
        was before the request was admitted, minus retained cache pages.
        Returns whether the uid was found live anywhere."""
        if self.scheduler is not None:
            for k, r in enumerate(self.scheduler.waiting):
                if r.uid == uid:
                    self.scheduler.waiting.pop(k)
                    r.cancelled = True
                    return True
            for s, t in list(self.scheduler.tasks.items()):
                if t.req.uid == uid:
                    self.scheduler.tasks.pop(s)
                    if self.paged:
                        self.pool.free_slot(s)
                        self._update_cache_stats()
                    t.req.cancelled = True
                    return True
        else:
            for k, r in enumerate(self.queue):
                if r.uid == uid:
                    self.queue.pop(k)
                    r.cancelled = True
                    return True
        for s in range(self.n_slots):
            req = self.slots[s]
            if req is not None and req.uid == uid:
                self.slots[s] = None
                if self.paged:
                    self.pool.free_slot(s)
                    self._update_cache_stats()
                req.cancelled = True
                return True
        return False

    # -- paged bookkeeping ------------------------------------------------
    def _prefix_page_keys(self, req: Request, n_prefix: int) -> List[bytes]:
        """Prefix-closed digests for ``req``'s full prompt pages (see the
        module-level ``prefix_page_keys`` — same function, engine config
        baked in). Empty when the prefix cache is disabled."""
        if not self.prefix_cache:
            return []
        return prefix_page_keys(self.cfg.name, self.page_size, self.kv_dtype,
                                req.prompt, req.patches, n_prefix)

    def _update_cache_stats(self):
        st, pool = self.stats, self.pool
        st.pages_in_use = pool.pages_in_use
        st.pages_hwm = max(st.pages_hwm, pool.pages_hwm)
        # the pool tracks page indices only; bytes-per-page is the engine's
        # layout knowledge (KVPool.byte_stats keeps the pool mesh-blind)
        st.cache_bytes_hwm = max(
            st.cache_bytes_hwm,
            pool.byte_stats(self._bytes_per_page)["bytes_in_use"])
        st.cache_bytes_hwm_shard = max(
            st.cache_bytes_hwm_shard,
            pool.byte_stats(self._bytes_per_page_shard)["bytes_in_use"])
        st.prefix_hits = pool.prefix_hits

    def _page_table_device(self):
        """Page table for the *decode* tick. The fused tick issues cache
        writes for every row, done or not — done rows sink into the null
        page because ``free_slot`` nulled them. A mid-prefill slot's row is
        live, though (its chunks need it), and the decode tick must not let
        that slot's stale index clobber freshly-written chunk KV: its row is
        nulled in the decode snapshot only."""
        pt = self.pool.page_table
        if self.scheduler is not None and self.scheduler.tasks:
            pt = pt.copy()
            for s in self.scheduler.tasks:
                pt[s, :] = 0
        return jnp.asarray(pt)

    def _slot_req(self, s: int) -> Optional[Request]:
        """The request occupying slot ``s`` — decoding or mid-prefill."""
        if self.slots[s] is not None:
            return self.slots[s]
        if self.scheduler is not None and s in self.scheduler.tasks:
            return self.scheduler.tasks[s].req
        return None

    def _preempt_slot(self, s: int):
        """Evict a live slot under pool pressure: free its pages and requeue
        the request from scratch. Works on both a decoding slot and a
        mid-prefill slot (chunked mode) — in the latter case the in-flight
        chunks are discarded; pages its first attempt registered in the
        prefix cache may still be retained, so the retry can prefix-skip
        what it already computed. Under greedy sampling the regenerated
        stream is identical (deterministic), so correctness is preserved;
        under temperature sampling the retried stream may differ (the
        degraded mode of an under-provisioned pool, not a crash)."""
        self.pool.free_slot(s)
        if self.slots[s] is not None:
            req = self.slots[s]
            self.slots[s] = None
            req.out_tokens = []
            self.stats.record_preemption(req)
            if self.scheduler is not None:
                self.scheduler.submit(req, front=True)
            else:
                insert_by_class(self.queue, req, front=True)
        elif self.scheduler is not None:
            task = self.scheduler.requeue_task(s)
            if task is not None:
                self.stats.record_preemption(task.req)

    def _evict_longest_idle(self, exclude: int = -1) -> bool:
        """Pool-aware admission policy: instead of blindly deferring on
        ``PoolExhausted``, preempt the longest-idle *queued-behind* slot —
        a prefill task already stalled on pool pressure. Only stalled tasks
        are candidates: decoders and progressing prefills free their pages
        by finishing, so evicting them would trade guaranteed progress for
        a restart (and two mutually-starved slots could ping-pong-evict
        each other forever). Candidates are additionally class-filtered
        (``scheduler.eviction_victims``): realtime prefill is never a
        victim — realtime never preempts realtime, and best-effort
        preempting realtime would be priority inversion. Returns whether
        a victim was evicted."""
        if self.scheduler is None:
            return False
        cands = eviction_victims(self.scheduler.tasks, exclude=exclude)
        if not cands:
            return False
        self._preempt_slot(min(cands, key=lambda s: self._last_active[s]))
        return True

    def _ensure_pages(self, steps: int, extra: int = 0):
        """Pre-allocate pages covering every position the next tick may
        write (index .. index+steps-1 per live slot), and copy-on-write any
        shared page in that range (none in normal engine flow — admission
        only shares full prompt pages — but enforced, not assumed).
        ``extra`` covers positions written but not necessarily *kept*: the
        speculative tick writes up to ``spec_k - 1`` draft/verify rows past
        the last accepted token, so its rounds need ``extra = spec_k - 1``
        backing pages beyond the budget-capped emit range (rows past
        ``max_seq`` are masked to the null sink instead and need none).

        Pool pressure degrades instead of crashing: if growth fails, the
        live slot holding the most pages (excluding the one being grown) is
        preempted and retried later; a single request the pool cannot hold
        at all is a sizing error and raises.

        Quantized pools: pages handed out by growth may have been freed by
        an earlier request and still carry its scale rows; those rows are
        zeroed on device before the tick, so the monotone-amax write policy
        starts from a clean scale and quantization stays history-independent
        (the admission path needs no reset — ``_scatter_pages`` overwrites
        scale rows wholesale)."""
        copies = []
        held_before: Dict[int, set] = {}
        for s in range(self.n_slots):
            if self.slots[s] is None:
                continue
            held_before[s] = set(self.pool.slot_pages[s])
            start = int(self.index[s])
            # never reserve past the slot's remaining budget — backing pages
            # a finishing slot cannot write could preempt a healthy one
            end = min(start + min(steps, max(int(self.budget[s]), 1)) + extra,
                      self.max_seq)
            while True:
                try:
                    self.pool.ensure(s, end)
                    copies += self.pool.prepare_write(s, start, end)
                    break
                except PoolExhausted:
                    victims = [v for v in range(self.n_slots)
                               if v != s
                               and (self.slots[v] is not None
                                    or (self.scheduler is not None
                                        and v in self.scheduler.tasks))]
                    if not victims:
                        raise PoolExhausted(
                            f"KV pool too small for a single request "
                            f"(slot {s} needs pages for {end} positions)")
                    # class preference: best-effort work yields first;
                    # realtime is only ever preempted here when nothing
                    # else can free pages (the no-deadlock fallback —
                    # decode growth must make progress or the pool is
                    # simply too small for the realtime working set)
                    be = [v for v in victims
                          if not is_realtime(self._slot_req(v))]
                    self._preempt_slot(max(
                        be or victims,
                        key=lambda v: len(self.pool.slot_pages[v])))
            self.slots[s].pages_used = len(self.pool.slot_pages[s])
        # pages a slot gained this call (growth and COW destinations;
        # diffed against entry so pages appended by an ensure() that
        # then raised are included too). Scale rows are zeroed *before*
        # the COW copy below, which restores the destinations' scales.
        self._reset_fresh_scales(sorted(
            {p for s, held in held_before.items()
             if self.slots[s] is not None
             for p in self.pool.slot_pages[s]
             if p not in held}))
        self._dispatch_copies(copies)
        self._update_cache_stats()

    def _dispatch_copies(self, copies: List):
        """Materialize copy-on-write (src, dst) page pairs with one jitted
        gather/scatter (zero-padded pairs are null->null no-ops)."""
        if not copies:
            return
        width = self.pool.pages_per_slot * self.n_slots
        src = np.zeros(width, np.int32)
        dst = np.zeros(width, np.int32)
        for i, (a, b) in enumerate(copies):
            src[i], dst[i] = a, b
        self.caches = self._copy_pages_fn(self.caches, jnp.asarray(src),
                                          jnp.asarray(dst))

    def _clamped_budget(self, req: Request, pos: int) -> int:
        """Clamp generation to cache capacity: decode writes at positions
        pos..pos+budget-1, which must stay < max_seq in *both* layouts
        (unclamped, each layout clamps its scatter differently and the
        bit-equality contract breaks). Warns when the clamp bites."""
        budget = min(req.max_tokens - 1, self.max_seq - pos)
        if budget < req.max_tokens - 1:
            warnings.warn(
                f"request {req.uid}: max_tokens {req.max_tokens} "
                f"exceeds cache capacity (prompt {pos} + budget > "
                f"max_seq {self.max_seq}); clamping",
                RuntimeWarning, stacklevel=2)
        return budget

    def _finish_slot(self, s: int, now: float):
        req = self.slots[s]
        req.done = True
        req.t_done = now
        self.stats.record_deadline(req)
        if self.paged:
            req.pages_used = len(self.pool.slot_pages[s])
            self.pool.free_slot(s)
            self._update_cache_stats()
        self.finished.append(req)
        self.slots[s] = None

    def _admit(self):
        """Monolithic (admit-stall) admission: pop the queue head into every
        free slot, running its *whole* prompt through one prefill dispatch.

        Per admitted request, in order: (1) capacity check —
        ``KVPool.can_admit`` over the prompt pages *plus the first decode
        write* must pass before anything is paid for (a deferred request
        must not waste a vision pass); (2) vision, as its own jitted stage
        so phase accounting survives; (3) batch-1 prefill + first-token
        sample (the TTFT boundary); (4) page allocation + page-wise scatter
        (paged) or batch-row scatter (dense). A request that already
        finishes at prefill (EOS first token / ``max_tokens <= 1`` / no
        cache headroom) never takes a slot — the inner loop retries the
        same slot with the next queued request.

        Atomicity under pool races: ``can_admit`` ran before vision+prefill,
        but a retained cache page can be reclaimed in between, so a raising
        ``admit`` rolls back every stat this attempt recorded (queue/TTFT
        samples, prefill token and key-lane counters) and requeues the
        request at the front — the retry must not double-count."""
        for s in range(self.n_slots):
            # the inner loop retries the slot when a request already finishes
            # at prefill (EOS first token, or max_tokens == 1)
            while self.slots[s] is None and self.queue:
                req = self.queue[0]
                with span("engine.admit", self.stats.spans, uid=req.uid):
                    if not self._admit_one(req, s):
                        return

    def _admit_one(self, req: Request, s: int) -> bool:
        """One monolithic admission attempt of the queue's head ``req``
        into slot ``s``: capacity check, vision, prefill, first token,
        scatter. False when the request has to wait (deferred for pages,
        or the pool raced it back to the queue's head)."""
        n_prefix = (self.cfg.vision.num_tokens
                    if req.patches is not None and self._vision
                    else 0)
        pos = n_prefix + len(req.prompt)
        keys = (self._prefix_page_keys(req, n_prefix)
                if self.paged else [])
        # capacity must cover the prompt AND the first decode write
        # at position pos (requests finishing at prefill need none)
        need = (0 if req.max_tokens <= 1
                else min(pos + 1, self.max_seq))
        if self.paged and need and not self.pool.can_admit(need,
                                                           keys):
            if not any(r is not None for r in self.slots):
                # nothing in flight will ever free pages: sizing error
                raise PoolExhausted(
                    f"KV pool ({self.pool.num_pages - 1} pages) too "
                    f"small for request {req.uid} "
                    f"({self.pool.num_pages_for(need)} pages)")
            # defer *before* paying for vision + prefill; retry when
            # a finishing slot frees pages
            return False
        self.queue.pop(0)
        rec = self.stats.spans
        req.queue_s = time.perf_counter() - req.t_submit
        self.stats.queue_s.append(req.queue_s)
        batch = {"tokens": jnp.asarray(req.prompt[None, :])}
        if n_prefix:
            with span("engine.vision", rec, uid=req.uid):
                prefix = self._vision(self.params,
                                      jnp.asarray(req.patches[None]))
                prefix.block_until_ready()
            batch["prefix"] = prefix
        with span("engine.first_token", rec, uid=req.uid):
            logits, cache1 = self._prefill(self.params, batch)
            tok = int(self._sample_host(logits)[0])
        req.t_prefill = time.perf_counter()
        self.stats.prefill_tokens += pos
        # monolithic prefill attends the banded live prefix too
        # (model.prefill derives live_len from the prompt shape)
        self.stats.prefill_key_lanes += pos * band_len(
            pos, self.opts.prefill_band, self.max_seq)
        self.stats.prefill_key_lanes_full += pos * self.max_seq
        req.ttft_s = req.t_prefill - req.t_submit
        self.stats.ttft_s.append(req.ttft_s)
        req.out_tokens.append(tok)
        budget = self._clamped_budget(req, pos)
        if tok == self.eos or req.max_tokens <= 1 or budget <= 0:
            req.done = True
            req.t_done = req.t_prefill
            self.stats.record_deadline(req)
            self.finished.append(req)
            return True
        if self.paged:
            try:
                pages, n_shared = self.pool.admit(s, pos, keys)
            except PoolExhausted:
                # can_admit() raced a cached-page eviction; defer
                # and roll the attempt's stats back too, so the
                # retry doesn't double-count this request
                self.queue.insert(0, req)
                req.out_tokens.pop()
                self.stats.queue_s.pop()
                self.stats.ttft_s.pop()
                self.stats.prefill_tokens -= pos
                self.stats.prefill_key_lanes -= pos * band_len(
                    pos, self.opts.prefill_band, self.max_seq)
                self.stats.prefill_key_lanes_full -= pos * self.max_seq
                return False
            req.pages_used = len(pages)
            req.pages_shared = n_shared
            # shared pages already hold this prefix's KV — route
            # their rows to the null sink instead of re-writing
            dest = np.zeros(self.pool.pages_per_slot, np.int32)
            dest[n_shared:len(pages)] = pages[n_shared:]
            self.caches = self._scatter_pages_fn(self.caches, cache1,
                                                 jnp.asarray(dest))
            self.caches = self._scatter_slot_fn(self.caches, cache1,
                                                s, True)
            self._update_cache_stats()
        else:
            self.caches = self._scatter_slot_fn(self.caches, cache1,
                                                s, False)
        self.index[s] = pos
        self.budget[s] = budget
        self.tokens[s, 0] = tok
        self.slots[s] = req
        self._last_active[s] = req.t_prefill
        return True

    # -- one engine tick ---------------------------------------------------
    def step(self) -> int:
        """Reference path: one decode step, one host sync per token."""
        if self.scheduler is not None:
            raise RuntimeError("chunked_prefill engines tick via "
                               "step_fused()/run() (fused only)")
        with span("engine.tick", self.stats.spans):
            return self._step_reference()

    def _step_reference(self) -> int:
        t_tick = time.perf_counter()
        pf0 = self.stats.prefill_tokens
        kl0 = self.stats.prefill_key_lanes
        self._admit()
        active = [s for s in range(self.n_slots) if self.slots[s] is not None]
        if not active:
            self.stats.tick_prefill_tokens.append(
                self.stats.prefill_tokens - pf0)
            self.stats.tick_key_lanes.append(
                self.stats.prefill_key_lanes - kl0)
            wall = time.perf_counter() - t_tick
            self.stats.tick_s.append(wall)
            self.stats.record_tick_wall(wall)
            return 0
        rec = self.stats.spans
        with span("engine.decode", rec):
            pt = None
            if self.paged:
                self._ensure_pages(1)
                pt = self._page_table_device()
                # growth may have preempted a slot under pool pressure
                active = [s for s in range(self.n_slots)
                          if self.slots[s] is not None]
            index = self._decode_index()
            logits, self.caches = self._decode(
                self.params, jnp.asarray(self.tokens), self.caches,
                jnp.asarray(index), pt)
        with span("engine.readback", rec):
            nxt = np.asarray(self._sample_host(logits))
        with span("engine.emit", rec):
            now = time.perf_counter()
            self.stats.decode_syncs += 1
            self.stats.ticks += 1
            self.stats.device_steps += 1
            self.stats.tokens_decoded += len(active)
            self._count_kv_pages(index, np.ones_like(index), 1)
            for s in active:
                req = self.slots[s]
                tok = int(nxt[s])
                req.out_tokens.append(tok)
                self._last_active[s] = now
                self.index[s] += 1
                self.budget[s] -= 1
                if tok == self.eos or self.budget[s] <= 0:
                    self._finish_slot(s, now)
                else:
                    self.tokens[s, 0] = tok
        self.stats.tick_prefill_tokens.append(
            self.stats.prefill_tokens - pf0)
        self.stats.tick_key_lanes.append(
            self.stats.prefill_key_lanes - kl0)
        wall = time.perf_counter() - t_tick
        self.stats.tick_s.append(wall)
        self.stats.record_tick_wall(wall)
        return len(active)

    def step_fused(self) -> int:
        """Fused path: up to ``tick_tokens`` decode steps per host sync.
        With ``chunked_prefill`` the tick additionally packs prefill chunks
        under the token budget (see ``_tick_chunked``)."""
        with span("engine.tick", self.stats.spans):
            if self.scheduler is not None:
                return self._tick_chunked()
            return self._tick_admit_stall()

    def _tick_admit_stall(self) -> int:
        t_tick = time.perf_counter()
        pf0 = self.stats.prefill_tokens
        kl0 = self.stats.prefill_key_lanes
        self._admit()
        emitted = self._decode_tick(self.tick_tokens)
        self.stats.tick_prefill_tokens.append(
            self.stats.prefill_tokens - pf0)
        self.stats.tick_key_lanes.append(
            self.stats.prefill_key_lanes - kl0)
        wall = time.perf_counter() - t_tick
        self.stats.tick_s.append(wall)
        self.stats.record_tick_wall(wall)
        return emitted

    def _decode_tick(self, max_steps: int) -> int:
        """The fused decode stage of one tick: up to ``max_steps`` (<= the
        compiled ``tick_tokens`` bound) device steps, one host sync. In
        scheduler mode ``max_steps`` is the planned per-slot token cap —
        with ``spec_decode`` it bounds *accepted* tokens, not passes, so
        the scheduler's token-budget accounting holds unchanged (a verify
        pass that would overshoot the cap has its emit clamped)."""
        active = [s for s in range(self.n_slots) if self.slots[s] is not None]
        if not active:
            return 0
        rec = self.stats.spans
        with span("engine.decode", rec):
            pt = None
            if self.paged:
                self._ensure_pages(min(max_steps, self.tick_tokens),
                                   extra=self.spec_k - 1 if self.spec_decode
                                   else 0)
                pt = self._page_table_device()
                # growth may have preempted a slot under pool pressure
                active = [s for s in range(self.n_slots)
                          if self.slots[s] is not None]
                if not active:
                    return 0
            if self.spec_decode:
                outs, bounds = self._dispatch_spec_tick(max_steps, active, pt)
            else:
                done0 = np.asarray([self.slots[s] is None
                                    for s in range(self.n_slots)])
                index0 = self._decode_index()
                (tokens, self.caches, index, budget, done, self.key, out,
                 n_emit, steps) = self._tick(
                    self.params, jnp.asarray(self.tokens), self.caches,
                    jnp.asarray(index0), jnp.asarray(self.budget),
                    jnp.asarray(done0), self.key,
                    jnp.asarray(max_steps, jnp.int32), pt)
                outs = (out, n_emit, index, budget, done, tokens, steps)
        with span("engine.readback", rec):
            got = jax.device_get(outs)
        with span("engine.emit", rec):
            if self.spec_decode:
                return self._emit_spec_tick(got, bounds, active)
            out_h, n_emit_h, idx_h, bud_h, done_h, tok_h, steps_h = got
            self.stats.decode_syncs += 1
            self.stats.ticks += 1
            self.stats.device_steps += int(steps_h)
            self._count_kv_pages(index0, n_emit_h, int(steps_h))
            return self._emit(active, out_h, n_emit_h, idx_h, bud_h, done_h,
                              tok_h)

    def _decode_index(self) -> np.ndarray:
        """Decode positions for the device: an idle slot's row attends
        position 0 alone (its cache row sinks into the null page), so the
        paged kernel visits one page for it, not its last request's."""
        return np.where([r is None for r in self.slots], 0,
                        self.index).astype(np.int32)

    def _count_kv_pages(self, index0, n_emit, steps: int):
        """Add one decode stage's paged-kernel pages to the stats: slot s
        sits at ``index0[s] + min(j, n_emit[s])`` in step j (a slot that
        finished stays where it stopped)."""
        if not self.paged or not steps:
            return
        ps, npg = self.pool.page_size, self.pool.pages_per_slot
        pos = np.asarray(index0)[:, None] + np.minimum(
            np.arange(steps)[None], np.asarray(n_emit)[:, None])
        pattern = self.cfg.window_pattern
        for w in pattern:
            self.stats.decode_kv_pages += int(
                live_pages(pos, ps, w, xp=np)[1].sum())
        self.stats.decode_kv_pages_full += (steps * self.n_slots * npg
                                            * len(pattern))

    def _emit(self, active: List[int], out_h, n_emit_h, idx_h, bud_h,
              done_h, tok_h) -> int:
        """Hand a tick's read-back tokens to their requests and finish the
        slots that are done; returns the tokens emitted."""
        now = time.perf_counter()
        self.index = np.array(idx_h, np.int32)
        self.budget = np.array(bud_h, np.int32)
        self.tokens = np.array(tok_h, np.int32)
        emitted = 0
        for s in active:
            req = self.slots[s]
            k = int(n_emit_h[s])
            req.out_tokens.extend(int(t) for t in out_h[s, :k])
            emitted += k
            if k:
                self._last_active[s] = now
            if done_h[s]:
                self._finish_slot(s, now)
        self.stats.tokens_decoded += emitted
        return emitted

    def _dispatch_spec_tick(self, max_steps: int, active: List[int], pt):
        """Speculative decode stage: draft -> verify -> accept rounds on
        device (``_fused_spec_tick``), one host sync. Called by
        ``_decode_tick`` after page growth reserved ``spec_k - 1`` extra
        write rows per slot; returns the tick's device outputs and each
        live slot's verify key bound."""
        K = self.spec_k
        cap = int(min(max_steps, self.tick_tokens))
        idx0 = self.index.copy()
        done0 = np.asarray([self.slots[s] is None
                            for s in range(self.n_slots)])
        # per-slot static verify bounds (satellite: per-slot live bounds in
        # chunk dispatch): each slot's deepest verify row this tick is
        # index + cap + K - 2, so its banded key bound is independent of
        # the batch's oldest slot. The *dispatch* uses the collapsed max —
        # a per-slot tuple as a static jit argument would retrace per age
        # mix — while the per-slot bounds drive the key-lane accounting,
        # which is what the mixed-age over-attend ratio is measured from.
        bounds = {s: band_len(min(int(idx0[s]) + cap + K - 1, self.max_seq),
                              self.opts.prefill_band, self.max_seq)
                  for s in active}
        live_len = max(bounds.values())
        (tokens, self.caches, index, budget, done, out, e, hist, passes,
         iters) = self._spec_tick(
            live_len, self.params, self.draft_params,
            jnp.asarray(self.tokens), self.caches, jnp.asarray(self.index),
            jnp.asarray(self.budget), jnp.asarray(done0),
            jnp.asarray(max_steps, jnp.int32), pt)
        return ((out, e, index, budget, done, tokens, hist, passes, iters),
                bounds)

    def _emit_spec_tick(self, got, bounds: Dict[int, int],
                        active: List[int]) -> int:
        """The host half of a speculative tick: accounting of its verify
        passes and accepted drafts, then the tokens to their requests."""
        (out_h, e_h, idx_h, bud_h, done_h, tok_h, hist_h, passes_h,
         iters_h) = got
        st = self.stats
        K = self.spec_k
        st.decode_syncs += 1
        st.ticks += 1
        # one loop round == one full-model pass (the verify chunk), same
        # HBM-pass denomination as the plain fused tick's per-token steps
        st.device_steps += int(iters_h)
        vp = int(passes_h.sum())
        st.spec_verify_passes += vp
        st.spec_draft_steps += vp * (K - 1)
        st.spec_draft_pass_equiv += (vp * (K - 1) * self.draft_layers
                                     / max(1, self.cfg.num_layers))
        if len(st.spec_accept_hist) < K + 1:
            st.spec_accept_hist.extend(
                [0] * (K + 1 - len(st.spec_accept_hist)))
        for n, c in enumerate(hist_h):
            st.spec_accept_hist[n] += int(c)
        for s in active:
            st.spec_key_lanes += int(passes_h[s]) * K * bounds[s]
            st.spec_key_lanes_full += int(passes_h[s]) * K * self.max_seq
        return self._emit(active, out_h, e_h, idx_h, bud_h, done_h, tok_h)

    # -- chunked-prefill scheduler mode ------------------------------------
    def _reset_fresh_scales(self, fresh: List[int]):
        """Quantized pools: zero the scale rows of pages just handed to a
        slot, so the monotone-amax write policy starts clean instead of
        inheriting a dead request's range (history-independence)."""
        if not fresh or kv_quant.quant_dtype(self.kv_dtype) is None:
            return
        width = self.pool.pages_per_slot * self.n_slots
        ids = np.zeros(width, np.int32)     # 0-pads hit the null page
        ids[:len(fresh)] = fresh
        self.caches = self._reset_scales_fn(self.caches, jnp.asarray(ids))

    def _admit_chunked(self):
        """Admission in scheduler mode: assign waiting requests to free
        slots as *prefill tasks* (no prompt compute yet — chunks run under
        the tick budget). Paged pools allocate chunk-granularly: shared
        prefix pages plus the first chunk's pages now, the rest as chunks
        arrive (``ensure``), so a long prompt doesn't lock down its whole
        footprint before producing a single token. On a prefix-cache hit
        chunking starts at the first non-shared token — capped one position
        before the prompt end so the last-token logits are always computed —
        and the skipped positions are never recomputed."""
        sched = self.scheduler
        for s in range(self.n_slots):
            # inner loop: an eviction requeues its victim at the *front* of
            # the waiting queue, so the head must be re-read before this
            # slot admits (popping a stale head would drop the victim and
            # double-admit the request behind it)
            while (sched.waiting and self.slots[s] is None
                   and s not in sched.tasks):
                req = sched.waiting[0]
                with span("engine.admit", self.stats.spans, uid=req.uid):
                    if not self._admit_task(req, s):
                        return

    def _admit_task(self, req: Request, s: int) -> bool:
        """One admission attempt of the waiting queue's head ``req`` into
        slot ``s`` as a prefill task; False when admission has to stop
        for this tick (the pool defers it)."""
        sched = self.scheduler
        n_prefix = (self.cfg.vision.num_tokens
                    if req.patches is not None and self._vision
                    else 0)
        total = n_prefix + len(req.prompt)
        if total > self.max_seq:
            raise ValueError(
                f"request {req.uid}: prompt ({total} positions) "
                f"exceeds max_seq {self.max_seq}")
        n_skip = 0
        keys: List[bytes] = []
        if self.paged:
            keys = self._prefix_page_keys(req, n_prefix)
            n_hit = self.pool.match_prefix(keys)
            # never skip the final position: its logits seed decode
            skip_pages = min(n_hit, (total - 1) // self.page_size)
            n_skip = skip_pages * self.page_size
            first_len = min(total, n_skip + self.chunk_size)
            need_total = min(
                total + (0 if req.max_tokens <= 1 else 1),
                self.max_seq)
            # structural sizing check (drain limit: everything else
            # eventually finishes and frees its pages, but the
            # request's own holdings — shared hits included — still
            # occupy capacity). Two ways a request can never
            # complete, each a raise-now instead of stall-forever:
            # absolute capacity must cover prompt + the first
            # decode page, and — when any prefill page must be
            # freshly allocated — the whole prompt footprint must
            # fit the admission side, which cannot touch the decode
            # headroom reserve (the decode page itself may).
            usable = self.pool.num_pages - 1
            prefill_pages = self.pool.num_pages_for(total)
            if (self.pool.num_pages_for(need_total) > usable
                    or (prefill_pages - n_hit > 0 and prefill_pages
                        > usable - self.pool.reserve)):
                raise PoolExhausted(
                    f"KV pool ({usable} pages, {self.pool.reserve} "
                    f"reserved) too small for request {req.uid} "
                    f"({prefill_pages} prompt pages, "
                    f"{n_hit} prefix-shared)")
            if not self.pool.can_admit(first_len, keys):
                in_flight = any(
                    self.slots[v] is not None or v in sched.tasks
                    for v in range(self.n_slots))
                if not in_flight:
                    raise PoolExhausted(
                        f"KV pool cannot admit request {req.uid} "
                        f"with nothing in flight to free pages")
                # pool-aware policy: evict the longest-idle stalled
                # task and re-evaluate with the (possibly new) queue
                # head; with no stalled victim, defer — something
                # in flight is progressing and will free pages
                if not self._evict_longest_idle():
                    return False
                return True
            try:
                pages, n_shared = self.pool.admit(s, first_len, keys,
                                                  register=False)
                # recomputed positions may land in shared pages when
                # the skip cap pulled below the hit run: COW them
                copies = self.pool.prepare_write(s, n_skip, total)
            except PoolExhausted:
                self.pool.free_slot(s)
                return False
            req.pages_shared = n_shared
            self._reset_fresh_scales(list(pages[n_shared:])
                                     + [d for _, d in copies])
            self._dispatch_copies(copies)
            self._update_cache_stats()
        sched.waiting.pop(0)
        rec = self.stats.spans
        req.queue_s = time.perf_counter() - req.t_submit
        self.stats.queue_s.append(req.queue_s)
        batch = {"tokens": jnp.asarray(req.prompt[None, :])}
        if n_prefix:
            if n_skip < n_prefix:
                with span("engine.vision", rec, uid=req.uid):
                    prefix = self._vision(self.params,
                                          jnp.asarray(req.patches[None]))
                    prefix.block_until_ready()
            else:
                # the whole vision prefix is prefix-cache shared: its KV
                # already sits in pool pages, so the tower run itself is
                # skipped (chunks never read these embedding rows)
                prefix = jnp.zeros((1, n_prefix, self.cfg.d_model),
                                   self.cache_dtype)
            batch["prefix"] = prefix
        t0 = time.perf_counter()
        with span("engine.embed", rec, uid=req.uid):
            embeds = M.embed_prompt(self.cfg, self.opts, self.params, batch)
        cache1 = None if self.paged else self._fresh_cache1()
        req.prefill_skipped = n_skip
        self.stats.prefill_skipped += n_skip
        sched.start_task(PrefillTask(req=req, slot=s, total=total,
                                     n_skip=n_skip, embeds=embeds,
                                     cache1=cache1, prefix_keys=keys,
                                     t_start=t0))
        self._last_active[s] = t0
        return True

    def _run_chunk(self, cp: ChunkPlan):
        """Execute one planned prefill chunk: grow pages to cover it (paged),
        pad the embedding slice to the static chunk shape, dispatch the
        jitted positioned prefill, and — on the final chunk — sample the
        request's first token and flip the slot to decoding."""
        task, s = cp.task, cp.task.slot
        if self.paged:
            end = cp.start + cp.n_tok
            held0 = set(self.pool.slot_pages[s])
            stalled = False
            try:
                self.pool.ensure(s, end, use_reserve=False)
            except PoolExhausted:
                # admission-side growth must not eat decode headroom: mark
                # the task stalled, try evicting another (longest-idle)
                # stalled task, else wait — in-flight decoders/prefills
                # free pages by finishing, and the stalled task retries
                # (deprioritized) every tick
                task.stalled = True
                stalled = True
                if self._evict_longest_idle(exclude=s):
                    try:
                        self.pool.ensure(s, end, use_reserve=False)
                        stalled = False
                    except PoolExhausted:
                        pass
            # scale-reset by diff against entry, not ensure()'s return: a
            # raising ensure() keeps its partial growth on the slot, and
            # those pages must lose their previous owner's scale rows even
            # on the stall path (the next tick's retry won't see them as
            # fresh again) — same invariant as _ensure_pages
            self._reset_fresh_scales(sorted(
                p for p in self.pool.slot_pages[s] if p not in held0))
            if stalled:
                return
            pt_row = jnp.asarray(self.pool.page_table[s:s + 1])
        emb = task.embeds
        chunk = jnp.zeros((1, self.chunk_size, emb.shape[-1]), emb.dtype)
        chunk = chunk.at[:, :cp.n_tok].set(
            emb[:, cp.start:cp.start + cp.n_tok])
        start = jnp.asarray(cp.start, jnp.int32)
        n_valid = jnp.asarray(cp.n_tok, jnp.int32)
        # banded key-axis bound: the chunk attends the live prefix
        # [0, start + n_tok) rounded up to whole bands — a static jit arg
        # with at most max_seq / prefill_band distinct values, vs the old
        # full-max_seq cache view every chunk paid for
        live = band_len(cp.start + cp.n_tok, self.opts.prefill_band,
                        self.max_seq)
        if self.paged:
            logits, self.caches = self._prefill_chunk(
                self.params, chunk, self.caches, start, n_valid, pt_row,
                live)
            self.pool.register_prefix_pages(s, task.prefix_keys or (),
                                            cp.start + cp.n_tok)
            self._update_cache_stats()
        else:
            logits, task.cache1 = self._prefill_chunk(
                self.params, chunk, task.cache1, start, n_valid, live)
        self.stats.prefill_key_lanes += self.chunk_size * live
        self.stats.prefill_key_lanes_full += self.chunk_size * self.max_seq
        task.pos = cp.start + cp.n_tok
        task.stalled = False
        self.stats.prefill_tokens += cp.n_tok
        self._last_active[s] = time.perf_counter()
        if task.pos >= task.total:
            self._finish_prefill(task, logits)

    def _finish_prefill(self, task: PrefillTask, logits):
        """Last chunk done: sample the first token (TTFT boundary) from the
        chunk's last-valid-position logits [B,1,V] and either finish the
        request outright (EOS / max_tokens<=1 / no cache headroom) or hand
        the slot to the decode stage."""
        req, s = task.req, task.slot
        pos = task.total
        with span("engine.first_token", self.stats.spans, uid=req.uid):
            tok = int(self._sample_host(logits)[0])
        now = time.perf_counter()
        req.t_prefill = now
        req.ttft_s = now - req.t_submit
        self.stats.ttft_s.append(req.ttft_s)
        req.out_tokens.append(tok)
        budget = self._clamped_budget(req, pos)
        self.scheduler.finish_task(s)
        if tok == self.eos or req.max_tokens <= 1 or budget <= 0:
            req.done = True
            req.t_done = now
            self.stats.record_deadline(req)
            if self.paged:
                req.pages_used = len(self.pool.slot_pages[s])
                self.pool.free_slot(s)
                self._update_cache_stats()
            self.finished.append(req)
            return
        if self.paged:
            req.pages_used = len(self.pool.slot_pages[s])
        else:
            self.caches = self._scatter_slot_fn(self.caches, task.cache1, s,
                                                False)
            task.cache1 = None
        self.index[s] = pos
        self.budget[s] = budget
        self.tokens[s, 0] = tok
        self.slots[s] = req
        self._last_active[s] = now

    def _tick_chunked(self) -> int:
        """One scheduler tick: admit waiting requests into prefill tasks,
        pack chunks + decode under the token budget, run the chunks, then
        the (budget-capped) fused decode stage. See docs/scheduler.md for
        the tick anatomy.

        Stage order and the invariants each stage hands the next:

        1. **Admit** (``_admit_chunked``): every free slot without a task
           gets one, pages for shared prefix + first chunk allocated. After
           this, ``scheduler.tasks`` names exactly the mid-prefill slots.
        2. **Plan** (``ChunkedScheduler.plan_tick``): pure policy over host
           state — decode reservation first
           (``decode_steps = clamp(budget // n_active, 1, tick_tokens)``),
           then FCFS chunks into the remainder. ``n_active`` is read
           *before* chunks run, so a prefill finishing mid-tick joins this
           same tick's decode stage without shrinking anyone's reservation.
        3. **Chunks** (``_run_chunk`` per plan entry): each entry is
           validated against live state first — the task may have been
           preempted/finished by an earlier entry this tick, or an earlier
           chunk of the same task may have stalled on pool pressure
           (``cp.task.pos != cp.start`` — positions must be written in
           order, so the successor chunk is dropped and replanned next
           tick rather than leaving a hole in the cache).
        4. **Decode** (``_decode_tick(plan.decode_steps)``): the fused tick
           capped at the planned depth — a dynamic operand, so the budget
           never recompiles the loop.

        Per-tick stats appended here (``tick_prefill_tokens``,
        ``tick_key_lanes``, ``tick_s``) are the head-of-line metrics the
        scheduler bench gates on: no tick's prefill may exceed the token
        budget."""
        t_tick = time.perf_counter()
        pf0 = self.stats.prefill_tokens
        kl0 = self.stats.prefill_key_lanes
        sched = self.scheduler
        self._admit_chunked()
        n_active = sum(r is not None for r in self.slots)
        with span("engine.plan", self.stats.spans):
            slo = None
            if self._slo is not None:
                # the deadline check: remaining work + slack per
                # realtime decoding slot, plus whether realtime prefill is
                # still in the pipe, against the measured tick EWMA (see
                # SLOController)
                rt_decode = [(int(self.budget[s]),
                              req_deadline(self.slots[s]))
                             for s in range(self.n_slots)
                             if self.slots[s] is not None
                             and is_realtime(self.slots[s])]
                rt_prefill = (any(is_realtime(t.req)
                                  for t in sched.tasks.values())
                              or any(is_realtime(r) for r in sched.waiting))
                slo = self._slo.plan(t_tick, self.stats.tick_ewma_s,
                                     rt_decode, rt_prefill)
            plan = sched.plan_tick(n_active, self.tick_tokens, slo=slo)
        for cp in plan.chunks:
            if sched.tasks.get(cp.task.slot) is not cp.task:
                continue    # finished or preempted earlier this tick
            if cp.task.pos != cp.start:
                continue    # an earlier chunk of this task stalled
            with span("engine.chunk", self.stats.spans, uid=cp.task.req.uid,
                      start=cp.start):
                self._run_chunk(cp)
        emitted = 0
        if n_active:
            emitted = self._decode_tick(plan.decode_steps)
        elif plan.chunks:
            self.stats.ticks += 1
        self.stats.tick_prefill_tokens.append(
            self.stats.prefill_tokens - pf0)
        self.stats.tick_key_lanes.append(
            self.stats.prefill_key_lanes - kl0)
        wall = time.perf_counter() - t_tick
        self.stats.tick_s.append(wall)
        self.stats.record_tick_wall(wall)
        return emitted

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        """Drive ticks until the queue and slots drain, or ``max_ticks`` is
        hit. A hit tick budget is surfaced (warning + ``pending`` count)
        rather than silently returning partial work."""
        step = self.step_fused if self.fused else self.step
        ticks = 0
        while self.pending and ticks < max_ticks:
            step()
            ticks += 1
        if self.pending:
            queued = (len(self.scheduler.waiting) if self.scheduler
                      else len(self.queue))
            # Surface the phase/queue/TTFT decomposition alongside the count:
            # a stalled fleet is diagnosed from this one line (growing
            # queue_p99 with flat decode percentiles = admission starvation;
            # the reverse = the decode path itself slowed down).
            ph = self.stats.phase_report()
            diag = (f"phases vision={ph['vision']:.3f}s "
                    f"prefill={ph['prefill']:.3f}s "
                    f"decode={ph['decode']:.3f}s")
            for k in ("queue_p50", "queue_p99", "ttft_p50", "ttft_p99",
                      "decode_tick_p99"):
                if k in ph:
                    diag += f"; {k}={ph[k]:.4f}s"
            warnings.warn(
                f"ServingEngine.run: tick budget ({max_ticks}) exhausted "
                f"with {self.pending} requests pending "
                f"({queued} queued, "
                f"{sum(r is not None for r in self.slots)} in flight; "
                f"{diag})",
                RuntimeWarning, stacklevel=2)
        return self.finished


def _serving_specs(cfg: ModelConfig, mesh: Mesh):
    """The ``serving_rules`` table of a ``model=N`` serving engine, and a
    function mapping a param or cache template to its PartitionSpec tree
    under those rules."""
    rules = serving_rules(mesh.shape["model"], cfg.num_heads,
                          cfg.num_kv_heads)

    def specs_of(template):
        return jax.tree_util.tree_map(
            lambda s: spec_for(s.shape, s.axes, mesh, rules), template,
            is_leaf=is_pspec)
    return rules, specs_of


def serving_param_specs(cfg: ModelConfig, mesh: Mesh):
    """PartitionSpec tree of a ``model=N`` serving engine's params: the
    ``serving_rules`` layout, with the towers (vision / encoder / action
    head) whole on every shard — they run as plain einsum stacks with no
    collective insertion. ``init_params`` takes these as its output
    shardings, so each device only ever holds its own shard."""
    templ = M.model_template(cfg)
    pspecs = _serving_specs(cfg, mesh)[1](templ)
    for k in ("vision", "encoder", "action_dit"):
        if k in pspecs:
            pspecs[k] = jax.tree_util.tree_map(lambda s: P(), templ[k],
                                               is_leaf=is_pspec)
    return pspecs


def _path_keys(path):
    """Pytree path -> hashable tuple of dict keys (for cross-tree lookups:
    a quantized paged cache has scale leaves the dense prefill cache lacks,
    so the two trees cannot be tree_map'd jointly)."""
    return tuple(getattr(p, "key", p) for p in path)


def _scatter_slot(caches, cache1, slot: int, skip_paged: bool = False):
    """Copy a batch-1 prefill cache into slot `slot` of the slot caches.
    The batch axis of every leaf comes from the cache pytree's explicit
    annotation (stacks.cache_batch_axis): block caches are layer-stacked, so
    batch sits at axis 1; tail caches carry it at axis 0. With
    ``skip_paged`` the pool-layout leaves (attention k/v and their scale
    siblings) are left untouched — they are filled by ``_scatter_pages``.
    Leaves are matched across the two trees by path key, because the
    quantized slot cache carries scale leaves the dense prefill cache
    doesn't have."""
    flat1 = {_path_keys(p): leaf for p, leaf
             in jax.tree_util.tree_leaves_with_path(cache1)}

    def scatter(path, big):
        if skip_paged and is_paged_leaf(path):
            return big
        small = flat1[_path_keys(path)]
        axis = cache_batch_axis(path)
        assert small.shape[axis] == 1, (path, small.shape, axis)
        idx = [slice(None)] * big.ndim
        idx[axis] = slice(slot, slot + 1)
        return big.at[tuple(idx)].set(small.astype(big.dtype))
    return jax.tree_util.tree_map_with_path(scatter, caches)


def _scatter_pages_impl(caches, cache1, dest_pages, page_size: int):
    """Scatter a batch-1 dense prefill cache into pool pages, quantizing on
    the way in when the pool stores int8/fp8 codes.

    ``dest_pages`` [pages_per_slot] int32 holds the physical destination for
    each prompt page; entries routed to 0 (the null page) are write sinks —
    used both for prefix-shared pages (already holding identical KV) and for
    pages past the slot's allocation.

    Quantized pools: each prompt page's scale is its amax / qmax at the
    pool's granularity — per (page, KV head) or per token row, inferred
    from the scale leaf's shape — computed from the fp32 prefill KV,
    written to the sibling ``k_scale``/``v_scale`` leaf for the same
    destination pages, and used to encode the value rows. Decode writes
    into the tail page later grow a "head" scale monotonically, or replace
    a "token" row outright (see layers.update_cache_paged)."""
    flat_big, treedef = jax.tree_util.tree_flatten_with_path(caches)
    big_by_key = {_path_keys(p): leaf for p, leaf in flat_big}
    flat1 = {_path_keys(p): leaf for p, leaf
             in jax.tree_util.tree_leaves_with_path(cache1)}

    def page_rows(keys, stacked):
        """Dense prefill leaf -> page-major rows [(nb,) P, K, ps, h]."""
        small = flat1[keys]
        if not stacked:               # tail: [1, K, S, h]
            small = small[None]
        nb, _, K, seq, h = small.shape    # blocks: [nb, 1, K, S, h]
        rows = small.reshape(nb, K, seq // page_size, page_size, h)
        rows = rows.swapaxes(1, 2)
        return rows if stacked else rows[0]

    out = []
    for path, big in flat_big:
        if not is_paged_leaf(path):
            out.append(big)
            continue
        keys = _path_keys(path)
        stacked = cache_batch_axis(path) == 1
        # scale and value leaves both derive from one quantize_page_rows
        # call on the same dense rows (XLA CSEs the duplicate), so the
        # stored scales can never diverge from the scales the codes were
        # encoded under
        if is_scale_leaf(path):
            vkey = keys[:-1] + ("k" if keys[-1] == "k_scale" else "v",)
            gran = ("token" if big.ndim == (4 if stacked else 3)
                    else "head")       # leaf shape encodes the granularity
            _, scale = kv_quant.quantize_page_rows(page_rows(vkey, stacked),
                                                   big_by_key[vkey].dtype,
                                                   gran)
            out.append(big.at[:, dest_pages].set(scale) if stacked
                       else big.at[dest_pages].set(scale))
            continue
        rows = page_rows(keys, stacked)
        if kv_quant.is_quantized(big.dtype):
            sc = big_by_key[keys[:-1] + (keys[-1] + "_scale",)]
            gran = "token" if sc.ndim == (4 if stacked else 3) else "head"
            rows, _ = kv_quant.quantize_page_rows(rows, big.dtype, gran)
        out.append(big.at[:, dest_pages].set(rows.astype(big.dtype))
                   if stacked else
                   big.at[dest_pages].set(rows.astype(big.dtype)))
    return jax.tree_util.tree_unflatten(treedef, out)


# jitted single-device entry points; the sharded engine wraps the raw impls
# in shard_map instead (per-shard bodies are unchanged — the K axis of every
# paged leaf is untouched by page scatter/copy/reset)
_scatter_pages = functools.partial(
    jax.jit, static_argnames=("page_size",),
    donate_argnums=0)(_scatter_pages_impl)


def _reset_page_scales_impl(caches, page_ids):
    """Zero the quantization-scale rows of ``page_ids`` (padded with 0 — the
    null page, harmless to reset). Run on pages entering a slot via decode
    growth, whose previous owner's scale rows would otherwise leak into the
    monotone-amax write policy and make quantization history-dependent."""
    def reset(path, big):
        if not is_scale_leaf(path):
            return big
        if cache_batch_axis(path) == 1:   # blocks: [nb, P, K]
            return big.at[:, page_ids].set(0.0)
        return big.at[page_ids].set(0.0)
    return jax.tree_util.tree_map_with_path(reset, caches)


_reset_page_scales = functools.partial(
    jax.jit, donate_argnums=0)(_reset_page_scales_impl)


def _copy_pages_impl(caches, src_pages, dst_pages):
    """Device-side page copies for copy-on-write: page dst <- page src for
    every pair (padding pairs are 0 -> 0, a null-page no-op)."""
    def copy(path, big):
        if not is_paged_leaf(path):
            return big
        if cache_batch_axis(path) == 1:   # blocks: [nb, P, K, ps, h]
            return big.at[:, dst_pages].set(big[:, src_pages])
        return big.at[dst_pages].set(big[src_pages])
    return jax.tree_util.tree_map_with_path(copy, caches)


_copy_pages = functools.partial(
    jax.jit, donate_argnums=0)(_copy_pages_impl)
