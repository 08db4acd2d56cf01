"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix, each
a file of its own under ``bench/``. The run draws the weights and the
fleet's inputs from ``--seed``, builds one ``ServingEngine`` the way
``repro.launch.serve`` builds it (over a ``model=N`` mesh of the cell's
chips where the configuration's ``mesh_model`` is N > 1), puts an
``AsyncFrontend`` in front of it, and lets a closed-loop robot fleet drive
it. Set-up (loading, compiling,
warming every shape the window uses, and the fleet's first steps) ends when
every robot has finished a step; the window then runs ``--seconds`` and
ends on a step completion. After it, the steps still in flight finish, the
device's peak memory is read, the engine is freed, and a plain reference
judges a seeded sample of the served answers. ``--trace 1`` records a
profiler trace of the window and reports the cell's per-layer metrics
instead of its end-to-end ones.

The run fails, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. ``--rehearse`` runs the same control flow on any
platform at the registry's reduced size with two robots; it prints counts
and checks but no metric.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

from harness import spec  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


# -- set-up ------------------------------------------------------------------

def sizes_of(pcfg) -> dict:
    """A configuration dict with the sizes of a program config (the
    rehearsal's reduced models, which have no file of their own)."""
    v = pcfg.vision
    return dict(
        name=pcfg.name, num_layers=pcfg.num_layers, d_model=pcfg.d_model,
        num_heads=pcfg.num_heads, num_kv_heads=pcfg.num_kv_heads,
        head_dim=pcfg.head_dim, d_ff=pcfg.d_ff, vocab_size=pcfg.vocab_size,
        qkv_bias=pcfg.qkv_bias, tie_embeddings=pcfg.tie_embeddings,
        rope_theta=pcfg.rope_theta, norm_eps=pcfg.norm_eps,
        vision=dict(num_layers=v.num_layers, d_model=v.d_model,
                    num_heads=v.num_heads, d_ff=v.d_ff,
                    num_tokens=v.num_tokens, embed_dim=v.embed_dim,
                    ln_eps=1e-6))


def program_config(c: dict, rehearse: bool):
    """The program's config for configuration file ``c``; every size the
    file states must be the program's."""
    from repro.configs import get_config
    pcfg = get_config(c["model"])
    if rehearse:
        pcfg = pcfg.reduced()
        return pcfg, dict(c, **sizes_of(pcfg))
    pcfg = dataclasses.replace(pcfg, num_layers=c["num_layers"])
    got = sizes_of(pcfg)
    for k, v in got.items():
        if k in ("name",):
            continue
        want = c[k] if k != "vision" else {
            kk: c["vision"][kk] for kk in v}
        if want != v:
            raise ValueError(f"{c['name']}: {k} is {want} in the file but "
                             f"{v} in the program's config")
    if pcfg.vision is None or pcfg.encoder is not None:
        raise ValueError(f"{c['name']}: not a vision-language decoder")
    return pcfg, c


def check_tree(W, pcfg):
    """The benchmark's weights have the program's tree and shapes."""
    import jax
    from repro.models import model as M
    from repro.models.params import is_pspec
    tmpl = M.model_template(pcfg)
    want = jax.tree.map(lambda s: tuple(s.shape), tmpl, is_leaf=is_pspec)
    got = jax.tree.map(lambda x: tuple(x.shape), W)
    if jax.tree.structure(want, is_leaf=lambda x: isinstance(x, tuple)) != \
            jax.tree.structure(got, is_leaf=lambda x: isinstance(x, tuple)) \
            or jax.tree.leaves(want, is_leaf=lambda x: isinstance(x, tuple)) \
            != jax.tree.leaves(got, is_leaf=lambda x: isinstance(x, tuple)):
        raise ValueError("the benchmark's weight tree is not the program's")


def serving_mesh(c: dict):
    """The ``model=N`` mesh of a configuration whose ``mesh_model`` is N > 1
    (as ``repro.launch.serve --mesh-model N`` makes it), else None."""
    n = int(c.get("mesh_model", 1))
    if n == 1:
        return None
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(n)


def make_weights(pcfg, c: dict, seed: int, mesh):
    """The configuration's weights from the seed, laid out as the engine
    serves them: whole on one chip, or each chip's shard of the serving
    layout on a mesh."""
    from harness import weights
    shardings = None
    if mesh is not None:
        import jax
        from jax.sharding import NamedSharding
        from repro.serving.engine import serving_param_specs
        shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                 serving_param_specs(pcfg, mesh))
    return weights.make(c, seed, shardings=shardings)


def build_engine(pcfg, c, W, slots: int, mesh=None):
    from repro.launch.serve import build_engine as serve_engine
    from repro.launch.serve import build_parser
    args = build_parser().parse_args(list(c["engine_flags"])
                                     + ["--slots", str(slots)])
    return serve_engine(pcfg, W, args, mesh)


def warm_shapes(eng, prompt: int, d_model: int, patches):
    """Compile every program and eager shape a chunk of a ``prompt``-long
    prompt can take: the chunk program at each banded key bound, and the
    engine's per-chunk slicing of the prompt embeddings
    (``ServingEngine._run_chunk``) at every start and length the token
    budget allows (multiples of gcd(budget, tick tokens)). Writes go to the
    pool's null page."""
    import jax
    import jax.numpy as jnp
    vision, chunk, _ = eng.stage_programs()
    dt = eng.cache_dtype
    C, band, ms = eng.chunk_size, eng.opts.prefill_band, eng.max_seq
    jax.block_until_ready(vision(eng.params, jnp.asarray(patches[None])))
    x = jnp.zeros((1, C, d_model), dt)
    pt = jnp.zeros((1, ms // eng.page_size), jnp.int32)
    lives = sorted({min(-(-e // band) * band, ms)
                    for e in range(1, prompt + 1)})
    for live in lives:
        _, eng.caches = chunk(eng.params, x, eng.caches,
                              jnp.asarray(0, jnp.int32),
                              jnp.asarray(1, jnp.int32), pt, live)
    g = math.gcd(eng.token_budget, eng.tick_tokens)
    emb = jnp.zeros((1, prompt, d_model), dt)
    n_slices = 0
    for n in range(g, min(C, prompt) + 1, g):
        for s in range(0, prompt - n + 1, g):
            piece = emb[:, s:s + n]
            n_slices += 1
        jnp.zeros((1, C, d_model), dt).at[:, :n].set(piece)
    jax.block_until_ready(eng.caches)
    return len(lives), n_slices


def warm_engine(eng, fleet):
    """One request through the engine itself before the fleet starts, so
    that every program of its path (the fused tick among them) is compiled
    or loaded before the first robot is timed: a compile inside the first
    fleet cycle would shift the robots' phases, and the window's."""
    from repro.serving import Request
    eng.submit(Request(uid=-1, prompt=fleet.instructions[0],
                       max_tokens=eng.tick_tokens + 1,
                       patches=fleet.frame()))
    if len(eng.run()) != 1:
        raise RuntimeError("the warm-up request did not finish")


# -- the window --------------------------------------------------------------

@dataclasses.dataclass
class Window:
    fleet: object
    open: float
    close: float
    counters: dict          # engine counters over the window
    compiles: int           # compilations inside the window
    cut: int                # steps in flight at the close, not judged
    trace: object = None    # harness.trace.Trace of the window
    trace_span: tuple = None  # the window on the trace's clock
    peak_bytes: int = 0


def _counters(eng):
    st = eng.stats
    return dict(tokens_decoded=st.tokens_decoded,
                device_steps=st.device_steps,
                prefill_tokens=st.prefill_tokens, ticks=st.ticks)


def serve_window(eng, fleet, seconds: float, traced: bool, compiles,
                 trace_dir: Path) -> Window:
    import jax
    from repro.serving import AsyncFrontend
    marks, ann = {}, []

    def on_open():
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans only, no calls
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            ann.append(jax.profiler.TraceAnnotation("bench.window"))
            ann[0].__enter__()
        marks["open"] = (time.perf_counter(), _counters(eng), compiles.count)

    def on_close():
        marks["close"] = (time.perf_counter(), _counters(eng),
                          compiles.count)
        if traced:
            ann[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    async def drive():
        async with AsyncFrontend([eng], queue_limit=max(64, fleet.robots),
                                 offload_ticks=False) as fe:
            return await fleet.run(fe, seconds, on_open, on_close,
                                   time.perf_counter())

    cut = asyncio.run(drive())
    (_, c0, k0), (_, c1, k1) = marks["open"], marks["close"]
    w = Window(fleet, fleet.window_open, fleet.window_close,
               {k: c1[k] - c0[k] for k in c0}, k1 - k0, cut)
    if traced:
        from harness import trace as T
        w.trace = T.load(T.xplane_file(str(trace_dir)))
        spans = [e for e in w.trace.host if e.name == "bench.window"]
        if not spans:
            raise RuntimeError("the trace holds no bench.window span")
        w.trace_span = (spans[0].start, spans[0].end)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return w


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# -- correctness -------------------------------------------------------------

def judge(W, c, fleet, w: Window, lim: dict, seed: int, control=False):
    """Compare a seeded sample of the window's served answers with the
    plain reference. Every step completed in the window is attempted; one
    that did not return all its tokens, each in the vocabulary, failed.
    With ``control`` the fp8 control's first token at each served position
    takes the served token's place in the comparison, which then has to
    come out not correct. Returns (checks, attempted, failed, readings)."""
    from harness import reference
    new = fleet.new_tokens
    vocab = c["vocab_size"]
    attempted = fleet.counted()
    bad = [s for s in attempted if len(s.tokens) != new
           or not all(0 <= t < vocab for t in s.tokens)]
    whole = [s for s in attempted if s not in bad]
    checks = {"max_logit_gap": {"value": None,
                                "limit": float(lim["max_logit_gap"])},
              "incomplete_steps": {"value": len(bad), "limit": 0}}
    readings = {"gap": None, "control_gap": None, "reference_s": 0.0,
                "sampled": 0, "served_tokens": 0}
    if whole:
        rng = np.random.default_rng([seed, 0xC0FFEE])
        longest = max(range(len(whole)), key=lambda i: len(whole[i].tokens))
        rest = [i for i in range(len(whole)) if i != longest]
        k = min(int(lim["sample_steps"]), len(whole))
        sample = [whole[i] for i in
                  [longest] + list(rng.choice(rest, k - 1, replace=False))]
        served = np.asarray([s.tokens for s in sample])
        t0 = time.perf_counter()
        got, ctrl = reference.served_gaps(
            W, c, np.stack([s.request.patches for s in sample]),
            np.stack([np.asarray(s.request.prompt) for s in sample]),
            served, control=control)
        readings = {"gap": got, "control_gap": ctrl,
                    "reference_s": time.perf_counter() - t0,
                    "sampled": len(sample), "served_tokens": int(served.size)}
        checks["max_logit_gap"]["value"] = float(
            (ctrl if control else got).max())
    return checks, len(attempted), len(bad), readings


def decide(checks: dict) -> bool:
    """Correct when every number is within its limit; a number that could
    not be read (no whole step to judge) fails."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


# -- metrics -----------------------------------------------------------------

def p95(x):
    return float(np.percentile(np.asarray(x, np.float64), 95))


def end_to_end(w: Window, setup_s: float) -> dict:
    steps = w.fleet.counted()
    span = w.close - w.open
    return {
        "steps_per_s": len(steps) / span,
        "step_p95_s": p95([s.t_done - s.t_submit for s in steps]),
        "setup_s": setup_s,
    }


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader (``bench/metrics/<name>.py``) may
    read: the configuration and mix, the engine's shape settings, the
    window with its steps and counters, its trace, and the chip's peaks."""
    config: dict
    traffic: dict
    engine: dict
    window: Window
    peaks: dict
    chips: int

    @property
    def steps(self):
        return self.window.fleet.counted()


def engine_shape(eng, prompt: int) -> dict:
    return dict(slots=eng.n_slots, page_size=eng.page_size,
                chunk_size=eng.chunk_size, max_seq=eng.max_seq,
                tick_tokens=eng.tick_tokens, prompt=prompt)


def per_layer(bm, wl, run: Run) -> dict:
    out = {}
    for m in spec.per_layer_metrics(bm, wl):
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(w: Window) -> dict:
    """The ten device operations that took most time (leaf ops, named by
    stage, op and shape), and the ten longest idle gaps, named by the
    benchmark's host span around them and the stage that ran before."""
    from harness import programs
    from harness import trace as T
    a, b = w.trace_span
    dev = min(w.trace.ops)
    ops = T.clip(w.trace.ops[dev], a, b)
    ran = programs.assign(T.clip(w.trace.modules.get(dev, []), a, b), ops)
    spent: dict = {}
    for _, stage, inner in ran:
        for e in programs.leaves(inner):
            key = f"{stage}: {programs.short(e.name)}"
            spent[key] = spent.get(key, 0.0) + e.dur
    top = sorted(spent.items(), key=lambda kv: -kv[1])[:10]
    ends = [m.end for m, _, _ in ran]
    idle = []
    for g0, g1 in T.gaps(ops, a, b)[:10]:
        i = bisect.bisect_right(ends, g0 + 1e-9) - 1
        before = ran[i][1] if i >= 0 else "window open"
        idle.append([f"{T.host_span_at(w.trace.host, (g0 + g1) / 2)}; "
                     f"after {before}", g1 - g0])
    return {"device_ops": [[n, s] for n, s in top], "idle_gaps": idle}


def device_busy(w: Window) -> float:
    from harness import trace as T
    a, b = w.trace_span
    return float(np.mean([T.busy(ops, a, b)
                          for ops in w.trace.ops.values()]))


# -- main --------------------------------------------------------------------

def prepare(wl_name: str, rehearse: bool):
    bm = spec.benchmark()
    wl = spec.workload(bm, wl_name)
    c = spec.config(wl["config"])
    mix = dict(spec.traffic(wl["traffic"]))
    lim = spec.limits(wl["name"])
    if rehearse:
        mix["robots"] = min(int(mix["robots"]), 2)
        mix["stagger_s"] = min(float(mix["stagger_s"]), 0.05)
    return bm, wl, c, mix, lim


def setup_jax(chips: int, rehearse: bool):
    """The cell's devices and a compile counter. The persistent compile
    cache is ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else the
    fixed ``.jax_compile_cache/`` in the checkout
    (``repro.launch.compile_cache``)."""
    import jax
    from repro.launch.compile_cache import CompileCounter, enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips], CompileCounter()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="any platform, the registry's reduced config, two "
                        "robots: control flow only, no metric")
    args = p.parse_args(argv)

    bm, wl, c, mix, lim = prepare(args.workload, args.rehearse)
    if int(c.get("mesh_model", 1)) != wl["chips"]:
        raise ValueError(f"{wl['name']}: {wl['chips']} chips but "
                         f"mesh_model {c.get('mesh_model', 1)}: one engine "
                         f"spans the cell's chips")
    try:
        devices, compiles = setup_jax(wl["chips"], args.rehearse)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    from harness.fleet import Fleet

    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x {len(devices)}")
    pk = None if args.rehearse else spec.peaks(dev.device_kind)
    pcfg, c = program_config(c, args.rehearse)
    mesh = serving_mesh(c)
    W = make_weights(pcfg, c, args.seed, mesh)
    check_tree(W, pcfg)
    jax.block_until_ready(W)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(W))
    log(f"weights {nbytes / 1e9:.3f} GB bf16 from seed {args.seed} "
        f"on {len(devices)} chip(s) "
        f"({time.perf_counter() - T_PROCESS:.1f} s since start)")
    fleet = Fleet(mix, (c["vision"]["num_tokens"], c["vision"]["embed_dim"]),
                  c["vocab_size"], args.seed)
    prompt = c["vision"]["num_tokens"] + int(mix["instruction_tokens"])
    eng = build_engine(pcfg, c, W, fleet.robots, mesh)
    n_live, n_slices = warm_shapes(eng, prompt, c["d_model"], fleet.pool[0])
    warm_engine(eng, fleet)
    if mesh is not None:
        w0 = W["decoder"]["blocks"]["sub0"]["wi"]
        log(f"engine mesh {eng.stats.mesh_shape}; a chip holds "
            f"{w0.addressable_shards[0].data.shape} of wi {w0.shape}")
    log(f"warmed {n_live} chunk key bounds and {n_slices} chunk slices "
        f"({compiles.summary()}; "
        f"{time.perf_counter() - T_PROCESS:.1f} s since start)")

    traced = bool(args.trace)
    w = serve_window(eng, fleet, args.seconds, traced, compiles,
                     TRACE_DIR / args.workload)
    setup_s = w.open - T_PROCESS
    w.peak_bytes = peak_bytes(devices)
    log(f"window {w.close - w.open:.3f} s, {len(fleet.counted())} steps "
        f"completed; compilations inside the window: {w.compiles}; "
        f"counters {w.counters}; steps cut at the close: {w.cut}")
    shape = engine_shape(eng, prompt)
    del eng
    gc.collect()

    checks, attempted, failed, rd = judge(W, c, fleet, w, lim, args.seed)
    log(f"reference over {rd['sampled']} steps, {rd['served_tokens']} "
        f"served tokens, {rd['reference_s']:.1f} s")
    correct = decide(checks)

    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if args.rehearse:
        result.update(rehearsal=True, steps=len(fleet.counted()),
                      compiles_in_window=w.compiles)
    else:
        run = Run(c, mix, shape, w, pk, len(devices))
        if traced:
            metrics = per_layer(bm, wl, run)
        else:
            e2e = end_to_end(w, setup_s)
            metrics = {m["name"]: {"value": e2e[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec.end_to_end_metrics(bm, wl)}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": w.peak_bytes}
        if traced:
            device.update(busy_s=device_busy(w),
                          window_s=w.trace_span[1] - w.trace_span[0])
        result.update(metrics=metrics, device=device)
        if traced:
            result["breakdown"] = breakdown(w)
    result["checks"] = checks
    for name, v in checks.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
