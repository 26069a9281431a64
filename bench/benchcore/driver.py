"""One run of one cell: set-up, the measured window, the reading of the
trace, the correctness comparison, and the result line.

The window is an open loop over `Engine.submit` and `Engine.step`: before
each step every request now due is submitted; each output token is
stamped when the step that produced it returns. Nothing compiles inside
the window (the run counts compilations there and prints the count).

`prepare`, `open_loop` and `compare` are the phases; `run` chains them
for one run, and `bench/calibrate.py` reuses them to read many seeds in
one process.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from benchcore import check, e2e, models, spec, traffic, warmup, weights

TRACE_AT = 0.3             # share of the window before the trace starts
TRACE_SECONDS = 3.0        # a trace lasts at least this long, and on
TRACE_DECODE_STEPS = 8     # until it holds this many pure decode steps
                           # (the decode kernels run only in those)
SAMPLE_TOKENS = 300        # served tokens the comparison aims for
SAMPLE_REQUESTS = 12
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
# the engine's jitted programs; `reuse` carries them from one engine to
# the next of a calibration process, so a new seed compiles nothing
ENGINE_PROGRAMS = ("_step", "_fused", "_reset", "_clear_seen", "_sample",
                   "_seen_update", "_copy", "_seed_seen", "_copy_pages",
                   "_gather_pages", "_scatter_pages", "_reset_pages")


class NoChip(RuntimeError):
    pass


def clock() -> float:
    return time.monotonic()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Step:
    t0: float
    t1: float
    active: int
    traced: bool = False
    call: Optional[dict] = None      # pos, nval, width of the fused call


@dataclass
class RunRecord:
    """What the per-layer readers read."""
    shape: models.Shape
    peaks: dict
    kv_dtype: str
    max_len: int
    page: int                        # 0 for the contiguous cache
    steps: List[Step] = field(default_factory=list)
    stats: Dict[str, float] = field(default_factory=dict)
    trace: Optional[object] = None   # trace.Reduced of the traced part


@dataclass
class Session:
    """One seed's weights, requests and engine, ready for a window."""
    cell: "spec.Cell"
    seed: int
    shape: models.Shape
    params: object
    eng: object
    reqs: list
    SamplingParams: type
    rid_of: Dict[int, int] = field(default_factory=dict)


@dataclass
class Window:
    t0: float                        # clock() at the window's start
    seconds: float
    attempted: int
    refused: int
    submit_t: List[float]
    tok_t: List[List[float]]
    steps: List[Step]
    stats: Dict[str, float]
    pending: List[tuple]             # (t, submitted - finished)
    trace_dir: Optional[str]
    traced: bool
    gc_pauses: List[tuple] = field(default_factory=list)  # (gen, s)
    paged: Dict[str, float] = field(default_factory=dict)


def load_peaks(kind: str, bench_dir: Path = spec.BENCH_DIR) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def program_config(fam, config: dict):
    """The program's ModelConfig for a configuration file, built by its
    model family `fam` and checked against every number the file
    states."""
    cfg, want = fam.program_config(config)
    alt = config["altup"]
    got = {k: getattr(cfg, k) for k in want}
    if got != want or cfg.altup.recycled != bool(alt["recycled"]):
        raise RuntimeError(f"program config {got} (recycled="
                           f"{cfg.altup.recycled}) differs from the "
                           f"configuration file {want} ({alt})")
    return cfg.replace(kv_cache_dtype=config["deployment"]["kv_cache_dtype"])


def compile_counter():
    import jax
    box = {"on": False, "n": 0}

    def on(event, duration, **_):
        if box["on"] and event in COMPILE_EVENTS:
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return box


def gc_watch(pauses: list):
    """A `gc.callbacks` entry that appends (generation, seconds) of each
    collection to `pauses`."""
    box = {}

    def cb(phase, info):
        if phase == "start":
            box["t"] = time.perf_counter()
        elif "t" in box:
            pauses.append((info["generation"],
                           time.perf_counter() - box.pop("t")))

    return cb


def _paged_counts(eng) -> Dict[str, float]:
    ps = eng.paged_stats or {}
    return {k: float(ps[k]) for k in ("spills", "restores", "host_dropped",
                                      "fresh_acquisitions") if k in ps}


def _sampling(req, SamplingParams):
    if req.greedy:
        return SamplingParams(max_new=req.max_new)
    return SamplingParams(max_new=req.max_new, temperature=req.temperature,
                          top_p=req.top_p, seed=req.sample_seed)


def _record_calls(eng, sink: list) -> None:
    """Observe each fused-step call: positions, fed tokens, width."""
    fused = eng._fused

    def observed(*args, **kw):
        out = fused(*args, **kw)
        sink.append({"pos": np.asarray(args[4]).tolist(),
                     "nval": np.asarray(args[5]).tolist(),
                     "width": int(args[3].shape[1])})
        return out

    eng._fused = observed


def prepare(cell, seed: int, seconds: float, *, warm: bool = True,
            reuse: Optional[dict] = None,
            engine_hook: Optional[Callable] = None) -> Session:
    """Weights and requests from the seed, the engine, and set-up."""
    import jax
    from repro.models.transformer import init_params
    from repro.serve.engine import Engine
    from repro.serve.sampling import SamplingParams

    dep = cell.deployment
    cfg = program_config(cell.family, cell.config)
    shape = cell.family.shape(cell.config)
    params = weights.make_params(cell.family.layout(cell.config),
                                 shape.vocab, seed)
    weights.check_layout(params, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    docs = traffic.documents(cell.traffic, seed, shape.vocab)
    reqs = traffic.generate(cell.traffic, seed, seconds, shape.vocab, docs)
    max_len = int(dep["max_len"])
    # every seed draws the same sizes, so this is the cell's deepest slot
    need = max(len(r.prompt) + r.max_new for r in reqs)
    if need > max_len:
        raise ValueError(f"traffic needs {need} rows > max_len {max_len}")
    eng = Engine(cfg, params, max_len, n_slots=int(dep["n_slots"]),
                 paged=bool(dep.get("paged", False)),
                 page_size=int(dep.get("page_size", 16)))
    if reuse is not None:
        eng._ensure_slots()
        for name in ENGINE_PROGRAMS:
            if name in reuse:
                setattr(eng, name, reuse[name])
    if engine_hook is not None:
        engine_hook(eng)
    if warm:
        kinds = sorted({not r.greedy for r in reqs})
        warmup.warm(eng, kinds, need, shape.vocab,
                    traffic.rng_for(seed, 9), SamplingParams)
    if (cell.traffic.get("shared_prefix") or {}).get("preload"):
        warmup.preload(eng, docs, SamplingParams)
    if reuse is not None:
        for name in ENGINE_PROGRAMS:
            if hasattr(eng, name):
                reuse.setdefault(name, getattr(eng, name))
    return Session(cell=cell, seed=seed, shape=shape, params=params,
                   eng=eng, reqs=reqs, SamplingParams=SamplingParams)


def open_loop(sess: Session, seconds: float, *, trace: bool = False,
              compiles: Optional[dict] = None) -> Window:
    """The measured window."""
    import jax
    eng, reqs = sess.eng, sess.reqs
    calls: list = []
    if trace:
        _record_calls(eng, calls)
    stats0 = dict(eng.stats)
    paged0 = _paged_counts(eng)
    gc_pauses: List[tuple] = []
    watch = gc_watch(gc_pauses)
    n = len(reqs)
    rid_of = sess.rid_of = {}
    submit_t = [math.nan] * n
    tok_t: List[List[float]] = [[] for _ in range(n)]
    steps: List[Step] = []
    pending: List[tuple] = []
    refused = n_done = 0
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    tracing = traced = False
    span = jax.profiler.TraceAnnotation

    def submit(k: int) -> None:
        nonlocal refused
        try:
            rid = eng.submit(reqs[k].prompt,
                             sampling=_sampling(reqs[k], sess.SamplingParams))
            rid_of[rid] = k
        except ValueError:
            refused += 1

    # what is due at the start is queued before it: a backlog is waiting
    # when the window opens, and its submission is not timed
    i = 0
    while i < n and reqs[i].due_s <= 0.0:
        submit(i)
        submit_t[i] = 0.0
        i += 1
    jax.effects_barrier()
    gc.callbacks.append(watch)
    t0 = clock()
    if compiles is not None:
        compiles["on"] = True
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if trace and not tracing and not traced \
                and now >= TRACE_AT * seconds:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # spans only, no call tracing
            jax.profiler.start_trace(tdir, profiler_options=opts)
            tracing = True
            trace_end = now + min(TRACE_SECONDS, 0.5 * seconds)
            decode_traced = 0
        elif tracing and now >= trace_end \
                and decode_traced >= TRACE_DECODE_STEPS:
            jax.profiler.stop_trace()
            tracing, traced = False, True
        ctx = span if tracing else (lambda _name: nullcontext())
        while i < n and reqs[i].due_s <= now:
            with ctx("bench.submit"):
                submit(i)
            submit_t[i] = clock() - t0
            i += 1
        if eng.has_work:
            a = clock()
            n_calls = len(calls)
            with ctx("bench.step"):
                active = eng.step()
            b = clock() - t0
            call = calls[-1] if len(calls) > n_calls else None
            steps.append(Step(a - t0, b, active, traced=tracing, call=call))
            if tracing and call is not None and call["width"] == 1:
                decode_traced += 1
            for rid, _tok in eng._events:
                k = rid_of[rid]
                tok_t[k].append(b)
                n_done += len(tok_t[k]) == reqs[k].max_new
            pending.append((b, i - refused - n_done))
        else:
            nxt = reqs[i].due_s if i < n else seconds
            with ctx("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - (clock() - t0)))
    if tracing:
        jax.profiler.stop_trace()
        traced = True
    if compiles is not None:
        compiles["on"] = False
    gc.callbacks.remove(watch)
    paged1 = _paged_counts(eng)
    return Window(t0=t0, seconds=seconds, attempted=i, refused=refused,
                  submit_t=submit_t, tok_t=tok_t, steps=steps,
                  stats={k: eng.stats[k] - stats0[k] for k in stats0},
                  pending=pending, trace_dir=tdir, traced=traced,
                  gc_pauses=gc_pauses,
                  paged={k: paged1[k] - paged0[k] for k in paged1})


def finished(sess: Session) -> List[tuple]:
    """(request, served tokens) of every request the engine finished."""
    done = sess.eng.collect()
    return [(sess.reqs[sess.rid_of[r]], c.tokens) for r, c in done.items()]


def samples(cell) -> bool:
    """Whether the cell's traffic has requests that sample."""
    return float(cell.traffic.get("sampling", {}).get("greedy_share",
                                                      1.0)) < 1.0


def compare(sess: Session, done: List[tuple], *, control: bool = False):
    """The numbers compared for `correct`, and readings beside them for
    calibration: the control's widest gap (with `control`) and the
    largest mass past top_p at a sampled served token. Frees the engine
    first: the reference runs on the chip after the program's state is
    gone."""
    greedy = [(r, t) for r, t in done if r.greedy]
    picked = check.sample(greedy, sess.seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    picked_s = check.sample([(r, t) for r, t in done if not r.greedy],
                            sess.seed, SAMPLE_TOKENS, SAMPLE_REQUESTS,
                            stream=check.SAMPLED_STREAM)
    sess.eng = None
    gc.collect()
    cell = sess.cell
    gaps = check.served_gaps(sess.params, cell.family, cell.config, picked,
                             control=control)
    numbers = {
        "max_logit_gap": max((float(g.max()) for g, _ in gaps if g.size),
                             default=0.0),
        "compared_tokens": float(sum(g.size for g, _ in gaps)),
        "bad_completions": float(check.bad_completions(done,
                                                       sess.shape.vocab)),
    }
    readings = {}
    if control:
        readings["control_max_logit_gap"] = max(
            (float(c.max()) for _, c in gaps if c.size), default=0.0)
    if samples(sess.cell):
        nuc = check.served_nucleus(sess.params, cell.family, cell.config,
                                   picked_s)
        numbers.update(check.sampled_numbers(nuc))
        readings["nucleus_excess_max"] = max(
            (float(e.max()) for e, *_ in nuc if e.size), default=None)
    return numbers, readings


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        process_start: float, benchmark: Optional[dict] = None,
        bench_dir: Path = spec.BENCH_DIR, root: Path = spec.ROOT,
        require_chip: bool = True, configure_cache: bool = True,
        engine_hook: Optional[Callable] = None) -> dict:
    cell = spec.resolve(workload, benchmark, bench_dir, root)
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        raise NoChip(f"cell {workload} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    kind = devs[0].device_kind
    peaks = load_peaks(kind, bench_dir) if require_chip else {}
    if configure_cache:
        use_cache_dir(root)
    compiles = compile_counter()
    sess = prepare(cell, seed, seconds, engine_hook=engine_hook)
    win = open_loop(sess, seconds, trace=trace, compiles=compiles)
    setup_s = win.t0 - process_start
    mem = devs[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    done = finished(sess)

    dep = cell.deployment
    device = {"platform": devs[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": peak_bytes}
    extra: Dict[str, object] = {}
    names = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in names}
    n = win.attempted
    if not trace:
        vals = e2e.metrics([r.due_s for r in sess.reqs[:n]], win.tok_t[:n],
                           seconds, setup_s)
        metrics = {k: {"value": vals[k], "unit": units[k]}
                   for k in units if k in vals}
    else:
        from benchcore import trace as tr
        paged = bool(dep.get("paged", False))
        record = RunRecord(shape=sess.shape, peaks=peaks,
                           kv_dtype=dep["kv_cache_dtype"],
                           max_len=int(dep["max_len"]),
                           page=int(dep.get("page_size", 16)) if paged
                           else 0, steps=win.steps, stats=win.stats)
        record.trace = tr.Reduced(tr.load(win.trace_dir)) \
            if win.traced else None
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        metrics = {}
        for name, reader in cell.readers.items():
            v = reader(record)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": units[name]}
        if record.trace is not None:
            device["busy_s"] = record.trace.busy_ns * 1e-9
            device["window_s"] = record.trace.active_ns * 1e-9
            extra["breakdown"] = {"device_ops": record.trace.top_ops(),
                                  "idle_gaps": record.trace.idle_gaps()}

    write_log(root, workload, seed, trace, sess.reqs[:n], win)
    late = np.array([win.submit_t[k] - sess.reqs[k].due_s for k in range(n)])
    log(f"compilations in window: {compiles['n']}")
    if n:
        log(f"generator lateness ms: median {1e3 * np.median(late):.3f} "
            f"p95 {1e3 * np.percentile(late, 95):.3f} "
            f"max {1e3 * late.max():.3f}")
    log(f"requests: attempted {n} completed {len(done)} "
        f"failed {win.refused}")
    log_host(win)

    numbers, _ = compare(sess, done)
    verdict = check.judge(numbers, {"bad_completions": 0.0, **cell.limits})
    for name, v in verdict.items():
        log(f"{name} {v['value']!r} {v['rule']} {v['limit']!r}")
    out = {"correct": all(v["ok"] for v in verdict.values()),
           "attempted": n,
           "failed": win.refused + int(numbers["bad_completions"]),
           "metrics": metrics, "device": device, **extra}
    out["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in verdict.items()}
    return out


def log_host(win: Window) -> None:
    """What the host did in the window that could stall the loop: its
    longest step, the garbage collector's pauses and, on a paged cache,
    the page pool's spills to the host and restores."""
    if win.steps:
        s = max(win.steps, key=lambda st: st.t1 - st.t0)
        log(f"host: longest step {1e3 * (s.t1 - s.t0):.1f} ms at "
            f"{s.t0:.2f} s of {len(win.steps)} steps")
    gp = win.gc_pauses
    top = max(gp, key=lambda g: g[1]) if gp else (0, 0.0)
    log(f"host: gc {len(gp)} collections, {1e3 * sum(g for _, g in gp):.1f}"
        f" ms in all, longest {1e3 * top[1]:.1f} ms (generation {top[0]})")
    if win.paged:
        log("host: page pool " + " ".join(
            f"{k} {v:g}" for k, v in sorted(win.paged.items())))


def use_cache_dir(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless JAX_COMPILATION_CACHE_DIR names one; every program,
    however quick to compile, is kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def write_log(root: Path, workload, seed, trace, reqs, win: Window) -> None:
    """Per-request token log, one JSON object per request."""
    d = root / "chiprun_out" / "bench"
    d.mkdir(parents=True, exist_ok=True)
    with open(d / f"{workload}.{seed}.t{int(trace)}.jsonl", "w") as f:
        for k, r in enumerate(reqs):
            f.write(json.dumps({
                "index": r.index, "due_s": r.due_s,
                "submit_s": win.submit_t[k], "prompt": int(len(r.prompt)),
                "max_new": r.max_new, "greedy": r.greedy, "doc": r.doc,
                "token_s": [round(t, 6) for t in win.tok_t[k]]}) + "\n")
