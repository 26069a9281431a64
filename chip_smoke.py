#!/usr/bin/env python3
"""Smoke run of the served path on a TPU: the quickest proof that the
system still starts on the chip. Not a benchmark.

    python chip_smoke.py [--seed N]              # one chip: serving
    python chip_smoke.py --four-chips [--seed N]  # four chips: training

One chip. Qwen3-0.6B at its published widths with AltUp K=2, random bf16
weights from --seed, served through
`Engine(cfg, params, max_len=4096, n_slots=8)` in three phases: the
default contiguous cache, an int8 cache, and a paged cache. Each phase
submits 8 requests (seeded prompt lengths 64..512, max_new=32, half
greedy, half seeded-sampled) and runs them to completion. A phase fails
if it raises; if a token or chosen-token logprob is out of range or not
finite; if its compiled decode step lacks a TPU custom call for a kernel
the phase should use (ragged or paged decode attention, fused AltUp);
or if its greedy requests disagree with the same engine on the dense
path (`ragged_decode_attn=False, fused_decode_altup=False`) beyond
LOGPROB_TOL.

Four chips. `Trainer` on a (data=2, model=2) mesh for TRAIN_STEPS steps
of the same config, against the same steps on one chip in the same
process; per-step losses must agree within LOSS_TOL, and the sharded
parameters must span all four devices.

Exits non-zero, and prints no result, where JAX finds no TPU: there is
no CPU fallback and no interpret mode. On success the last line of
standard output is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH, ALTUP_K = "qwen3-0.6b", 2
MAX_LEN, N_SLOTS = 4096, 8
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, MAX_NEW = 8, 64, 512, 32
# Greedy requests are compared token by token with the dense engine, up
# to and including the first position where the two pick different
# tokens (after it the contexts differ). At every compared position the
# chosen-token logprob is the maximum of log_softmax(logits), a
# continuous function of the logits, so it stays comparable even at a
# near-tie. The two paths differ only in rounding: the kernels keep f32
# inside and round their outputs to bf16 once, the dense path rounds
# between einsums. bf16 keeps 8 bits of mantissa (relative step 2^-8 =
# 0.0039), and such steps move a logit of magnitude ~5 by a few
# hundredths: the largest difference measured on a TPU v5e at seed 0
# was 0.031 in all three phases. LOGPROB_TOL allows ~5x that; a wrong
# kernel (a dropped head, a misplaced block) moves logprobs by whole
# units.
LOGPROB_TOL = 0.15
TRAIN_STEPS, TRAIN_SEQ, TRAIN_BATCH = 4, 256, 8
# Sharded and single-chip steps sum the same bf16 products in different
# orders (the model axis splits contractions); a loss near ln(V) ~ 12
# moves by ~1e-3 relative per step from that alone.
LOSS_TOL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def make_requests(seed: int, vocab: int):
    """N_REQUESTS (prompt, SamplingParams) pairs, from `seed` alone."""
    import numpy as np
    from repro.serve.sampling import SamplingParams
    rng = np.random.default_rng(seed)
    lengths = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, size=N_REQUESTS)
    out = []
    for i, n in enumerate(lengths):
        prompt = rng.integers(0, vocab, size=int(n)).astype(np.int32)
        sampled = i % 2 == 1
        out.append((prompt, SamplingParams(
            max_new=MAX_NEW, temperature=0.8 if sampled else 0.0,
            top_k=50 if sampled else 0, seed=seed * 1000 + i,
            logprobs=True)))
    return out


def serve(cfg, params, requests, engine_kw, *, want_hlo: bool):
    """Run `requests` through a fresh Engine to completion. Returns the
    completions in submission order, the engine's counters, the wall
    time, and (want_hlo) the compiled text of its first pure-decode
    step — the S=1 step the decode kernels serve."""
    import jax
    from repro.serve.engine import Engine
    eng = Engine(cfg, params, max_len=MAX_LEN, n_slots=N_SLOTS, **engine_kw)
    fused, first_decode = eng._fused, []

    def observe(*args, **kw):
        # record the shapes of the first S=1 call before it donates them
        if not first_decode and args[3].shape[1] == 1:
            first_decode.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding)
                if isinstance(x, jax.Array) else x, (args, kw)))
        return fused(*args, **kw)

    if want_hlo:
        eng._fused = observe
    rids = [eng.submit(p, sampling=sp) for p, sp in requests]
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    hlo = None
    if want_hlo and first_decode:
        args, kw = first_decode[0]
        hlo = fused.lower(*args, **kw).compile().as_text()
    return [done[r] for r in rids], dict(eng.stats), wall, hlo


def check_outputs(comps, vocab: int) -> list:
    errors = []
    for i, c in enumerate(comps):
        if len(c.tokens) != MAX_NEW:
            errors.append(f"request {i}: {len(c.tokens)} tokens")
        if any(not 0 <= t < vocab for t in c.tokens):
            errors.append(f"request {i}: token out of [0, {vocab})")
        lps = c.logprobs or ()
        if len(lps) != len(c.tokens) or any(
                not math.isfinite(x) or x > 1e-3 for x in lps):
            errors.append(f"request {i}: logprobs not finite or > 0")
    return errors


def compare_greedy(comps, ref) -> tuple:
    """(max |logprob difference|, positions compared, tokens equal, all
    tokens) over the greedy requests; see LOGPROB_TOL."""
    worst, compared, same, total = 0.0, 0, 0, 0
    for a, b in zip(comps, ref):
        n = len(a.tokens)
        agree = next((j for j in range(n) if a.tokens[j] != b.tokens[j]), n)
        upto = min(agree + 1, n)
        for j in range(upto):
            worst = max(worst, abs(a.logprobs[j] - b.logprobs[j]))
        compared += upto
        same += agree
        total += n
    return worst, compared, same, total


def serve_phase(name, cfg, params, requests, engine_kw, attn_kernel,
                clock) -> bool:
    from repro.kernels import compiled_kernels
    vocab = cfg.vocab_size
    t_compile = clock.seconds
    comps, stats, wall, hlo = serve(cfg, params, requests, engine_kw,
                                    want_hlo=True)
    compile_s = clock.seconds - t_compile
    errors = check_outputs(comps, vocab)
    kernels = compiled_kernels(hlo) if hlo else set()
    missing = {attn_kernel, "altup_predict_correct"} - kernels
    if missing:
        errors.append(f"decode step lacks TPU custom calls for "
                      f"{sorted(missing)} (has {sorted(kernels)})")
    greedy = [i for i, (_, sp) in enumerate(requests) if sp.greedy]
    dense_cfg = cfg.replace(ragged_decode_attn=False,
                            fused_decode_altup=False)
    ref, _, _, _ = serve(dense_cfg, params, [requests[i] for i in greedy],
                         engine_kw, want_hlo=False)
    errors += check_outputs(ref, vocab)
    worst, compared, same, total = compare_greedy(
        [comps[i] for i in greedy], ref)
    if worst > LOGPROB_TOL:
        errors.append(f"greedy logprobs differ from the dense path by "
                      f"{worst} > {LOGPROB_TOL}")
    log(f"[{name}] not a benchmark: compile_s={compile_s:.1f} "
        f"steps={stats['steps']} prefill_tokens={stats['prefill_tokens']} "
        f"decode_tokens={stats['decode_tokens']} wall_s={wall:.2f} "
        f"kernels={sorted(kernels)}")
    log(f"[{name}] greedy vs dense: max_abs_logprob_diff={worst!r} over "
        f"{compared} positions, {same}/{total} tokens equal before the "
        f"first divergence (tol {LOGPROB_TOL})")
    for e in errors:
        log(f"[{name}] FAIL: {e}")
    return not errors


def serve_on_one_chip(seed: int) -> bool:
    import jax
    from repro.configs import get_config
    from repro.kernels import quant
    from repro.models.transformer import init_params
    log(f"fp8_native={quant.fp8_native()}")
    clock = CompileClock()
    cfg = get_config(ARCH, altup_k=ALTUP_K)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    requests = make_requests(seed, cfg.vocab_size)
    phases = [
        ("contiguous", cfg, {}, "ragged_decode_attention"),
        ("int8", cfg.replace(kv_cache_dtype="int8"), {},
         "ragged_decode_attention"),
        ("paged", cfg, {"paged": True}, "paged_ragged_decode_attention"),
    ]
    ok = True
    for name, pcfg, kw, attn in phases:
        try:
            ok &= serve_phase(name, pcfg, params, requests, kw, attn, clock)
        except Exception as e:   # a phase that raises fails; go on
            traceback.print_exc()
            log(f"[{name}] FAIL: {type(e).__name__}: {e}")
            ok = False
        gc.collect()             # drop the phase's caches before the next
    return ok


def train_on_four_chips(seed: int) -> bool:
    import jax
    from repro.config import OptimizerConfig, TrainConfig
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.train.trainer import Trainer
    cfg = get_config(ARCH, altup_k=ALTUP_K)
    tcfg = TrainConfig(
        steps=TRAIN_STEPS, seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH,
        seed=seed, checkpoint_every=0, log_every=TRAIN_STEPS + 1,
        optimizer=OptimizerConfig(name="adafactor", learning_rate=0.3,
                                  warmup_steps=10))
    quiet = lambda s: None
    t0 = time.perf_counter()
    one = Trainer(cfg, tcfg, mesh=None).run(log=quiet)
    one_s = time.perf_counter() - t0
    gc.collect()
    mesh = make_mesh((2, 2), ("data", "model"))
    trainer = Trainer(cfg, tcfg, mesh=mesh)
    leaves = jax.tree_util.tree_leaves(trainer.params)
    used = {d for x in leaves for d in x.devices()}
    split = sum(x.sharding.shard_shape(x.shape) != x.shape for x in leaves)
    t0 = time.perf_counter()
    four = trainer.run(log=quiet)
    four_s = time.perf_counter() - t0
    errors = []
    if used != set(jax.devices()[:4]) or not split:
        errors.append(f"parameters on {len(used)} devices, {split} "
                      f"sharded leaves: the mesh does not span 4 chips")
    l1 = [m["loss"] for m in one["history"]]
    l4 = [m["loss"] for m in four["history"]]
    diff = max(abs(a - b) for a, b in zip(l1, l4))
    if len(l1) != TRAIN_STEPS or len(l4) != TRAIN_STEPS or \
            not all(map(math.isfinite, l1 + l4)) or diff > LOSS_TOL:
        errors.append(f"losses disagree: one chip {l1}, four chips {l4}")
    log(f"[train] not a benchmark: one_chip_s={one_s:.1f} "
        f"four_chip_s={four_s:.1f} (compile included) mesh="
        f"{dict(mesh.shape)} devices={len(used)} sharded_leaves={split}")
    log(f"[train] losses one chip {l1}")
    log(f"[train] losses 4 chips  {l4} max_abs_diff={diff!r} "
        f"(tol {LOSS_TOL})")
    for e in errors:
        log(f"[train] FAIL: {e}")
    return not errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded training on a 2x2 mesh")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {cache_dir}")
    if args.four_chips:
        ok = train_on_four_chips(args.seed)
    else:
        ok = serve_on_one_chip(args.seed)
    if not ok:
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
