"""Serving engine: continuous batching over slot-based KV caches.

Request API v2 — three typed objects define the contract
(serve/sampling.py): `SamplingParams` (per-request temperature / top-k /
top-p / min-p / repetition penalty / stops / seed / logprobs, validated
at construction), `Completion` (token ids + finish_reason + optional
logprobs + timing, popped from collect()/run()), and a `stream()`
iterator yielding (rid, token) deltas as fused steps complete.

Two decode surfaces share one sampling implementation:

* submit()/step()/collect()/stream() — continuous batching. Requests are
  admitted into cache slots by serve/scheduler.SlotScheduler; every
  fused step advances EVERY active slot at its own depth (per-slot (B,)
  position vector). A slot in the prefill phase consumes its next CHUNK
  of prompt tokens (chunked prefill: up to `prefill_chunk` tokens per
  step through the same jitted step); a slot in the decode phase
  consumes its last sampled token. Finished requests (eos / stop /
  length) retire immediately and their slot is recycled.

* generate() — static batch (uniform prefill + scalar-pos decode loop).
  Kept as the baseline the continuous path is benchmarked against
  (benchmarks/serve_bench.py) and as the oracle it must match token-for-
  token (tests/test_serve.py) — including under seeded sampling, since
  both paths run serve/sampling.sample_rows.

Sampling happens ON DEVICE, fused into the jitted step
(models/decode.decode_sample_step): the active slots' SamplingParams ride
in as per-slot (B,) arrays, filtering + categorical sampling run under
per-request counter-based keys (jax.random.fold_in(key(seed),
sample_index)), and only the (B,) sampled ids — plus (B,) chosen-token
logprobs when requested — transfer to host. No (B, V) logits row crosses
the device boundary during decode (benchmarks/serve_bench.py records the
before/after bytes). Greedy decode is bit-identical to the pre-v2 host
argmax, and a seeded sampled request is run-to-run reproducible and
token-identical to a seeded B=1 static generate() with the same params.

Prefix-cache reuse (docs/serving.md "Prefix caching"): admission
consults the scheduler's host-side PrefixIndex (a trie over admitted
prompt ids). On a hit against a resident or retained donor slot, the
jitted models/decode.copy_prefix clones the first p cache rows —
K/V, ring rows, MLA latents, quantized codes AND scales in lockstep —
into the new slot, the repetition-penalty seen row is seeded from the
prefix ids, the slot position starts at p, and only the prompt SUFFIX
prefills through the chunked path. Hit decode is token-identical to the
cold path (tests/test_prefix_cache.py oracles); retired slots are
RETAINED as cached prefixes and LRU-evicted when admission needs
capacity. Disable with Engine(prefix_cache=False).

Decode-hot-path economics (see docs/kernels.md): the engine passes each
step's per-slot depths down to the attention layers, which (a) slice the
cache read to a host-computed power-of-two `kv-len bucket` >= the deepest
slot (a STATIC slice — a handful of jit specializations instead of O(T)
reads at every depth), and (b) on TPU route S=1 attention through the
ragged Pallas decode kernel, which additionally skips kv blocks past each
individual slot's depth. With cfg.kv_cache_dtype = int8/fp8 the slot
caches hold 1-byte codes + per-head, per-position scales (docs/
serving.md). Chunked prefill is automatically disabled (chunk=1) for
recurrent (rwkv/mamba) and ring-cache (sliding-window) models.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models.decode import (copy_pages, copy_prefix,
                                 decode_sample_step, decode_step,
                                 gather_pages, init_cache,
                                 init_paged_cache, kv_quant_spec, prefill,
                                 reset_pages, reset_slot, restore_rows,
                                 scatter_pages, snapshot_rows)
from repro.serve.sampling import (Completion, SamplingParams,
                                  base_key_data, blank_slot_params,
                                  fill_slot_params, key_data_of,
                                  key_width, sample_rows, update_seen)
from repro.serve.scheduler import SlotScheduler, serve_clock
from repro.serve.speculative import (AdaptiveK, SpecConfig,
                                     default_draft_layers,
                                     draft_round, spec_verify_step)


PHASES = ("admit", "prepare", "dispatch", "wait", "commit")


@dataclass
class StepRecord:
    """What one Engine.step() did (Engine.last_step).

    width: tokens fed per slot (the chunk width; k + 1 in a speculative
    round; 0 when no slot was active). pos / nval: host copies of the
    (B,) positions and fed-token counts the step ran with. events: the
    step's (rid, token) deltas in generation order. phase_s: seconds
    spent in each phase of PHASES that the step entered."""
    width: int = 0
    kv_len: int = 0
    active: int = 0
    admitted: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    pos: Optional[np.ndarray] = None
    nval: Optional[np.ndarray] = None
    events: List[Tuple[int, int]] = field(default_factory=list)
    phase_s: Dict[str, float] = field(default_factory=dict)


class _PhaseClock:
    """Splits one Engine.step() into consecutive phases. Each phase is a
    profiler span `engine.<phase>` (jax.profiler.TraceAnnotation, about
    a microsecond when no profile is being taken) and adds its
    serve_clock() seconds to stats["<phase>_s"] and the step record."""

    def __init__(self, stats: Dict[str, float], record: StepRecord):
        self._stats, self._record = stats, record
        self._name: Optional[str] = None

    def start(self, name: str) -> None:
        """End the open phase, if any, and open `name`."""
        self.stop()
        self._name = name
        self._span = jax.profiler.TraceAnnotation("engine." + name)
        self._span.__enter__()
        self._t = serve_clock()

    def stop(self) -> None:
        if self._name is None:
            return
        dt = serve_clock() - self._t
        self._span.__exit__(None, None, None)
        self._stats[self._name + "_s"] += dt
        self._record.phase_s[self._name] = dt
        self._name = None


def _named(name: str, fn, *args, **kwargs):
    """partial(fn, *args, **kwargs) called `name`: jax.jit names its
    program jit_<name>, which profiler traces and compiled-module dumps
    show (an unnamed partial shows as jit__unknown)."""
    f = partial(fn, *args, **kwargs)
    f.__name__ = f.__qualname__ = name
    return f


def clear_seen(seen, slot):
    """Clear one slot's repetition-penalty row (a cold admission)."""
    return seen.at[slot].set(False)


def seed_seen(seen, slot, ids):
    """Clear one slot's row, then mark the prefix ids a prefix hit
    replays (ids padded with the vocabulary size are dropped)."""
    return seen.at[slot].set(False).at[slot, ids].set(True, mode="drop")


def kv_bucket(needed: int, lo: int, cap: int) -> int:
    """Static kv read-slice length: smallest power-of-two >= needed
    (floored at `lo`, capped at `cap`). Shared by the engine and the
    decode microbench (benchmarks/kernel_bench.py) so the benchmark
    measures exactly the bucket policy the serving path dispatches.

    needed > cap is an ERROR: the bucket used to clamp silently, which
    would hand the attention layers a read slice shorter than the fill
    depth — a truncated cache read with no signal. Requests that cannot
    fit must be rejected at admission (SlotScheduler.submit's
    prompt + max_new <= max_len check), never clamped here."""
    if lo < 1:
        raise ValueError(f"kv_bucket floor must be >= 1, got lo={lo} "
                         f"(lo <= 0 never reaches `needed` by doubling)")
    if needed > cap:
        raise ValueError(
            f"kv_bucket: needed={needed} exceeds the cache capacity "
            f"cap={cap}; a clamped bucket would silently truncate the "
            f"cache read — reject the request at admission instead")
    b = lo
    while b < needed:
        b *= 2
    return min(b, cap)


class Engine:
    def __init__(self, cfg: ModelConfig, params, max_len: int, *,
                 n_slots: int = 8, mesh=None, prefill_chunk: int = 8,
                 kv_buckets: bool = True, kv_bucket_min: int = 32,
                 prefix_cache: bool = True,
                 speculative: Union[bool, SpecConfig] = False,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 host_spill_pages: int = 0):
        if kv_bucket_min < 1:
            raise ValueError(
                f"kv_bucket_min must be >= 1, got {kv_bucket_min}")
        self.cfg, self.params, self.mesh = cfg, params, mesh
        self.max_len = max_len
        self.n_slots = n_slots
        self._kv_buckets = kv_buckets
        self._kv_bucket_min = kv_bucket_min
        self._prefix_cache = prefix_cache
        self._prefill_chunk = max(1, prefill_chunk)
        # paged KV cache (serve/paging.py): the continuous-batching slot
        # caches become a page pool + per-slot block tables. n_pages
        # defaults to full contiguous capacity (n_slots full-length
        # requests); size it SMALLER to over-commit slots against typical
        # (shorter / prefix-shared) requests — admission reserves each
        # request's worst-case ceil((prompt+max_new)/page) pages, so
        # over-commit is safe, just admission-limited. host_spill_pages
        # bounds the host spill tier for evicted prefix pages (0 = off).
        # generate() (the static oracle) always runs contiguous.
        if paged and page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self._paged = bool(paged)
        self._page_size = int(page_size)
        self._n_pages = n_pages
        self._host_spill_pages = int(host_spill_pages)
        # self-speculative decoding (serve/speculative.py): True enables
        # it with defaults, a SpecConfig tunes it; recurrent plans fall
        # back to normal decode at _ensure_slots (state cannot rewind)
        if speculative is True:
            speculative = SpecConfig()
        elif speculative is False:
            speculative = None
        self._spec_cfg: Optional[SpecConfig] = speculative
        self._spec = False          # resolved against the plan lazily
        self._step = jax.jit(_named("decode_step", decode_step, cfg=cfg,
                                    mesh=mesh),
                             static_argnames=("kv_len",))
        # continuous-batching state (allocated lazily on first submit).
        # Paged engines bake the static page size into the fused step;
        # the block table rides in as a traced kwarg each call.
        self._fused = jax.jit(
            _named("fused_step", decode_sample_step, cfg=cfg, mesh=mesh,
                   page_size=(self._page_size if self._paged else 0)),
            static_argnames=("kv_len", "want_logprobs", "any_sampled"),
            donate_argnums=(1, 2))
        self._reset = jax.jit(
            _named("reset_slot", reset_slot, only_recurrent=self._paged),
            donate_argnums=(0,))
        self._clear_seen = jax.jit(clear_seen, donate_argnums=(0,))
        # generate()'s per-token sampling: the SAME sample_rows as the
        # fused serving step, jitted standalone for the static loop
        self._sample = jax.jit(
            sample_rows, static_argnames=("want_logprobs", "any_sampled"))
        self._seen_update = jax.jit(update_seen)
        self._sched: Optional[SlotScheduler] = None
        self._caches = None
        self._seen = None
        self._base_keys: Dict[int, np.ndarray] = {}   # rid -> key data
        self._events: List[Tuple[int, int]] = []      # last step's deltas
        self.last_step: Optional[StepRecord] = None
        # (B, n) chosen-token logprobs of the most recent generate() run
        # with sampling.logprobs=True; None otherwise
        self.last_logprobs = None
        # Counters, always on. <phase>_s: seconds spent in each phase of
        # step() (PHASES; the engine.<phase> profiler spans time the
        # same intervals). queue_wait_s / admitted: summed admission
        # minus arrival time of every admitted request, and their count.
        # prefix_hits / prefill_tokens_saved count prefix-cache reuse:
        # saved tokens are prompt tokens that arrived by slot-to-slot
        # copy instead of being prefilled. spec_* counters cover the
        # speculative rounds: drafted/accepted feed the accept rate,
        # spec_k_sum / spec_rounds the mean k. concurrency_peak: most
        # slots active in any one step (the paged over-commit headline —
        # can exceed the FULL-length request count the same pool would
        # fit contiguously)
        self.stats = {"steps": 0, "prefill_tokens": 0, "decode_tokens": 0,
                      **{ph + "_s": 0.0 for ph in PHASES},
                      "queue_wait_s": 0.0, "admitted": 0,
                      "prefix_hits": 0, "prefill_tokens_saved": 0,
                      "spec_rounds": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "spec_k_sum": 0,
                      "concurrency_peak": 0}

    def reset_stats(self) -> None:
        """Zero every counter (benchmarks call this after their warmup
        pass so compile time stays out of the phase times)."""
        for k in self.stats:
            self.stats[k] = type(self.stats[k])()

    @property
    def paged_stats(self) -> Optional[Dict[str, object]]:
        """Page-pool counters (None unless paged=True): pool occupancy,
        aliasing vs fresh page acquisitions, and the spill tier's
        traffic. Lifetime counters — NOT reset by reset_stats (the pool
        outlives benchmark warmup passes)."""
        if not self._paged or self._sched is None:
            return None
        pool = self._pool
        return {"page_size": pool.page, "n_pages": pool.n_pages,
                "pages_in_use": pool.pages_in_use,
                "pages_in_use_peak": pool.pages_in_use_peak,
                "alias_acquisitions": pool.alias_acquisitions,
                "fresh_acquisitions": pool.fresh_acquisitions,
                "page_share_rate": pool.page_share_rate,
                "spills": pool.spills, "restores": pool.restores,
                "host_dropped": pool.host_dropped,
                "host_pages_used": self._sched.host_pages_used}

    def _bucket(self, needed: int) -> int:
        """Each bucket value is one jit specialization — log2(max_len)
        of them, total."""
        if not self._kv_buckets:
            return self.max_len
        return kv_bucket(needed, self._kv_bucket_min, self.max_len)

    # ------------------------------------------------------------------
    # continuous batching: submit / step / collect / stream
    # ------------------------------------------------------------------

    def _prefix_usable_len(self, p: int, depth: int) -> int:
        """Model-kind validity of a prefix match (scheduler hook; p is
        already capped to min(LCP, donor depth, prompt_len - 1)).

        * recurrent segments: the donor's rwkv/mamba state reflects ALL
          `depth` fed tokens, so reuse is exact only when the donor
          stopped at the prefix boundary (depth == p).
        * ring segments (capacity W): a donor that decoded past the
          prefix overwrote ring rows the prefix still needs once it
          wraps; rows [max(0, p-W), p) survive iff depth <= max(p, W).
        """
        if p <= 0:
            return 0
        if self._has_recurrent and depth != p:
            return 0
        for W in self._ring_caps:
            if depth > max(p, W):
                return 0
        return p

    def _paged_usable_len(self, p: int, depth: int) -> int:
        """Paged-mode prefix validity. Recurrent plans get no paged
        prefix reuse at all: a retained entry owns only PAGES (its slot —
        and the per-slot rwkv/mamba state leaves with it — was recycled
        at retirement), so there is no state to copy even when depth ==
        p. Ring windows keep the contiguous overwrite rule."""
        if p <= 0 or self._has_recurrent:
            return 0
        for W in self._ring_caps:
            if depth > max(p, W):
                return 0
        return p

    def _pad_pages(self, pages) -> np.ndarray:
        """Fixed-width page vector for the jitted page helpers: pad to
        npages_max with -1 (dropped/ignored rows) so every copy/gather/
        scatter/reset shares ONE compile regardless of page count."""
        out = np.full((self._pool.npages_max,), -1, np.int32)
        out[:len(pages)] = pages
        return out

    def _spill_entry(self, entry) -> "object":
        """PagedScheduler spill_fn: gather a retained entry's pages into
        a host numpy blob (device gather, then one sync transfer) BEFORE
        the scheduler releases them."""
        blob = self._gather_pages(self._caches,
                                  jnp.asarray(self._pad_pages(entry.pages)))
        return jax.tree_util.tree_map(np.asarray, blob)

    def _ensure_slots(self):
        if self._sched is not None:
            return
        if self.cfg.family == "encdec":
            raise NotImplementedError(
                "continuous batching serves decoder-only families; "
                "use generate() for encoder-decoder models")
        # attention/MLA caches self-clean on recycle (per-slot position
        # masking); only recurrent segments need a reset at admission
        from repro.models.transformer import layer_plan
        plan = layer_plan(self.cfg)
        self._has_recurrent = any(s.kind in ("rwkv", "mamba")
                                  for s in plan)
        self._ring_caps = [min(self.max_len, s.window) for s in plan
                           if s.kind in ("attn", "shared_attn")
                           and s.window > 0]
        has_ring = any(s.kind in ("attn", "shared_attn") and s.window > 0
                       for s in plan)
        if self._paged:
            from repro.serve.paging import PagePool, PagedScheduler
            P = self._page_size
            npages_max = -(-self.max_len // P)
            n_pages = (self._n_pages if self._n_pages is not None
                       else self.n_slots * npages_max)
            self._pool = PagePool(n_pages, P, self.n_slots, self.max_len)
            # page-granular jitted helpers; all take fixed-width padded
            # page vectors (_pad_pages) -> one compile each. The gather
            # is read-only (the caches survive a spill), the rest donate.
            self._copy_pages = jax.jit(
                _named("copy_pages", copy_pages, page=P),
                donate_argnums=(0,))
            self._gather_pages = jax.jit(
                _named("gather_pages", gather_pages, page=P))
            self._scatter_pages = jax.jit(
                _named("scatter_pages", scatter_pages, page=P),
                donate_argnums=(0,))
            self._reset_pages = jax.jit(
                _named("reset_pages", reset_pages, page=P),
                donate_argnums=(0,))
            self._sched = PagedScheduler(
                self.n_slots, self.max_len, pool=self._pool,
                prefix_cache=self._prefix_cache,
                prefix_usable_len=self._paged_usable_len,
                # ring plans copy prefix pages instead of aliasing: a
                # sharer's ring writes wrap back into low pages, which
                # would corrupt the donor's shared rows
                alias_ok=not has_ring,
                spill_fn=self._spill_entry,
                host_budget=self._host_spill_pages)
        else:
            self._sched = SlotScheduler(
                self.n_slots, self.max_len,
                prefix_cache=self._prefix_cache,
                prefix_usable_len=self._prefix_usable_len)
        # slot-to-slot prefix copy (one specialization: dst/src/p traced)
        # and the seen-row seeding that replays the prefix ids into the
        # repetition-penalty table exactly as cold prefill would. The
        # ids ride in as a FIXED (max_len,) int32 array padded with V
        # (out-of-range -> dropped by the scatter): one compile for every
        # prefix length, max_len*4 bytes to device per hit — never a
        # (V,)-sized host row on the admission path
        self._copy = jax.jit(
            _named("copy_prefix", copy_prefix,
                   copy_recurrent=self._has_recurrent),
            donate_argnums=(0,))
        self._seed_seen = jax.jit(seed_seen, donate_argnums=(0,))
        # quantized caches also reset at admission: reset_slot zeroes the
        # slot's scale leaves so stale rows dequantize to exact 0 and a
        # NaN/Inf scale from an aborted request cannot survive recycling.
        # Paged mode splits the sweep: reset_slot (only_recurrent baked)
        # covers per-slot recurrent leaves, reset_pages zeroes the scale
        # rows of each admission's FRESH pages (aliased pages keep the
        # donor's live scales and must not be touched).
        self._quantized = kv_quant_spec(self.cfg).quantized
        self._admit_reset = (self._has_recurrent
                             or (self._quantized and not self._paged))
        # chunked prefill needs token-order-free cache writes: recurrent
        # state advances token-by-token, and ring writes of a whole chunk
        # overwrite keys earlier chunk tokens still need
        self._chunk = (1 if self._has_recurrent or has_ring
                       else self._prefill_chunk)
        # self-speculative decoding state. Recurrent plans fall back to
        # normal decode (rwkv/mamba state advances token-by-token and
        # cannot rewind a rejected suffix mid-chunk — the same reasoning
        # that forces chunk=1 prefill above); ring plans cap the draft
        # length so one round never wraps a ring row onto itself.
        if self._spec_cfg is not None and not self._has_recurrent:
            sc = self._spec_cfg
            k_cap = min(self._ring_caps) - 1 if self._ring_caps else None
            if k_cap is None or k_cap >= 1:
                D = (sc.draft_layers if sc.draft_layers is not None
                     else default_draft_layers(self.cfg))
                self._spec_k = AdaptiveK(sc, k_cap)
                self._spec_has_ring = bool(self._ring_caps)
                pg = self._page_size if self._paged else 0
                self._spec_draft = jax.jit(
                    _named("spec_draft", draft_round, cfg=self.cfg,
                           draft_layers=D, page_size=pg, mesh=self.mesh),
                    static_argnames=("k", "kv_len", "any_sampled"),
                    donate_argnums=(1,))
                self._spec_verify = jax.jit(
                    _named("spec_verify", spec_verify_step, cfg=self.cfg,
                           page_size=pg, mesh=self.mesh),
                    static_argnames=("kv_len", "want_logprobs",
                                     "any_sampled"),
                    donate_argnums=(1, 2))
                self._spec_snap = jax.jit(
                    _named("spec_snapshot", snapshot_rows, self.cfg,
                           page=pg),
                    static_argnames=("S",))
                self._spec_restore = jax.jit(
                    _named("spec_restore", restore_rows, self.cfg,
                           page=pg),
                    static_argnames=("S",), donate_argnums=(0,))
                self._spec = True
        if self._paged:
            caches = init_paged_cache(self.cfg, self.n_slots, self.max_len,
                                      n_pages=self._pool.n_pages,
                                      page=self._page_size)
        else:
            caches = init_cache(self.cfg, self.n_slots, self.max_len)
        seen = jnp.zeros((self.n_slots, self.cfg.vocab_size), bool)
        self._sp_shardings = None
        if self.mesh is not None:
            from repro.sharding import (cache_shardings,
                                        paged_cache_shardings,
                                        prefix_copy_shardings,
                                        sampling_param_shardings)
            shard_fn = (paged_cache_shardings if self._paged
                        else cache_shardings)
            caches = jax.device_put(
                caches, shard_fn(self.cfg, caches, self.mesh))
            sh = sampling_param_shardings(
                {"seen": seen, **blank_slot_params(self.n_slots)},
                self.mesh)
            seen = jax.device_put(seen, sh.pop("seen"))
            self._sp_shardings = sh
            # pin the prefix copy's output to the cache layout: the copy
            # stays mesh-local (src->dst row movement only, no gather,
            # no reshard before the next fused step consumes the result).
            # Paged mode never row-copies slot-to-slot (prefix reuse is
            # page aliasing / page copies), so the pin only applies to
            # the contiguous layout.
            if not self._paged:
                self._copy = jax.jit(
                    _named("copy_prefix", copy_prefix,
                           copy_recurrent=self._has_recurrent),
                    donate_argnums=(0,),
                    out_shardings=prefix_copy_shardings(self.cfg, caches,
                                                        self.mesh))
        self._caches = caches
        self._seen = seen

    def submit(self, prompt, *, sampling: SamplingParams) -> int:
        """Enqueue one request. prompt: 1-D sequence of token ids.

        submit(prompt, sampling=SamplingParams(...)). Returns a request
        id for collect()/stream(). sampling.seed=None gives each sampled
        request an independent stream (seeded by its rid). The pre-v2
        positional (max_new, temperature, eos_id, seed) shim is GONE —
        its one-release deprecation window closed; passing those kwargs
        now raises TypeError."""
        self._ensure_slots()
        if not isinstance(sampling, SamplingParams):
            raise TypeError(
                f"sampling must be a SamplingParams, got "
                f"{type(sampling).__name__}")
        prompt = np.asarray(prompt).reshape(-1).tolist()
        rid = self._sched.submit(prompt, sampling)
        s = sampling.seed if sampling.seed is not None else rid
        self._base_keys[rid] = base_key_data(s)
        return rid

    def step(self) -> int:
        """One fused step: admit queued requests into free slots, advance
        every active slot (a chunk of prompt tokens while prefilling, one
        token while decoding), retire finished requests. The (rid, token)
        deltas sampled this step are exposed via stream() and, with the
        step's shape and phase times, via last_step.
        Returns the number of slots that were active this step."""
        if self._sched is None:
            return 0
        rec = self.last_step = StepRecord()
        self._events = rec.events
        clock = _PhaseClock(self.stats, rec)
        with jax.profiler.TraceAnnotation("engine.step"):
            try:
                return self._run_step(clock, rec)
            finally:
                clock.stop()

    def _admit(self) -> int:
        """Admit queued requests and run their admission programs (slot
        resets, prefix and page copies, seen-row seeding). Returns how
        many were admitted."""
        admitted = self._sched.admit()
        for st in admitted:
            hit = st.prefix_len > 0
            if self._paged:
                # paged admission actions (serve/paging.py), in order:
                # recurrent reset -> zero fresh pages' scale rows ->
                # land the prefix rows (spill restore or page copy).
                # Aliased pages need nothing — they ARE the donor's rows.
                acts = st.paged or {}
                if self._has_recurrent:
                    self._caches = self._reset(self._caches, st.slot)
                if self._quantized and acts.get("fresh"):
                    self._caches = self._reset_pages(
                        self._caches,
                        jnp.asarray(self._pad_pages(acts["fresh"])))
                if "blob" in acts:
                    self._caches = self._scatter_pages(
                        self._caches, acts["blob"],
                        jnp.asarray(self._pad_pages(acts["blob_dst"])))
                elif "copy_src" in acts:
                    # admission order matters: an earlier admission in
                    # this batch may own (or share) the source pages,
                    # and its writes have already landed
                    self._caches = self._copy_pages(
                        self._caches,
                        jnp.asarray(self._pad_pages(acts["copy_dst"])),
                        jnp.asarray(self._pad_pages(acts["copy_src"])))
            else:
                self_donor = hit and st.prefix_src == st.slot
                # recycled slots keep stale attention rows (masked out by
                # the per-slot position), but recurrent rwkv/mamba state
                # carries over and must be zeroed — and quantized-cache
                # scale leaves are cleared so stale rows dequantize to
                # exact zeros. A SELF-donor hit skips the reset: the
                # slot's own rows ARE the prefix (zeroing them first
                # would destroy what the in-place "copy" reuses); its
                # stale rows past the prefix stay masked by the per-slot
                # position like any recycled slot.
                if self._admit_reset and not self_donor:
                    self._caches = self._reset(self._caches, st.slot)
                if hit and not self_donor:
                    # admission order matters: an earlier admission in
                    # this same batch may be this one's donor, and its
                    # copy has already landed by the time we read its
                    # rows here
                    self._caches = self._copy(self._caches,
                                              jnp.int32(st.slot),
                                              jnp.int32(st.prefix_src),
                                              jnp.int32(st.prefix_len))
            # the repetition-penalty seen table always resets (it carries
            # the previous occupant's consumed-token set); a prefix hit
            # seeds it with the prefix ids — the exact row cold prefill
            # would have built by feeding those tokens
            if hit:
                ids = np.full((self.max_len,), self.cfg.vocab_size,
                              np.int32)
                ids[:st.prefix_len] = st.request.prompt[:st.prefix_len]
                self._seen = self._seed_seen(self._seen, np.int32(st.slot),
                                             jnp.asarray(ids))
                self.stats["prefix_hits"] += 1
                self.stats["prefill_tokens_saved"] += st.prefix_len
            else:
                self._seen = self._clear_seen(self._seen,
                                              np.int32(st.slot))
            self._sched.release_donor(st)
            self.stats["queue_wait_s"] += st.t_admit - st.request.arrival
        self.stats["admitted"] += len(admitted)
        return len(admitted)

    def _run_step(self, clock: _PhaseClock, rec: StepRecord) -> int:
        """step()'s body, phase by phase: admit, prepare (the step's
        inputs, put on the device), dispatch (the jitted call), wait (the
        host blocked on the sampled ids), commit (advance, note tokens,
        retire)."""
        clock.start("admit")
        rec.admitted = self._admit()
        active = dict(self._sched.active)
        if not active:
            return 0
        clock.start("prepare")
        rec.active = len(active)
        self.stats["concurrency_peak"] = max(
            self.stats["concurrency_peak"], len(active))
        if self._paged:
            # page assignments are static per request life, so the table
            # only changes at admission/retire boundaries — one small
            # (n_slots, npages_max) int32 transfer per step
            self._bt = jnp.asarray(self._sched.pool.block_table())
        # speculative rounds only when EVERY active slot is decoding: the
        # draft runs a truncated layer stack, so a prefilling slot (which
        # must populate ALL layers' caches) pins the step to the normal
        # fused path. A degenerate round (every slot at its last token)
        # also falls through.
        if self._spec and not any(st.in_prefill for st in active.values()):
            n = self._spec_round(active, clock, rec)
            if n is not None:
                return n
        B = self.n_slots
        # pure-decode steps stay (B, 1); chunk width only when a prefill
        # slot can use it (each width is one jit specialization)
        C = self._chunk if any(st.in_prefill for st in active.values()) \
            else 1
        tokens = np.zeros((B, C), np.int32)
        pos = np.zeros((B,), np.int32)
        nval = np.zeros((B,), np.int32)
        sparams = blank_slot_params(B)
        samples: Dict[int, bool] = {}
        want_lp = any_sampled = False
        pf_tokens = dec_tokens = 0
        needed = 1
        for slot, st in active.items():
            toks = st.next_tokens(C)
            n = len(toks)
            tokens[slot, :n] = toks
            pos[slot] = st.pos
            nval[slot] = n
            samples[slot] = st.samples_after(n)
            sp = st.request.sampling
            fill_slot_params(sparams, slot, sp,
                             self._base_keys[st.request.rid],
                             len(st.generated))
            want_lp |= sp.logprobs
            any_sampled |= not sp.greedy
            if st.in_prefill:
                pf_tokens += n
            else:
                dec_tokens += n
            needed = max(needed, st.pos + n)
        kv_len = self._bucket(needed)
        sp_dev = {k: jnp.asarray(v) for k, v in sparams.items()}
        if self._sp_shardings is not None:
            sp_dev = jax.device_put(sp_dev, self._sp_shardings)
        pkw = {"block_table": self._bt} if self._paged else {}
        args = (jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(nval),
                sp_dev)
        rec.width, rec.kv_len, rec.pos, rec.nval = C, kv_len, pos, nval
        rec.prefill_tokens, rec.decode_tokens = pf_tokens, dec_tokens
        clock.start("dispatch")
        ids, lps, self._caches, self._seen = self._fused(
            self.params, self._caches, self._seen, *args,
            kv_len=kv_len, want_logprobs=want_lp,
            any_sampled=any_sampled, **pkw)
        clock.start("wait")
        ids = np.asarray(ids)                 # (B,) — the only per-step
        lps = np.asarray(lps) if want_lp else None  # device->host pulls
        clock.start("commit")
        # ONE clock (serve_clock) for step timing AND token timestamps:
        # Completion.ttft_s/latency_s are differences against Request
        # .arrival on the same monotonic base, so they cannot go negative
        now = serve_clock()
        self.stats["steps"] += 1
        self.stats["prefill_tokens"] += pf_tokens
        self.stats["decode_tokens"] += dec_tokens
        for slot, st in active.items():
            st.advance(int(nval[slot]))
            if not samples[slot]:
                continue
            tok = int(ids[slot])
            lp = (float(lps[slot])
                  if lps is not None and st.request.sampling.logprobs
                  else None)
            st.note_token(tok, lp, now=now)
            self._events.append((st.request.rid, tok))
            if st.should_retire():
                self._sched.retire(st.slot)
                self._base_keys.pop(st.request.rid, None)
        return len(active)

    def _spec_round(self, active, clock: _PhaseClock,
                    rec: StepRecord) -> Optional[int]:
        """One speculative draft/verify/rollback round (the step() body
        when speculative mode is on and every active slot is decoding),
        entered in the prepare phase. Returns the active-slot count, or
        None when the round would be degenerate (every slot's per-slot
        draft budget is 0) — the caller then falls through to the normal
        fused step."""
        B = self.n_slots
        # per-slot draft budget: the controller's k, clamped so the round
        # cannot overrun max_new (a round commits <= k_b + 1 tokens) or
        # the slot's cache capacity
        caps: Dict[int, int] = {}
        for slot, st in active.items():
            rem = st.request.sampling.max_new - len(st.generated)
            caps[slot] = max(0, min(self._spec_k.k, rem - 1,
                                    self.max_len - 1 - st.pos))
        k = max(caps.values())
        if k < 1:
            return None
        S = k + 1
        tokens = np.zeros((B, S), np.int32)
        pos = np.zeros((B,), np.int32)
        nval = np.zeros((B,), np.int32)
        caps_arr = np.zeros((B,), np.int32)
        sparams = blank_slot_params(B)
        want_lp = any_sampled = False
        needed = 1
        for slot, st in active.items():
            tokens[slot, 0] = st.next_token()
            pos[slot] = st.pos
            nval[slot] = caps[slot] + 1
            caps_arr[slot] = caps[slot]
            sp = st.request.sampling
            fill_slot_params(sparams, slot, sp,
                             self._base_keys[st.request.rid],
                             len(st.generated))
            want_lp |= sp.logprobs
            any_sampled |= not sp.greedy
            needed = max(needed, st.pos + caps[slot] + 1)
        kv_len = self._bucket(needed)
        sp_dev = {name: jnp.asarray(v) for name, v in sparams.items()}
        if self._sp_shardings is not None:
            sp_dev = jax.device_put(sp_dev, self._sp_shardings)
        pos_dev = jnp.asarray(pos)
        tok_dev = jnp.asarray(tokens[:, :1])
        caps_dev, nval_dev = jnp.asarray(caps_arr), jnp.asarray(nval)
        pkw = {"block_table": self._bt} if self._paged else {}
        rec.width, rec.kv_len, rec.pos, rec.nval = S, kv_len, pos, nval
        clock.start("dispatch")
        # 0. snapshot the ring rows this round will touch (codes+scales)
        snap = None
        if self._spec_has_ring:
            snap = self._spec_snap(self._caches, pos_dev, S=S, **pkw)
        # 1. draft k tokens through the predict-only path — one fused
        # launch for the whole loop (k is jit-static). The seen copy is
        # throwaway (rejected drafts must never reach the persistent
        # repetition-penalty table); self._seen itself is not donated
        # here, so its buffer survives for the verify step.
        tok_mat, q_mat, caches, _ = self._spec_draft(
            self.params, self._caches, self._seen, tok_dev, pos_dev,
            caps_dev, sp_dev, k=k, kv_len=kv_len, any_sampled=any_sampled,
            **pkw)
        # 2. undo the draft's ring writes BEFORE verify: the chunk reads
        # the pre-round window (read-before-write path in decode_attn)
        if self._spec_has_ring:
            caches = self._spec_restore(
                caches, snap, pos_dev, jnp.zeros((B,), jnp.int32), S=S,
                **pkw)
        # 3. fused chunk verify + on-device acceptance
        committed, n_comm, lps, caches, self._seen = self._spec_verify(
            self.params, caches, self._seen, tok_mat, pos_dev, nval_dev,
            sp_dev, q_mat, kv_len=kv_len, want_logprobs=want_lp,
            any_sampled=any_sampled, **pkw)
        clock.start("wait")
        comm_np = np.asarray(committed)
        nc_np = np.asarray(n_comm)
        lps_np = np.asarray(lps) if want_lp else None
        clock.start("commit")
        now = serve_clock()
        # 4. host commit: per-rid deltas strictly in generation order,
        # finish reasons re-checked token-by-token so eos/stop can
        # truncate a round's tail mid-commit
        drafted_total = int(caps_arr.sum())
        accepted_total = committed_total = 0
        starts = np.full((B,), S, np.int32)
        for slot, st in active.items():
            m = int(nc_np[slot])
            accepted_total += m - 1
            done_at = m
            for j in range(m):
                tok = int(comm_np[slot, j])
                st.advance(1)
                lp = (float(lps_np[slot, j])
                      if lps_np is not None
                      and st.request.sampling.logprobs else None)
                st.note_token(tok, lp, now=now)
                self._events.append((st.request.rid, tok))
                if st.should_retire():
                    done_at = j + 1
                    break
            committed_total += done_at
            starts[slot] = done_at
            if st.finish_reason is not None:
                self._sched.retire(st.slot)
                self._base_keys.pop(st.request.rid, None)
        # 5. ring rollback of every uncommitted row — device-rejected
        # suffixes AND host-truncated ones (eos mid-round), so retained
        # prefix donors keep a clean window
        if self._spec_has_ring:
            caches = self._spec_restore(caches, snap, pos_dev,
                                        jnp.asarray(starts), S=S, **pkw)
        self._caches = caches
        self._spec_k.update(accepted_total, drafted_total)
        rec.decode_tokens = committed_total
        self.stats["steps"] += 1
        self.stats["decode_tokens"] += committed_total
        self.stats["spec_rounds"] += 1
        self.stats["spec_drafted"] += drafted_total
        self.stats["spec_accepted"] += accepted_total
        self.stats["spec_k_sum"] += k
        return len(active)

    def stream(self) -> Iterator[Tuple[int, int]]:
        """Drive step() while work remains, yielding (rid, token) deltas
        as each fused step completes — tokens arrive per request the
        step they are sampled, interleaved across the active slots. When
        a step lands SEVERAL tokens for one request (a speculative round
        committing accepted drafts), its deltas are yielded strictly in
        generation order. Finished requests remain collectable via
        collect()."""
        self._ensure_slots()
        while self._sched.has_work:
            self.step()
            yield from self._events

    def _completion(self, st) -> Completion:
        r = st.request
        return Completion(
            rid=r.rid, tokens=tuple(st.generated),
            finish_reason=st.finish_reason or "length",
            prompt_len=len(r.prompt),
            prefix_len=st.prefix_len,
            logprobs=(tuple(st.logprobs) if r.sampling.logprobs
                      else None),
            submitted_at=r.arrival, admitted_at=st.t_admit,
            first_token_at=st.t_first,
            finished_at=st.t_done)

    def collect(self, rid: Optional[int] = None):
        """Pop finished results as typed Completions. With rid: that
        request's Completion (None if not finished). Without:
        {rid: Completion} for every finished request."""
        if self._sched is None:
            return None if rid is not None else {}
        if rid is not None:
            st = self._sched.pop_finished(rid)
            return None if st is None else self._completion(st)
        return {r: self._completion(st)
                for r, st in self._sched.pop_finished().items()}

    def run(self, max_steps: int = 100_000) -> Dict[int, Completion]:
        """Drive step() until queue + slots drain; returns collect().
        Raises if max_steps is exhausted with work still pending, so
        callers never see a silently-partial result set."""
        self._ensure_slots()
        for _ in range(max_steps):
            if not self._sched.has_work:
                break
            self.step()
        if self._sched.has_work:
            raise RuntimeError(
                f"run() exhausted max_steps={max_steps} with "
                f"{len(self._sched.active)} active and "
                f"{self._sched.n_queued} queued requests remaining")
        return self.collect()

    @property
    def has_work(self) -> bool:
        return self._sched is not None and self._sched.has_work

    # ------------------------------------------------------------------
    # static batch (baseline / oracle) — same sampler as the fused step
    # ------------------------------------------------------------------

    def generate(self, prompt_tokens: jax.Array, n_new: Optional[int] = None,
                 *, sampling: Optional[SamplingParams] = None,
                 temperature: float = 0.0, key=None,
                 encoder_frames=None) -> jax.Array:
        """prompt_tokens: (B, S). Returns (B, n) generated ids.

        v2: generate(prompts, sampling=SamplingParams(...)) — row b
        samples under base key jax.random.key(sampling.seed + b) (seed
        defaults to 0), folded by token index, through the SAME
        serve/sampling.sample_rows as the continuous path. The static
        batch always emits the full n tokens per row (fixed output
        shape) — eos_id/stop_token_ids/stop_sequences are scheduler-
        level retirement concerns — so a B=1 seeded call yields the
        undiminished stream of which a continuous request with the same
        SamplingParams returns the PREFIX up to its finish reason
        (token-identical when nothing stops early). The legacy (n_new,
        temperature, key) form is kept for the oracle/baseline callers:
        key=None means greedy; with a key, row b uses fold_in(key, b) as
        its base key. n defaults to sampling.max_new; an explicit n_new
        is capped at sampling.max_new."""
        cfg = self.cfg
        B, S = prompt_tokens.shape
        if sampling is None:
            if n_new is None:
                raise TypeError("generate() needs n_new or sampling=")
            sampled = temperature > 0.0 and key is not None
            sampling = SamplingParams(
                max_new=int(n_new),
                temperature=float(temperature) if sampled else 0.0)
            if sampled:
                base = jax.random.wrap_key_data(
                    jnp.asarray(key_data_of(key)))
                keys = np.stack([
                    np.asarray(jax.random.key_data(
                        jax.random.fold_in(base, b)), np.uint32)
                    for b in range(B)])
            else:
                keys = np.zeros((B, key_width()), np.uint32)
        elif key is not None or temperature != 0.0:
            raise TypeError(
                "pass either sampling=SamplingParams(...) or the legacy "
                "(temperature, key) kwargs — not both")
        else:
            seed = sampling.seed if sampling.seed is not None else 0
            keys = np.stack([base_key_data(seed + b) for b in range(B)])
            if n_new is not None:
                n_new = min(int(n_new), sampling.max_new)
        n = int(n_new) if n_new is not None else sampling.max_new
        assert S + n <= self.max_len
        any_sampled = not sampling.greedy
        sparams = blank_slot_params(B)
        for b in range(B):
            fill_slot_params(sparams, b, sampling, keys[b], 0)
        sp_dev = {k: jnp.asarray(v) for k, v in sparams.items()}
        want_lp = sampling.logprobs
        seen = jnp.zeros((B, cfg.vocab_size), bool)

        logits, caches = prefill(
            self.params, cfg, prompt_tokens, T=self.max_len, mesh=self.mesh,
            encoder_frames=encoder_frames,
            step_fn=lambda p, c, tk, t: self._step(
                p, caches=c, tokens=tk, pos=jnp.asarray(t),
                kv_len=self._bucket(t + 1)))
        seen = self._seen_update(seen, jnp.asarray(prompt_tokens))
        outs, lp_outs = [], []

        def sample_at(logits, t):
            rows = logits[:, -1, :cfg.vocab_size]
            sp_dev["sample_idx"] = jnp.full((B,), t, jnp.int32)
            return self._sample(rows, sp_dev, seen,
                                want_logprobs=want_lp,
                                any_sampled=any_sampled)

        tok, lp = sample_at(logits, 0)
        tok = tok[:, None]
        outs.append(tok)
        lp_outs.append(lp)
        for t in range(1, n):
            logits, caches = self._step(self.params, caches=caches,
                                        tokens=tok,
                                        pos=jnp.asarray(S + t - 1),
                                        kv_len=self._bucket(S + t))
            seen = self._seen_update(seen, tok)
            tok, lp = sample_at(logits, t)
            tok = tok[:, None]
            outs.append(tok)
            lp_outs.append(lp)
        out = jnp.concatenate(outs, axis=1)
        self.last_logprobs = (jnp.stack(lp_outs, axis=1)   # (B, n)
                              if want_lp else None)
        return out
