"""Serving request API v2: typed request/result objects + the batched
on-device sampler.

Three pieces:

* `SamplingParams` — a frozen, validated per-request sampling contract
  (temperature / top-k / top-p / min-p / repetition penalty / stops /
  seed / logprobs). The engine packs the active slots' params into
  per-slot (B,) arrays (`slot_params`) that ride INTO the jitted fused
  step, so filtering + categorical sampling happen on device and only
  the (B,) sampled ids (plus optional chosen-token logprobs) ever
  transfer to host — never a (B, V) logits row.

* `Completion` — the typed result popped from `Engine.collect()/run()`:
  token ids, finish_reason ("stop" | "eos" | "length"), optional
  per-token logprobs, and timing.

* `sample_rows` / `update_seen` — the sampler itself, shared verbatim by
  the continuous fused step (models/decode.decode_sample_step) and the
  static `generate()` oracle, which is what makes seeded sampled decode
  continuous==static testable.

Reproducibility contract: token t of a request is a pure function of
(seed, t, that step's logits row). The per-request base key is
`jax.random.key(seed)` (seed defaults to the request id in the engine),
folded by the per-request SAMPLE INDEX t — not the engine step count —
so a request's stream is independent of which other requests share the
batch, of chunked-prefill scheduling, and of kv-bucket sizing. Static
`generate()` derives row b's key as `jax.random.key(seed + b)` and
always emits its full fixed-shape stream (eos/stop retirement is a
scheduler concern), so a continuous request with seed s returns exactly
the prefix of a B=1 static call's stream up to its finish reason —
token-identical end-to-end when nothing stops early.

Greedy (temperature <= 0) bypasses the filters entirely and argmaxes the
penalty-adjusted row; with the default repetition_penalty=1.0 the
adjustment is a bitwise no-op (x/1.0 and x*1.0 are exact), so greedy
decode is bit-identical to the pre-v2 host argmax.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FINISH_REASONS = ("stop", "eos", "length")


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling contract, validated at construction.

    temperature <= 0 selects greedy decoding (filters are bypassed).
    top_k=0 disables top-k; top_p=1.0 disables nucleus filtering; both
    operate on the temperature-scaled distribution (HF/vLLM order).
    min_p keeps tokens whose probability is >= min_p * max-probability.
    repetition_penalty > 1 demotes every token id previously fed to the
    model for this request (prompt + generated, CTRL-style).
    stop_token_ids / stop_sequences retire the request with
    finish_reason="stop"; stop matching runs over GENERATED tokens only
    and the matched tokens are kept in the completion. Finish-reason
    precedence when several trigger on the same token: eos > stop >
    length. seed=None lets the engine default to the request id.
    """
    max_new: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    repetition_penalty: float = 1.0
    eos_id: Optional[int] = None
    stop_token_ids: Tuple[int, ...] = ()
    stop_sequences: Tuple[Tuple[int, ...], ...] = ()
    seed: Optional[int] = None
    logprobs: bool = False

    def __post_init__(self):
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        if not np.isfinite(self.temperature) or self.temperature < 0.0:
            raise ValueError(f"temperature must be finite and >= 0, got "
                             f"{self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 disables), got "
                             f"{self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if not 0.0 <= self.min_p <= 1.0:
            raise ValueError(f"min_p must be in [0, 1], got {self.min_p}")
        if self.repetition_penalty <= 0.0:
            raise ValueError(f"repetition_penalty must be > 0, got "
                             f"{self.repetition_penalty}")
        # normalize stop specs to hashable int tuples (callers may pass
        # lists / np ints); empty stop sequences are meaningless
        object.__setattr__(self, "stop_token_ids",
                           tuple(int(t) for t in self.stop_token_ids))
        seqs = tuple(tuple(int(t) for t in s) for s in self.stop_sequences)
        if any(len(s) == 0 for s in seqs):
            raise ValueError("empty stop sequence")
        object.__setattr__(self, "stop_sequences", seqs)

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


@dataclass(frozen=True)
class Completion:
    """One finished request, popped from Engine.collect()/run().

    tokens include any matched stop suffix / eos / stop token id;
    finish_reason records why decoding ended. logprobs (present only
    when SamplingParams.logprobs was set) are the chosen tokens'
    log-probabilities under the model's penalty-adjusted, UNscaled
    distribution at each step. prefix_len counts the prompt tokens that
    arrived by prefix-cache copy instead of prefill (0 = cold path; the
    generated tokens are identical either way). Timestamps are
    serve/scheduler.serve_clock() seconds — one monotonic clock for
    every serving timestamp, so ttft_s/latency_s cannot go negative.
    """
    rid: int
    tokens: Tuple[int, ...]
    finish_reason: str
    prompt_len: int = 0
    prefix_len: int = 0
    logprobs: Optional[Tuple[float, ...]] = None
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0

    @property
    def latency_s(self) -> float:
        """submit -> finished wall time."""
        return self.finished_at - self.submitted_at

    @property
    def ttft_s(self) -> float:
        """submit -> first sampled token wall time."""
        return self.first_token_at - self.submitted_at


# ---------------------------------------------------------------------------
# per-slot parameter arrays (the pytree that rides into the jitted step)
# ---------------------------------------------------------------------------

_KEY_WIDTH: Optional[int] = None


def key_width() -> int:
    """uint32 words per PRNG key under the configured default impl
    (2 for threefry, 4 for rbg/unsafe_rbg) — sized once so the key-data
    arrays work under any jax_default_prng_impl."""
    global _KEY_WIDTH
    if _KEY_WIDTH is None:
        _KEY_WIDTH = int(base_key_data(0).shape[0])
    return _KEY_WIDTH


def blank_slot_params(n_slots: int) -> Dict[str, np.ndarray]:
    """Host-side (B,) parameter arrays at inactive-slot defaults (greedy,
    no filtering). The engine overwrites the active slots each step and
    ships the dict into the fused step."""
    return {
        "temperature": np.zeros((n_slots,), np.float32),
        "top_k": np.zeros((n_slots,), np.int32),
        "top_p": np.ones((n_slots,), np.float32),
        "min_p": np.zeros((n_slots,), np.float32),
        "rep_pen": np.ones((n_slots,), np.float32),
        "key": np.zeros((n_slots, key_width()), np.uint32),
        "sample_idx": np.zeros((n_slots,), np.int32),
    }


def fill_slot_params(arrs: Dict[str, np.ndarray], slot: int,
                     sp: SamplingParams, key_data: np.ndarray,
                     sample_idx: int) -> None:
    arrs["temperature"][slot] = sp.temperature
    arrs["top_k"][slot] = sp.top_k
    arrs["top_p"][slot] = sp.top_p
    arrs["min_p"][slot] = sp.min_p
    arrs["rep_pen"][slot] = sp.repetition_penalty
    arrs["key"][slot] = key_data
    arrs["sample_idx"][slot] = sample_idx


def base_key_data(seed: int) -> np.ndarray:
    """uint32 key data of jax.random.key(seed) — the per-request base key
    the sampler folds by sample index. Stored as plain numpy so the
    scheduler/engine bookkeeping stays host-side."""
    return np.asarray(jax.random.key_data(jax.random.key(int(seed))),
                      np.uint32)


def key_data_of(key) -> np.ndarray:
    """Normalize a user-supplied jax PRNG key (typed or legacy uint32)
    to its uint32 key-data array."""
    arr = jnp.asarray(key)
    if jax.dtypes.issubdtype(arr.dtype, jax.dtypes.prng_key):
        return np.asarray(jax.random.key_data(key), np.uint32)
    return np.asarray(arr, np.uint32)


# ---------------------------------------------------------------------------
# the on-device sampler (runs INSIDE the jitted fused step)
# ---------------------------------------------------------------------------

def update_seen(seen, tokens, n_valid=None):
    """Mark this step's fed token ids in the per-slot seen table.

    seen: (B, V) bool — which vocab ids each slot has consumed so far
    (prompt + generated; the repetition-penalty support set). tokens:
    (B, C) int32 fed this step; rows past n_valid are padding and their
    ids are remapped out of range so the scatter drops them."""
    B, C = tokens.shape
    idx = tokens
    if n_valid is not None:
        V = seen.shape[1]
        idx = jnp.where(jnp.arange(C)[None, :] < n_valid[:, None],
                        tokens, V)
    return seen.at[jnp.arange(B)[:, None], idx].set(True, mode="drop")


def _filter_logits(z, top_k, top_p, min_p):
    """Mask (to -inf) tokens excluded by per-slot top-k / top-p / min-p.

    z: (B, V) temperature-scaled logits. All three filters key off ONE
    descending sort. Ties at each threshold are kept (standard), and
    every filter keeps at least the max token, so a row can never be
    fully masked."""
    B, V = z.shape
    srt = jnp.sort(z, axis=-1)[:, ::-1]                    # descending
    # top-k: value threshold at the k-th largest (0 -> disabled)
    k = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    keep = z >= kth
    # top-p: smallest sorted prefix whose mass reaches top_p (position j
    # survives while the mass BEFORE j is < top_p, so j=0 always does).
    # top_p >= 1 maps to +inf: "disabled" must keep every token even
    # when the f32 cumsum saturates to 1.0 before the tail
    probs = jax.nn.softmax(srt, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    p_lim = jnp.where(top_p >= 1.0, jnp.inf, top_p)
    n_keep = jnp.sum(mass_before < p_lim[:, None], axis=-1)
    pth = jnp.take_along_axis(srt, (n_keep - 1)[:, None], axis=-1)
    keep &= z >= pth
    # min-p: prob >= min_p * max-prob  <=>  z >= z_max + log(min_p)
    # (min_p=0 -> log 0 = -inf -> keeps everything)
    keep &= z >= srt[:, :1] + jnp.log(min_p)[:, None]
    return jnp.where(keep, z, -jnp.inf)


def sample_rows(rows, sparams, seen, *, want_logprobs=False,
                any_sampled=True):
    """Batched per-slot sampling on (B, V) logits rows, on device.

    sparams: the slot_params dict ((B,) temperature/top_k/top_p/min_p/
    rep_pen, (B, 2) uint32 key data, (B,) sample_idx). seen: (B, V) bool
    repetition-penalty support set (already updated with this step's fed
    tokens). Greedy slots (temperature <= 0) take the argmax of the
    penalty-adjusted row; sampling slots filter the temperature-scaled
    row and draw via jax.random.categorical under the per-slot key
    fold_in(key, sample_idx). any_sampled is a STATIC flag callers set
    from host-side request metadata: False (an all-greedy batch — the
    oracle/benchmark common case) skips the sort/filter/categorical
    machinery entirely; greedy ids are the same argmax either way.
    Returns (ids (B,) int32, logprobs (B,) f32 or None) — chosen-token
    logprobs are under the penalty-adjusted UNscaled distribution."""
    rows = rows.astype(jnp.float32)
    rp = sparams["rep_pen"][:, None]
    penalized = jnp.where(rows > 0, rows / rp, rows * rp)
    rows = jnp.where(seen, penalized, rows)
    ids = jnp.argmax(rows, axis=-1).astype(jnp.int32)

    if any_sampled:
        temp = sparams["temperature"]
        z = rows / jnp.where(temp > 0, temp, 1.0)[:, None]
        z = _filter_logits(z, sparams["top_k"], sparams["top_p"],
                           sparams["min_p"])
        keys = jax.vmap(jax.random.fold_in)(
            jax.random.wrap_key_data(sparams["key"]),
            sparams["sample_idx"])
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(keys, z)
        ids = jnp.where(temp > 0, sampled.astype(jnp.int32), ids)
    if not want_logprobs:
        return ids, None
    lps = jax.nn.log_softmax(rows, axis=-1)
    return ids, lps[jnp.arange(rows.shape[0]), ids]


# ---------------------------------------------------------------------------
# host-side stop handling (scheduler/RequestState support)
# ---------------------------------------------------------------------------

def finish_reason_for(generated: Sequence[int],
                      sp: SamplingParams) -> Optional[str]:
    """Why (if at all) a request with these generated tokens is done.

    Precedence on the same token: eos > stop (token id, then sequence
    suffix match) > length. Stop sequences suffix-match over GENERATED
    tokens only — a "match" whose head lies in the prompt does not
    count."""
    if not generated:
        return None
    last = generated[-1]
    if sp.eos_id is not None and last == sp.eos_id:
        return "eos"
    if last in sp.stop_token_ids:
        return "stop"
    for seq in sp.stop_sequences:
        if len(generated) >= len(seq) and \
                tuple(generated[-len(seq):]) == seq:
            return "stop"
    if len(generated) >= sp.max_new:
        return "length"
    return None
