"""Request scheduler for continuous batching.

Pure host-side bookkeeping — no jax. The scheduler owns the mapping from
requests to cache slots:

  submit(prompt, SamplingParams) -> admission queue (FIFO)
  admit()  -> pops queued requests into free slots (in-flight batching)
  note_token() / should_retire() -> per-request finish tracking
  retire() -> frees the slot for recycling

The engine (serve/engine.py) drives it: one admit() before every fused
step, one retire() per finished request after sampling. Slot recycling is
safe without touching attention caches — a recycled slot rewrites cache
positions 0..pos sequentially and per-slot position masking hides stale
rows; only recurrent state (rwkv/mamba) needs an explicit reset, which
the engine performs at admission (models/decode.reset_slot).

Request lifecycle:  QUEUED -> PREFILL -> DECODE -> FINISHED
(PREFILL consumes prompt tokens — possibly several per fused step under
chunked prefill — DECODE consumes the last sampled token; the phase
boundary is where sampling starts.) A request finishes with a typed
reason — "eos" | "stop" | "length" (serve/sampling.finish_reason_for
defines the precedence) — and stop-sequence suffix matching over the
generated tokens happens HERE, in RequestState.should_retire().

Prefix caching (`SlotScheduler(prefix_cache=True)`): a host-side trie
over prompt token ids (`PrefixIndex`) maps every admitted request's
prompt to its slot. On admission the queue head is matched against the
index — the donor may be a RESIDENT slot (its request still decoding;
rows 0..pos-1 are written and append-only) or a RETAINED one (the
request retired but its slot was kept out of the free pool as a cached
prefix, evicted LRU when admission needs capacity). A hit hands the
engine (donor_slot, p): the engine clones the first p cache rows
(models/decode.copy_prefix), seeds the slot's repetition-penalty seen
row from the prefix ids, sets the slot position to p, and prefills only
the suffix. Matched donors are refcount-pinned from match until the
engine confirms the copy (release_donor), so a donor can never be
evicted out from under a pending copy — with one deliberate exception:
when no other slot is available, a retained donor pinned only by its
own match is handed to the matching request itself (src == dst, the
copy is a no-op and the prefix rows are reused in place).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

from repro.serve.sampling import SamplingParams, finish_reason_for


def serve_clock() -> float:
    """THE serving clock. Every serving timestamp — Request.arrival,
    RequestState.t_admit/t_first/t_done, the engine's step timing — reads this
    one monotonic clock, so Completion.ttft_s/latency_s are differences
    on a single time base and can never go negative from clock mixing
    (time.monotonic and time.perf_counter have unrelated epochs)."""
    return time.monotonic()


@dataclass
class Request:
    rid: int
    prompt: List[int]
    sampling: SamplingParams
    arrival: float = 0.0            # serve_clock() at submit


@dataclass
class RequestState:
    """One in-flight request pinned to a slot.

    pos    : model position of the NEXT token to feed (== tokens consumed)
    cursor : index into prompt of the next token to feed

    On a prefix-cache hit, pos and cursor START at prefix_len: the first
    prefix_len cache rows arrive by slot-to-slot copy from prefix_src
    (the donor slot; == slot for the self-donor reuse path) and only the
    prompt suffix is prefilled.
    """
    request: Request
    slot: int
    pos: int = 0
    cursor: int = 0
    generated: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    finish_reason: Optional[str] = None
    t_admit: float = 0.0            # admission into a slot (serve_clock)
    t_first: float = 0.0            # first sampled token (serve_clock)
    t_done: float = 0.0             # retirement (serve_clock)
    prefix_len: int = 0             # cache rows inherited from a donor
    prefix_src: Optional[int] = None      # donor slot of the hit
    donor_entry: Optional["PrefixEntry"] = None   # pinned until copied
    paged: Optional[dict] = None    # paged-admission actions (paging.py)

    @property
    def in_prefill(self) -> bool:
        return self.cursor < len(self.request.prompt)

    def next_tokens(self, budget: int) -> List[int]:
        """Tokens to feed at pos..pos+n-1 this step (chunked prefill):
        up to `budget` prompt tokens while prefilling, else the single
        last sampled token."""
        if self.in_prefill:
            return self.request.prompt[self.cursor: self.cursor + budget]
        return [self.generated[-1]]

    def next_token(self) -> int:
        """Single-token (budget-1) form of next_tokens."""
        return self.next_tokens(1)[0]

    def samples_after(self, n: int) -> bool:
        """Whether feeding the next `n` tokens reaches the last prompt
        token, i.e. this step's logits (row n-1) are sampled."""
        return not self.in_prefill or \
            self.cursor + n >= len(self.request.prompt)

    @property
    def samples_this_step(self) -> bool:
        """Sampling starts at the LAST prompt token's logits
        (single-token form of samples_after)."""
        return self.samples_after(1)

    def advance(self, n: int = 1) -> None:
        if self.in_prefill:
            self.cursor += n
        self.pos += n

    def note_token(self, token: int, logprob: Optional[float] = None,
                   now: Optional[float] = None) -> None:
        if not self.generated:
            self.t_first = serve_clock() if now is None else now
        self.generated.append(token)
        if logprob is not None:
            self.logprobs.append(logprob)

    def should_retire(self) -> bool:
        """Check eos / stop-token / stop-sequence / max_new against the
        generated tokens; records the finish reason when one fires."""
        reason = finish_reason_for(self.generated, self.request.sampling)
        if reason is not None:
            self.finish_reason = reason
        return reason is not None


class PrefixEntry:
    """One donor in the prefix index: the slot whose cache holds valid
    rows for the first `depth` fed tokens of `tokens` (the registering
    request's prompt; rows beyond the prompt hold its generated tokens
    and are never matched). While the request is in flight, depth tracks
    its live RequestState.pos; on retirement the slot is RETAINED and
    depth freezes at the final fill. refcount pins the entry against LRU
    eviction from match until the engine's copy lands."""

    __slots__ = ("rid", "slot", "tokens", "_depth", "state", "retained",
                 "refcount", "last_used", "pages", "spilled", "blob")

    def __init__(self, rid: int, slot: int, tokens: Sequence[int],
                 state: Optional[RequestState] = None):
        self.rid = rid
        self.slot = slot
        self.tokens: Tuple[int, ...] = tuple(tokens)
        self._depth = 0
        self.state = state              # live while the request is active
        self.retained = False
        self.refcount = 0
        self.last_used = 0
        # paged mode (serve/paging.PagedScheduler): device pages owned by
        # a retained entry, plus the host-tier spill state
        self.pages: Optional[List[int]] = None
        self.spilled = False
        self.blob = None                # host numpy pytree when spilled

    @property
    def depth(self) -> int:
        """Written cache rows of the donor slot, live for active donors."""
        return self.state.pos if self.state is not None else self._depth

    def retain(self) -> None:
        self._depth = self.depth
        self.state = None
        self.retained = True


class _TrieNode:
    __slots__ = ("children", "entries")

    def __init__(self):
        self.children: Dict[int, "_TrieNode"] = {}
        self.entries: Dict[int, PrefixEntry] = {}     # rid -> entry


class PrefixIndex:
    """Token trie over registered prompts -> donor slots.

    Every entry appears at each trie node along its prompt's path, so a
    lookup walks the query prompt once and evaluates each candidate at
    the DEEPEST shared node — i.e. at its exact longest-common-prefix
    length with the query. Size is bounded by the slot count (every
    donor occupies a slot), so per-node entry maps stay tiny."""

    def __init__(self):
        self._root = _TrieNode()
        self._entries: Dict[int, PrefixEntry] = {}
        self._tick = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, rid: int) -> Optional[PrefixEntry]:
        return self._entries.get(rid)

    def touch(self, entry: PrefixEntry) -> None:
        self._tick += 1
        entry.last_used = self._tick

    def insert(self, entry: PrefixEntry) -> None:
        node = self._root
        for tok in entry.tokens:
            node = node.children.setdefault(tok, _TrieNode())
            node.entries[entry.rid] = entry
        self._entries[entry.rid] = entry
        self.touch(entry)

    def remove(self, rid: int) -> Optional[PrefixEntry]:
        entry = self._entries.pop(rid, None)
        if entry is None:
            return None
        node, path = self._root, []
        for tok in entry.tokens:
            path.append((node, tok))
            node = node.children[tok]
            node.entries.pop(rid, None)
        # prune now-empty suffix nodes so the trie never outgrows the
        # live entry set
        for parent, tok in reversed(path):
            child = parent.children[tok]
            if child.entries or child.children:
                break
            del parent.children[tok]
        return entry

    def match(self, prompt: Sequence[int],
              usable_len: Callable[[int, PrefixEntry], int]
              ) -> Tuple[Optional[PrefixEntry], int]:
        """Best donor for `prompt`: walk the trie along the prompt, and
        for each candidate entry (evaluated once, at its deepest shared
        node = its exact LCP with the prompt) ask `usable_len(lcp,
        entry)` how many rows are actually reusable — the caller caps by
        donor fill depth and applies the model-kind validity rules
        (ring-wraparound, recurrent-boundary). Returns (entry, p) with
        the largest usable p, or (None, 0). Ties prefer the most
        recently used donor (LRU freshness)."""
        node, nodes = self._root, []
        for tok in prompt:
            node = node.children.get(tok)
            if node is None:
                break
            nodes.append(node)
        best, best_p, seen = None, 0, set()
        for lcp in range(len(nodes), 0, -1):          # deepest first
            for rid, entry in nodes[lcp - 1].entries.items():
                if rid in seen:
                    continue
                seen.add(rid)
                p = usable_len(lcp, entry)
                if p > best_p or (p == best_p and p > 0 and
                                  entry.last_used > best.last_used):
                    best, best_p = entry, p
        return best, best_p


class SlotScheduler:
    """Admission queue + slot allocator for `n_slots` concurrent requests.

    With prefix_cache=True the scheduler also maintains the PrefixIndex:
    admitted prompts are registered, retiring requests RETAIN their slot
    as a cached prefix instead of freeing it (LRU-evicted when admission
    needs capacity), and each admitted RequestState carries its matched
    (prefix_src, prefix_len) for the engine's cache copy.
    prefix_usable_len(p, depth) -> int is the engine's model-kind
    validity hook (ring windows, recurrent boundaries); it sees p
    already capped to min(LCP, donor depth, prompt_len - 1)."""

    def __init__(self, n_slots: int, max_len: int, *,
                 prefix_cache: bool = False,
                 prefix_usable_len: Optional[
                     Callable[[int, int], int]] = None):
        assert n_slots >= 1
        self.n_slots = n_slots
        self.max_len = max_len
        self._free: Deque[int] = deque(range(n_slots))
        self._queue: Deque[Request] = deque()
        self.active: Dict[int, RequestState] = {}     # slot -> state
        self.finished: Dict[int, RequestState] = {}   # rid  -> state
        self._next_rid = 0
        self.prefix_cache = prefix_cache
        self._usable_len = prefix_usable_len or (lambda p, depth: p)
        self.index = PrefixIndex() if prefix_cache else None
        self.retained: Dict[int, PrefixEntry] = {}    # slot -> entry

    # -- submission --------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               sampling: SamplingParams) -> int:
        """Enqueue one request under a validated SamplingParams (the
        per-request sampling contract; max_new/eos/stops live there)."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + sampling.max_new > self.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({sampling.max_new}) "
                f"exceeds max_len={self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid, prompt, sampling,
                                   arrival=serve_clock()))
        return rid

    # -- prefix cache ------------------------------------------------------
    def _match_prefix(self, req: Request) -> Tuple[Optional[PrefixEntry],
                                                   int]:
        """Freshest-possible lookup (donor depths move between steps, so
        matching happens at ADMISSION, not submit): LCP capped by donor
        fill depth and prompt_len - 1 (at least one suffix token must
        prefill — sampling needs the last prompt token's logits), then
        the engine's model-kind validity hook."""
        cap = len(req.prompt) - 1

        def usable(lcp: int, entry: PrefixEntry) -> int:
            p = min(lcp, entry.depth, cap)
            return self._usable_len(p, entry.depth) if p > 0 else 0

        return self.index.match(req.prompt, usable)

    def _evict(self, entry: PrefixEntry) -> int:
        """Drop a retained entry from the index and reclaim its slot."""
        self.index.remove(entry.rid)
        del self.retained[entry.slot]
        return entry.slot

    def _acquire_slot(self) -> Optional[int]:
        """A free slot, else the LRU unpinned retained slot, else None."""
        if self._free:
            return self._free.popleft()
        victims = [e for e in self.retained.values() if e.refcount == 0]
        if victims:
            return self._evict(min(victims, key=lambda e: e.last_used))
        return None

    def release_donor(self, st: RequestState) -> None:
        """Unpin st's matched donor once the engine's copy has landed
        (called for every admitted state; no-op on a cold admission)."""
        if st.donor_entry is not None:
            st.donor_entry.refcount -= 1
            st.donor_entry = None

    @property
    def n_retained(self) -> int:
        return len(self.retained)

    # -- slot allocation ---------------------------------------------------
    def admit(self) -> List[RequestState]:
        """Move queued requests into slots (FIFO). Returns the newly
        admitted states — the engine must reset their recurrent cache
        rows (and their seen-table row), and perform the prefix-cache
        copy for states with prefix_len > 0, before the next fused step
        (in admission order: an earlier admission may be a later one's
        donor), then release_donor() each state."""
        admitted = []
        while self._queue:
            req = self._queue[0]
            donor, p = (self._match_prefix(req) if self.prefix_cache
                        else (None, 0))
            if donor is not None:
                donor.refcount += 1           # pin across slot acquisition
            slot = self._acquire_slot()
            if slot is None and donor is not None and donor.retained:
                # last resort: hand the donor slot to the matching request
                # itself — src == dst, the prefix rows are reused in place.
                # Pins held by EARLIER admissions in this same batch don't
                # block the handoff: the engine performs copies in
                # admission order, so their reads of the donor rows land
                # before the new occupant's first write.
                batch_pins = sum(1 for a in admitted
                                 if a.donor_entry is donor)
                if donor.refcount == 1 + batch_pins:
                    slot = self._evict(donor)
            if slot is None:
                if donor is not None:
                    donor.refcount -= 1
                break
            self._queue.popleft()
            st = RequestState(request=req, slot=slot, t_admit=serve_clock())
            if donor is not None:
                st.prefix_len, st.prefix_src = p, donor.slot
                st.pos = st.cursor = p
                st.donor_entry = donor
                self.index.touch(donor)
            self.active[slot] = st
            if self.prefix_cache:
                self.index.insert(PrefixEntry(req.rid, slot, req.prompt,
                                              state=st))
            admitted.append(st)
        return admitted

    def retire(self, slot: int) -> RequestState:
        """Finish the request in `slot` and recycle the slot — into the
        free pool, or (prefix_cache) retained as a cached prefix until
        LRU eviction."""
        st = self.active.pop(slot)
        st.t_done = serve_clock()
        self.finished[st.request.rid] = st
        entry = self.index.get(st.request.rid) if self.prefix_cache \
            else None
        if entry is not None:
            entry.retain()
            self.retained[slot] = entry
            self.index.touch(entry)
        else:
            self._free.append(slot)
        return st

    # -- introspection -----------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self.active or self._queue)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def pop_finished(self, rid: Optional[int] = None):
        """Remove + return finished state(s): one by rid, or all."""
        if rid is not None:
            return self.finished.pop(rid, None)
        out = self.finished
        self.finished = {}
        return out
