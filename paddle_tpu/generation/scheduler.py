"""Continuous-batching scheduler: prefill/decode split over fixed slots.

Static batching pads every request to the longest sequence and holds the
whole batch until the slowest member finishes; continuous batching
(the Ragged Paged Attention serving model) instead keeps a fixed set of
decode SLOTS and lets sequences join and leave every step:

    submit() -> AdmissionQueue -> [pending] -> slot: PREFILL -> DECODE loop
                 (bounded,                      (page capacity              \
                  typed busy/deadline           gated)                       -> retire: free pages
                  rejection)                                                /   + slot
                                   preempt (pages exhausted): pages freed,
                                   sequence re-queued for RE-PREFILL

Admission reuses the serving subsystem's AdmissionQueue verbatim — a
full queue rejects with ServerBusyError at submit, deadline-expired
requests resolve with DeadlineExceededError on any scan — with the
counters landing under `generation.*` (GenerationMetrics implements the
queue's metrics interface).

Preemption is recompute-style: the victim's pages return to the pool and
its tokens-so-far become a new prefill when capacity returns.  Because
sampling state is per-request (seeded RNG) and prefill logits at the
last position equal the decode logits for the same prefix, a preempted
sequence resumes token-identically — preemption changes WHEN tokens are
computed, never WHICH.
"""
import collections
import math
import time

from ..serving.admission import (AdmissionQueue, DeadlineExceededError,
                                 Request, RequestTooLargeError, ServingError)
from .kv_cache import OutOfPagesError, UnknownSequenceError


class GenerationRequest(Request):
    """One generation request riding the serving AdmissionQueue.

    `args` carries the prompt token ids; `future` is the streaming
    GenerationHandle (duck-typed: done()/set_exception(), so the queue's
    deadline reaping resolves it with the typed error)."""

    __slots__ = ("prompt", "max_new_tokens", "stop_tokens", "params")

    def __init__(self, prompt, handle, params, max_new_tokens=16,
                 stop_tokens=(), deadline=None):
        super().__init__(list(prompt), 1, handle, deadline=deadline)
        self.prompt = [int(t) for t in prompt]
        if not self.prompt:
            raise ValueError("prompt must contain at least one token")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {max_new_tokens}")
        self.stop_tokens = frozenset(int(t) for t in stop_tokens)
        self.params = params


class SequenceState:
    """One sequence occupying a decode slot (or awaiting re-admission
    after preemption).  `tokens` is prompt + everything sampled so far;
    the KV cache holds entries for exactly `tokens[:cache_len]`.

    `prefilling` / `prefill_pos` track the prefill→decode transition:
    a freshly admitted (or preempted-and-readmitted) sequence is
    `prefilling` with `prefill_pos` tokens already written to the cache;
    chunked prefill advances `prefill_pos` one chunk per step, full
    prefill jumps it to the whole prompt in one go.  Only sequences
    with `prefilling == False` join the decode batch.  `prewarmed`
    remembers that the fused-decode executable this sequence will land
    in was already pre-compiled mid-prefill (at most one pre-warm per
    prefill)."""

    __slots__ = ("seq_id", "request", "tokens", "n_generated", "rng",
                 "slot", "preemptions", "prefilling", "prefill_pos",
                 "prewarmed")

    def __init__(self, seq_id, request):
        self.seq_id = seq_id
        self.request = request
        self.tokens = list(request.prompt)
        self.n_generated = 0
        self.rng = request.params.make_rng()
        self.slot = None
        self.preemptions = 0
        self.prefilling = True
        self.prefill_pos = 0
        self.prewarmed = False

    @property
    def handle(self):
        return self.request.future


class ContinuousBatchingScheduler:
    """Owns the admission queue, the decode slots, and the page-capacity
    admission gate.  The engine drives it: admit() -> prefill work,
    active() -> the decode batch, retire()/preempt_for_pages() on exit
    paths."""

    def __init__(self, cache, num_slots=8, queue_depth=64, metrics=None,
                 prefix_cache=False):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.cache = cache
        self.num_slots = int(num_slots)
        self.queue = AdmissionQueue(queue_depth, metrics=metrics)
        self._metrics = metrics
        # prefix caching: admission looks up the longest cached page
        # run for every placed sequence and aliases it (the engine
        # flips this after resolving its prefill-path policy — a warm
        # hit resumes prefill MID-prompt, which needs a chunk-capable
        # prefill path)
        self.prefix_cache = bool(prefix_cache)
        self.slots = [None] * self.num_slots
        # polled-but-not-yet-placed work: new requests waiting for pages,
        # and preempted SequenceStates waiting to re-prefill (these take
        # priority — they already consumed steps)
        self._pending = collections.deque()
        self._next_seq = 0

    # ------------------------- submission ---------------------------
    def submit(self, request):
        """Bounded admission; raises ServerBusyError when full and
        RequestTooLargeError when the prompt can never fit the pool."""
        need = self._pages_for(len(request.prompt) + 1)
        if need > self.cache.num_pages:
            raise RequestTooLargeError(
                f"prompt of {len(request.prompt)} tokens needs {need} "
                f"pages; the pool only has {self.cache.num_pages}")
        self.queue.offer(request)

    def _pages_for(self, tokens):
        return math.ceil(tokens / self.cache.page_size)

    # ------------------------- admission ----------------------------
    def free_slots(self):
        return sum(1 for s in self.slots if s is None)

    def active(self):
        """Sequences currently holding decode slots, slot order."""
        return [s for s in self.slots if s is not None]

    def decode_ready(self):
        """Slot-holders whose prefill is complete — the decode batch.
        Mid-prefill sequences hold their slot (they will decode there)
        but never join a decode dispatch."""
        return [s for s in self.slots
                if s is not None and not s.prefilling]

    def prefilling(self):
        """Slot-holders mid-prefill, oldest (smallest seq_id) first —
        chunked prefill serves them FIFO, one chunk per step."""
        return sorted((s for s in self.slots
                       if s is not None and s.prefilling),
                      key=lambda s: s.seq_id)

    def plan_pack(self, chunk_tokens, room=None, max_seqs=None):
        """Prefill plan for one engine step: MULTIPLE prompts' chunks
        packed FIFO into `room` tokens (the RPA-paper packing rule —
        short prompts stop queueing behind long ones for TTFT).

        The oldest mid-prefill sequence gets its next
        ``min(chunk_tokens, remaining prompt, room)`` tokens first —
        exactly the old one-chunk plan — then the step's LEFTOVER room
        goes to the next prompts in FIFO order, each clipped the same
        way, until the room (None = unbounded), the descriptor budget
        `max_seqs`, or the prefilling line runs out.  Returns
        ``[(state, n), ...]`` (possibly empty).

        The decode batch ALWAYS runs alongside; there is no token-budget
        competition and no decode-owed debt anymore.  The old dance
        existed because the legacy step paid two dispatches (chunk +
        decode) whose combined token work a tight budget had to
        arbitrate by stalling one of them; the ragged step put both in
        ONE dispatch whose token axis is sized for the full decode batch
        plus a chunk by construction, and the legacy chunked path
        inherits the same plan (each packed chunk is its own
        dispatch there, the packed-axis room its per-step prefill token
        budget)."""
        pack = []
        left = None if room is None else int(room)
        for cand in self.prefilling():
            if left is not None and left <= 0:
                break
            if max_seqs is not None and len(pack) >= max_seqs:
                break
            n = min(int(chunk_tokens), len(cand.tokens) - cand.prefill_pos)
            if left is not None:
                n = min(n, left)
            if n <= 0:
                continue
            pack.append((cand, n))
            if left is not None:
                left -= n
        return pack

    def plan_spec(self, proposer, spec_tokens, room=None):
        """Draft plan for one SPECULATIVE ragged step: ask the
        prompt-lookup proposer for up to `spec_tokens` draft
        continuations per GREEDY decode-ready sequence, slot order
        (the packed-axis order, so the room clip is deterministic).
        Returns ``{seq_id: [draft ids]}`` — rows absent from the plan
        decode exactly as today.

        Three clips keep speculation a pure optimization:

        - stochastic rows never speculate (the accept rule compares
          argmax against argmax; a sampled token has no draft to
          verify against);
        - a row drafts at most ``remaining_budget - 1`` tokens — the
          step emits accepted + 1 tokens and the final sampled token
          is never cache-resident, so drafting past the request's
          max_new_tokens would reserve positions the model can never
          legally hold;
        - `room` (the packed token axis's leftover after the one-token
          decode rows) bounds the TOTAL drafts FIFO, so speculation
          can never push a decode row or the step's prefill-chunk row
          out of the fixed axis."""
        plan = {}
        left = None if room is None else int(room)
        # persistent-index proposers (NgramProposer.propose_for) index
        # incrementally per sequence; evict finished sequences' indexes
        # first, then catch each live row's index up to its history.
        # Duck-typed so any propose(history, k) object still plugs in.
        propose_for = getattr(proposer, "propose_for", None)
        if propose_for is not None:
            live = {s.seq_id for s in self.active()}
            live.update(s.seq_id for s in self._pending
                        if isinstance(s, SequenceState))
            proposer.retain(live)
        for state in self.decode_ready():
            if left is not None and left <= 0:
                break
            if not state.request.params.greedy:
                continue
            remaining = state.request.max_new_tokens - state.n_generated
            k = min(int(spec_tokens), remaining - 1)
            if left is not None:
                k = min(k, left)
            if k <= 0:
                continue
            drafts = (propose_for(state.seq_id, state.tokens, k)
                      if propose_for is not None
                      else proposer.propose(state.tokens, k))
            if not drafts:
                continue
            plan[state.seq_id] = drafts
            if left is not None:
                left -= len(drafts)
        return plan

    def plan_step(self, chunk_tokens, max_chunk=None):
        """The single-chunk view of plan_pack (the oldest mid-prefill
        sequence's next chunk, clipped to `max_chunk`), as
        ``(chunk_state, chunk_len)`` or ``(None, 0)`` — kept for
        callers that dispatch exactly one chunk."""
        pack = self.plan_pack(chunk_tokens, room=max_chunk, max_seqs=1)
        return pack[0] if pack else (None, 0)

    def _place(self, state):
        for i, s in enumerate(self.slots):
            if s is None:
                state.slot = i
                self.slots[i] = state
                # under which id this engine's steps list the request
                # (a traced ragged_step's `seqs`); a live-migrated
                # resident gets its new engine's here
                state.handle.seq_id = state.seq_id
                return
        raise AssertionError("no free slot (checked by caller)")

    def next_seq_id(self):
        """Allocate one sequence id outside the admission path — the
        live-migration import (engine.import_sequence) builds its
        SequenceState directly, bypassing the queue."""
        sid = self._next_seq
        self._next_seq += 1
        return sid

    def place_imported(self, state):
        """Seat a live-migrated SequenceState straight into a free slot
        (the caller verified free_slots() > 0 and installed its pages):
        migration moves a resident, it never queues one."""
        self._place(state)

    def admit(self, limit=None):
        """Move work into free slots while pages allow; returns the newly
        placed SequenceStates (each needs a prefill over state.tokens).
        Head-of-line on capacity: admission stops at the first item that
        doesn't fit, preserving arrival order.  `limit` caps admissions
        per call — the engine passes its prefill batch size, so one
        step's prefill work is one batched chunk, never a whole queue
        (prefill/decode interleaving keeps time-to-next-token bounded
        for sequences already decoding).

        With the prefix cache on, each placement first looks up the
        longest cached page run for the sequence's tokens and ALIASES it
        (adopt_prefix — zero bytes moved, refcounts bumped, prefill_pos
        advanced past the matched span), so the page-need accounting
        charges only the divergent suffix: total pages minus aliased
        pages, plus one copy-on-write page when the match was clipped
        mid-page.  The capacity gate compares against available_pages
        (free + evictable cached runs): a resident cache can always be
        reclaimed for admission, so it never blocks the front of the
        line.  Preempted sequences re-match on re-admission — their own
        prompt's cached run typically survives them, turning a
        recompute-preemption re-prefill into a warm resume."""
        admitted = []
        wg = self.cache.window_group  # None: every layer keeps all
        committed_window = 0
        committed = 0  # pages promised to THIS call's earlier admits
        # (their prefills run after admit() returns, so available pages
        # alone would let several admits all claim the same free pages)
        while self.free_slots() > 0 and (limit is None
                                         or len(admitted) < limit):
            item = self._pending.popleft() if self._pending else \
                self.queue.poll(timeout=0)
            if item is None:
                break
            if isinstance(item, SequenceState):
                state, req = item, item.request
            else:
                state, req = None, item
            if req.expired():
                req.reject_expired()
                if self._metrics is not None:
                    self._metrics.count_rejected_deadline()
                continue
            readmitted = state is not None
            token_list = state.tokens if state else req.prompt
            tokens = len(token_list)
            match_pages, match_tokens = ((), 0)
            if self.prefix_cache:
                match_pages, match_tokens = \
                    self.cache.match_prefix(token_list)
            # +1: room for the first decode append after prefill;
            # aliased pages are free of charge, a clipped match owes
            # its tail page's copy-on-write
            need = self._pages_for(tokens + 1) - len(match_pages)
            if match_tokens % self.cache.page_size:
                need += 1
            # matched refcount-0 pages leave the evictable set the
            # moment adoption pins them: they must not count as BOTH
            # aliased-for-free (excluded from need) and evictable (in
            # available_pages), or the suffix reserve could fail after
            # the gate passed instead of waiting in line
            avail = (self.cache.available_pages
                     - self.cache.evictable_pages_in(match_pages))
            # where some layers keep only a window, a context is
            # charged there for its window and a chunk at the most, so
            # a long prompt is admitted on its full-group need and a
            # window's worth of the other group
            need_window = wg.pages_for(tokens + 1) if wg is not None else 0
            if (need > avail - committed or (
                    wg is not None
                    and need_window > wg.free_pages - committed_window)) \
                    and (self.active() or self._pending or admitted):
                # not enough pages now, but retiring sequences will free
                # some — wait in line rather than rejecting
                self._pending.appendleft(item)
                break
            committed += need
            committed_window += need_window
            if state is None:
                state = SequenceState(self._next_seq, req)
                self._next_seq += 1
            self.cache.allocate(state.seq_id)
            if match_tokens:
                # same-step adoption: the incref pins the matched pages
                # before any later reserve() could evict them
                self.cache.adopt_prefix(state.seq_id, match_pages,
                                        match_tokens)
                state.prefill_pos = match_tokens
            handle = state.handle
            if getattr(handle, "admitted_s", None) is None:
                # first admission only, like prefix_hit_tokens below: a
                # preempted sequence's re-admission does not move it
                handle.admitted_s = time.monotonic()
            if getattr(handle, "prefix_hit_tokens", 0) is None:
                # first admission stamps the handle: the serving tier
                # reads warm-vs-cold per request, not per re-admission
                handle.prefix_hit_tokens = match_tokens
            if self.prefix_cache and self._metrics is not None \
                    and not readmitted:
                # hit counters measure CROSS-REQUEST sharing, so only
                # first admissions count: a preempted re-admission
                # re-matching its own run (prompt + generated tokens)
                # would inflate the rate without any sharing — its
                # savings are already visible in prefill_tokens_total
                self._metrics.count_prefix_lookup(match_tokens, tokens)
            self._place(state)
            admitted.append(state)
        return admitted

    # ------------------------- exit paths ---------------------------
    def retire(self, state):
        """Sequence left the batch (finished or failed): free its slot
        and every page it owns."""
        if state.slot is not None:
            self.slots[state.slot] = None
            state.slot = None
        if self.cache.has(state.seq_id):
            self.cache.free(state.seq_id)

    def preempt(self, state):
        """Recompute-preempt: free pages + slot, queue for re-prefill at
        the FRONT of the pending line (it has seniority over new work).
        A mid-prefill victim restarts its prefill from position 0 — its
        pages are gone, and chunked prefill re-chunks the whole prefix
        on re-admission (the preemption oracle covers this)."""
        self.retire(state)
        state.preemptions += 1
        state.prefilling = True
        state.prefill_pos = 0
        state.prewarmed = False
        self._pending.appendleft(state)

    def preempt_youngest(self, exclude=None):
        """Preempt the single youngest active sequence (most recently
        admitted = least sunk cost) and return it — unless it is the
        only one, in which case return None: the batch must keep making
        progress, so the lone/oldest sequence is never preempted.  The
        caller re-evaluates capacity after every single preemption (a
        victim's own page need leaves the books with it, so a batchwide
        shortfall computed up front would over-preempt or give up too
        early).  `exclude` shields one sequence (the one whose prefill
        chunk needs the pages — preempting it to feed itself would free
        nothing it can keep)."""
        active = [s for s in self.active() if s is not exclude]
        if not active or (exclude is None and len(active) < 2):
            return None
        victim = max(active, key=lambda s: s.seq_id)
        self.preempt(victim)
        return victim

    def pending_count(self):
        return len(self._pending) + len(self.queue)

    def take_pending(self):
        """Pull every NOT-YET-PLACED item out of the scheduler — the
        pending line first (preempted sequences have seniority), then
        the admission queue in FIFO order — for a fleet-tier drain
        (engine.evacuate): the caller resubmits each request elsewhere.
        Returns ``[(GenerationRequest, n_emitted)]`` where `n_emitted`
        is how many tokens the request has already streamed (nonzero
        only for preempted SequenceStates; their pages were freed at
        preemption, so nothing else needs releasing).  Expired requests
        are reaped with the typed deadline error on the way, exactly as
        a queue poll would."""
        out = []
        while self._pending:
            item = self._pending.popleft()
            if isinstance(item, SequenceState):
                if item.request.expired():
                    item.request.reject_expired()
                    if self._metrics is not None:
                        self._metrics.count_rejected_deadline()
                    continue
                out.append((item.request, item.n_generated))
            else:
                out.append((item, 0))
        while True:
            req = self.queue.poll(timeout=0)   # reaps expired itself
            if req is None:
                break
            out.append((req, 0))
        return out

    def cancel_pending(self, handle):
        """Remove the not-yet-placed item owned by `handle` (pending
        re-prefill line or admission queue) WITHOUT resolving it — the
        engine's cancel path owns the resolution.  Returns the removed
        item (a preempted SequenceState or a queued GenerationRequest)
        or None when nothing pending matches (it may be active,
        finished, or elsewhere).  Preempted SequenceStates freed their
        pages at preemption, so dropping the entry is the whole
        cleanup."""
        for i, item in enumerate(self._pending):
            owner = item.handle if isinstance(item, SequenceState) \
                else item.future
            if owner is handle:
                del self._pending[i]
                return item
        taken = self.queue.remove(lambda r: r.future is handle)
        return taken[0] if taken else None

    def close(self):
        """Reject everything still queued (typed shutdown error)."""
        self.queue.close()
        while self._pending:
            item = self._pending.popleft()
            fut = item.handle if isinstance(item, SequenceState) else \
                item.future
            if not fut.done():
                try:
                    fut.set_exception(ServingError(
                        "generation engine shut down with request queued"))
                except Exception:
                    pass


__all__ = [
    "ContinuousBatchingScheduler", "GenerationRequest", "SequenceState",
    "DeadlineExceededError", "OutOfPagesError", "UnknownSequenceError",
]
