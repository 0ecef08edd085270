"""The one general traffic generator.

A traffic mix is a JSON file of parameters; `schedule()` turns it and a
seed into requests, and `LoadDriver` offers them to a server from one
thread.  No JAX here: the same seed gives the same schedule, lengths and
prompts on any machine.

Serving mixes (`"kind": "serve"`):

    loop        "open": arrivals on a schedule whether or not earlier
                requests have finished; "closed": `clients` callers, each
                sending its next request when its last one completed
    arrivals    open loop: {"process": "poisson" | "gamma", "rate_per_s",
                "cv" (gamma only: coefficient of variation of the gaps),
                "block_s" (poisson only: the count in every block of so
                many seconds is fixed at its mean)}
    clients     closed loop: number of callers, no think time
    stagger_first
                closed loop: the first request of each caller asks for an
                evenly spread fraction of its tokens, so that the callers
                do not run in step
    prefix      optional shared system prompts: {"count", "tokens",
                "zipf_a", "block"}: `count` seeded prefixes of `tokens`
                tokens, one drawn per request with probability ~
                rank**-zipf_a
    prompt_tokens, output_tokens
                {"dist": "const" | "uniform" | "loguniform", ..., "block"}:
                the request's own part of the prompt, and the tokens it
                asks for.  With "block": B every B consecutive draws take
                one from each B-quantile stratum, in a seeded order
    schedule_seed
                optional: the arrival instants, both lengths and the choice
                of prefix are drawn from this seed and not from the run's,
                so every run offers the same requests' sizes at the same
                instants: one realisation of the arrival process, the same
                for the parent and the change.  The run's seed still draws
                every token id (and the runner's weights).  For an open loop
                under its knee, where how the arrivals happen to bunch
                moves every percentile more than a PR does
    ramp_s      seconds of the same traffic offered before the window opens
    timeout_ms  a request not finished this long after it was due failed

Training jobs (`"kind": "train"`) are read by `batches()`.
"""
import bisect
import queue
import threading
import time

import numpy as np


class Request:
    """One request of a schedule.  `due_s` is relative to the start of the
    load (None in a closed loop: due when its client is free)."""

    __slots__ = ("index", "due_s", "prompt", "max_new_tokens", "prefix_id")

    def __init__(self, index, due_s, prompt, max_new_tokens, prefix_id):
        self.index = index
        self.due_s = due_s
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.prefix_id = prefix_id


def _quantiles(rng, n, block):
    """n numbers in (0, 1).  Independent uniforms, or with `block` B one
    from each of the B strata [k / B, (k + 1) / B) in a seeded order,
    block after block: every B consecutive draws then cover the whole
    distribution, so two seeds offer nearly the same work in another
    order and a window's mix no longer hangs on the luck of a few draws."""
    u = rng.random(n)
    if not block:
        return u
    block = int(block)
    strata = np.concatenate([rng.permutation(block)
                             for _ in range(-(-n // block))])[:n]
    return (strata + u) / block


def draw(rng, spec, n):
    """n integer draws from a `{"dist": ..., "block": B}` spec."""
    dist = spec["dist"]
    if dist == "const":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["lo"]), int(spec["hi"])
    q = _quantiles(rng, n, spec.get("block"))
    if dist == "uniform":
        x = lo + q * (hi + 1 - lo)
    elif dist == "loguniform":
        # uniform in log space over [lo, hi + 1), floored: every integer
        # in [lo, hi] can come up and small ones come up more often
        x = np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown dist {dist!r}")
    return np.floor(x).astype(np.int64).clip(lo, hi)


def zipf_choice(rng, count, a, n, block=None):
    """n draws from {0..count-1} with P(k) ~ (k + 1) ** -a."""
    w = np.arange(1, count + 1, dtype=np.float64) ** -float(a)
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, _quantiles(rng, n, block)),
                      count - 1)


def arrival_times(rng, spec, horizon_s):
    """Arrival times in [0, horizon_s) of an open loop."""
    rate = float(spec["rate_per_s"])
    if spec.get("block_s"):
        # a Poisson process conditioned on its count: each block of
        # `block_s` seconds holds exactly rate * block_s arrivals, placed
        # as a Poisson process places a known number of them (uniformly).
        # Bursts inside a block stay; the load over a window is the same
        # for every seed.
        if spec["process"] != "poisson":
            raise ValueError("block_s is for the poisson process")
        block_s = float(spec["block_s"])
        per = rate * block_s
        if abs(per - round(per)) > 1e-9 or per < 1:
            raise ValueError(f"rate_per_s * block_s = {per} is not a whole "
                             f"number of arrivals")
        blocks = int(np.ceil(horizon_s / block_s))
        times = np.concatenate([
            np.sort(rng.uniform(b * block_s, (b + 1) * block_s,
                                int(round(per)))) for b in range(blocks)])
        return times[times < horizon_s]
    n = int(rate * horizon_s * 2) + 64
    if spec["process"] == "poisson":
        gaps = rng.exponential(1.0 / rate, n)
    elif spec["process"] == "gamma":
        # gamma gaps with mean 1/rate and coefficient of variation cv:
        # cv 1 is Poisson, cv > 1 is burstier
        shape = 1.0 / float(spec["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0 / (rate * shape), n)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    times = np.cumsum(gaps)
    return times[times < horizon_s]


def schedule(traffic, seed, vocab_size, horizon_s):
    """The requests of one run: an open loop's arrivals over `horizon_s`
    seconds (ramp included), or a closed loop's pool, which is as long as
    `pool` says (clients take from it in order)."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    # `shape`: who draws the instants and the sizes.  Without a
    # `schedule_seed` it is the run's own generator, and the draws below
    # come from one stream in the order they always did
    shape = rng if traffic.get("schedule_seed") is None else \
        np.random.default_rng([int(traffic["schedule_seed"]), 0x5EED])
    if traffic["loop"] == "open":
        due = arrival_times(shape, traffic["arrivals"], horizon_s)
        n = len(due)
    elif traffic["loop"] == "closed":
        n = int(traffic["pool"])
        due = [None] * n
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    own = draw(shape, traffic["prompt_tokens"], n)
    out = draw(shape, traffic["output_tokens"], n)
    prefix = traffic.get("prefix")
    if prefix:
        prefixes = rng.integers(0, vocab_size,
                                (int(prefix["count"]), int(prefix["tokens"])))
        which = zipf_choice(shape, int(prefix["count"]), prefix["zipf_a"], n,
                            prefix.get("block"))
    if traffic["loop"] == "closed" and traffic.get("stagger_first"):
        # callers that all start at once stay in step for generations:
        # their contexts grow together and the batch's cost swings with
        # their phase.  The first request of each caller is cut to an
        # evenly spread fraction, as if it had begun before the load did,
        # so the load starts where a long-running one would be.
        c = int(traffic["clients"])
        out[:c] = np.maximum(1, out[:c] * (shape.permutation(c) + 0.5) // c)
    requests = []
    for i in range(n):
        body = rng.integers(0, vocab_size, int(own[i])).tolist()
        if prefix:
            body = prefixes[which[i]].tolist() + body
        requests.append(Request(
            i, None if due[i] is None else float(due[i]), body, int(out[i]),
            int(which[i]) if prefix else None))
    return requests


def batches(traffic, seed, vocab_size, data_replicas):
    """The ring of host batches of a training job: `ring` seeded
    (ids, labels) pairs of int32 [batch_per_replica * data_replicas, seq].

    Token ids are drawn Zipf(zipf_a) over the vocabulary, as the unigram
    distribution of text is, so that the loss can fall on fresh batches.
    objective "mlm": `mask_rate` of the positions are replaced by
    `mask_id` and are the only ones with a label (-100 elsewhere, the
    loss's ignore index).  objective "causal_lm": the label of a position
    is the next token, the last position has none."""
    rng = np.random.default_rng([int(seed), 0xBA7C])
    b = int(traffic["batch_per_replica"]) * int(data_replicas)
    s = int(traffic["seq"])
    # the ids a tokenizer would emit: the configuration may pad its
    # embedding table beyond them
    top = int(traffic.get("token_ids_below", vocab_size))
    w = np.arange(1, top + 1, dtype=np.float64) ** -float(traffic["zipf_a"])
    cdf = np.cumsum(w / w.sum())
    ranks = rng.permutation(top)      # which id holds which rank
    ring = []
    for _ in range(int(traffic["ring"])):
        draws = np.searchsorted(cdf, rng.random((b, s + 1)))
        tokens = ranks[np.minimum(draws, top - 1)].astype(np.int32)
        if traffic["objective"] == "mlm":
            ids = tokens[:, :s].copy()
            masked = rng.random((b, s)) < float(traffic["mask_rate"])
            labels = np.where(masked, ids, -100).astype(np.int32)
            ids[masked] = int(traffic["mask_id"])
        elif traffic["objective"] == "causal_lm":
            ids, labels = tokens[:, :s], tokens[:, 1:]
        else:
            raise ValueError(f"unknown objective {traffic['objective']!r}")
        ring.append((np.ascontiguousarray(ids), np.ascontiguousarray(labels)))
    return ring


class Tracked:
    """What the driver and the runner know of one offered request.  The
    runner's handle stamps `token_s` as the server pushes tokens."""

    __slots__ = ("request", "due_abs", "sent_abs", "token_s", "done_abs",
                 "error", "handle")

    def __init__(self, request, due_abs):
        self.request = request
        self.due_abs = due_abs      # when it was due (monotonic seconds)
        self.sent_abs = None        # when submit() returned
        self.token_s = []           # monotonic stamp of every token pushed
        self.done_abs = None
        self.error = None           # the exception that ended it, if any
        self.handle = None


class LoadDriver:
    """Offers a schedule to `submit(tracked)` from one thread.

    `submit` hands the request to the server and returns at once; it
    raises what the server raises on a refusal.  The server's side calls
    `finished(tracked)` (any thread) when a request ends.  Latency is
    timed from `due_abs`, so a stall of the server or of this thread
    shows in every request that was due meanwhile, and `lateness_s()`
    says how late the thread itself ran."""

    def __init__(self, traffic, requests, submit):
        self.traffic = traffic
        self._requests = requests
        self._submit = submit
        self._events = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="loadgen",
                                        daemon=True)
        self.tracked = []
        self.start_abs = None
        self.exhausted_abs = None    # when a closed loop's pool ran out

    def start(self):
        self.start_abs = time.monotonic()
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._events.put(None)
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("the load generator did not stop")

    def finished(self, tracked):
        tracked.done_abs = time.monotonic()
        self._events.put(tracked)

    def _offer(self, request, due_abs):
        t = Tracked(request, due_abs)
        self.tracked.append(t)
        try:
            self._submit(t)
        except Exception as e:  # noqa: BLE001 — a refusal is a failed
            # request of the run, whatever the server raised
            t.error = e
            t.done_abs = time.monotonic()
        t.sent_abs = time.monotonic()

    def _run(self):
        if self.traffic["loop"] == "open":
            self._run_open()
        else:
            self._run_closed()

    def _run_open(self):
        for request in self._requests:
            due_abs = self.start_abs + request.due_s
            while not self._stop.is_set():
                wait = due_abs - time.monotonic()
                if wait <= 0:
                    break
                self._stop.wait(min(wait, 0.05))
            if self._stop.is_set():
                return
            self._offer(request, due_abs)

    def _run_closed(self):
        pending = iter(self._requests)
        for _ in range(int(self.traffic["clients"])):
            self._offer(next(pending), time.monotonic())
        while not self._stop.is_set():
            done = self._events.get()
            if done is None:
                return
            request = next(pending, None)
            if request is None:
                self.exhausted_abs = time.monotonic()
                return
            # due the moment its client's last request completed
            self._offer(request, done.done_abs)

    def lateness_s(self, lo_abs, hi_abs):
        """How long after it was due each request of [lo, hi) was handed
        to the server."""
        return [t.sent_abs - t.due_abs for t in self.tracked
                if t.sent_abs is not None and lo_abs <= t.due_abs < hi_abs]


def window_view(tracked, lo_abs, hi_abs, timeout_s):
    """What a window [lo, hi) of host time saw: every number the serving
    metrics are made of, from the stamps alone."""
    ttft, gaps, tokens = [], [], 0
    attempted = failed = finished = 0
    for t in tracked:
        stamps = t.token_s
        i0 = bisect.bisect_left(stamps, lo_abs)
        i1 = bisect.bisect_left(stamps, hi_abs)
        tokens += i1 - i0
        if stamps and lo_abs <= stamps[0] < hi_abs:
            ttft.append(stamps[0] - t.due_abs)
        for i in range(max(i0, 1), i1):
            gaps.append(stamps[i] - stamps[i - 1])
        if lo_abs <= t.due_abs < hi_abs:
            attempted += 1
            end = t.done_abs if t.done_abs is not None else hi_abs
            if t.error is not None or end - t.due_abs > timeout_s:
                failed += 1
        if (t.done_abs is not None and t.error is None
                and lo_abs <= t.done_abs < hi_abs):
            finished += 1
    return {"ttft_s": ttft, "gap_s": gaps, "tokens": tokens,
            "attempted": attempted, "failed": failed, "finished": finished}
