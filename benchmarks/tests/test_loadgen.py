"""The traffic generator: seeded, timed from the due time, honest about
its own lateness, and drawing what its parameters say."""
import json
import os
import threading
import time

import numpy as np
import pytest

from benchmarks.harness import loadgen

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def _mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _flat(requests):
    return [(r.due_s, r.prompt, r.max_new_tokens, r.prefix_id)
            for r in requests]


@pytest.mark.parametrize("mix", ["chat-steady", "decode-closed"])
def test_same_seed_same_schedule_lengths_and_prompts(mix):
    traffic = dict(_mix(mix), pool=64)
    a = loadgen.schedule(traffic, 7, 50272, 60.0)
    b = loadgen.schedule(traffic, 7, 50272, 60.0)
    c = loadgen.schedule(traffic, 8, 50272, 60.0)
    assert _flat(a) == _flat(b)
    assert _flat(a) != _flat(c)
    assert len(a) > 10


def test_a_schedule_seed_fixes_the_instants_and_sizes_not_the_tokens():
    traffic = dict(_mix("chat-steady"), schedule_seed=5)
    a = loadgen.schedule(traffic, 7, 50272, 60.0)
    b = loadgen.schedule(traffic, 8, 50272, 60.0)

    def shape(reqs):
        return [(r.due_s, len(r.prompt), r.max_new_tokens, r.prefix_id)
                for r in reqs]

    assert shape(a) == shape(b) and len(a) > 100
    # every token id is the run's: system prompts and own parts alike
    assert all(x.prompt[:8] != y.prompt[:8] and x.prompt[-8:] != y.prompt[-8:]
               for x, y in zip(a, b))
    assert _flat(a) == _flat(loadgen.schedule(traffic, 7, 50272, 60.0))
    # another realisation under another schedule seed; and without one the
    # run's seed draws the shape too
    c = loadgen.schedule(dict(traffic, schedule_seed=6), 7, 50272, 60.0)
    assert shape(c) != shape(a)
    free = dict(traffic, schedule_seed=None)
    assert shape(loadgen.schedule(free, 7, 50272, 60.0)) != \
        shape(loadgen.schedule(free, 8, 50272, 60.0))


def test_chat_steady_shape():
    traffic = _mix("chat-steady")
    reqs = loadgen.schedule(traffic, 3, 50272, 2000.0)
    rate = traffic["arrivals"]["rate_per_s"]
    assert len(reqs) == round(2000.0 * rate)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) > 0).all()
    by_prefix = {}
    for r in reqs:
        by_prefix.setdefault(r.prefix_id, set()).add(tuple(r.prompt[:256]))
        own = len(r.prompt) - 256
        assert 16 <= own <= 640 and 16 <= r.max_new_tokens <= 96
        assert len(r.prompt) + r.max_new_tokens <= 1024   # the 64-page bucket
    # four system prompts, each always the same 256 tokens
    assert sorted(by_prefix) == [0, 1, 2, 3]
    assert all(len(v) == 1 for v in by_prefix.values())


def test_zipf_draws_match_their_exponent():
    rng = np.random.default_rng(0)
    picks = loadgen.zipf_choice(rng, 4, 1.0, 200_000)
    share = np.bincount(picks, minlength=4) / len(picks)
    want = np.array([1, 1 / 2, 1 / 3, 1 / 4])
    assert np.allclose(share, want / want.sum(), atol=0.005)


def test_loguniform_draws_are_uniform_in_the_logarithm():
    rng = np.random.default_rng(0)
    x = loadgen.draw(rng, {"dist": "loguniform", "lo": 16, "hi": 768},
                     200_000)
    assert x.min() == 16 and x.max() == 768
    # the share under the geometric middle is a half, under the lowest
    # quarter of the log range a quarter
    lo, hi = np.log(16), np.log(769)
    for q in (0.25, 0.5, 0.75):
        assert abs(np.mean(x < np.exp(lo + q * (hi - lo))) - q) < 0.01
    u = loadgen.draw(rng, {"dist": "uniform", "lo": 16, "hi": 64}, 100_000)
    assert u.min() == 16 and u.max() == 64 and abs(u.mean() - 40) < 0.2


@pytest.mark.parametrize("spec, cv", [
    ({"process": "poisson", "rate_per_s": 5.0}, 1.0),
    ({"process": "gamma", "rate_per_s": 5.0, "cv": 3.0}, 3.0)])
def test_arrival_processes_keep_their_rate_and_burstiness(spec, cv):
    times = loadgen.arrival_times(np.random.default_rng(1), spec, 20_000.0)
    gaps = np.diff(times)
    assert abs(1.0 / gaps.mean() - 5.0) < 0.15
    assert abs(gaps.std() / gaps.mean() - cv) < 0.1 * cv


def test_latency_is_timed_from_the_due_time():
    req = loadgen.Request(0, 1.0, [1, 2, 3], 4, None)
    t = loadgen.Tracked(req, due_abs=10.0)
    t.sent_abs = 10.2           # the generator was 0.2 s late
    t.token_s = [10.5, 10.7, 11.0]
    t.done_abs = 11.0
    view = loadgen.window_view([t], 10.0, 12.0, timeout_s=30.0)
    assert view["ttft_s"] == [pytest.approx(0.5)]     # not 0.3
    assert view["gap_s"] == [pytest.approx(0.2), pytest.approx(0.3)]
    assert (view["tokens"], view["attempted"], view["failed"],
            view["finished"]) == (3, 1, 0, 1)
    # a window that opens mid-request sees its later tokens and gaps only
    late = loadgen.window_view([t], 10.6, 12.0, timeout_s=30.0)
    assert late["ttft_s"] == [] and late["tokens"] == 2
    assert late["gap_s"] == [pytest.approx(0.2), pytest.approx(0.3)]
    assert late["attempted"] == 0


def test_a_refused_or_overdue_request_failed():
    ok = loadgen.Tracked(loadgen.Request(0, 0, [1], 1, None), 1.0)
    ok.token_s, ok.done_abs = [1.5], 1.5
    refused = loadgen.Tracked(loadgen.Request(1, 0, [1], 1, None), 2.0)
    refused.error, refused.done_abs = RuntimeError("busy"), 2.0
    overdue = loadgen.Tracked(loadgen.Request(2, 0, [1], 1, None), 3.0)
    overdue.token_s, overdue.done_abs = [9.0], 9.0
    stuck = loadgen.Tracked(loadgen.Request(3, 0, [1], 1, None), 4.0)
    view = loadgen.window_view([ok, refused, overdue, stuck], 0.0, 10.0,
                               timeout_s=5.0)
    assert (view["attempted"], view["failed"]) == (4, 3)


def test_open_loop_reports_how_late_it_ran():
    traffic = {"loop": "open"}
    reqs = [loadgen.Request(i, 0.01 * i, [1], 1, None) for i in range(10)]

    def slow_submit(tracked):
        time.sleep(0.03)        # a server that blocks the caller

    driver = loadgen.LoadDriver(traffic, reqs, slow_submit)
    driver.start()
    time.sleep(0.6)
    driver.stop()
    assert len(driver.tracked) == 10
    due = [t.due_abs - driver.start_abs for t in driver.tracked]
    assert due == pytest.approx([0.01 * i for i in range(10)])
    late = driver.lateness_s(driver.start_abs, driver.start_abs + 10)
    assert len(late) == 10 and min(late) >= 0.03
    # arrivals 10 ms apart, 30 ms a submit: the last is > 0.15 s late
    assert max(late) > 0.15


def test_closed_loop_sends_on_completion_only():
    traffic = {"loop": "closed", "clients": 3}
    reqs = [loadgen.Request(i, None, [1], 1, None) for i in range(12)]
    in_flight, worst, lock = [], [0], threading.Lock()

    def submit(tracked):
        with lock:
            in_flight.append(tracked)
            worst[0] = max(worst[0], len(in_flight))

    driver = loadgen.LoadDriver(traffic, reqs, submit)
    driver.start()
    deadline = time.monotonic() + 5
    while len(driver.tracked) < 12 and time.monotonic() < deadline:
        with lock:
            done = in_flight.pop(0) if in_flight else None
        if done is not None:
            driver.finished(done)
        time.sleep(0.005)
    driver.stop()
    assert len(driver.tracked) == 12 and worst[0] == 3
    # a request is due the moment its client's last one completed
    assert all(t.sent_abs >= t.due_abs for t in driver.tracked)


@pytest.mark.parametrize("mix, vocab", [("pretrain-s128", 30522),
                                        ("zero3-2x2-s1024", 50304)])
def test_training_batches(mix, vocab):
    traffic = _mix(mix)
    a = loadgen.batches(traffic, 5, vocab, 2)
    b = loadgen.batches(traffic, 5, vocab, 2)
    c = loadgen.batches(traffic, 6, vocab, 2)
    assert len(a) == traffic["ring"]
    assert all((x[0] == y[0]).all() and (x[1] == y[1]).all()
               for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()
    assert not (a[0][0] == a[1][0]).all()
    ids, labels = a[0]
    shape = (2 * traffic["batch_per_replica"], traffic["seq"])
    assert ids.shape == labels.shape == shape and ids.dtype == np.int32
    top = traffic.get("token_ids_below", vocab)
    assert 0 <= ids.min() and ids.max() < top
    if traffic["objective"] == "mlm":
        masked = labels != -100
        assert abs(masked.mean() - traffic["mask_rate"]) < 0.02
        assert (ids[masked] == traffic["mask_id"]).all()
        assert labels[masked].max() < top
    else:
        assert (ids[:, 1:] == labels[:, :-1]).all()
    # Zipf(1): the commonest id is seen about twice as often as the next
    counts = np.sort(np.bincount(np.concatenate(
        [x[0].ravel() for x in a] + [x[1][x[1] >= 0].ravel() for x in a])))
    assert 1.5 < counts[-1] / counts[-2] < 2.7


def test_blocked_draws_take_one_from_every_stratum():
    rng = np.random.default_rng(0)
    spec = {"dist": "uniform", "lo": 0, "hi": 159, "block": 16}
    x = loadgen.draw(rng, spec, 16 * 50)
    for block in x.reshape(50, 16):
        assert sorted(block // 10) == list(range(16))   # one per stratum
    assert not (x[:16] == x[16:32]).all()               # in a seeded order
    # so the work of a block barely differs, where free draws differ widely
    free = loadgen.draw(rng, dict(spec, block=None), 16 * 50)
    assert x.reshape(50, 16).sum(1).std() < 0.2 * \
        free.reshape(50, 16).sum(1).std()
    picks = loadgen.zipf_choice(rng, 4, 1.0, 12 * 10, block=12)
    for block in picks.reshape(10, 12):
        assert abs(np.sum(block == 0) - 6) <= 1 and np.sum(block == 3) >= 1


def test_blocked_arrivals_fix_the_count_not_the_instants():
    spec = {"process": "poisson", "rate_per_s": 0.48, "block_s": 12.5}
    a = loadgen.arrival_times(np.random.default_rng(1), spec, 100.0)
    b = loadgen.arrival_times(np.random.default_rng(2), spec, 100.0)
    assert len(a) == len(b) == 48 and not np.allclose(a, b)
    assert (np.diff(a) >= 0).all()
    assert [int(np.sum((a >= k * 12.5) & (a < (k + 1) * 12.5)))
            for k in range(8)] == [6] * 8
    with pytest.raises(ValueError):
        loadgen.arrival_times(np.random.default_rng(1),
                              dict(spec, rate_per_s=0.5), 100.0)


def _open_loop_mixes():
    mixes = {name[:-5]: _mix(name[:-5])
             for name in sorted(os.listdir(TRAFFIC)) if name.endswith(".json")}
    return [pytest.param(mix, id=name) for name, mix in mixes.items()
            if mix.get("loop") == "open" and mix["arrivals"].get("block_s")]


@pytest.mark.parametrize("mix", _open_loop_mixes())
def test_a_block_of_arrivals_holds_whole_sets_of_the_length_strata(mix):
    """A re-rating changes `rate_per_s`: the arrivals of one block of
    seconds must stay a whole number and whole sets of each length
    distribution's strata, or two seeds' windows stop offering the same
    work."""
    per = mix["arrivals"]["rate_per_s"] * mix["arrivals"]["block_s"]
    assert per >= 1 and abs(per - round(per)) < 1e-9
    for key in ("prompt_tokens", "output_tokens"):
        block = mix[key].get("block")
        assert block and round(per) % int(block) == 0, (key, per, block)


def test_closed_loop_callers_do_not_start_in_step():
    traffic = dict(_mix("decode-closed"), pool=96)
    reqs = loadgen.schedule(traffic, 4, 50272, 60.0)
    first, later = reqs[:32], reqs[32:]
    assert all(64 <= r.max_new_tokens <= 128 for r in later)
    # the callers' first requests end at evenly spread times
    asked = sorted(r.max_new_tokens for r in first)
    assert 1 <= asked[0] <= 4 and 30 <= asked[16] <= 66 and asked[-1] >= 60
    assert len(set(asked)) > 24
