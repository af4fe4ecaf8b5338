"""The load generator offers the same work under every seed, times a
request from the instant it was due, and counts throughput at the window's
edges from the engine's counter."""
import json
import os
import threading
import time

import numpy as np
import pytest

from benchmark import loadgen
from benchmark.run import metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                       "benchmark", "traffic")
SERVING = ("chat-open", "batch-closed")


def traffic(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", SERVING)
def test_same_counts_and_length_multisets_under_every_seed(name):
    t = traffic(name)
    a = loadgen.length_summary(loadgen.make_requests(t, 50.0, 1, 32768))
    b = loadgen.length_summary(
        loadgen.make_requests(t, 50.0, 2 ** 31 + 12345, 32768))
    assert a == b
    for phase in a.values():
        assert phase["n"] > 0


@pytest.mark.parametrize("name", SERVING)
def test_the_seed_draws_the_token_ids_and_nothing_else(name):
    t = traffic(name)
    a = loadgen.make_requests(t, 50.0, 1, 32768)
    b = loadgen.make_requests(t, 50.0, 2, 32768)
    assert [r["prompt"].size for r in a] == [r["prompt"].size for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert not all(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))
    again = loadgen.make_requests(t, 50.0, 1, 32768)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, again))
    # the order is the traffic file's own, and another file's is another
    c = loadgen.make_requests(dict(t, order_seed=1), 50.0, 1, 32768)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in c]
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in c)


def test_request_count_comes_from_the_file_not_from_a_draw():
    t = traffic("chat-open")
    for seconds in (10.0, 50.0):
        reqs = loadgen.make_requests(t, seconds, 7, 1000)
        n_win = sum(r["phase"] == "window" for r in reqs)
        n_lead = sum(r["phase"] == "lead" for r in reqs)
        assert n_win == round(t["rate_rps"] * seconds)
        assert n_lead == round(t["rate_rps"] * t["lead_in_s"])
        due = [r["due_s"] for r in reqs if r["phase"] == "window"]
        assert min(due) >= 0.0 and max(due) < seconds
        lead = [r["due_s"] for r in reqs if r["phase"] == "lead"]
        assert min(lead) >= -t["lead_in_s"] and max(lead) < 0.0


def test_lengths_are_the_quantiles_of_the_stated_distribution():
    d = {"dist": "lognormal", "median": 160, "sigma": 0.8,
         "min": 16, "max": 512}
    q = loadgen.quantile_lengths(d, 150)
    assert q == sorted(q) and len(q) == 150
    assert q[0] >= 16 and q[-1] == 512
    assert abs(q[75] - 160) <= 2              # the median
    # one sigma up: exp(ln 160 + 0.8) = 356, at the 84.13th percentile
    assert abs(q[round(0.8413 * 150 - 0.5)] - 356) <= 8


@pytest.mark.parametrize("seed", [0, 1, 99])
def test_every_run_of_a_stratified_order_holds_an_even_sample(seed):
    values = list(range(256))
    out = loadgen.stratified_order(values, np.random.RandomState(seed))
    assert sorted(out) == values
    for start in range(0, 256, loadgen.BLOCK):
        run = sorted(out[start:start + loadgen.BLOCK])
        # one value from each stratum of 8 consecutive values
        assert [v // 8 for v in run] == list(range(32))


@pytest.mark.parametrize("n,span", [(1, 5.0), (150, 50.0), (36, 12.0)])
def test_conditioned_arrivals_put_exactly_n_inside_the_span(n, span):
    t = loadgen.conditioned_arrivals(n, span, np.random.RandomState(3))
    assert len(t) == n and (np.diff(t) > 0).all()
    assert t[0] > 0.0 and t[-1] < span


def test_every_seed_has_the_same_gaps_in_another_order():
    a = loadgen.conditioned_arrivals(140, 50.0, np.random.RandomState(1))
    b = loadgen.conditioned_arrivals(140, 50.0, np.random.RandomState(2))
    assert not np.allclose(a, b)
    gaps = [np.sort(np.diff(np.concatenate([[0.0], t, [50.0]])))
            for t in (a, b)]
    assert np.allclose(gaps[0], gaps[1])
    # exponential gaps: the mean is the span over n + 1, the median
    # ln 2 of it, the longest several times it
    mean = 50.0 / 141
    assert np.median(gaps[0]) == pytest.approx(np.log(2) * mean, rel=0.03)
    assert gaps[0][-1] > 4 * mean
    # any run of BLOCK arrivals takes about the same time
    for t in (a, b):
        spans = t[loadgen.BLOCK::loadgen.BLOCK] - t[:-loadgen.BLOCK:loadgen.BLOCK]
        assert (abs(spans - loadgen.BLOCK * mean) < 0.25 * loadgen.BLOCK * mean).all()


def test_longest_stall_is_the_longest_time_the_counter_stood_still():
    progress = [(0.0, 5), (0.1, 5), (0.2, 9), (0.3, 9), (0.4, 9), (0.75, 9),
                (0.8, 12), (0.9, 12), (1.5, 12)]
    length, began = loadgen.longest_stall(progress, 0.15, 1.0)
    assert (length, began) == (pytest.approx(0.55), 0.2)
    assert loadgen.longest_stall([], 0.0, 1.0) == (0.0, 0.0)


class FakeHandle:
    def __init__(self, max_new, enqueued_at):
        self.enqueued_at, self.ttft_s = enqueued_at, None
        self._cbs, self._done = [], threading.Event()
        self.tokens = np.arange(max_new, dtype=np.int64)

    def add_done_callback(self, fn):
        self._cbs.append(fn)

    def settle(self, first_token_at):
        self.ttft_s = first_token_at - self.enqueued_at
        self._done.set()
        for fn in self._cbs:
            fn(self)

    def wait(self, timeout=None):
        return self._done.wait(timeout)

    def result(self, timeout=None):
        return self.tokens


class FakeEngine:
    """Settles every request ``service_s`` after it was submitted, from a
    timer thread, with its first token half way; submit() itself is slow
    by ``submit_s`` so that a late generator can be told from a slow
    engine."""

    def __init__(self, service_s=0.02, submit_s=0.0):
        self.service_s, self.submit_s = service_s, submit_s
        self.submitted = []
        self.allocator = type("A", (), {"in_use": 3})()

    def submit(self, prompt, max_new=None):
        now = time.monotonic()
        time.sleep(self.submit_s)
        h = FakeHandle(max_new, now)
        self.submitted.append((now, prompt.size, max_new))
        threading.Timer(self.service_s, h.settle,
                        args=(now + self.service_s / 2,)).start()
        return h


def test_open_loop_times_a_request_from_its_due_instant():
    t = {"loop": "open", "rate_rps": 40.0, "lead_in_s": 0.25,
         "order_seed": 0,
         "prompt_len": {"median": 8, "sigma": 0.3, "min": 4, "max": 16},
         "output_len": {"median": 6, "sigma": 0.3, "min": 2, "max": 12}}
    reqs = loadgen.make_requests(t, 1.0, 5, 100)
    engine = FakeEngine(service_s=0.02, submit_s=0.004)
    seen = []
    recs, late, t0 = loadgen.drive_open(engine, reqs, 1.0, seen.append)
    assert seen == ["start", "end"]
    assert len(recs) == len(reqs) == 50 and len(late) == 50
    for rec, req in zip(recs, reqs):
        assert rec.due == pytest.approx(t0 + req["due_s"], abs=1e-9)
        assert rec.submitted >= rec.due          # never sent early
        assert rec.n_out == req["max_new"] and rec.error is None
        # the first token is stamped on the engine's clock, and timed
        # from the due instant it includes the generator's lateness
        assert rec.first_token - rec.due >= 0.01 - 1e-6
    assert all(x >= 0 for x in late)


def test_closed_loop_keeps_exactly_its_clients_in_flight():
    t = {"loop": "closed", "clients": 4, "list_len": 32,
         "order_seed": 0,
         "lead_in_s": 0.1,
         "prompt_len": {"median": 8, "sigma": 0.3, "min": 4, "max": 16},
         "output_len": {"median": 6, "sigma": 0.3, "min": 2, "max": 12}}
    reqs = loadgen.make_requests(t, 0.5, 5, 100)
    engine = FakeEngine(service_s=0.01)
    recs, _, t0 = loadgen.drive_closed(engine, reqs, 0.5, 0.1, 4,
                                       lambda which: None)
    time.sleep(0.05)
    done = [r for r in recs if r.done is not None]
    # about 0.6 s / 0.01 s x 4 clients, the list cycled past its end
    assert 100 < len(done) <= 4 * 70
    assert len(recs) - len(done) <= 4
    order = [r.idx for r in recs]
    assert order == list(range(len(recs)))
    assert recs[40].max_new == reqs[40 % 32]["max_new"]


def test_snap_to_tick_returns_at_the_counters_next_change():
    box = {"v": 10}
    threading.Timer(0.05, lambda: box.update(v=74)).start()
    t0 = time.monotonic()
    t, v = loadgen.snap_to_tick(lambda: box["v"])
    assert v == 74 and 0.04 <= t - t0 < 0.2
    t1 = time.monotonic()
    t, v = loadgen.snap_to_tick(lambda: 5, timeout_s=0.05)
    assert v == 5 and t - t1 >= 0.05


def test_out_tok_s_is_the_counters_delta_at_the_windows_edges():
    run = {"kind": "serve",
           "edges": {"start": {"t": 100.0, "generated_tokens_total": 5000},
                     "end": {"t": 150.5, "generated_tokens_total": 25200}},
           # finished requests would give another number: not used
           "requests": [{"in_sample": True, "n_out": 7, "error": None}]}
    assert metric_reader("out_tok_s")(run) == pytest.approx(20200 / 50.5)


def test_percentile_is_over_all_the_values():
    assert loadgen.percentile([], 90) is None
    assert loadgen.percentile(list(range(101)), 90) == pytest.approx(90.0)
