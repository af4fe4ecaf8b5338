"""Traffic for the serving cells: what is offered, when, and how it is timed.

One generator reads a traffic file (``traffic/<name>.json``). It offers the
same work under every seed: the lengths of a cell's N requests are the N
quantiles of the stated distribution and the arrival gaps the quantiles of
the exponential distribution, both in an order that the traffic file fixes
(``order_seed``, shuffled in even strata), and ``--seed`` draws only the
token ids (and, in the builder, the weights). An earlier version let the
seed choose the order: the multisets were the same but the part of them that
fell inside a window was not, and runs of different seeds differed by 1-2%
in out_tok_s and 8% in median time to first token where two runs of one
seed agreed to 0.1% (PERF.md, Findings, PR 23). N comes from the file
(rate x window, or the closed loop's list), never from a draw. The arithmetic of
``poisson_arrivals`` is tools/servebench.py's, conditioned on its count; a
request is timed from the instant it was due, not from ``submit``, and its
completion is stamped by ``add_done_callback``, not by polling.
"""
import math
import statistics
import threading
import time

import numpy as np

BLOCK = 32   # requests to a stratum: any BLOCK consecutive requests hold
             # an even sample of the whole distribution, under every seed


def quantile_lengths(dist, n):
    """The ``n`` quantiles of a clipped log-normal at (i + 0.5) / n, as
    whole numbers, sorted. ``dist``: median, sigma, min, max."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
    out = []
    for i in range(n):
        x = math.exp(nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def stratified_order(values, rng, block=BLOCK):
    """A permutation of sorted ``values`` in which every run of ``block``
    consecutive items is one item from each of ``block`` even strata of
    the distribution: the seed shuffles inside a run and the order of the
    runs, so a window that holds any part of the list sees the same mix."""
    values = sorted(values)
    n = len(values)
    n_runs = -(-n // block)
    runs = [values[r::n_runs] for r in range(n_runs)]
    out = []
    for r in rng.permutation(n_runs):
        run = list(runs[r])
        rng.shuffle(run)
        out.extend(run)
    return out


def conditioned_arrivals(n, span_s, rng):
    """``n`` arrival offsets in [0, span_s), the same work under every
    seed: the n + 1 gaps (the last one runs to the span's end) are the
    quantiles of the exponential distribution at (i + 0.5) / (n + 1),
    ordered by the seed in even strata and rescaled so that exactly ``n``
    arrivals fall inside the span. A Poisson process conditioned on its
    count has such gaps in a random order; here every seed has the same
    multiset of them, and any run of BLOCK arrivals takes about the same
    time, so no seed offers a busier window than another."""
    if n == 0:
        return np.zeros((0,))
    gaps = [-math.log(1.0 - (i + 0.5) / (n + 1)) for i in range(n + 1)]
    t = np.cumsum(stratified_order(gaps, rng))
    return t[:n] * (span_s / t[n])


def make_requests(traffic, seconds, seed, vocab_size):
    """The cell's requests: sizes, order and arrivals from its traffic
    file, token ids from the seed.

    Returns a list of dicts (``prompt`` int64 array, ``max_new``, ``due_s``
    relative to the window's start or None for a closed loop, ``phase``
    "lead"/"window"/"list"). Open loop: round(rate x lead_in) lead-in
    requests due before 0 and round(rate x seconds) window requests due in
    [0, seconds). Closed loop: a list of ``list_len`` requests the clients
    take in order, cycling if it runs out."""
    order = np.random.RandomState(int(traffic["order_seed"]))
    rng = np.random.RandomState(seed % (2 ** 32))
    reqs = []

    def add(n, phase, due):
        p = stratified_order(quantile_lengths(traffic["prompt_len"], n),
                             order)
        o = stratified_order(quantile_lengths(traffic["output_len"], n),
                             order)
        for i in range(n):
            reqs.append({
                "prompt": rng.randint(0, vocab_size, p[i]).astype(np.int64),
                "max_new": o[i], "phase": phase,
                "due_s": None if due is None else float(due[i])})

    if traffic["loop"] == "open":
        rate, lead = traffic["rate_rps"], traffic["lead_in_s"]
        n_lead, n_win = round(rate * lead), round(rate * seconds)
        add(n_lead, "lead", conditioned_arrivals(n_lead, lead, order) - lead)
        add(n_win, "window", conditioned_arrivals(n_win, seconds, order))
    elif traffic["loop"] == "closed":
        add(traffic["list_len"], "list", None)
    else:
        raise ValueError(f"unknown loop kind {traffic['loop']!r}")
    return reqs


def length_summary(reqs):
    """What was offered, for the lines before the last: counts and sorted
    length multisets by phase (the multisets are what must not change
    with the seed)."""
    out = {}
    for phase in sorted({r["phase"] for r in reqs}):
        rs = [r for r in reqs if r["phase"] == phase]
        out[phase] = {
            "n": len(rs),
            "prompt_tokens": int(sum(r["prompt"].size for r in rs)),
            "output_tokens": int(sum(r["max_new"] for r in rs)),
            "prompt_lens": sorted(int(r["prompt"].size) for r in rs),
            "output_lens": sorted(int(r["max_new"]) for r in rs)}
    return out


class _Record:
    """One request's times, all on ``time.monotonic`` (the engine's own
    clock, so ``enqueued_at + ttft_s`` is the first token's instant)."""
    __slots__ = ("idx", "phase", "due", "submitted", "first_token", "done",
                 "n_out", "max_new", "error", "tokens", "pages_in_use")

    def __init__(self, idx, phase, max_new):
        self.idx, self.phase, self.max_new = idx, phase, max_new
        self.due = self.submitted = self.first_token = self.done = None
        self.n_out = 0
        self.error = self.tokens = self.pages_in_use = None

    def as_dict(self):
        return {k: getattr(self, k) for k in self.__slots__ if k != "tokens"}


def _submit(engine, req, rec, due, on_done=None):
    """Submits one request and stamps its record when it settles. A
    refusal at the door is a failed request; its client sends no more."""
    rec.due = due
    rec.submitted = time.monotonic()
    try:
        handle = engine.submit(req["prompt"], max_new=req["max_new"])
    except Exception as e:      # the engine's typed refusal, counted
        rec.error = f"{type(e).__name__}: {e}"[:200]
        rec.done = time.monotonic()
        return None

    def settled(h, rec=rec):
        rec.done = time.monotonic()
        rec.pages_in_use = engine.allocator.in_use
        if h.ttft_s is not None:
            rec.first_token = h.enqueued_at + h.ttft_s
        try:
            rec.tokens = np.asarray(h.result(0))
            rec.n_out = int(rec.tokens.size)
        except Exception as e:  # the engine's typed error, counted
            rec.error = f"{type(e).__name__}: {e}"[:200]
        if on_done is not None:
            on_done()

    handle.add_done_callback(settled)
    return handle


def _watch_window(t0, seconds, edge):
    """A thread that calls ``edge("start")`` at t0 and ``edge("end")`` at
    t0 + seconds, so that a snapshot never delays a submission."""
    def watch():
        _sleep_until(t0)
        edge("start")
        _sleep_until(t0 + seconds)
        edge("end")
    th = threading.Thread(target=watch, name="bench-window", daemon=True)
    th.start()
    return th


def drive_open(engine, reqs, seconds, edge):
    """Open loop. Submits every request at its due instant whatever the
    engine is doing, from this one thread. ``edge(which)`` is called at
    the window's start and end. Returns (records, lateness in seconds of
    each submission, t0). Waits, untimed, for every request due inside
    the window to settle."""
    order = sorted(range(len(reqs)), key=lambda i: reqs[i]["due_s"])
    recs = [_Record(i, reqs[i]["phase"], reqs[i]["max_new"])
            for i in range(len(reqs))]
    lead = -min(0.0, reqs[order[0]]["due_s"]) if order else 0.0
    t0 = time.monotonic() + lead + 0.05
    watcher = _watch_window(t0, seconds, edge)
    late, handles = [], {}
    for i in order:
        due = t0 + reqs[i]["due_s"]
        _sleep_until(due)
        late.append(time.monotonic() - due)
        handles[i] = _submit(engine, reqs[i], recs[i], due)
    watcher.join()
    for i in order:
        if recs[i].phase == "window" and handles[i] is not None:
            handles[i].wait(60.0)
    return recs, late, t0


def drive_closed(engine, reqs, seconds, lead_in_s, clients, edge):
    """Closed loop: ``clients`` callers, each sending its next request the
    moment its last one settles (from the settling callback, so there is
    no client thread to be scheduled late; the callbacks of one 50 s window
    hold the engine's thread for 5 ms in all: my chip runs, PR 23). The window opens ``lead_in_s``
    after the first submissions. Nothing is drained: the samples are the
    requests that completed inside the window."""
    recs, lock = [], threading.Lock()
    state = {"next": 0, "stop": False, "error": None}

    def send_next():
        try:
            with lock:
                if state["stop"]:
                    return
                i = state["next"]
                state["next"] += 1
                rec = _Record(i, "list", reqs[i % len(reqs)]["max_new"])
                recs.append(rec)
            _submit(engine, reqs[i % len(reqs)], rec, time.monotonic(),
                    on_done=send_next)
        except BaseException as e:   # the engine swallows a callback's
            state["error"] = e       # error; the driver raises it below
            raise

    t0 = time.monotonic() + lead_in_s
    watcher = _watch_window(t0, seconds, edge)
    for _ in range(clients):
        send_next()
    watcher.join()
    with lock:
        state["stop"] = True
        out = list(recs)
    if state["error"] is not None:
        raise state["error"]
    return out, [], t0


def _sleep_until(t):
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.5) if d > 0.002 else 0)


def snap_to_tick(read, timeout_s=1.0):
    """(instant, value) at the counter's next change: spins on ``read()``
    until the value moves, so that a window's edge falls on a dispatch
    boundary and the delta between two edges is whole dispatches over the
    time they took. Falls back to now after ``timeout_s`` (an idle
    engine)."""
    v0 = read()
    end = time.monotonic() + timeout_s
    while True:
        v, now = read(), time.monotonic()
        if v != v0 or now >= end:
            return now, v
        time.sleep(0.002)


def longest_stall(progress, t0, t_end):
    """(seconds, instant it began) of the longest stretch inside
    [t0, t_end) over which a sampled counter did not change.
    ``progress``: [(instant, value)] in time order."""
    best, since = (0.0, t0), None
    for (t, v) in progress:
        if t < t0 or t >= t_end:
            continue
        if since is None or v != since[1]:
            since = (t, v)
        elif t - since[0] > best[0]:
            best = (t - since[0], since[0])
    return best


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, over all the values given; None if there are none."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
