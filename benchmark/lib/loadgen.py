"""The one general traffic generator for serving cells.  Pure stdlib.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``); this
module turns its parameters and ``--seed`` into requests and drives them.
Copied in shape from the program's ``serving/decode/loadgen.py`` (seeded,
stdlib only, per-request ``ttft_ms``/``token_ms``), with two differences
made on purpose: a request's latency is counted from when it was DUE, not
from when the client got round to sending it, and the request list is a
fixed multiset of sizes per mix that the seed only re-orders, block by
block, so two seeds offer the same work (the contract's rule against a seed that
changes the work).
"""

import math
import random
import threading
import time


def lognormal_quantile_lengths(n, median, sigma, lo, hi):
    """``n`` lengths at evenly spaced quantiles of a lognormal with this
    median and sigma, clipped to [lo, hi]: the same multiset for every
    seed.  Quantile (i + 0.5) / n, inverse normal CDF by stdlib."""
    from statistics import NormalDist

    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def requests_from_mix(mix, seed, vocab_size):
    """The request list of one run.  The mix fixes a BLOCK of
    ``block_requests`` (prompt length, output length) pairs: lengths at
    the quantiles of the mix's two lognormals, paired by the mix's own
    ``pairing_seed``.  The list is ``blocks`` such blocks, each in an
    order the run's seed draws, so any stretch of a block's length holds
    the same work whatever the seed.  Prompt tokens are seeded,
    independent, and never 0 (0 is the program's padding)."""
    n = int(mix["block_requests"])
    p, o = mix["prompt_len"], mix["output_len"]
    plens = lognormal_quantile_lengths(n, p["median"], p["sigma"],
                                       p["min"], p["max"])
    olens = lognormal_quantile_lengths(n, o["median"], o["sigma"],
                                       o["min"], o["max"])
    random.Random(int(mix["pairing_seed"])).shuffle(olens)
    pairs = list(zip(plens, olens))
    rng = random.Random(int(seed))
    reqs = []
    for _ in range(int(mix["blocks"])):
        order = pairs[:]
        rng.shuffle(order)
        for plen, olen in order:
            prompt = [rng.randrange(1, int(vocab_size)) for _ in range(plen)]
            reqs.append({"id": len(reqs), "prompt": prompt,
                         "max_tokens": olen})
    return reqs


def poisson_arrivals(n, rate_rps, seed):
    """Open-loop schedule: ``n`` arrival offsets in seconds of a seeded
    Poisson process (as the program's ``run_open_loop`` draws them)."""
    rng = random.Random(int(seed))
    t, out = 0.0, []
    for _ in range(int(n)):
        out.append(t)
        t += rng.expovariate(float(rate_rps))
    return out


class ClosedLoop:
    """``clients`` callers, each sending its next request when its reply
    arrives (API callers that wait for an answer).  ``send(request)``
    returns the reply dict or raises.  Requests are handed out in list
    order from one shared cursor; the loop runs until ``stop()``.

    Every finished request is recorded as
    ``{"id", "due", "sent", "done", "reply" | "error"}`` with times from
    ``time.perf_counter()``.  In a closed loop a request is due the
    moment its client's previous reply arrived; ``sent`` is when the
    client thread actually got to it (the lateness of the generator).
    """

    def __init__(self, requests, clients, send):
        self._requests = requests
        self._send = send
        self._cursor = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.records = []
        self._threads = [
            threading.Thread(target=self._client, name=f"bench-client-{i}",
                             daemon=True) for i in range(int(clients))]

    def _next(self):
        with self._lock:
            if self._cursor >= len(self._requests):
                return None
            req = self._requests[self._cursor]
            self._cursor += 1
            return req

    def _client(self):
        due = time.perf_counter()
        while not self._stop.is_set():
            req = self._next()
            if req is None:
                return
            rec = {"id": req["id"], "due": due, "sent": time.perf_counter()}
            try:
                rec["reply"] = self._send(req)
            except Exception as e:  # noqa: BLE001 - counted as failed
                rec["error"] = repr(e)
            rec["done"] = due = time.perf_counter()
            with self._lock:
                self.records.append(rec)

    def start(self):
        for t in self._threads:
            t.start()
        return self

    def stop(self, timeout):
        """No new request is started; waits for the ones in flight."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in self._threads)

    @property
    def started(self):
        """Requests handed to a client so far."""
        with self._lock:
            return self._cursor

    @property
    def exhausted(self):
        with self._lock:
            return self._cursor >= len(self._requests)
