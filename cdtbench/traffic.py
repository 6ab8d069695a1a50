"""The one general traffic generator. A mix is a data file of parameters
(``traffic/<mix>.json``); this module turns it and ``--seed`` into
requests, closed loop or open loop.

Closed loop: ``clients`` callers, each posting its next prompt when its
last is done, until the window's seconds are over; the request in flight
then runs to its end and counts. Open loop: arrivals on a schedule fixed
before the window, whatever the server does; a request's latency counts
from when it was DUE, and how late the generator ran is reported.
"""

from __future__ import annotations

import itertools
import math
import random
import threading
import time

# the vocabulary prompts are drawn from: every request's text differs, so
# its positive conditioning is encoded, never served from the cache
WORDS = ("lighthouse dawn harbor cinematic fog granite copper orchard "
         "river glass meadow lantern storm cathedral desert market violin "
         "glacier canyon neon rooftop ember forest tide marble papercraft "
         "isometric golden portrait mountain village winter").split()


def request_stream(seed: int, words_per_prompt: int = 8):
    """Endless ``(request_seed, prompt_text)`` from ``--seed``: the same
    seed gives the same requests. Seeds stay under 2**31."""
    rng = random.Random(seed)
    while True:
        text = " ".join(rng.choice(WORDS) for _ in range(words_per_prompt))
        yield rng.randrange(1, 2**31 - 1), f"a photo of {text}"


def arrival_schedule(seed: int, rate: float, seconds: float,
                     burst: int = 1) -> list[float]:
    """Due times (seconds from the window's start) of an open loop at
    ``rate`` requests/s in bursts of ``burst``. The gaps between bursts
    are the quantiles of the exponential distribution — the same set for
    every seed, in an order drawn from the seed — so no seed offers more
    load than another and the schedule ends inside the window."""
    bursts = max(1, int(rate * seconds / burst))
    mean_gap = burst / rate
    gaps = [-mean_gap * math.log(1.0 - (i + 0.5) / bursts)
            for i in range(bursts)]
    random.Random(seed).shuffle(gaps)
    due, t = [], 0.0
    for gap in gaps:
        due.extend([t] * burst)
        t += gap
    return [d for d in due if d < seconds]


def lateness(records) -> dict:
    """How late the generator posted against its schedule."""
    late = sorted(r["posted"] - r["due"] for r in records if "due" in r)
    if not late:
        return {}
    return {"mean_s": sum(late) / len(late), "max_s": late[-1]}


def run_closed(do_request, stream, seconds: float, clients: int = 1,
               on_index=None) -> list[dict]:
    """``do_request(index, seed, prompt) -> record``. ``on_index(i)`` is
    called (single client only) before request ``i`` is posted and once
    after the last one: the hook the traced run starts and stops the
    profiler from."""
    records, lock = [], threading.Lock()
    counter = itertools.count()
    t_end = time.monotonic() + seconds

    def client():
        while time.monotonic() < t_end:
            with lock:
                i = next(counter)
                seed, prompt = next(stream)
            if on_index is not None:
                on_index(i)
            record = do_request(i, seed, prompt)
            with lock:
                records.append(record)

    if clients == 1:
        client()
        if on_index is not None:
            on_index(len(records))
    else:
        threads = [threading.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return sorted(records, key=lambda r: r["index"])


def run_open(do_request, stream, due_times, max_in_flight: int = 64
             ) -> list[dict]:
    """Post request ``i`` at ``due_times[i]`` after the start, each on a
    thread of its own (at most ``max_in_flight``; a full house makes the
    generator late, which ``lateness`` shows). Latency is taken from the
    due time: ``record["seconds_from_due"]``."""
    records, lock = [], threading.Lock()
    slots = threading.Semaphore(max_in_flight)
    threads = []
    t0 = time.monotonic()

    def one(i, due, seed, prompt):
        try:
            record = do_request(i, seed, prompt)
            record["due"] = t0 + due
            record["seconds_from_due"] = record["done"] - record["due"]
            with lock:
                records.append(record)
        finally:
            slots.release()

    for i, due in enumerate(due_times):
        seed, prompt = next(stream)
        delay = t0 + due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        slots.acquire()
        thread = threading.Thread(target=one, args=(i, due, seed, prompt))
        thread.start()
        threads.append(thread)
    for thread in threads:
        thread.join()
    return sorted(records, key=lambda r: r["index"])
