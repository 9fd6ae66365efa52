"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``
and drives a submit function with it for a fixed window.

A mix is data. Its keys:

* ``loop``: ``"closed"`` (each of ``clients`` callers submits one image,
  waits for its answer and submits the next) or ``"open"`` (requests are
  sent on a schedule whether or not earlier ones have finished);
* ``images``: how many distinct images the seed draws; requests pick among
  them;
* for ``"open"``: ``rate_per_s``, and optionally ``burst_factor``,
  ``burst_ms`` and ``burst_every_s`` (the rate is ``burst_factor`` times
  higher for ``burst_ms`` at the start of every ``burst_every_s``).

Open-loop arrivals are a fixed number per segment of the schedule (the
segment's rate times its length), each placed uniformly at random within
its segment: every seed offers the same amount of work, in another order.
A request of an open loop is timed from when it was due; one of a closed
loop from when its client sent it.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent.parent / "traffic"
RESULT_TIMEOUT_S = 60.0  # an answer later than this past the window is lost


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} in {TRAFFIC_DIR}")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"traffic {name!r}: loop must be closed or open")
    return mix


def batch_sizes(mix: dict, max_batch: int) -> range:
    """The dispatch sizes this mix can form, so set-up can warm each."""
    if mix["loop"] == "closed":
        return range(1, min(mix["clients"], max_batch) + 1)
    return range(1, max_batch + 1)


@dataclass
class Request:
    client: int
    image: int
    due: float                 # perf_counter when it was due to be sent
    sent: float = 0.0
    done: float | None = None  # perf_counter when its answer was in hand
    answer: object = None      # the logits as served
    error: BaseException | None = None

    @property
    def latency(self) -> float | None:
        return None if self.done is None else self.done - self.due


@dataclass
class Window:
    start: float
    end: float
    requests: list = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def sent(self) -> list:
        """Requests due inside the window: the ones it is judged by."""
        return [r for r in self.requests if self.start <= r.due < self.end]

    def completed_in_window(self) -> int:
        return sum(1 for r in self.requests if r.error is None
                   and r.done is not None and self.start <= r.done <= self.end)

    def longest_stall(self) -> float:
        """The longest time (s) inside the window with no answer coming
        back: a stall of the whole server, which a percentile can hide."""
        done = sorted([self.start, self.end] + [
            r.done for r in self.requests if r.done is not None
            and self.start <= r.done <= self.end])
        return max(b - a for a, b in zip(done, done[1:]))


def open_schedule(mix: dict, seconds: float, rng) -> np.ndarray:
    """Offsets (s) from the window start at which requests fall due."""
    rate = float(mix["rate_per_s"])
    factor = float(mix.get("burst_factor", 1.0))
    burst = mix.get("burst_ms", 0) / 1e3
    period = float(mix.get("burst_every_s", seconds)) or seconds
    times = []
    t = 0.0
    while t < seconds:
        for length, r in ((burst, rate * factor), (period - burst, rate)):
            length = min(length, seconds - t)
            if length > 0:
                n = int(round(r * length))
                times.append(t + length * rng.random(n))
                t += length
    return np.sort(np.concatenate(times)) if times else np.zeros(0)


def run(submit, mix: dict, seconds: float, seed: int,
        mark=None) -> Window:
    """Offer ``mix`` to ``submit(image_index) -> Ticket`` for ``seconds``.

    ``mark`` is a context manager held for exactly the window (a traced run
    marks the window on the device's timeline with it). Returns the window
    with every request it sent, each settled: answered, failed, or given up
    ``RESULT_TIMEOUT_S`` after the window closed.
    """
    n_images = int(mix["images"])
    mark = mark if mark is not None else contextlib.nullcontext()
    if mix["loop"] == "closed":
        return _closed(submit, int(mix["clients"]), n_images, seconds, seed,
                       mark)
    return _open(submit, mix, n_images, seconds, seed, mark)


def _closed(submit, clients, n_images, seconds, seed, mark) -> Window:
    start_gate = threading.Barrier(clients + 1)
    window = Window(0.0, 0.0)
    per_client = [[] for _ in range(clients)]

    def client(c):
        rng = np.random.default_rng([seed, c])
        out = per_client[c]
        start_gate.wait()
        while True:
            now = time.perf_counter()
            if now >= window.end:
                return
            req = Request(c, int(rng.integers(n_images)), now, now)
            out.append(req)
            try:
                req.answer = submit(req.image).result(
                    timeout=max(window.end - now, 0) + RESULT_TIMEOUT_S)
                req.done = time.perf_counter()
            except Exception as e:  # a failed request is counted, not fatal
                req.error = e
                return

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    with mark:
        window.start = time.perf_counter()
        window.end = window.start + seconds
        start_gate.wait()
        time.sleep(max(window.end - time.perf_counter(), 0))
    for t in threads:
        t.join()
    window.requests = [r for reqs in per_client for r in reqs]
    return window


def _open(submit, mix, n_images, seconds, seed, mark) -> Window:
    rng = np.random.default_rng(seed)
    offsets = open_schedule(mix, seconds, rng)
    images = rng.integers(n_images, size=len(offsets))
    window = Window(0.0, 0.0)
    tickets = []
    with mark:
        window.start = time.perf_counter()
        window.end = window.start + seconds
        for off, img in zip(offsets, images):
            req = Request(0, int(img), window.start + off)
            delay = req.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            req.sent = time.perf_counter()
            try:
                tickets.append((req, submit(req.image)))
            except Exception as e:  # a refused request is counted
                req.error = e
            window.requests.append(req)
        time.sleep(max(window.end - time.perf_counter(), 0))
    deadline = window.end + RESULT_TIMEOUT_S
    for req, ticket in tickets:
        try:
            req.answer = ticket.result(
                timeout=max(deadline - time.perf_counter(), 0))
            req.done = ticket.done_at
        except Exception as e:
            req.error = e
    return window
