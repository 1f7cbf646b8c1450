"""One general traffic generator, driven by a mix's data file.

A mix file holds:

    loop         "open" (arrivals on a schedule) or "closed" (each of
                 ``concurrency`` users sends its next request when the
                 last one is done)
    rate_rps     open loop: mean arrivals per second (Poisson)
    lead_in_s    open loop: arrivals before the window, so the window
                 opens on a loaded server (set-up, not measured)
    concurrency  closed loop: users
    prompt       {"dist": "lognormal", "median", "sigma", "min", "max"}
                 or {"dist": "uniform", "min", "max"} (tokens, inclusive)
    max_new      output tokens of every request
    lane         "interactive" or "batch"
    shared_prefix  tokens every prompt starts with (0: unique prompts)
    grace_s      open loop: how long past the window a due request may
                 take before it counts as failed; closed loop: how long
                 requests in flight may take to finish the check's sample
                 when the window finished fewer
    sample       requests the correctness check compares

Every seed gets the same set of prompt lengths and of gaps between
arrivals, drawn at stratified quantiles of their distributions; the
seed only orders them and picks the token ids.  So two seeds do the
same work in another order, and a spread between seeds is the
system's, not the generator's.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

POOL = 4096          # prompt lengths a closed loop cycles through


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def length_set(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` prompt lengths at the stratified quantiles (i + 1/2) / n
    of ``spec``'s distribution, clipped to [min, max]; sorted."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown prompt distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def gap_set(rate: float, n: int, span: float) -> np.ndarray:
    """``n`` exponential gaps (mean 1/rate) at stratified quantiles,
    scaled so that they sum to ``span``."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    return gaps * (span / gaps.sum())


@dataclasses.dataclass
class Item:
    prompt: np.ndarray
    due: float = 0.0               # seconds after the schedule's origin
    in_window: bool = True


class Plan:
    """The requests of one run, made from the mix and ``seed``."""

    def __init__(self, mix: Dict[str, Any], seed: int, seconds: float,
                 vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self._ids = _rng(seed, 1)
        shared = int(mix.get("shared_prefix", 0))
        self.prefix = self._ids.integers(0, vocab, shared).astype(np.int32)
        if mix["loop"] == "open":
            self.items = self._open_schedule()
        elif mix["loop"] != "closed":
            raise ValueError(f"unknown loop kind {mix['loop']!r}")
        else:
            lengths = _rng(seed, 2).permutation(
                length_set(mix["prompt"], POOL))
            self._lengths = iter(np.tile(lengths, 64))
            self._lock = threading.Lock()

    @property
    def max_prompt(self) -> int:
        return int(self.mix["prompt"]["max"])

    def prompt(self, length: int) -> np.ndarray:
        n = max(1, int(length) - len(self.prefix))
        body = self._ids.integers(0, self.vocab, n).astype(np.int32)
        return np.concatenate([self.prefix, body])[:max(1, int(length))]

    def _open_schedule(self) -> List[Item]:
        mix, rate = self.mix, float(self.mix["rate_rps"])
        lead = float(mix.get("lead_in_s", 0.0))
        out: List[Item] = []
        for k, (span, start, in_window) in enumerate(
                ((lead, -lead, False), (self.seconds, 0.0, True))):
            n = int(round(rate * span))
            if n == 0:
                continue
            lengths = _rng(self.seed, 10 + k).permutation(
                length_set(mix["prompt"], n))
            gaps = _rng(self.seed, 20 + k).permutation(
                gap_set(rate, n, span))
            dues = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            out += [Item(self.prompt(ln), float(d), in_window)
                    for ln, d in zip(lengths, dues)]
        return out

    def next_closed(self) -> np.ndarray:
        with self._lock:
            return self.prompt(next(self._lengths))

    def warmup(self, n: int) -> List[np.ndarray]:
        """``n`` prompts that cover the mix's range of lengths."""
        lengths = length_set(self.mix["prompt"], max(n, 1))
        return [self.prompt(ln) for ln in lengths[::-1][:n]]


@dataclasses.dataclass
class Req:
    """One request as the client saw it.  ``res`` is the client's live
    ``QueryResult``: its TOKENS and DONE frames fold into it."""
    prompt_len: int
    due: float                     # monotonic seconds
    sent: float
    in_window: bool
    res: Any = None
    error: Optional[str] = None    # submission failed

    @property
    def ok(self) -> bool:
        r = self.res
        return (r is not None and r.done.is_set() and r.status == "ok")

    def progress(self) -> int:
        """Output tokens the client holds now."""
        r = self.res
        if r is None:
            return 0
        if r.done.is_set() and r.tokens is not None:
            return len(r.tokens)
        return len(r.stream)


def submit(client, prompt: np.ndarray, lane: str):
    """Send one prompt; returns the client's live record of it."""
    qid = client.submit(prompt, lane=lane)
    # the client keeps each query's QueryResult under its qid until
    # result() collects it; the benchmark reads it in place so that it
    # can see tokens arrive before DONE
    return client._requests[qid]


class OpenLoop:
    """Sends each item at its due time, whatever the server is doing."""

    def __init__(self, client, plan: Plan, origin: float, lane: str):
        self.client, self.plan, self.origin, self.lane = (
            client, plan, origin, lane)
        self.reqs: List[Req] = []
        self._thread = threading.Thread(target=self._send, daemon=True,
                                        name="bench-open-loop")

    def start(self) -> "OpenLoop":
        self._thread.start()
        return self

    def _send(self) -> None:
        for it in self.plan.items:
            due = self.origin + it.due
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            req = Req(len(it.prompt), due, time.monotonic(), it.in_window)
            try:
                req.res = submit(self.client, it.prompt, self.lane)
            except ConnectionError as exc:
                req.error = str(exc)
            self.reqs.append(req)

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)


class ClosedLoop:
    """``concurrency`` users; each sends its next request as soon as
    its last one is done, until ``stop()``."""

    def __init__(self, client, plan: Plan, concurrency: int, lane: str):
        self.client, self.plan, self.lane = client, plan, lane
        self.reqs: List[Req] = []
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._user, daemon=True,
                                          name=f"bench-user-{i}")
                         for i in range(concurrency)]

    def start(self) -> "ClosedLoop":
        for t in self._threads:
            t.start()
        return self

    def _user(self) -> None:
        while not self._stop.is_set():
            prompt = self.plan.next_closed()
            now = time.monotonic()
            req = Req(len(prompt), now, now, True)
            try:
                req.res = submit(self.client, prompt, self.lane)
            except ConnectionError as exc:
                req.error = str(exc)
                self.reqs.append(req)
                return
            self.reqs.append(req)
            while not req.res.done.wait(0.2):
                if self._stop.is_set():
                    return

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(5.0)
