#!/usr/bin/env python3
"""The benchmark's client side: one load-generator process.

It imports numpy and the standard library only, never JAX, so it can run
beside the process that holds the chip without touching it.  ``run.py``
starts a few of these, sends each a JSON spec on stdin, waits for their
``ready`` line, then sends the start time (``time.monotonic()``, which every
process on the host shares).  Each process then drives its share of the
traffic over keep-alive ``http.client`` connections, one per thread, and
prints one JSON result line when its last request has been answered or has
timed out.

Requests are ``POST /v1/infer/<net>`` with an ``application/x-npy`` float32
body and ``Accept: application/x-npy``; each input of the pool is encoded
once.  Two loop kinds:

  ``closed``  each client sends its next image when the last is answered,
              from the start until the window closes.
  ``open``    every request has a due time from the schedule; a free thread
              sends it at that time, and latency is timed from the due time,
              so a late send counts against the server, and the lateness is
              reported beside it.
"""

from __future__ import annotations

import base64
import hashlib
import http.client
import io
import json
import sys
import threading
import time

import numpy as np


def make_pool(seed: int, size: int, shape) -> np.ndarray:
    """``size`` distinct N(0, 1) float32 images drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.normal(0, 1, (size,) + tuple(shape)).astype(np.float32)


def encode_npy(x: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(x), allow_pickle=False)
    return buf.getvalue()


def open_schedule(seed: int, rate_per_s: float, warm_s: float,
                  window_s: float, pool_size: int) -> tuple:
    """Due times (s from the start) and pool indices of an open loop.

    The warm-up and the window are scheduled apart, each with
    ``round(rate * seconds)`` requests whose gaps are the quantiles of the
    exponential law at that rate, scaled to fill it.  Every seed gets the same
    requests and the same set of gaps; the seed only orders the gaps and picks
    the images.  So the offered work is equal for all seeds, and the arrivals
    are still Poisson-like."""
    rng = np.random.default_rng([int(seed), 2])
    dues = []
    for start, seconds in ((0.0, warm_s), (warm_s, window_s)):
        n = int(round(rate_per_s * seconds))
        if n == 0:
            continue
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(gaps * seconds / gaps.sum())
        dues.append(start + np.cumsum(gaps) - gaps[0])
    due = np.concatenate(dues)
    return due, rng.integers(0, pool_size, len(due))


class _Client:
    """One keep-alive connection; ``post`` returns (status, body)."""

    def __init__(self, host: str, port: int, path: str, timeout_s: float):
        self.host, self.port, self.path = host, port, path
        self.timeout_s = timeout_s
        self.conn = None

    def post(self, body: bytes) -> tuple:
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout_s)
            try:
                self.conn.request("POST", self.path, body, {
                    "Content-Type": "application/x-npy",
                    "Accept": "application/x-npy"})
                resp = self.conn.getresponse()
                data = resp.read()
                if resp.will_close:
                    self.close()
                return resp.status, data
            except (ConnectionError, http.client.HTTPException):
                # a keep-alive connection the server closed: reopen once
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def run(spec: dict, bodies: list, t0: float) -> dict:
    """Drive this process's share of the traffic from ``t0``; the record."""
    path = f"/v1/infer/{spec['net']}"
    w0 = t0 + spec["warm_s"]
    w1 = w0 + spec["window_s"]
    lock = threading.Lock()
    recs = []                    # [due, send, done, status, idx], rel. t0
    answers = {}                 # (idx, sha1) -> [count, body]

    def one(client, due, i):
        send = time.monotonic()
        try:
            status, data = client.post(bodies[i])
        except Exception:        # noqa: BLE001 - timed out or refused
            status, data = -1, b""
        done = time.monotonic()
        with lock:
            recs.append((due - t0, send - t0, done - t0, status, i))
            if status == 200:
                key = (i, hashlib.sha1(data).hexdigest())
                if key in answers:
                    answers[key][0] += 1
                else:
                    answers[key] = [1, data]

    def closed_client(c):
        client = _Client(spec["host"], spec["port"], path, spec["timeout_s"])
        i = c % len(bodies)
        while True:
            now = time.monotonic()
            if now >= w1:
                break
            one(client, max(now, t0), i)
            i = (i + 1) % len(bodies)
        client.close()

    due = np.asarray(spec.get("due", []), np.float64)
    idx = spec.get("idx", [])
    nxt = iter(range(len(due)))

    def open_worker():
        client = _Client(spec["host"], spec["port"], path, spec["timeout_s"])
        while True:
            with lock:
                k = next(nxt, None)
            if k is None:
                break
            d = t0 + due[k]
            wait = d - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            one(client, d, idx[k])
        client.close()

    if spec["loop"] == "closed":
        threads = [threading.Thread(target=closed_client,
                                    args=(spec["first_client"] + c,))
                   for c in range(spec["clients"])]
    else:
        threads = [threading.Thread(target=open_worker)
                   for _ in range(spec["threads"])]
    wait = t0 - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    for t in threads:
        t.start()
    # CPU seconds this process spent inside the window, for its busy share
    time.sleep(max(0.0, w0 - time.monotonic()))
    cpu0 = time.process_time()
    time.sleep(max(0.0, w1 - time.monotonic()))
    cpu1 = time.process_time()
    for t in threads:
        t.join()
    return {
        "records": recs,
        "busy_share": (cpu1 - cpu0) / spec["window_s"],
        "answers": [[i, n, base64.b64encode(body).decode()]
                    for (i, _), (n, body) in answers.items()],
    }


def main() -> None:
    spec = json.loads(sys.stdin.readline())
    bodies = [encode_npy(x) for x in make_pool(
        spec["seed"], spec["pool_size"], spec["input_shape"])]
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    print(json.dumps(run(spec, bodies, t0)), flush=True)


if __name__ == "__main__":
    main()
