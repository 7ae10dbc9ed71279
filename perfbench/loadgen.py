"""Open-loop load: seeded arrival schedules, one client process that sends
each request when it is due, and latency counted from the due time.

The arrival builders are copied from ``benchmarks/loadgen.py`` (Poisson and
burst schedules from a seeded ``random.Random``), reduced to the times.  What
is changed is the clock: that file timed a request from when it was put in
the server's queue, so a generator or server that fell behind made later
requests look fast.  Here a request's latency runs from the instant the
schedule says it was due, and how late the generator really sent it is
reported beside it.

The client is one thread around ``selectors``: it sleeps until the next
request is due or a reply is readable, so it holds no lock against itself
and needs one core.  Replies on a connection come back in request order
(the server's contract), which is how a reply finds its request.
"""

from __future__ import annotations

import collections
import gc
import random
import selectors
import socket
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import common


def poisson_times(rate_rps: float, duration_s: float, seed: int) -> List[float]:
    """Homogeneous Poisson arrivals: exponential gaps at ``rate_rps``."""
    rng = random.Random(seed)
    out: List[float] = []
    t = rng.expovariate(rate_rps)
    while t < duration_s:
        out.append(t)
        t += rng.expovariate(rate_rps)
    return out


def burst_times(base_rps: float, burst_rps: float, period_s: float,
                burst_s: float, duration_s: float, seed: int) -> List[float]:
    """Piecewise-constant Poisson: ``burst_rps`` for the first ``burst_s``
    of every ``period_s``, ``base_rps`` otherwise (thinning against the
    higher rate)."""
    peak = max(base_rps, burst_rps)
    rng = random.Random(seed)
    out: List[float] = []
    t = rng.expovariate(peak)
    while t < duration_s:
        rate = burst_rps if (t % period_s) < burst_s else base_rps
        if rng.random() < rate / peak:
            out.append(t)
        t += rng.expovariate(peak)
    return out


def arrival_times(spec: Dict, duration_s: float, seed: int) -> List[float]:
    """Due times (seconds from the window's start) for a traffic file's
    ``arrivals`` section."""
    process = spec["process"]
    if process == "poisson":
        return poisson_times(spec["rate_rps"], duration_s, seed)
    if process == "bursts":
        return burst_times(spec["base_rps"], spec["burst_rps"],
                           spec["period_s"], spec["burst_s"], duration_s, seed)
    raise ValueError(f"unknown arrival process {process!r}")


def summarize(due: Sequence[float], sent: Sequence[Optional[float]],
              received: Sequence[Optional[float]], limit_ms: float) -> Dict:
    """Latency from the due time, over every request that was due.  One
    that got no reply has no latency: it counts as ``unanswered`` and as
    missing the limit, and the percentiles rank it above every answer."""
    n = len(due)
    latency = [
        (received[i] - due[i]) * 1e3 if received[i] is not None else float("inf")
        for i in range(n)
    ]
    lateness = [(sent[i] - due[i]) * 1e3 for i in range(n) if sent[i] is not None]
    return {
        "requests": n,
        "unanswered": sum(1 for r in received if r is None),
        "latency_p50_ms": common.percentile(latency, 0.50),
        "latency_p99_ms": common.percentile(latency, 0.99),
        "met_limit_share": sum(1 for v in latency if v <= limit_ms) / max(1, n),
        "lateness_median_ms": common.median(lateness) if lateness else None,
        "lateness_max_ms": max(lateness) if lateness else None,
    }


def connect(path: str, timeout_s: float = 5.0) -> socket.socket:
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(timeout_s)
    sock.connect(path)
    return sock


def call(path: str, payload: bytes, timeout_s: float = 30.0) -> bytes:
    """One request line, one reply line, on a connection of its own."""
    with connect(path, timeout_s) as sock:
        sock.sendall(payload)
        data = b""
        while not data.endswith(b"\n"):
            chunk = sock.recv(1 << 20)
            if not chunk:
                break
            data += chunk
    return data


class OpenLoopClient:
    """Sends ``lines[i]`` at ``due[i]`` over ``connections`` pipelined
    connections, round robin, and notes when each reply line arrived."""

    def __init__(self, path: str, connections: int) -> None:
        self.socks = [connect(path) for _ in range(connections)]
        for sock in self.socks:
            sock.setblocking(False)

    def close(self) -> None:
        for sock in self.socks:
            sock.close()

    def run(self, lines: Sequence[bytes], due: Sequence[float],
            grace_s: float = 10.0,
            timers: Sequence[Tuple[float, Callable[[], None]]] = (),
            ) -> Dict[str, list]:
        n = len(lines)
        sent: List[Optional[float]] = [None] * n
        received: List[Optional[float]] = [None] * n
        raw: List[Optional[bytes]] = [None] * n
        selector = selectors.DefaultSelector()
        pending_out = [bytearray() for _ in self.socks]
        pending_in = [bytearray() for _ in self.socks]
        order = [collections.deque() for _ in self.socks]
        for c, sock in enumerate(self.socks):
            selector.register(sock, selectors.EVENT_READ, c)
        timers = sorted(timers, key=lambda t: t[0])
        fired = 0
        outstanding = 0
        nxt = 0
        end = (due[-1] if n else 0.0) + grace_s

        def flush(c: int) -> None:
            buf = pending_out[c]
            while buf:
                try:
                    done = self.socks[c].send(buf)
                except BlockingIOError:
                    break
                del buf[:done]
            want = selectors.EVENT_READ | (selectors.EVENT_WRITE if buf else 0)
            selector.modify(self.socks[c], want, c)

        # a collection of this process's many request and reply objects
        # stalled the sender for 0.1 s in the first sweep: none in the window
        gc.collect()
        gc.disable()
        try:
            t0 = time.monotonic()
            while nxt < n or outstanding:
                now = time.monotonic() - t0
                if nxt >= n and now > end:
                    break
                while fired < len(timers) and timers[fired][0] <= now:
                    timers[fired][1]()
                    fired += 1
                while nxt < n and due[nxt] <= now:
                    c = nxt % len(self.socks)
                    pending_out[c] += lines[nxt]
                    order[c].append(nxt)
                    sent[nxt] = time.monotonic() - t0
                    flush(c)
                    nxt += 1
                    outstanding += 1
                wait = min(due[nxt] - (time.monotonic() - t0), 0.05) if nxt < n else 0.05
                # epoll sleeps in whole milliseconds and rounds up: sleep short
                # of the due time and poll through the last millisecond and a half
                for key, mask in selector.select(max(0.0, wait - 0.0015)):
                    c = key.data
                    if mask & selectors.EVENT_WRITE:
                        flush(c)
                    if mask & selectors.EVENT_READ:
                        try:
                            data = self.socks[c].recv(1 << 18)
                        except BlockingIOError:
                            continue
                        t_recv = time.monotonic() - t0
                        if not data:
                            selector.unregister(self.socks[c])
                            outstanding -= len(order[c])
                            order[c].clear()
                            continue
                        buf = pending_in[c]
                        buf += data
                        while True:
                            cut = buf.find(b"\n")
                            if cut < 0:
                                break
                            i = order[c].popleft()
                            received[i] = t_recv
                            raw[i] = bytes(buf[:cut])
                            del buf[:cut + 1]
                            outstanding -= 1
        finally:
            gc.enable()
            selector.close()
        return {"sent": sent, "received": received, "raw": raw, "t0": t0}
