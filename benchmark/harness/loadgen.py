"""The closed-loop load generator: a few client threads that do nothing
but I/O.  Each keeps one connection, sends the next pre-encoded query
of a shared seeded stream, reads the whole response and keeps its bytes
with two clock readings.  Nothing is parsed or compared here: that
happens after the window, on every response (benchmark/run.py).

The window is a pair of timestamps laid over a loop that is already in
steady state and keeps running past its end."""

from __future__ import annotations

import http.client
import itertools
import threading
import time
from dataclasses import dataclass


@dataclass
class Sample:
    index: int        # position in the query stream
    t_send: float     # perf_counter() before the request was written
    t_done: float     # perf_counter() after the last byte was read
    status: int       # HTTP status, 0 for a transport error
    body: bytes


class LoadGen:
    def __init__(self, port: int, endpoint: str, bodies: list[bytes],
                 clients: int):
        self.port = port
        self.endpoint = endpoint
        self.bodies = bodies
        self._next = itertools.count()
        self._stop = threading.Event()
        self._samples: list[list[Sample]] = [[] for _ in range(clients)]
        self._threads = [
            threading.Thread(target=self._client, args=(k,), daemon=True,
                             name=f"bench-client-{k}")
            for k in range(clients)]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def completed(self) -> int:
        return sum(len(s) for s in self._samples)

    def stop(self, timeout: float = 120.0) -> list[Sample]:
        """Let every client finish the request it has in flight, then
        return all samples in completion order."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout)
            if t.is_alive():
                raise RuntimeError(f"{t.name} did not stop")
        return sorted((s for per in self._samples for s in per),
                      key=lambda s: s.t_done)

    def _client(self, k: int) -> None:
        out = self._samples[k]
        headers = {"Content-Type": "application/json"}
        conn = None
        while not self._stop.is_set():
            i = next(self._next)
            body = self.bodies[i % len(self.bodies)]
            t_send = time.perf_counter()
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=330.0)
                conn.request("POST", self.endpoint, body=body,
                             headers=headers)
                resp = conn.getresponse()
                data = resp.read()
                status = resp.status
            except (OSError, http.client.HTTPException) as e:
                data, status = repr(e).encode(), 0
                if conn is not None:
                    conn.close()
                conn = None
            out.append(Sample(i, t_send, time.perf_counter(), status, data))
        if conn is not None:
            conn.close()
