"""The benchmark's own HTTP client and load loops (stdlib only).

Few threads, one keep-alive connection each; every time is
``time.monotonic()`` on this host.  What load does to a request is recorded
on the request (``failed`` with a reason) and never enters ``correct``.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional, Tuple


@dataclass
class Sample:
    """One request as the client saw it."""

    kind: str
    due: float  # when the client decided to send it
    sent: float = 0.0
    done: float = 0.0
    status: Optional[int] = None
    failed: Optional[str] = None  # None = served in full
    route: Optional[str] = None
    trace_id: Optional[str] = None
    delta_times: List[float] = field(default_factory=list)


class Connection:
    """One keep-alive connection; reconnects after any error."""

    def __init__(self, host: str, port: int, timeout: float):
        self.host, self.port, self.timeout = host, port, timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def _get(self) -> http.client.HTTPConnection:
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout
            )
        return self._conn

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, body=None):
        """(status, headers, parsed JSON or None); (None, {}, None) when
        no answer came in time."""
        data = None if body is None else json.dumps(body).encode()
        try:
            conn = self._get()
            conn.request(method, path, body=data,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
        except (OSError, http.client.HTTPException):
            self.close()
            return None, {}, None
        try:
            payload = json.loads(raw.decode() or "null")
        except ValueError:
            payload = None
        return resp.status, dict(resp.getheaders()), payload

    def ask(self, question: str, sample: Sample) -> None:
        """``POST /ask/``: fills ``sample``."""
        sample.sent = time.monotonic()
        status, headers, payload = self.request(
            "POST", "/ask/", {"question": question}
        )
        sample.done = time.monotonic()
        sample.status = status
        sample.trace_id = headers.get("X-Trace-Id")
        if status != 200 or not isinstance(payload, dict):
            sample.failed = f"http_{status}"
        elif payload.get("degraded"):
            sample.failed = f"degraded_{payload.get('degrade_reason')}"
        elif not payload.get("answer"):
            sample.failed = "empty_answer"
        else:
            sample.route = payload.get("route") or "generative"

    def ask_stream(self, question: str, sample: Sample) -> None:
        """``POST /ask/stream``: one entry of ``delta_times`` per SSE
        delta, read as it arrives."""
        data = json.dumps({"question": question}).encode()
        sample.sent = time.monotonic()
        try:
            conn = self._get()
            conn.request("POST", "/ask/stream", body=data,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            sample.status = resp.status
            sample.trace_id = resp.getheader("X-Trace-Id")
            if resp.status != 200:
                resp.read()
                sample.failed = f"http_{resp.status}"
                return
            event, ended = None, False
            while True:
                line = resp.readline()
                if not line:
                    break
                now = time.monotonic()
                line = line.strip()
                if line.startswith(b"event:"):
                    event = line[6:].strip().decode()
                elif line.startswith(b"data:"):
                    if event == "error":
                        sample.failed = "sse_error:" + line[5:165].decode(
                            errors="replace").strip()
                    elif event == "done":
                        ended = True
                    else:
                        sample.delta_times.append(now)
                    event = None
            resp.read()
            if sample.failed is None and not ended:
                sample.failed = "stream_cut"
            elif sample.failed is None and not sample.delta_times:
                sample.failed = "no_tokens"
            elif sample.failed is None:
                sample.route = "generative"
        except (OSError, http.client.HTTPException):
            self.close()
            sample.failed = sample.failed or "connection"
        finally:
            sample.done = time.monotonic()


# an endpoint of the served surface -> how a request is sent to it and
# read back; a traffic file names one of these
SENDERS = {"/ask/": Connection.ask, "/ask/stream": Connection.ask_stream}


def send(conn: Connection, endpoint: str, kind: str, question: str,
         due: float) -> Sample:
    sample = Sample(kind=kind, due=due)
    SENDERS[endpoint](conn, question, sample)
    return sample


def closed_loop(
    host: str, port: int, endpoint: str, timeout: float,
    streams: List[Iterator[Tuple[str, str]]],
    ramp_requests: int, seconds: float,
    on_window: Callable[[float], None], lockstep: bool = False,
) -> Tuple[List[Sample], float, float]:
    """One thread per stream; the window opens when every client has
    completed ``ramp_requests`` requests and closes ``seconds`` later;
    requests in flight then run to their end.  Returns (samples, t0, t1).

    ``lockstep``: the clients send in rounds — nobody sends request n + 1
    before everybody has the whole of request n — so every round meets an
    idle decoder in the same state.  Free-running clients phase-lock onto
    the server's decode chunks in one of several patterns, and which one
    is an accident of the first few milliseconds (two patterns 11 % apart
    in tokens/s on the chip, PERF.md)."""
    samples: List[Sample] = []
    lock = threading.Lock()
    ramped = threading.Semaphore(0)
    stop = threading.Event()
    # lockstep: whether a round is sent is decided once, by the barrier's
    # action, so that either every client sends or none does
    go = [True]
    rounds = threading.Barrier(
        len(streams), action=lambda: go.__setitem__(0, not stop.is_set())
    ) if lockstep else None

    def client(stream):
        conn = Connection(host, port, timeout)
        try:
            for i, (kind, text) in enumerate(stream):
                if i == ramp_requests:
                    ramped.release()
                if rounds is not None:
                    try:
                        rounds.wait(timeout=timeout + 5)
                    except threading.BrokenBarrierError:
                        break
                    if not go[0]:
                        break
                elif stop.is_set():
                    break
                s = send(conn, endpoint, kind, text, time.monotonic())
                with lock:
                    samples.append(s)
        finally:
            if rounds is not None:
                rounds.abort()  # the others must not wait for this one
            conn.close()

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in streams]
    for t in threads:
        t.start()
    for _ in threads:
        ramped.acquire()
    t0 = time.monotonic()
    on_window(t0)
    time.sleep(seconds)
    t1 = time.monotonic()
    stop.set()
    for t in threads:
        t.join(timeout=timeout + 5)
    return samples, t0, t1
