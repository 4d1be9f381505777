"""A closed-loop load generator for a server that streams tokens over HTTP:
every client sends its next request when its last one has finished. One
thread drives all the clients' sockets through a selector, so that the
generator adds one thread to the process that holds the chip, not one a
client, and every token is stamped by one clock as it arrives.

It speaks just enough HTTP/1.1 for `POST /generate` with `"stream": true`:
a chunked response of one JSON line a token, `{"token": t}`, ended by
`{"done": true, ...}` or `{"error": ...}`.
"""
from __future__ import annotations

import json
import selectors
import socket
import time
from typing import Any, Dict, List, Optional


class Request:
    """One request as the client saw it."""

    __slots__ = ("client", "spec", "t_send", "token_times", "tokens",
                 "status", "finished", "error", "_buf", "_headers_done",
                 "_completed", "sock")

    def __init__(self, client: int, spec: Dict[str, Any]) -> None:
        self.client = client
        self.spec = spec
        self.t_send = 0.0
        self.token_times: List[float] = []
        self.tokens: List[int] = []
        self.status: Optional[int] = None
        self.finished = False      # the stream said done
        self.error: Optional[str] = None
        self._buf = b""
        self._headers_done = False
        self._completed = False    # counted out of in_flight
        self.sock: Optional[socket.socket] = None

    @property
    def ok(self) -> bool:
        return self.finished and self.error is None


class ClosedLoop:
    def __init__(self, port: int, clients: List[List[Dict[str, Any]]],
                 path: str = "/generate", repeat: bool = True) -> None:
        """`clients[c]` is what client c sends, in order; with `repeat` it
        starts over when the list ends, without it the client stops."""
        self.port = port
        self.path = path
        self.clients = clients
        self.repeat = repeat
        self.next_index = [0] * len(clients)
        self.requests: List[Request] = []
        self.in_flight = 0
        self.sel = selectors.DefaultSelector()

    # -- sending -----------------------------------------------------------
    def _send_next(self, client: int) -> None:
        specs = self.clients[client]
        if not self.repeat and self.next_index[client] >= len(specs):
            return
        spec = specs[self.next_index[client] % len(specs)]
        self.next_index[client] += 1
        req = Request(client, spec)
        body = json.dumps({
            "tokens": [int(t) for t in spec["tokens"]],
            "n_new": spec["n_new"], "temperature": spec["temperature"],
            "seed": spec["seed"], "stream": True}).encode()
        head = (f"POST {self.path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
                ).encode()
        self.requests.append(req)
        try:
            sock = socket.create_connection(("127.0.0.1", self.port),
                                            timeout=10)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            req.t_send = time.perf_counter()
            sock.sendall(head + body)
            sock.setblocking(False)
        except OSError as e:
            req.t_send = req.t_send or time.perf_counter()
            req.error = f"connect/send: {e}"
            req._completed = True    # this client sends no more
            return
        req.sock = sock
        self.in_flight += 1
        self.sel.register(sock, selectors.EVENT_READ, req)

    def start(self) -> float:
        """Every client sends its first request. Returns the time."""
        t0 = time.perf_counter()
        for c in range(len(self.clients)):
            self._send_next(c)
        return t0

    # -- receiving ---------------------------------------------------------
    def _close(self, req: Request, send_new: bool = False) -> None:
        self.sel.unregister(req.sock)
        req.sock.close()
        req.sock = None
        if not req.finished and req.error is None:
            req.error = "connection closed before the stream ended"
        self._complete(req, send_new)

    def _complete(self, req: Request, send_new: bool) -> None:
        """The request has its answer (or its failure): its client is free
        to send the next. The socket stays open until the server closes it."""
        if req._completed:
            return
        req._completed = True
        self.in_flight -= 1
        if send_new:
            self._send_next(req.client)

    def _parse(self, req: Request, now: float) -> None:
        if not req._headers_done:
            end = req._buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = req._buf[:end].decode("latin-1")
            req._buf = req._buf[end + 4:]
            req._headers_done = True
            try:
                req.status = int(head.split(" ", 2)[1])
            except (IndexError, ValueError):
                req.error = f"bad status line: {head[:80]!r}"
                return
            if req.status != 200:
                req.error = f"HTTP {req.status}"
                return
        while req.error is None:
            eol = req._buf.find(b"\r\n")
            if eol < 0:
                return
            try:
                size = int(req._buf[:eol], 16)
            except ValueError:
                req.error = f"bad chunk size {req._buf[:eol][:20]!r}"
                return
            if size == 0:
                return
            if len(req._buf) < eol + 2 + size + 2:
                return
            line = req._buf[eol + 2:eol + 2 + size]
            req._buf = req._buf[eol + 2 + size + 2:]
            try:
                obj = json.loads(line)
            except ValueError:
                req.error = f"bad stream line {line[:60]!r}"
                return
            if "token" in obj:
                req.tokens.append(obj["token"])
                req.token_times.append(now)
            elif obj.get("done"):
                req.finished = True
                if obj.get("tokens") != req.tokens:
                    req.error = "the stream's summary differs from its tokens"
            elif "error" in obj:
                req.error = str(obj["error"])

    def pump(self, until: float, send_new: bool) -> None:
        """Handle arrivals until the clock reaches `until` or nothing is in
        flight; a client whose request ended sends its next one while
        `send_new`."""
        while True:
            now = time.perf_counter()
            if now >= until or self.in_flight == 0:
                return
            for key, _ in self.sel.select(timeout=min(0.05, until - now)):
                req: Request = key.data
                now = time.perf_counter()
                try:
                    data = req.sock.recv(1 << 16)
                except BlockingIOError:
                    continue
                except OSError as e:
                    data = b""
                    req.error = req.error or f"recv: {e}"
                if data:
                    req._buf += data
                    self._parse(req, now)
                    if req.finished or req.error is not None:
                        self._complete(req, send_new)
                else:
                    self._close(req, send_new)

    def close(self) -> None:
        for key in list(self.sel.get_map().values()):
            self._close(key.data)
        self.sel.close()
