"""The feed's wire protocol and the health surface, as a client sees them.

Stdlib only. The protocol is newline-delimited JSON over TCP, one
acknowledgement line per event line (`scheduler_plugins_tpu/bridge/feed.py`,
whose `FeedClient` these few lines copy so that the client process imports
nothing of the program).
"""

from __future__ import annotations

import json
import socket
import urllib.request


def refused(ack_line: bytes) -> bool:
    """True unless the ack line says `"ok": true` (as `json.dumps` writes
    it); cheaper than parsing every ack of a million."""
    return b'"ok": true' not in ack_line


class Feed:
    def __init__(self, host: str, port: int):
        self._sock = socket.create_connection((host, port))
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._file = self._sock.makefile("rwb")

    def send_line(self, line: bytes) -> bytes:
        """One event line (newline included) out, its ack line back."""
        self._file.write(line)
        self._file.flush()
        return self._file.readline()

    def send(self, event: dict) -> dict:
        return json.loads(self.send_line((json.dumps(event) + "\n").encode()))

    def send_all(self, lines, window: int = 256) -> int:
        """Set-up traffic: keep up to `window` event lines in flight, read
        every ack. The server applies a connection's lines in order, so the
        result is that of sending them one by one. Returns how many acks
        were not ok."""
        count = 0
        in_flight = 0
        for line in lines:
            self._file.write(line)
            in_flight += 1
            if in_flight >= window:
                self._file.flush()
                while in_flight > window // 2:
                    count += refused(self._file.readline())
                    in_flight -= 1
        self._file.flush()
        while in_flight:
            count += refused(self._file.readline())
            in_flight -= 1
        return count

    def close(self) -> None:
        self._file.close()
        self._sock.close()


def healthz(url: str, timeout_s: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return json.loads(resp.read())
