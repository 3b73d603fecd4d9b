"""A keep-alive HTTP/1.1 client for ``POST /report``, one per caller
thread. A transport error reconnects and reads as status -1."""
from __future__ import annotations

import http.client

TIMEOUT_S = 120.0


class Connection:
    def __init__(self, port: int):
        self.port = port
        self.conn = None

    def post(self, body: bytes) -> tuple:
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=TIMEOUT_S)
            try:
                self.conn.request("POST", "/report", body=body, headers={
                    "Content-Type": "application/json"})
                r = self.conn.getresponse()
                return r.status, r.read()
            except (http.client.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError) as e:
                # a keep-alive socket the server closed: one fresh try
                self.close()
                if attempt:
                    return -1, repr(e).encode()
            except Exception as e:
                self.close()
                return -1, repr(e).encode()
        return -1, b""

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None
