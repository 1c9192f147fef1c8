"""Wire-transcript capture and replay — the offline half of the live tier.

The reference proves its storage clients against real services in a Docker
matrix (reference tests/README.md:30-60). This repo's counterpart has two
halves:

1. an env-gated LIVE tier (tests/test_storage_contract.py ``postgres-live`` /
   ``elasticsearch-live`` params + tests/LIVE_TESTS.md) that runs the full
   contract suite unchanged against real services, and
2. **recorded-transcript replay** (this module): a deterministic scenario is
   run through a TCP proxy that records every byte in both directions; the
   committed transcript then replays in default CI with no service — the
   replay server verifies the client still EMITS the recorded byte stream
   and feeds back the recorded server bytes, so both the client's framing
   and its response parsing are pinned to what was on the wire at capture
   time. Re-capturing against a real server upgrades the same transcript
   file to a real-server oracle without changing any test.

Transcript format (JSON): ``{"meta": {...}, "connections": [[["C"|"S",
hex], ...], ...]}`` — one entry list per TCP connection, consecutive
same-direction chunks coalesced so OS-level segmentation can't break replay.

Matching modes: ``exact`` (byte-for-byte — PostgreSQL wire protocol) and
``http`` (compare method + path + body, ignore headers — urllib's
User-Agent etc. varies across Python versions).
"""

from __future__ import annotations

import socket
import threading


class CaptureProxy:
    """TCP proxy recording both directions of every connection, in order."""

    def __init__(self, target_host: str, target_port: int):
        self.target = (target_host, target_port)
        self.connections: list[list[tuple[str, bytes]]] = []
        self._lsock = socket.socket()
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(8)
        self.port = self._lsock.getsockname()[1]
        self._stop = False
        self._thread = threading.Thread(target=self._accept_loop, daemon=True)
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stop:
            try:
                client, _ = self._lsock.accept()
            except OSError:
                return
            entries: list[tuple[str, bytes]] = []
            self.connections.append(entries)
            upstream = socket.create_connection(self.target)
            lock = threading.Lock()

            def pump(src, dst, tag, entries=entries, lock=lock):
                while True:
                    try:
                        data = src.recv(65536)
                    except OSError:
                        data = b""
                    if not data:
                        try:
                            dst.shutdown(socket.SHUT_WR)
                        except OSError:
                            pass
                        return
                    with lock:
                        if entries and entries[-1][0] == tag:
                            entries[-1] = (tag, entries[-1][1] + data)
                        else:
                            entries.append((tag, data))
                    dst.sendall(data)

            tc = threading.Thread(
                target=pump, args=(client, upstream, "C"), daemon=True)
            ts = threading.Thread(
                target=pump, args=(upstream, client, "S"), daemon=True)
            tc.start(), ts.start()
            tc.join(), ts.join()
            client.close()
            upstream.close()

    def close(self) -> None:
        self._stop = True
        self._lsock.close()

    def transcript(self, meta: dict) -> dict:
        return {
            "meta": meta,
            "connections": [
                [[tag, data.hex()] for tag, data in conn]
                for conn in self.connections if conn
            ],
        }


def _parse_http_requests(data: bytes) -> list[tuple[bytes, bytes, bytes]]:
    """Split a client byte stream into COMPLETE (method, path, body) triples
    (a request whose body hasn't fully arrived yet is not yielded)."""
    out = []
    pos = 0
    while pos < len(data):
        head_end = data.find(b"\r\n\r\n", pos)
        if head_end < 0:
            break
        head = data[pos:head_end].decode("latin1")
        lines = head.split("\r\n")
        method, path, _ = lines[0].split(" ", 2)
        length = 0
        for ln in lines[1:]:
            if ln.lower().startswith("content-length:"):
                length = int(ln.split(":")[1])
        if head_end + 4 + length > len(data):
            break  # body incomplete
        body = data[head_end + 4:head_end + 4 + length]
        out.append((method.encode(), path.encode(), body))
        pos = head_end + 4 + length
    return out


class ReplayServer:
    """Serves a recorded transcript: asserts the client's bytes match the
    recording (per the transcript's matching mode) and answers with the
    recorded server bytes."""

    def __init__(self, transcript: dict, mode: str = "exact",
                 rewrite: "tuple[bytes, bytes] | None" = None):
        self.connections = [
            [(tag, bytes.fromhex(h)) for tag, h in conn]
            for conn in transcript["connections"]
        ]
        self.mode = mode
        # (old, new) substitution on SERVER bytes — for recorded absolute
        # URLs (WebHDFS 307 Location) that must point at the replay server's
        # port instead of the capture-time proxy's. Headers only: port-digit
        # length may change, which never affects Content-Length (body bytes
        # carry no URLs in these protocols).
        self.rewrite = rewrite
        self.errors: list[str] = []
        self._lsock = socket.socket()
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(8)
        self.port = self._lsock.getsockname()[1]
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self) -> None:
        for entries in self.connections:
            try:
                conn, _ = self._lsock.accept()
            except OSError:
                return
            try:
                self._serve_one(conn, entries)
            finally:
                conn.close()
        # nothing left to send: a client that connects again (a retry after
        # a divergence) must be refused at once, not parked in the backlog
        # until its own handshake timeout
        self._lsock.close()

    def _recv_exact(self, conn, n: int) -> bytes:
        # a divergence that SHORTENS the client's stream must fail fast,
        # not deadlock until the client's own (10-minute) read timeout
        conn.settimeout(5.0)
        buf = b""
        try:
            while len(buf) < n:
                chunk = conn.recv(n - len(buf))
                if not chunk:
                    break
                buf += chunk
        except OSError:
            pass
        finally:
            conn.settimeout(None)
        return buf

    def _serve_one(self, conn, entries) -> None:
        if self.mode == "http":
            return self._serve_one_http(conn, entries)
        for tag, data in entries:
            if tag == "S":
                conn.sendall(data)
                continue
            got = self._recv_exact(conn, len(data))
            if got != data:
                self.errors.append(
                    f"client bytes diverged from transcript: "
                    f"expected {data[:64].hex()}… got {got[:64].hex()}…")
                return

    def _serve_one_http(self, conn, entries) -> None:
        """HTTP connections replay LOGICALLY: all recorded client bytes of
        the connection parse into complete requests (a server that responds
        before draining a request body interleaves C/S chunks in the
        recording — chunk-by-chunk replay would deadlock on that), the
        replayed client must produce the same requests (method + path +
        body; headers may drift across Python versions), then every
        recorded server byte is sent."""
        want = _parse_http_requests(
            b"".join(d for t, d in entries if t == "C"))
        responses = b"".join(d for t, d in entries if t == "S")
        if self.rewrite is not None:
            old, new = self.rewrite
            responses = responses.replace(old, new)
            # the client re-requests the rewritten URL, so its recorded
            # request paths/hosts need the same substitution to compare equal
            want = [
                (m, p.replace(old, new), b) for m, p, b in want
            ]
        got = b""
        conn.settimeout(5.0)
        try:
            while len(_parse_http_requests(got)) < len(want):
                chunk = conn.recv(65536)
                if not chunk:
                    break
                got += chunk
        except OSError:
            pass
        conn.settimeout(None)
        have = _parse_http_requests(got)
        if have != want:
            self.errors.append(
                f"HTTP requests diverged: expected {want!r} got {have!r}")
            return
        conn.sendall(responses)

    def close(self) -> None:
        self._lsock.close()
