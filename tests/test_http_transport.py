"""The wire contract of data/api/http.py's transports, byte for byte.

`pio deploy`'s threaded transport reads a request's head itself and
answers in one write; until PR 36 `http.server.BaseHTTPRequestHandler`
did both. GOLDEN holds what that handler put on the wire for a fixed
API under a frozen `Date` (recorded at the parent commit with
`python tests/test_http_transport.py --record`, `PYTHONPATH` naming its
checkout); every case must come back the same from both transports,
which share one head parser (`RequestHead`), one dispatch and one
renderer. `{SERVER}` stands for the stdlib's own version strings.
"""

import json
import socket
import ssl
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler

import pytest

from predictionio_tpu.common import resilience
from predictionio_tpu.data.api import http as http_mod
from predictionio_tpu.data.api.http import make_server

SERVER = (BaseHTTPRequestHandler.server_version + " "
          + BaseHTTPRequestHandler.sys_version)
DATE = "Thu, 01 Jan 2026 00:00:00 GMT"
BIG = 5 * 1024 * 1024


class _API:
    """Every payload shape `dispatch_request` serialises."""

    def handle(self, method, path, query=None, body=b"", headers=None):
        if path == "/json":
            return 200, {"m": method, "q": query, "n": len(body),
                         "dup": headers.get("X-Dup")}
        if path == "/busy":
            return 503, {"message": "saturated"}, {"Retry-After": "1"}
        if path == "/blob":
            return 200, b"\x00\x01PIOC\xff"
        if path == "/html":
            return 200, "<html><body>dashboard</body></html>"
        if path == "/ctype":
            return 200, "# HELP x\nx 1\n", {
                "Content-Type": "text/plain; version=0.0.4"}
        if path == "/nan":
            return 200, {"score": float("nan")}
        if path == "/echo":     # binary, both ways
            return 200, bytes(body)
        return 404, {"message": "Not Found"}


def _req(method, target, body=b"", headers=(), version="HTTP/1.1"):
    lines = [f"{method} {target} {version}", "Host: golden"]
    lines.extend(f"{k}: {v}" for k, v in headers)
    if body:
        lines.append(f"Content-Length: {len(body)}")
    return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1") + body


_BODY = b'{"user": "u1", "num": 4}'

#: name -> (request bytes, fault spec installed while it is served)
REQUESTS = {
    "json-200": (_req("POST", "/json?a=1&b=", _BODY), None),
    "busy-503-retry-after": (_req("GET", "/busy"), None),
    "binary": (_req("GET", "/blob"), None),
    "html": (_req("GET", "/html"), None),
    "handler-content-type": (_req("GET", "/ctype"), None),
    "non-finite-500": (_req("GET", "/nan"), None),
    "not-found-404": (_req("DELETE", "/nope"), None),
    "put": (_req("PUT", "/json", b"x"), None),
    "double-slash-target": (_req("GET", "//json"), None),
    "repeated-header-last-wins": (
        _req("GET", "/json", headers=[("X-Dup", "first"),
                                      ("x-other", "1"),
                                      ("X-Dup", "last")]), None),
    "injected-truncation": (_req("GET", "/html"), "truncate:1@server"),
    "injected-abort": (_req("GET", "/html"), "drop:1@server"),
    "injected-503": (_req("GET", "/html"), "error:1:503@server"),
    "connection-close": (
        _req("GET", "/json", headers=[("Connection", "close")]), None),
    "http10": (_req("GET", "/json", version="HTTP/1.0"), None),
    "http10-keep-alive": (
        _req("GET", "/json", headers=[("Connection", "Keep-Alive")],
             version="HTTP/1.0"), None),
    "expect-100-continue": (
        _req("POST", "/json", _BODY,
             headers=[("Expect", "100-continue")]), None),
    "bad-request-line-400": (b"GET /json extra HTTP/1.1\r\n\r\n", None),
    "uri-too-long-414": (
        b"GET /" + b"a" * 70000 + b" HTTP/1.1\r\n\r\n", None),
    "header-line-too-long-431": (
        b"GET /json HTTP/1.1\r\nX-Big: " + b"b" * 70000 + b"\r\n\r\n", None),
    "too-many-headers-431": (
        b"GET /json HTTP/1.1\r\n"
        + b"".join(b"X-H%d: v\r\n" % i for i in range(120)) + b"\r\n", None),
    "unsupported-method-501": (_req("PATCH", "/json"), None),
}

#: protocol errors the stdlib handler answered otherwise: it served a
#: request past a malformed header line, died on a malformed
#: Content-Length, and sent a 505's body without a head (its
#: `request_version` was still HTTP/0.9 there). Both transports now send
#: its `send_error(code, message)`, head and all: that is their golden.
SEND_ERROR_ONLY = {
    "bad-header-line-400": (
        b"GET /json HTTP/1.1\r\nHost golden\r\n\r\n",
        400, "Bad header line"),
    "bad-content-length-400": (
        b"POST /json HTTP/1.1\r\nContent-Length: 12x\r\n\r\n",
        400, "Bad Content-Length"),
    "bad-version-505": (
        b"GET /json HTTP/2.0\r\n\r\n", 505, "Invalid HTTP version (2.0)"),
}
for _name, (_request, _code, _message) in SEND_ERROR_ONLY.items():
    REQUESTS[_name] = (_request, None)

_ERR = ('<!DOCTYPE HTML>\n<html lang="en">\n    <head>\n'
        '        <meta charset="utf-8">\n'
        '        <title>Error response</title>\n    </head>\n    <body>\n'
        '        <h1>Error response</h1>\n'
        '        <p>Error code: %d</p>\n        <p>Message: %s.</p>\n'
        '        <p>Error code explanation: %d - %s.</p>\n'
        '    </body>\n</html>\n')


def _ok(ctype, body, status="200 OK", extra=""):
    return (f"HTTP/1.1 {status}\r\nServer: {{SERVER}}\r\nDate: {DATE}\r\n"
            f"Content-Type: {ctype}\r\nContent-Length: {len(body)}\r\n"
            f"{extra}\r\n").encode("latin-1") + body


def _err(code, reason, message, explain):
    body = (_ERR % (code, message, code, explain)).encode()
    return (f"HTTP/1.1 {code} {reason}\r\nServer: {{SERVER}}\r\n"
            f"Date: {DATE}\r\nConnection: close\r\n"
            "Content-Type: text/html;charset=utf-8\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode("latin-1") + body


_JSON = "application/json; charset=UTF-8"
_J = (b'{"m": "GET", "q": {}, "n": 0, "dup": null}')

#: name -> (the reply's bytes as BaseHTTPRequestHandler wrote them, whether
#: it then closed the connection); `--record` at the parent printed every
#: entry, the helpers above only fold the repetition
GOLDEN = {
    "json-200": (_ok(_JSON, b'{"m": "POST", "q": {"a": "1", "b": ""}, '
                            b'"n": 24, "dup": null}'), False),
    "busy-503-retry-after": (
        _ok(_JSON, b'{"message": "saturated"}', "503 Service Unavailable",
            "Retry-After: 1\r\n"), False),
    "binary": (_ok("application/octet-stream", b"\x00\x01PIOC\xff"), False),
    "html": (_ok("text/html; charset=UTF-8",
                 b"<html><body>dashboard</body></html>"), False),
    "handler-content-type": (
        _ok("text/plain; version=0.0.4", b"# HELP x\nx 1\n"), False),
    "non-finite-500": (
        _ok(_JSON, b'{"message": "response contains non-finite numbers"}',
            "500 Internal Server Error"), False),
    "not-found-404": (
        _ok(_JSON, b'{"message": "Not Found"}', "404 Not Found"), False),
    "put": (_ok(_JSON, b'{"m": "PUT", "q": {}, "n": 1, "dup": null}'),
            False),
    "double-slash-target": (_ok(_JSON, _J), False),
    "repeated-header-last-wins": (
        _ok(_JSON, b'{"m": "GET", "q": {}, "n": 0, "dup": "last"}'), False),
    # the ORIGINAL length advertised, half the body sent, then the drop
    "injected-truncation": (
        _ok("text/html; charset=UTF-8",
            b"<html><body>dashboard</body></html>")[:-18], True),
    "injected-abort": (b"", True),
    "injected-503": (
        _ok(_JSON, b'{"message": "injected fault: status 503"}',
            "503 Service Unavailable"), False),
    "connection-close": (_ok(_JSON, _J), True),
    "http10": (_ok(_JSON, _J), True),
    "http10-keep-alive": (_ok(_JSON, _J), False),
    "expect-100-continue": (
        b"HTTP/1.1 100 Continue\r\n\r\n"
        + _ok(_JSON, b'{"m": "POST", "q": {}, "n": 24, "dup": null}'),
        False),
    "bad-request-line-400": (
        _err(400, "Bad request syntax ('GET /json extra HTTP/1.1')",
             "Bad request syntax ('GET /json extra HTTP/1.1')",
             "Bad request syntax or unsupported method"), True),
    "bad-version-505": (
        _err(505, "Invalid HTTP version (2.0)",
             "Invalid HTTP version (2.0)", "Cannot fulfill request"), True),
    "uri-too-long-414": (
        _err(414, "Request-URI Too Long", "Request-URI Too Long",
             "URI is too long"), True),
    "header-line-too-long-431": (
        _err(431, "Line too long", "Line too long",
             "got more than 65536 bytes when reading header line"), True),
    "too-many-headers-431": (
        _err(431, "Too many headers", "Too many headers",
             "got more than 100 headers"), True),
    "unsupported-method-501": (
        _err(501, "Unsupported method ('PATCH')",
             "Unsupported method ('PATCH')",
             "Server does not support this operation"), True),
    "bad-header-line-400": (
        _err(400, "Bad header line", "Bad header line",
             "Bad request syntax or unsupported method"), True),
    "bad-content-length-400": (
        _err(400, "Bad Content-Length", "Bad Content-Length",
             "Bad request syntax or unsupported method"), True),
}


# ---------------------------------------------------------------------------
# the wire, from a client's side
# ---------------------------------------------------------------------------

def _read_reply(f):
    """One reply off a buffered socket file: head + Content-Length bytes
    (fewer where the server tore it); b"" at EOF. An interim 100 is read
    through to the reply it announces."""
    head, clen = b"", 0
    while True:
        try:
            line = f.readline()
        except ConnectionError:
            line = b""
        if not line:
            return head
        head += line
        if line.lower().startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
        if line == b"\r\n":
            if head.startswith(b"HTTP/1.1 100 "):
                return head + _read_reply(f)
            return head + (f.read(clen) if clen else b"")


def _closed(sock, f, patience) -> bool:
    """Did the server hang up after the reply, within `patience` s?"""
    sock.settimeout(patience)
    try:
        return f.read(1) == b""
    except ConnectionError:
        return True
    except (socket.timeout, TimeoutError):
        return False


def _exchange(port, request, fault=None, wrap=None, eof=False,
              closes=False):
    """One request on a fresh connection -> (reply bytes, whether the
    server then hung up). `closes`: a hang-up is expected, so a loaded
    machine gets seconds to deliver it, not 0.3."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    if wrap is not None:
        sock = wrap(sock)
    if fault:
        resilience.install(fault, seed=7)
    try:
        sock.sendall(request)
        if eof:
            sock.shutdown(socket.SHUT_WR)
        f = sock.makefile("rb")
        reply = _read_reply(f)
        return reply, _closed(sock, f, 10.0 if closes else 0.3)
    finally:
        resilience.clear()
        sock.close()


def _freeze_date(monkeypatch):
    """The new tree caches its Date in `_http_date`; the stdlib handler
    the goldens were recorded from rendered it per reply."""
    monkeypatch.setattr(http_mod, "_http_date", lambda: DATE, raising=False)
    monkeypatch.setattr(BaseHTTPRequestHandler, "date_time_string",
                        lambda self, timestamp=None: DATE)


@pytest.fixture
def served(monkeypatch):
    """`served(transport)` -> port of a fresh server round `_API`."""
    _freeze_date(monkeypatch)
    servers = []

    def start(transport="threaded", api=None, **kw):
        server = make_server(api or _API(), "127.0.0.1", 0,
                             transport=transport, **kw)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        servers.append(server)
        return server.server_address[1]

    start.servers = servers
    yield start
    resilience.clear()
    for server in servers:
        server.shutdown()
        server.server_close()


def _golden(name):
    reply, closed = GOLDEN[name]
    return reply.replace(b"{SERVER}", SERVER.encode()), closed


TRANSPORTS = ("threaded", "async")


@pytest.mark.parametrize("transport", TRANSPORTS)
@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_reply_bytes_are_the_stdlib_handlers(served, transport, name):
    request, fault = REQUESTS[name]
    want = _golden(name)
    assert _exchange(served(transport), request, fault,
                     closes=want[1]) == want


# ---------------------------------------------------------------------------
# the head parser, alone: one table, then the same table over each wire
# ---------------------------------------------------------------------------

def _head(**kw):
    want = {"method": "GET", "target": "/p", "headers": {}, "length": 0,
            "close_after": False, "expect_continue": False, "error": None}
    want.update(kw)
    return want


_TOO_LONG = b"X: " + b"v" * 65540 + b"\r\n"

#: name -> (the head's lines as a socket gives them, what RequestHead
#: makes of them; `error` is (code, reason), `None` the whole value for
#: a peer that closed)
HEAD_CASES = {
    "plain": ([b"GET /p HTTP/1.1\r\n", b"\r\n"], _head()),
    "bare-lf": ([b"GET /p HTTP/1.1\n", b"A: 1\n", b"\n"],
                _head(headers={"A": "1"})),
    "eof-ends-head": ([b"GET /p HTTP/1.1\r\n", b""], _head()),
    "headers-kept-as-sent": (
        [b"POST /p?x=1 HTTP/1.1\r\n", b"Host: h\r\n",
         b"content-LENGTH:  12 \r\n", b"X-PIO-Trace:a:b\r\n", b"\r\n"],
        _head(method="POST", target="/p?x=1", length=12,
              headers={"Host": "h", "content-LENGTH": "12",
                       "X-PIO-Trace": "a:b"})),
    "repeated-last-wins": (
        [b"GET /p HTTP/1.1\r\n", b"K: 1\r\n", b"K: 2\r\n", b"\r\n"],
        _head(headers={"K": "2"})),
    "close": ([b"GET /p HTTP/1.1\r\n", b"Connection: Close\r\n", b"\r\n"],
              _head(close_after=True, headers={"Connection": "Close"})),
    "http10": ([b"GET /p HTTP/1.0\r\n", b"\r\n"], _head(close_after=True)),
    "http10-keep-alive": (
        [b"GET /p HTTP/1.0\r\n", b"Connection: keep-alive\r\n", b"\r\n"],
        _head(headers={"Connection": "keep-alive"})),
    "http12-is-11": ([b"GET /p HTTP/1.2\r\n", b"\r\n"], _head()),
    "expect": (
        [b"PUT /p HTTP/1.1\r\n", b"Expect: 100-Continue\r\n", b"\r\n"],
        _head(method="PUT", expect_continue=True,
              headers={"Expect": "100-Continue"})),
    "expect-ignored-on-10": (
        [b"PUT /p HTTP/1.0\r\n", b"Expect: 100-continue\r\n", b"\r\n"],
        _head(method="PUT", close_after=True,
              headers={"Expect": "100-continue"})),
    "double-slash": ([b"GET ///p HTTP/1.1\r\n", b"\r\n"], _head()),
    "eof": ([b""], None),
    "blank-line-hangs-up": ([b"\r\n"], None),
    "one-word": ([b"GET\r\n"], _head(
        error=(400, "Bad request syntax ('GET')"))),
    "http09": ([b"GET /p\r\n"], _head(
        error=(400, "Bad request syntax ('GET /p')"))),
    "four-words": ([b"GET /p x HTTP/1.1\r\n"], _head(
        error=(400, "Bad request syntax ('GET /p x HTTP/1.1')"))),
    "version-not-http": ([b"GET /p FTP/1.1\r\n"], _head(
        error=(400, "Bad request version ('FTP/1.1')"))),
    "version-three-parts": ([b"GET /p HTTP/1.1.1\r\n"], _head(
        error=(400, "Bad request version ('HTTP/1.1.1')"))),
    "version-not-a-number": ([b"GET /p HTTP/1.\xb2\r\n"], _head(
        error=(400, "Bad request version ('HTTP/1.\xb2')"))),
    "version-2": ([b"GET /p HTTP/2.0\r\n"], _head(
        error=(505, "Invalid HTTP version (2.0)"))),
    "line-too-long": ([b"GET /" + b"a" * 65540 + b" HTTP/1.1\r\n"], _head(
        error=(414, "Request-URI Too Long"))),
    "header-too-long": ([b"GET /p HTTP/1.1\r\n", _TOO_LONG], _head(
        error=(431, "Line too long"))),
    "99-headers": (
        [b"GET /p HTTP/1.1\r\n"] + [b"H%d: v\r\n" % i for i in range(99)]
        + [b"\r\n"], _head(headers={f"H{i}": "v" for i in range(99)})),
    "100-headers": (
        [b"GET /p HTTP/1.1\r\n"] + [b"H%d: v\r\n" % i for i in range(100)],
        _head(error=(431, "Too many headers"))),
    "header-without-colon": ([b"GET /p HTTP/1.1\r\n", b"Host h\r\n"], _head(
        error=(400, "Bad header line"))),
    "folded-header": (
        [b"GET /p HTTP/1.1\r\n", b"A: 1\r\n", b"  more: 2\r\n"], _head(
            error=(400, "Bad header line"))),
    "length-not-digits": (
        [b"POST /p HTTP/1.1\r\n", b"Content-Length: -5\r\n"], _head(
            method="POST", error=(400, "Bad Content-Length"))),
    "length-empty": (
        [b"POST /p HTTP/1.1\r\n", b"Content-Length:\r\n"], _head(
            method="POST", error=(400, "Bad Content-Length"))),
    "method-unknown": ([b"PATCH /p HTTP/1.1\r\n", b"A: 1\r\n", b"\r\n"], _head(
        method="PATCH", error=(501, "Unsupported method ('PATCH')"))),
}


@pytest.mark.parametrize("name", sorted(HEAD_CASES))
def test_request_head(name):
    lines, want = HEAD_CASES[name]
    head = http_mod.RequestHead()
    fed = 0
    for line in lines:
        fed += 1
        if not head.feed(line):
            break
    assert fed == len(lines), "stopped early, or wanted more than the head"
    if want is None:
        assert head.method is None and head.error is None
        return
    if want["error"] is not None:
        code, reason = want["error"]
        assert (head.error.status, head.error.reason) == (code, reason)
        assert head.error.close
        return
    assert head.error is None
    got = {k: getattr(head, k) for k in want}
    assert got == want


class _HeadEcho:
    """Answers with what the transport made of the head."""

    def handle(self, method, path, query=None, body=b"", headers=None):
        return 200, {"method": method, "path": path, "query": query,
                     "headers": headers, "n": len(body)}


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_head_cases_over_the_wire(served, transport):
    """Each transport feeds the one parser: the table's verdicts are the
    wire's (status, reason phrase, whether the connection stays)."""
    import urllib.parse
    port = served(transport, api=_HeadEcho())
    for name, (lines, want) in sorted(HEAD_CASES.items()):
        if want is not None and want["length"]:
            continue    # a body the case does not carry
        eof = lines[-1] == b""
        reply, closed = _exchange(
            port, b"".join(lines), eof=eof,
            closes=eof or want is None or want["close_after"]
            or want["error"] is not None)
        if want is None:
            assert (reply, closed) == (b"", True), name
            continue
        status_line = reply.split(b"\r\n", 1)[0].decode("latin-1")
        if want["error"] is not None:
            code, reason = want["error"]
            assert status_line == f"HTTP/1.1 {code} {reason}", name
            assert closed, name
            continue
        if want["expect_continue"]:
            assert reply.startswith(b"HTTP/1.1 100 Continue\r\n\r\n"), name
            reply = reply[len(b"HTTP/1.1 100 Continue\r\n\r\n"):]
        seen = json.loads(reply.split(b"\r\n\r\n", 1)[1])
        parts = urllib.parse.urlsplit(want["target"])
        assert seen == {
            "method": want["method"], "path": parts.path,
            "query": dict(urllib.parse.parse_qsl(parts.query)),
            "headers": want["headers"], "n": 0}, name
        # a head that EOF ended has no one left to keep alive for
        assert closed == (want["close_after"] or eof), name


# ---------------------------------------------------------------------------
# how bytes arrive and leave
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport", TRANSPORTS)
def test_request_split_at_every_byte(served, transport):
    """A head and body delivered in two segments, cut anywhere, is the
    same request; all on one kept-alive connection."""
    request, _ = REQUESTS["json-200"]
    want, _closed_after = _golden("json-200")
    sock = socket.create_connection(("127.0.0.1", served(transport)),
                                    timeout=30)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    f = sock.makefile("rb")
    try:
        for cut in range(1, len(request)):
            sock.sendall(request[:cut])
            time.sleep(0.001)
            sock.sendall(request[cut:])
            assert _read_reply(f) == want, cut
    finally:
        sock.close()


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_two_requests_in_one_segment_answered_in_order(served, transport):
    first, _ = REQUESTS["json-200"]
    second, _ = REQUESTS["busy-503-retry-after"]
    sock = socket.create_connection(("127.0.0.1", served(transport)),
                                    timeout=30)
    try:
        sock.sendall(first + second + first)
        f = sock.makefile("rb")
        assert [_read_reply(f) for _ in range(3)] == [
            _golden("json-200")[0], _golden("busy-503-retry-after")[0],
            _golden("json-200")[0]]
    finally:
        sock.close()


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_five_megabytes_both_ways(served, transport):
    """The storage RPC's shape: the body read takes Content-Length bytes
    however they arrive, the write is a `sendall`."""
    blob = bytes(range(256)) * (BIG // 256)
    port = served(transport)
    sock = socket.create_connection(("127.0.0.1", port), timeout=60)
    try:
        request = _req("POST", "/echo", blob)
        # in uneven pieces, so the body straddles many reads
        sent, step = 0, 1
        while sent < len(request):
            sock.sendall(request[sent:sent + step])
            sent += step
            step = min(step * 3, 1 << 20)
        reply = _read_reply(sock.makefile("rb"))
    finally:
        sock.close()
    head, _, body = reply.partition(b"\r\n\r\n")
    assert f"Content-Length: {BIG}".encode() in head
    assert body == blob


class _CountingSocket:
    """Counts the send system calls a handler makes on its connection."""

    def __init__(self, sock, calls):
        self._sock, self._calls = sock, calls

    def __getattr__(self, name):
        return getattr(self._sock, name)

    def sendall(self, data, *a):
        self._calls.append(("sendall", len(data)))
        return self._sock.sendall(data, *a)

    def send(self, data, *a):
        self._calls.append(("send", len(data)))
        return self._sock.send(data, *a)


def test_one_send_a_reply(served):
    """The threaded transport hands a reply to the socket once: head and
    body in one buffer (`BaseHTTPRequestHandler` made two writes)."""
    port = served("threaded")
    server = served.servers[-1]
    calls = []

    class Counting(server.RequestHandlerClass):
        def setup(self):
            super().setup()
            self.connection = _CountingSocket(self.connection, calls)
            # a makefile()d writer would reach the socket past the count
            self.wfile = None

        def finish(self):
            self.rfile.close()

    server.RequestHandlerClass = Counting
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    f = sock.makefile("rb")
    names = ["json-200", "binary", "busy-503-retry-after", "html",
             "non-finite-500", "unsupported-method-501"]
    try:
        for name in names:
            sock.sendall(REQUESTS[name][0])
            assert _read_reply(f) == _golden(name)[0]
    finally:
        sock.close()
    assert calls == [("sendall", len(_golden(n)[0])) for n in names]


class _Tagged:
    def handle(self, method, path, query=None, body=b"", headers=None):
        return 200, {"tag": body.decode(), "q": query["n"]}


def test_128_connections_no_reply_lost_or_crossed(served):
    """One OS thread a connection: 128 kept-alive connections of 200
    requests each get their own replies, every one, in order."""
    port = served("threaded", api=_Tagged())
    conns, each = 128, 200
    failures = []

    def client(c):
        try:
            sock = socket.create_connection(("127.0.0.1", port), timeout=60)
            f = sock.makefile("rb")
            for k in range(each):
                tag = f"c{c}-r{k}".encode()
                sock.sendall(_req("POST", f"/t?n={k}", tag))
                reply = _read_reply(f)
                got = json.loads(reply.split(b"\r\n\r\n", 1)[1])
                if got != {"tag": tag.decode(), "q": str(k)}:
                    failures.append((c, k, got))
                    return
            sock.close()
        except Exception as e:   # noqa: BLE001 - reported below
            failures.append((c, repr(e)))

    before = http_mod.transport_status()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(conns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    assert not any(t.is_alive() for t in threads)
    assert failures == []
    after = http_mod.transport_status()
    assert after["requests"] - before["requests"] == conns * each
    assert after["writes"] - before["writes"] == conns * each
    assert after["protocolErrors"] == before["protocolErrors"]


def test_tls_wrapped_server_answers(served, tmp_path, monkeypatch):
    cert, key = tmp_path / "srv.crt", tmp_path / "srv.key"
    try:
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(key), "-out", str(cert), "-days", "1",
             "-subj", "/CN=localhost"],
            check=True, capture_output=True, timeout=60)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("openssl unavailable")
    monkeypatch.setenv("PIO_SSL_CERTFILE", str(cert))
    monkeypatch.setenv("PIO_SSL_KEYFILE", str(key))
    port = served("threaded", tls=True)
    ctx = ssl.create_default_context()
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    for name in ("json-200", "connection-close", "unsupported-method-501"):
        want = _golden(name)
        got = _exchange(port, REQUESTS[name][0], wrap=ctx.wrap_socket,
                        closes=want[1])
        assert got == want, name


# ---------------------------------------------------------------------------
# recording (run by hand at the commit whose handler is the reference)
# ---------------------------------------------------------------------------

def _record():
    """Print what this checkout's threaded transport answers, in GOLDEN's
    form; at the parent commit that is BaseHTTPRequestHandler."""
    BaseHTTPRequestHandler.date_time_string = (
        lambda self, timestamp=None: DATE)
    if hasattr(http_mod, "_http_date"):
        http_mod._http_date = lambda: DATE

    class SendError(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):   # noqa: N802
            _, code, message = self.path.split("/")
            self.send_error(int(code), message.replace("+", " "))

        def log_message(self, fmt, *args):
            pass

    from http.server import ThreadingHTTPServer
    stdlib = ThreadingHTTPServer(("127.0.0.1", 0), SendError)
    server = make_server(_API(), "127.0.0.1", 0, tls=False,
                         transport="threaded")
    for s in (stdlib, server):
        threading.Thread(target=s.serve_forever, daemon=True).start()
    for name, (request, fault) in REQUESTS.items():
        port = server.server_address[1]
        if name in SEND_ERROR_ONLY:
            _, code, message = SEND_ERROR_ONLY[name]
            request = (f"GET /{code}/{message.replace(' ', '+')} "
                       "HTTP/1.1\r\n\r\n").encode()
            port = stdlib.server_address[1]
        reply, closed = _exchange(port, request, fault)
        print(f"    {name!r}: ({reply.replace(SERVER.encode(), b'{SERVER}')!r},"
              f" {closed}),")
        if name in GOLDEN and (reply, closed) != _golden(name):
            print(f"    # ^ differs from GOLDEN[{name!r}]")
    server.shutdown()
    stdlib.shutdown()


if __name__ == "__main__" and "--record" in sys.argv:
    _record()
