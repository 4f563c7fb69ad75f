"""Shared HTTP transport for pure route handlers — threaded AND async.

Any object with `handle(method, path, query, body, headers) -> (status,
payload)` can be served; a handler may return a third element — a dict
of extra response headers (e.g. Retry-After on a 503 from the query
batcher's admission control). Two interchangeable transports sit under
every daemon (event, storage, query), selected by ``PIO_TRANSPORT``:

- ``threaded`` (default; what ``pio deploy`` starts): the stdlib
  ``ThreadingHTTPServer`` accept loop — one OS thread per connection,
  mirroring the reference's spray actors over a dispatcher
  (EventServer.scala:602-663) — under this module's own handler, not
  ``http.server``'s: one head parse into a plain dict, one ``sendall``
  of head + body a reply. The bytes are ``BaseHTTPRequestHandler``'s,
  reply for reply (tests/test_http_transport.py holds recordings of
  it), save that HTTP/0.9 and a malformed header line or
  ``Content-Length`` answer 400 and every error reply has its head.
- ``async``: a single-threaded selector event loop (asyncio) that owns
  accept/parse/serialize, with proper HTTP/1.1 keep-alive and
  pipelining — pipelined requests on one connection dispatch
  CONCURRENTLY (responses still written in request order), bounded by
  ``PIO_TRANSPORT_PIPELINE``. Handlers stay synchronous; because they
  can block (WAL group commit, device dispatch, storage RPC) they run
  on a bounded thread-pool executor (``PIO_TRANSPORT_WORKERS``), so
  the loop thread never touches a handler lock. This is the ingest
  front door's scaling path (ROADMAP item 4): the thread-per-request
  stack stops scaling past ~8 connections, the loop does not.

Both transports share ONE head parser (:class:`RequestHead`), ONE
dispatch function (:func:`dispatch_request`) and ONE head renderer
(:func:`_render_head`) — protocol checks, fault injection, trace
adoption, compile attribution, request telemetry, JSON strictness and
header assembly are decided once, so the two modes are wire-byte
identical on every endpoint (asserted by tests/test_async_transport.py;
only the Date header's clock value differs) and differ only in who
owns the socket.
"""

from __future__ import annotations

import asyncio
import contextlib
import email.utils
import html
import http.server
import json
import logging
import os
import signal
import socket
import socketserver
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional, Tuple

from predictionio_tpu.common import (devicewatch, profiling, resilience,
                                     telemetry, tracing)


def transport_mode(explicit: Optional[str] = None) -> str:
    """Resolve the transport: explicit argument > ``PIO_TRANSPORT`` env >
    ``threaded``. Unknown values raise — a typo'd transport silently
    falling back to threaded would invalidate every async claim."""
    mode = (explicit or os.environ.get("PIO_TRANSPORT", "threaded")).lower()
    if mode not in ("threaded", "async"):
        raise ValueError(
            f"PIO_TRANSPORT must be 'threaded' or 'async', got {mode!r}")
    return mode


def _executor_workers() -> int:
    raw = os.environ.get("PIO_TRANSPORT_WORKERS", "")
    try:
        v = int(raw) if raw else 0
    except ValueError:
        v = 0
    if v > 0:
        return v
    return min(32, (os.cpu_count() or 1) * 4)


def _pipeline_window() -> int:
    raw = os.environ.get("PIO_TRANSPORT_PIPELINE", "")
    try:
        v = int(raw) if raw else 0
    except ValueError:
        v = 0
    return v if v > 0 else 16


# ---------------------------------------------------------------------------
# the one dispatch path both transports share
# ---------------------------------------------------------------------------

class RequestOutcome:
    """Everything a transport needs to answer one request.

    ``advertised_len`` can exceed ``len(data)`` under injected
    truncation (PIO_FAULT_SPEC): the client must observe a genuinely
    torn response, so the transport sends the short body and drops the
    connection. ``abort`` means send NOTHING and sever (a mid-request
    kill). ``reason`` is set on a transport-level error reply alone
    (:func:`_error_outcome`)."""

    __slots__ = ("status", "data", "ctype", "extra_headers",
                 "advertised_len", "close", "abort", "reason")

    def __init__(self):
        self.status = 500
        self.data = b""
        self.ctype = "application/json; charset=UTF-8"
        self.extra_headers: Dict[str, str] = {}
        self.advertised_len = 0
        self.close = False
        self.abort = False
        self.reason: Optional[str] = None


def dispatch_request(api, method: str, target: str, body: bytes,
                     headers: Dict[str, str]) -> RequestOutcome:
    """Run one request through the full server-side stack: fault
    injection, trace adoption, compile attribution, the handler itself,
    request telemetry, and strict-JSON serialization. Transport-agnostic
    — the threaded handler and the async loop both call exactly this,
    which is what makes their wire bytes identical."""
    out = RequestOutcome()
    parsed = urllib.parse.urlsplit(target)
    query = dict(urllib.parse.parse_qsl(parsed.query,
                                        keep_blank_values=True))
    extra_headers: Dict[str, str] = {}
    # server-boundary fault injection (PIO_FAULT_SPEC, scope @server):
    # latency before dispatch, or an aborted connection — the client
    # sees exactly what a crashed/partitioned daemon produces
    inj = resilience.active()
    if inj is not None:
        try:
            inj.before_send("server", f"{method} {parsed.path}")
        except ConnectionError:
            out.abort = True   # no response bytes at all: a mid-request kill
            return out
    # request telemetry rides the transport so every daemon gets it
    # uniformly: an incoming X-PIO-Trace header is always adopted (the
    # upstream already sampled this request); fresh traces originate
    # only under PIO_TRACE=1, so default wire behavior is unchanged.
    ctx = tracing.server_context(headers)
    service = type(api).__name__
    t0 = time.perf_counter() if telemetry.on() else None
    try:
        # compile attribution lives in the transport (the Dapper
        # platform-layer lesson): an XLA compile triggered on ANY
        # daemon's request thread is attributed to its route without
        # per-handler wiring. The serving hot paths narrow this
        # further (batcher flush / inline predict regions).
        with devicewatch.attribution(f"server:{parsed.path}",
                                     phase="request"):
            with tracing.activate(ctx):
                with tracing.span(f"server:{parsed.path}",
                                  service=service):
                    response = api.handle(
                        method, parsed.path, query, body, headers)
        if len(response) == 3:
            status, payload, extra_headers = response
        else:
            status, payload = response
    except Exception as e:  # handler without its own guard
        status, payload = 500, {"message": str(e)}
    if status >= 500 and ctx is not None:
        # an errored traced request is exactly a trace worth keeping:
        # pin it in the tail ring so its id resolves after churn
        tracing.pin_trace(ctx.trace_id, "error")
    if t0 is not None:
        telemetry.registry().histogram(
            "pio_http_request_seconds",
            "HTTP request handling latency by daemon and method",
            labelnames=("service", "method")).labels(
                service=service, method=method
        ).observe(time.perf_counter() - t0)
        telemetry.registry().counter(
            "pio_http_requests_total",
            "HTTP requests served by daemon and status",
            labelnames=("service", "status")).labels(
                service=service, status=str(status)).inc()
    if isinstance(payload, (bytes, bytearray)):  # binary (storage RPC)
        data = bytes(payload)
        ctype = "application/octet-stream"
    elif isinstance(payload, str):  # pre-rendered HTML (dashboard pages)
        data = payload.encode("utf-8")
        ctype = "text/html; charset=UTF-8"
    else:
        try:
            # strict JSON: a bare NaN/Infinity token is not JSON and
            # breaks real clients; a payload carrying one is a server
            # bug (e.g. a poisoned model's scores), not data
            data = json.dumps(payload, allow_nan=False).encode("utf-8")
        except ValueError:
            status = 500
            data = json.dumps(
                {"message": "response contains non-finite numbers"}
            ).encode("utf-8")
        ctype = "application/json; charset=UTF-8"
    if extra_headers and "Content-Type" in extra_headers:
        # handler-chosen content type (GET /metrics serves Prometheus
        # text exposition, which is a str but not text/html)
        extra_headers = dict(extra_headers)
        ctype = extra_headers.pop("Content-Type")
    out.advertised_len = len(data)
    if inj is not None:
        new_status, new_data = inj.on_response(
            "server", f"{method} {parsed.path}", status, data)
        if new_status != status:
            # injected 5xx: a fully-formed synthetic error reply
            status, data = new_status, new_data
            out.advertised_len = len(data)
            ctype = "application/json; charset=UTF-8"
        elif len(new_data) != len(data):
            # injected truncation: advertise the ORIGINAL length but
            # send fewer bytes and drop the connection, so the client
            # observes a genuine torn response (IncompleteRead)
            data = new_data
            out.close = True
    out.status = status
    out.data = data
    out.ctype = ctype
    out.extra_headers = extra_headers or {}
    return out


# ---------------------------------------------------------------------------
# the wire format both transports share: one head parser, one renderer
# ---------------------------------------------------------------------------

#: methods a handler is asked for; anything else answers 501
_METHODS = frozenset({"GET", "POST", "PUT", "DELETE"})

#: the Server header `BaseHTTPRequestHandler.version_string()` gave
_SERVER_SOFTWARE = (BaseHTTPRequestHandler.server_version + " "
                    + BaseHTTPRequestHandler.sys_version)

#: `http.client`'s limits, which held under `BaseHTTPRequestHandler`: a
#: line of the head longer than this answers 414 (request line) or 431
#: (header line), as does the 100th header line
_MAX_LINE = 65536
_MAX_HEADERS = 100

_BLANK = (b"\r\n", b"\n", b"")
_CONTINUE = b"HTTP/1.1 100 Continue\r\n\r\n"

_log = logging.getLogger("predictionio_tpu.http")

_M_REQUESTS = telemetry.registry().counter(
    "pio_transport_requests_total",
    "Requests finished, answered or severed, as the reply goes out").child()
_M_WRITES = telemetry.registry().counter(
    "pio_transport_writes_total",
    "Socket writes made for replies: one a reply").child()
#: the threads that answer requests: a connection's thread (threaded),
#: the executor's (async); read from their CPU clocks, never per request
_REQUEST_CPU = profiling.ThreadCPU()
_M_PROTOCOL_ERRORS = telemetry.registry().counter(
    "pio_transport_protocol_errors_total",
    "Requests the transport itself refused, by status code",
    labelnames=("code",))


def transport_status() -> Dict[str, object]:
    """`GET /`'s transport block: the process-wide counters /metrics
    has. `writes == requests` says one write a reply (an injected abort
    is a request with none, an `Expect: 100-continue` one with two).
    `cpuSeconds` is the CPU of the threads that answer requests, read
    from their clocks as the page is built: the threaded transport's
    connection threads, the async one's executor (its loop thread's
    parse and write are not in it). A thread blocked on its socket or in
    the batcher runs up next to none."""
    return {"mode": transport_mode(),
            "requests": int(_M_REQUESTS.value),
            "writes": int(_M_WRITES.value),
            "cpuSeconds": _REQUEST_CPU.seconds(),
            "protocolErrors": int(sum(
                v for _n, _l, v in _M_PROTOCOL_ERRORS.samples()))}


def _collect_cpu():
    """Scrape-time `/metrics` lines of the request threads' CPU."""
    return ["# HELP pio_transport_cpu_seconds_total CPU seconds of the "
            "threads that answer requests",
            "# TYPE pio_transport_cpu_seconds_total counter",
            f"pio_transport_cpu_seconds_total {_REQUEST_CPU.seconds()!r}"]


def _status_phrase(code: int) -> str:
    got = BaseHTTPRequestHandler.responses.get(code)
    return got[0] if got else ""


#: (perf_counter stamp, rendered Date value) — HTTP Date has 1 s
#: precision, so re-rendering it per response is pure waste on the
#: ingest path; refreshed every 0.4 s (staleness bounded well under the
#: format's own resolution)
_date_cache = (float("-inf"), "")


def _http_date() -> str:
    global _date_cache
    now = time.perf_counter()
    stamp, value = _date_cache
    if now - stamp > 0.4:
        value = email.utils.formatdate(usegmt=True)
        _date_cache = (now, value)
    return value


def _render_head(out: RequestOutcome) -> bytes:
    """The exact byte sequence BaseHTTPRequestHandler emitted for this
    outcome: status line, Server, Date, Content-Type, Content-Length,
    extra headers, blank line; for a transport-level error its
    `send_error`'s: the message as the reason phrase and
    `Connection: close` ahead of the entity headers."""
    lines = [
        f"HTTP/1.1 {out.status} "
        f"{out.reason or _status_phrase(out.status)}\r\n",
        f"Server: {_SERVER_SOFTWARE}\r\n",
        f"Date: {_http_date()}\r\n",
    ]
    if out.reason:
        lines.append("Connection: close\r\n")
    lines.append(f"Content-Type: {out.ctype}\r\n")
    lines.append(f"Content-Length: {out.advertised_len}\r\n")
    lines.extend(f"{k}: {v}\r\n" for k, v in out.extra_headers.items())
    lines.append("\r\n")
    return "".join(lines).encode("latin-1", "strict")


def _error_outcome(code: int, message: Optional[str] = None,
                   explain: Optional[str] = None) -> RequestOutcome:
    """A transport-level error reply (malformed request line, oversized
    header, unsupported method) as the stdlib's send_error wrote it;
    the connection closes after it."""
    _M_PROTOCOL_ERRORS.labels(code=str(code)).inc()
    out = RequestOutcome()
    phrase, long_explain = (BaseHTTPRequestHandler.responses.get(code)
                            or ("???", "???"))
    out.reason = message or phrase
    body = (http.server.DEFAULT_ERROR_MESSAGE % {
        "code": code,
        "message": html.escape(out.reason, quote=False),
        "explain": html.escape(explain or long_explain, quote=False),
    }).encode("utf-8", "replace")
    out.status = code
    out.data = body
    out.advertised_len = len(body)
    out.ctype = http.server.DEFAULT_ERROR_CONTENT_TYPE
    out.close = True
    return out


class RequestHead:
    """One request's head, taken a line at a time from whichever
    transport owns the socket (`rfile`, or the loop's `StreamReader`):
    no I/O here, so both share `BaseHTTPRequestHandler.parse_request`'s
    checks, in its order. After the last line :meth:`feed` wants,
    `error` holds a protocol error's canned reply, or `method` is None
    (the peer closed, or sent a blank line for a request: hang up), or
    the other fields describe the request; `headers` is a plain dict
    under the names as sent, the last of a repeated name winning, as
    `dict(Message.items())` gave."""

    __slots__ = ("line", "method", "target", "headers", "length",
                 "close_after", "expect_continue", "error", "_http11",
                 "_lines")

    def __init__(self):
        self.line = b""
        self.method: Optional[str] = None
        self.target = ""
        self.headers: Dict[str, str] = {}
        self.length = 0
        self.close_after = True
        self.expect_continue = False
        self.error: Optional[RequestOutcome] = None
        self._http11 = False
        self._lines = 0

    def _refuse(self, code: int, message: Optional[str] = None,
                explain: Optional[str] = None) -> bool:
        self.error = _error_outcome(code, message, explain)
        return False

    def _request_line(self, line: bytes) -> bool:
        self.line = line
        if len(line) > _MAX_LINE:
            return self._refuse(414)
        text = line.decode("latin-1").rstrip("\r\n")
        words = text.split()
        if not words:
            return False
        if len(words) >= 3:
            version = words[-1]
            if version == "HTTP/1.1":
                self._http11 = True
            elif version != "HTTP/1.0":
                major, dot, minor = version[5:].partition(".")
                if not (version.startswith("HTTP/") and version.isascii()
                        and major.isdigit() and minor.isdigit()
                        and len(major) <= 10 and len(minor) <= 10):
                    return self._refuse(
                        400, f"Bad request version ({version!r})")
                if int(major) >= 2:
                    return self._refuse(
                        505, f"Invalid HTTP version ({version[5:]})")
                self._http11 = (int(major), int(minor)) >= (1, 1)
        if len(words) != 3:
            # two words were an HTTP/0.9 request, which is not served
            return self._refuse(400, f"Bad request syntax ({text!r})")
        self.method, target = words[0], words[1]
        if target.startswith("//"):
            # gh-87389: //host/path reads as a URI without its scheme
            target = "/" + target.lstrip("/")
        self.target = target
        self.close_after = not self._http11
        return True

    def feed(self, line: bytes) -> bool:
        """Take the next line of the head as the socket gave it (`b""`
        at EOF); False once no more is wanted."""
        if self.method is None:
            return self._request_line(line)
        if line in _BLANK:
            if self.method not in _METHODS:
                return self._refuse(
                    501, f"Unsupported method ({self.method!r})")
            return False
        self._lines += 1
        if len(line) > _MAX_LINE:
            return self._refuse(
                431, "Line too long",
                f"got more than {_MAX_LINE} bytes when reading header line")
        if self._lines >= _MAX_HEADERS:
            return self._refuse(
                431, "Too many headers",
                f"got more than {_MAX_HEADERS} headers")
        key, sep, value = line.decode("latin-1").partition(":")
        if not sep or key[:1] in " \t":
            return self._refuse(400, "Bad header line")
        key, value = key.strip(), value.strip()
        self.headers[key] = value
        lk = key.lower()
        if lk == "content-length":
            if not (value.isascii() and value.isdigit()):
                return self._refuse(400, "Bad Content-Length")
            self.length = int(value)
        elif lk == "connection":
            v = value.lower()
            if v == "close":
                self.close_after = True
            elif v == "keep-alive":
                self.close_after = False
        elif lk == "expect":
            self.expect_continue = (value.lower() == "100-continue"
                                    and self._http11)
        return True


# ---------------------------------------------------------------------------
# threaded transport (what `pio deploy` starts)
# ---------------------------------------------------------------------------

class _Handler(socketserver.StreamRequestHandler):
    """One connection's thread: requests off a kept-alive socket until
    either side closes it."""

    api = None  # set by make_server
    # serving-latency path: without this, Nagle + delayed-ACK adds ~40ms
    # per small keep-alive response (CreateServer.scala p50 parity target)
    disable_nagle_algorithm = True

    def handle(self):
        _REQUEST_CPU.join()
        try:
            while self._one_request():
                pass
        except ConnectionError:
            # the client gave up on this connection (timeout, retry on a
            # fresh one, or a mid-request kill); the work is done — losing
            # the response write is their failure mode, not ours
            pass
        finally:
            _REQUEST_CPU.leave()    # the thread ends with its connection

    def _one_request(self) -> bool:
        """Read, dispatch and answer one request; False to hang up."""
        readline, send = self.rfile.readline, self.connection.sendall
        head = RequestHead()
        while head.feed(readline(_MAX_LINE + 1)):
            pass
        out = head.error
        if out is None:
            if head.method is None:
                return False
            if head.expect_continue:
                _M_WRITES.inc()
                send(_CONTINUE)
            # read() on the buffered file takes Content-Length bytes
            # however they arrive (storage RPC bodies run to many MB)
            body = self.rfile.read(head.length) if head.length else b""
            out = dispatch_request(self.api, head.method, head.target,
                                   body, head.headers)
        if _log.isEnabledFor(logging.DEBUG):
            _log.debug('"%s" %s -', head.line.decode("latin-1").rstrip(),
                       "aborted" if out.abort else out.status)
        _M_REQUESTS.inc()
        if out.abort:
            return False   # no response bytes at all: a mid-request kill
        _M_WRITES.inc()
        send(_render_head(out) + out.data)
        return not (out.close or head.close_after)


# ---------------------------------------------------------------------------
# async transport (the event-loop rewrite, ROADMAP item 4)
# ---------------------------------------------------------------------------

#: known-nonblocking GET routes served inline on the loop thread; every
#: other request runs on the bounded executor because handlers may block
#: (WAL group commit, device dispatch, storage RPC)
_INLINE_PATHS = frozenset({"/healthz"})


def _dispatch_and_render(api, method, target, body, headers):
    """Executor-side unit of work for the async transport: run the
    handler AND assemble the response bytes off the loop thread, so the
    loop only writes. Returns (outcome, wire_bytes|None for abort)."""
    out = dispatch_request(api, method, target, body, headers)
    if out.abort:
        return out, None
    return out, _render_head(out) + out.data


class _Conn:
    """Book-keeping for one live async connection (drain accounting)."""

    __slots__ = ("task", "reader_task", "admitted", "served")

    def __init__(self):
        self.task = None
        self.reader_task = None
        self.admitted = 0
        self.served = 0


class AsyncHTTPServer:
    """asyncio transport with the ThreadingHTTPServer lifecycle surface
    (``serve_forever`` / ``shutdown`` / ``server_close`` /
    ``server_address``) so every existing call site — the daemons'
    serve loops, the tests — runs unmodified on either
    transport.

    The listening socket binds in the constructor (callers read
    ``server_address`` before starting the loop thread); the event loop
    itself lives in whatever thread calls :meth:`serve_forever`.
    ``shutdown`` is the graceful drain: stop accepting, stop READING
    new requests off live connections, finish every already-admitted
    request (their WAL group commits land and their responses go out —
    zero acknowledged-event loss), then stop the loop."""

    #: how long shutdown waits for admitted in-flight requests before
    #: cancelling stragglers
    drain_grace_s = 30.0

    def __init__(self, api, host: str = "localhost", port: int = 0,
                 tls: bool = True):
        self.api = api
        self._ssl = None
        if tls:
            from predictionio_tpu.common.server_security import (
                ssl_context_from_env,
            )
            self._ssl = ssl_context_from_env()
            if self._ssl is not None:
                _log.info("TLS enabled (PIO_SSL_CERTFILE)")
        # socketserver's default listen backlog of 5 resets bursts of
        # concurrent connects (measured: 32 parallel ingest clients) —
        # same 128 backlog as the threaded transport
        self._sock = socket.create_server((host, port), backlog=128)
        self.server_address = self._sock.getsockname()
        self.daemon_threads = True   # lifecycle-surface parity (no-op)
        self._pipeline = _pipeline_window()
        self._executor = ThreadPoolExecutor(
            max_workers=_executor_workers(), thread_name_prefix="pio-http",
            initializer=_REQUEST_CPU.join)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._stop_event: Optional[asyncio.Event] = None
        self._conns: set = set()
        self._started = threading.Event()
        self._done = threading.Event()
        self._shutdown_requested = threading.Event()
        self._closed = False

    # ------------------------------------------------------------ lifecycle
    def serve_forever(self):
        try:
            asyncio.run(self._main())
        finally:
            self._started.set()
            self._done.set()

    def shutdown(self):
        """Graceful drain; blocks until the loop exits (ThreadingHTTPServer
        contract). Safe to call before or without serve_forever."""
        self._shutdown_requested.set()
        # wait out the start race: serve_forever may be mid-startup on
        # its thread (a shutdown with no serve_forever at all times out
        # here and returns — nothing to stop)
        self._started.wait(5.0)
        loop, stop = self._loop, self._stop_event
        if loop is not None and stop is not None and loop.is_running():
            with contextlib.suppress(RuntimeError):
                loop.call_soon_threadsafe(stop.set)
        if self._started.is_set():
            self._done.wait(self.drain_grace_s + 10.0)

    def server_close(self):
        self._shutdown_requested.set()
        if not self._closed and not self._started.is_set():
            # loop never ran: nothing owns the socket but us
            self._closed = True
            with contextlib.suppress(OSError):
                self._sock.close()
        self._executor.shutdown(wait=False)

    # ------------------------------------------------------------ the loop
    async def _main(self):
        self._loop = asyncio.get_running_loop()
        self._stop_event = asyncio.Event()
        if self._shutdown_requested.is_set():
            self._stop_event.set()
        server = await asyncio.start_server(
            self._client, sock=self._sock, ssl=self._ssl)
        self._closed = True   # the asyncio server owns the socket now
        self._started.set()
        await self._stop_event.wait()
        server.close()
        await server.wait_closed()
        # drain: stop reading new requests everywhere; idle connections
        # close now, busy ones finish every admitted request first
        for conn in list(self._conns):
            if conn.reader_task is not None:
                conn.reader_task.cancel()
            if conn.admitted == conn.served and conn.task is not None:
                conn.task.cancel()
        deadline = self._loop.time() + self.drain_grace_s
        while self._conns and self._loop.time() < deadline:
            await asyncio.sleep(0.005)
        for conn in list(self._conns):
            if conn.task is not None:
                conn.task.cancel()
        await asyncio.sleep(0)
        self._executor.shutdown(wait=False)

    async def _client(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter):
        sock = writer.get_extra_info("socket")
        if sock is not None:
            with contextlib.suppress(OSError):
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _Conn()
        conn.task = asyncio.current_task()
        # per-connection pipeline: the read loop admits up to `window`
        # requests ahead of the write loop and dispatches each to the
        # executor immediately, so pipelined ingest batches on ONE
        # connection coalesce into one WAL group commit; responses are
        # written strictly in request order (HTTP/1.1 pipelining)
        queue: asyncio.Queue = asyncio.Queue()
        window = asyncio.Semaphore(self._pipeline)
        conn.reader_task = asyncio.create_task(
            self._read_loop(reader, writer, queue, window, conn))
        self._conns.add(conn)
        try:
            while True:
                item = await queue.get()
                if item is None:
                    break
                fut, close_after = item
                try:
                    out, payload = await fut
                except Exception:
                    _log.exception("async dispatch failed")
                    break
                _M_REQUESTS.inc()
                if out.abort:
                    break   # injected mid-request kill: sever, no bytes
                _M_WRITES.inc()
                writer.write(payload)
                try:
                    await writer.drain()
                except (ConnectionError, OSError):
                    break   # client gave up; the work is done
                conn.served += 1
                window.release()
                if out.close or close_after:
                    break
        except asyncio.CancelledError:
            pass
        finally:
            # bookkeeping FIRST, and nothing awaited after it: an await
            # here can re-raise CancelledError (a BaseException — it
            # sails past suppress(Exception)) and would skip the
            # discard+close, leaving the drain waiting on a connection
            # that will never go away
            self._conns.discard(conn)
            if conn.reader_task is not None:
                conn.reader_task.cancel()
            with contextlib.suppress(BaseException):
                writer.close()

    async def _read_loop(self, reader, writer, queue, window, conn):
        loop = asyncio.get_running_loop()
        try:
            while True:
                await window.acquire()
                head = RequestHead()
                try:
                    while head.feed(await reader.readline()):
                        pass
                except (asyncio.LimitOverrunError, ValueError):
                    # the StreamReader's own 64 KiB limit on a line
                    head.feed(b" " * (_MAX_LINE + 1))
                if head.error is None and head.method is None:
                    queue.put_nowait(None)   # the peer closed
                    return
                conn.admitted += 1
                err = head.error
                if err is not None:
                    fut = loop.create_future()
                    fut.set_result((err, _render_head(err) + err.data))
                    queue.put_nowait((fut, True))
                    return
                close_after = head.close_after
                if head.expect_continue:
                    _M_WRITES.inc()
                    writer.write(_CONTINUE)
                body = (await reader.readexactly(head.length)
                        if head.length else b"")
                if self._stop_event is not None \
                        and self._stop_event.is_set():
                    close_after = True   # draining: serve, then hang up
                work = (self.api, head.method, head.target, body,
                        head.headers)
                if head.method == "GET" and \
                        head.target.partition("?")[0] in _INLINE_PATHS:
                    # known-nonblocking probe: skip the executor hop
                    fut = loop.create_future()
                    fut.set_result(_dispatch_and_render(*work))
                else:
                    fut = loop.run_in_executor(
                        self._executor, _dispatch_and_render, *work)
                queue.put_nowait((fut, close_after))
                if close_after:
                    return
        except asyncio.CancelledError:
            queue.put_nowait(None)
            raise
        except (ConnectionError, OSError, asyncio.IncompleteReadError,
                ValueError):
            queue.put_nowait(None)


# ---------------------------------------------------------------------------
# construction + daemon lifecycle (transport-agnostic)
# ---------------------------------------------------------------------------

def make_server(api, host: str = "localhost", port: int = 0,
                tls: bool = True, transport: Optional[str] = None):
    """Build (without starting) an HTTP server around `api` on the
    configured transport (``transport`` argument > ``PIO_TRANSPORT`` >
    threaded).

    port=0 binds an ephemeral port; read it from server.server_address.
    TLS engages automatically when PIO_SSL_CERTFILE is configured
    (SSLConfiguration.scala role); pass tls=False to force plaintext.
    Both transports expose the same lifecycle surface
    (serve_forever/shutdown/server_close/server_address)."""
    telemetry.registry().register_collector(_collect_cpu)
    if transport_mode(transport) == "async":
        return AsyncHTTPServer(api, host, port, tls=tls)
    handler = type("BoundHandler", (_Handler,), {"api": api})
    # socketserver's default listen backlog of 5 resets bursts of
    # concurrent connects (measured: 32 parallel ingest clients)
    server_cls = type("BoundServer", (ThreadingHTTPServer,),
                      {"request_queue_size": 128})
    server = server_cls((host, port), handler)
    server.daemon_threads = True
    if tls:
        from predictionio_tpu.common.server_security import maybe_wrap_ssl
        scheme = maybe_wrap_ssl(server)
        if scheme == "https":
            _log.info("TLS enabled (PIO_SSL_CERTFILE)")
    return server


def serve_background(api, host: str = "localhost",
                     port: int = 0, transport: Optional[str] = None
                     ) -> Tuple[object, int]:
    """Start `api` on a daemon thread; returns (server, bound_port)."""
    server = make_server(api, host, port, transport=transport)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, server.server_address[1]


def install_sigterm_handler(fn: Callable[[], None]) -> bool:
    """Route SIGTERM to ``fn`` (run on a fresh thread so the signal
    frame never blocks). Returns False outside the main thread, where
    CPython refuses to install handlers — callers then rely on their
    explicit drain/stop paths instead."""
    def _handler(_signum, _frame):
        threading.Thread(target=fn, name="pio-drain", daemon=True).start()
    try:
        signal.signal(signal.SIGTERM, _handler)
        return True
    except ValueError:
        return False


def serve_forever(api, host: str = "localhost", port: int = 7070,
                  on_drain: Optional[Callable[[], None]] = None) -> None:
    """Run a daemon until SIGTERM/SIGINT, then shut down GRACEFULLY:
    mark the api draining (``/readyz`` flips to 503 so load balancers
    stop routing here), stop accepting connections, and run ``on_drain``
    exactly once (e.g. flush the eventlog WAL buffers) before returning.
    On the threaded transport, in-flight handler threads serialize on
    their backend locks, so a drain-time flush completes after the
    writes it races with; on the async transport, shutdown() itself
    waits for every admitted request (their WAL group commits included)
    before the loop exits — zero acknowledged-event loss either way."""
    server = make_server(api, host, port)
    drained = threading.Event()

    def _drain():
        if drained.is_set():
            return
        drained.set()
        setattr(api, "draining", True)
        server.shutdown()

    install_sigterm_handler(_drain)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        _drain()
        server.server_close()
        if on_drain is not None:
            on_drain()
