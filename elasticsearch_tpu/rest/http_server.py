"""Minimal asyncio HTTP/1.1 server fronting the RestController.

Plays the role of `Netty4HttpServerTransport` (reference layer 4): accepts
keep-alive connections, parses request line + headers + Content-Length
bodies, dispatches to the controller on a worker thread pool (handlers do
blocking engine work), renders JSON (or text for _cat) responses. No
external dependencies — stdlib asyncio only.

Who writes a response. The asyncio loop's one thread reads requests and
submits them; the pool's worker that computed an answer renders it
(`_render_response`, the one serialisation both routes share) and sends
it on the connection's own socket, a `dup()` of the transport's made
once a connection and closed with it. The loop is not woken to answer:
the connection's coroutine is back in `readline()` and looks at the
finished future when the next request line is in hand (it waits for it
only where a client pipelines, or turns around faster than the worker
returns). One request is in flight a connection, so answers leave in
request order.

The loop writes, through the `StreamWriter` as it always did, what no
worker may or could: every response of a TLS connection (only the
transport holds the TLS state), a 429 of a full queue (no worker took
the request), a response submitted while the transport still buffers an
earlier one, and the rest of a response the socket did not take whole
(`EAGAIN`, a short write: a `_bulk` answer larger than the send buffer).
The route is chosen from what the transport is and what the socket took,
never from a setting or the route's name. Counters
`http.responses.worker` and `http.responses.loop` (`GET _nodes/stats` →
`telemetry`) count each response once, under the thread that ended it.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import time
import urllib.parse
from typing import Optional

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.common import xcontent
from elasticsearch_tpu.common.threadpool import (
    EsRejectedExecutionError, ThreadPool, pool_for_route,
)
from elasticsearch_tpu.rest.controller import RestController


def _negotiate_accept(accept: Optional[str]) -> Optional[str]:
    """Multi-valued Accept header → first supported x-content type, or None
    for the JSON default (reference: media-type negotiation in
    AbstractHttpServerTransport/RestController)."""
    if not accept:
        return None
    for part in accept.split(","):
        media = part.split(";")[0].strip()
        if media in ("*/*", "application/json"):
            return None
        try:
            return xcontent.XContentType.from_media_type(part.strip())
        except Exception:
            continue
    return None

MAX_BODY = 100 * 1024 * 1024  # reference http.max_content_length default 100mb

RESPONSES_BY_WORKER = "http.responses.worker"
RESPONSES_BY_LOOP = "http.responses.loop"

_REASONS = {200: "OK", 201: "Created", 400: "Bad Request", 404: "Not Found",
            405: "Method Not Allowed", 409: "Conflict",
            429: "Too Many Requests", 500: "Internal Server Error",
            503: "Service Unavailable"}


def _render_response(status: int, payload, keep_alive: bool,
                     accept: Optional[str]) -> bytes:
    """Status line, headers and body of one response, as the bytes that go
    on the wire: the one serialisation, whichever thread sends them."""
    if payload is None:
        data = b""
        ctype = "application/json"
    elif isinstance(payload, str):
        data = payload.encode("utf-8")
        ctype = "text/plain; charset=UTF-8"
    else:
        data = None
        out_type = _negotiate_accept(accept)
        if out_type and out_type != "application/json":
            try:
                data = xcontent.dumps(payload, out_type)
                ctype = out_type
            except Exception:
                data = None  # unencodable in that format: JSON fallback
        if data is None:
            data = json.dumps(payload).encode("utf-8")
            ctype = "application/json"
    head = (f"HTTP/1.1 {status} {_REASONS.get(status, 'Unknown')}\r\n"
            f"content-type: {ctype}\r\n"
            f"content-length: {len(data)}\r\n"
            f"connection: {'keep-alive' if keep_alive else 'close'}\r\n"
            f"X-elastic-product: Elasticsearch\r\n\r\n")
    return head.encode("latin-1") + data


class _Connection:
    """One accepted connection: the loop's writer and, over plain TCP, a
    socket of its own that a pool worker may send on. What asyncio hands
    out as the transport's socket refuses `send`; its `dup()` is a real
    socket on the same connection whose descriptor no other connection
    can be given while a worker still holds the object. A TLS transport
    has no socket but its own: `sock` is None and the loop writes."""

    __slots__ = ("loop", "writer", "sock")

    def __init__(self, writer: asyncio.StreamWriter):
        self.loop = asyncio.get_running_loop()
        self.writer = writer
        self.sock = None
        transport = writer.transport
        if transport.get_extra_info("ssl_object") is None:
            raw = transport.get_extra_info("socket")
            if raw is not None:
                self.sock = raw.dup()   # non-blocking, like the original

    def worker_socket(self):
        """On the loop, before a submit: the socket the worker may send
        on, or None while the transport still buffers an earlier
        response (bytes sent past it would overtake it)."""
        if self.writer.transport.get_write_buffer_size():
            return None
        return self.sock

    def close(self) -> None:
        if self.sock is not None:
            self.sock.close()


class HttpServer:
    def __init__(self, controller: RestController, host: str = "127.0.0.1",
                 port: int = 9200, max_workers: int = 8, thread_pool=None,
                 ssl_context=None):
        self.controller = controller
        self.host = host
        self.port = port
        self._server: Optional[asyncio.AbstractServer] = None
        # http.ssl.*: TLS terminates in-process (reference:
        # SecurityRestFilter + Netty4HttpServerTransport with
        # xpack.security.http.ssl); plaintext bytes on a TLS port fail
        # the handshake and never reach the REST layer
        self.ssl_context = ssl_context
        # per-workload named executors (ThreadPool.java): requests route to
        # the pool their workload class owns, so e.g. a bulk flood queues in
        # `write` while `search` keeps draining; full queues answer 429
        self.thread_pool = thread_pool or ThreadPool()
        self._owns_pool = thread_pool is None
        # both exist from the start: one that never moves reads 0
        telemetry.metrics.counter(RESPONSES_BY_WORKER)
        telemetry.metrics.counter(RESPONSES_BY_LOOP)

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port, ssl=self.ssl_context)
        addr = self._server.sockets[0].getsockname()
        self.port = addr[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        if self._owns_pool:
            self.thread_pool.shutdown()

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        """One connection's coroutine: it reads. A request is submitted
        once the connection's previous one is answered (a client that
        pipelines has its next request read, not run), and nothing wakes
        this coroutine to answer."""
        conn = _Connection(writer)
        idle_ns = time.monotonic_ns()     # accepted; later: last response out
        in_flight = None        # the future of the request being answered
        try:
            while True:
                request = await self._read_request(reader)
                if in_flight is not None:
                    idle_ns = await self._answered(in_flight)
                    in_flight = None
                if request is None:
                    break
                start_ns, method, path, query, headers, body = request
                # a worker stamps its response a moment after the bytes
                # left: a client that turned around faster read them first
                front = telemetry.Front(min(idle_ns, start_ns), start_ns)
                keep_alive = headers.get("connection", "keep-alive").lower() != "close"
                accept = headers.get("accept")
                front.read_ns = front.submit_ns = time.monotonic_ns()
                try:
                    in_flight = self.thread_pool.submit(
                        pool_for_route(method, path),
                        self._run_handler, conn, conn.worker_socket(), front,
                        keep_alive, accept, method, path, query, body,
                        headers.get("content-type"), headers)
                except EsRejectedExecutionError as e:
                    idle_ns = await self._respond_on_loop(
                        conn, front, None, 429,
                        {"error": e.to_dict(), "status": 429},
                        keep_alive, accept)
                if not keep_alive:
                    break
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            # the writer and the workers' socket close only after the
            # worker in flight has finished with them
            try:
                with contextlib.suppress(Exception):
                    if in_flight is not None:
                        await self._answered(in_flight)
                with contextlib.suppress(Exception):
                    writer.close()
                    await writer.wait_closed()
            finally:
                conn.close()

    @staticmethod
    async def _answered(future) -> int:
        """The clock reading at which a submitted request's response was
        out. Usually the worker has returned by the time this is asked
        and nothing is awaited."""
        out = future.result() if future.done() \
            else await asyncio.wrap_future(future)
        if not isinstance(out, int):    # the loop is finishing it
            out = await asyncio.wrap_future(out)
        return out

    def _run_handler(self, conn: _Connection, sock, front, keep_alive: bool,
                     accept: Optional[str], *request):
        """On the pool's worker: the front marks the handler's start and
        return and rides the thread, so that a handler which samples the
        request (`telemetry.rest_request`) finds it. Then the answer goes
        out from here, on `sock`. Returns when the response was out, or
        the future of the loop's finishing it."""
        with front:
            status, payload = self.controller.dispatch(*request)
        if sock is None:
            return asyncio.run_coroutine_threadsafe(
                self._respond_on_loop(conn, front, None, status, payload,
                                      keep_alive, accept), conn.loop)
        front.wake_ns = time.monotonic_ns()
        with telemetry.annotation("http.respond"):
            data = _render_response(status, payload, keep_alive, accept)
            try:
                sent = sock.send(data)
            except OSError:     # EAGAIN, or a connection that is gone:
                sent = 0        # the transport finds out which
        if sent < len(data):
            return asyncio.run_coroutine_threadsafe(
                self._respond_on_loop(conn, front, data[sent:]), conn.loop)
        end_ns = time.monotonic_ns()
        front.finish(end_ns)
        telemetry.metrics.counter(RESPONSES_BY_WORKER).inc()
        return end_ns

    async def _respond_on_loop(self, conn: _Connection, front,
                               rest: Optional[bytes], *response) -> int:
        """The loop's route: `rest` of a response a worker began, or the
        whole of one no worker could (`*response` as `_render_response`
        takes it). Returns when it was drained."""
        try:
            with telemetry.annotation("http.respond"):
                if rest is None:
                    front.wake_ns = time.monotonic_ns()
                    rest = _render_response(*response)
                conn.writer.write(rest)
                await conn.writer.drain()
        finally:
            # the request's last stages are filed where its response
            # ends; a sampled trace ends socket to socket
            end_ns = time.monotonic_ns()
            front.finish(end_ns)
            telemetry.metrics.counter(RESPONSES_BY_LOOP).inc()
        return end_ns

    async def _read_request(self, reader: asyncio.StreamReader):
        try:
            request_line = await reader.readline()
        except (asyncio.LimitOverrunError, ValueError):
            return None
        if not request_line:
            return None
        # the request's life in the server starts here: its line in hand
        start_ns = time.monotonic_ns()
        with telemetry.annotation("http.read"):
            request = await self._read_rest(reader, request_line)
        return None if request is None else (start_ns,) + request

    async def _read_rest(self, reader: asyncio.StreamReader,
                         request_line: bytes):
        try:
            method, target, _version = request_line.decode("latin-1").split(" ", 2)
        except ValueError:
            return None
        parsed = urllib.parse.urlsplit(target)
        # keep the RAW path: the controller decodes per-SEGMENT, so an
        # encoded slash inside a segment (date-math index names) survives
        path = parsed.path
        query = {k: v[-1] for k, v in urllib.parse.parse_qs(
            parsed.query, keep_blank_values=True).items()}

        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            name, _, value = line.decode("latin-1").partition(":")
            headers[name.strip().lower()] = value.strip()

        body = b""
        length = int(headers.get("content-length", 0) or 0)
        if length > MAX_BODY:
            return None
        if length:
            body = await reader.readexactly(length)
        elif headers.get("transfer-encoding", "").lower() == "chunked":
            chunks = []
            while True:
                size_line = await reader.readline()
                size = int(size_line.strip() or b"0", 16)
                if size == 0:
                    await reader.readline()
                    break
                chunks.append(await reader.readexactly(size))
                await reader.readline()
            body = b"".join(chunks)
        return method.upper(), path, query, headers, body
