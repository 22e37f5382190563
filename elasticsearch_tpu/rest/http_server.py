"""Minimal asyncio HTTP/1.1 server fronting the RestController.

Plays the role of `Netty4HttpServerTransport` (reference layer 4): accepts
keep-alive connections, parses request line + headers + Content-Length
bodies, dispatches to the controller on a worker thread pool (handlers do
blocking engine work), renders JSON (or text for _cat) responses. No
external dependencies — stdlib asyncio only.
"""

from __future__ import annotations

import asyncio
import json
import time
import urllib.parse
from typing import Optional, Tuple

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.common import xcontent
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


class HttpServer:
    def __init__(self, controller: RestController, host: str = "127.0.0.1",
                 port: int = 9200, max_workers: int = 8, thread_pool=None,
                 ssl_context=None):
        from elasticsearch_tpu.common.threadpool import ThreadPool
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
        idle_ns = time.monotonic_ns()     # accepted; later: last response out
        try:
            while True:
                request = await self._read_request(reader, idle_ns)
                if request is None:
                    break
                front, method, path, query, headers, body = request
                from elasticsearch_tpu.common.threadpool import (
                    EsRejectedExecutionError, pool_for_route,
                )
                try:
                    front.submit_ns = time.monotonic_ns()
                    future = self.thread_pool.submit(
                        pool_for_route(method, path),
                        self._run_handler, front, method, path, query,
                        body, headers.get("content-type"), headers)
                    status, payload = await asyncio.wrap_future(future)
                    # the handler's return on the worker -> this
                    # coroutine running again: the loop's lag
                    front.wake_ns = time.monotonic_ns()
                except EsRejectedExecutionError as e:
                    status, payload = 429, {"error": e.to_dict(),
                                            "status": 429}
                keep_alive = headers.get("connection", "keep-alive").lower() != "close"
                try:
                    with telemetry.annotation("http.respond"):
                        await self._write_response(
                            writer, status, payload, keep_alive,
                            accept=headers.get("accept"))
                finally:
                    # the request's stages are filed here, together; a
                    # sampled trace ends socket to socket
                    idle_ns = time.monotonic_ns()
                    front.finish(idle_ns)
                if not keep_alive:
                    break
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    def _run_handler(self, front, *request):
        """On the pool's worker: the front marks the handler's start and
        return and rides the thread, so that a handler which samples the
        request (`telemetry.rest_request`) finds it."""
        with front:
            return self.controller.dispatch(*request)

    async def _read_request(self, reader: asyncio.StreamReader,
                            idle_ns: int):
        try:
            request_line = await reader.readline()
        except (asyncio.LimitOverrunError, ValueError):
            return None
        if not request_line:
            return None
        # the request's life in the server starts here: its line in hand
        front = telemetry.Front(idle_ns, time.monotonic_ns())
        with telemetry.annotation("http.read"):
            request = await self._read_rest(reader, request_line)
        front.read_ns = time.monotonic_ns()
        return None if request is None else (front,) + request

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

    async def _write_response(self, writer: asyncio.StreamWriter, status: int,
                              payload, keep_alive: bool,
                              accept: str = None) -> None:
        reasons = {200: "OK", 201: "Created", 400: "Bad Request", 404: "Not Found",
                   405: "Method Not Allowed", 409: "Conflict", 429: "Too Many Requests",
                   500: "Internal Server Error", 503: "Service Unavailable"}
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
        head = (f"HTTP/1.1 {status} {reasons.get(status, 'Unknown')}\r\n"
                f"content-type: {ctype}\r\n"
                f"content-length: {len(data)}\r\n"
                f"connection: {'keep-alive' if keep_alive else 'close'}\r\n"
                f"X-elastic-product: Elasticsearch\r\n\r\n")
        writer.write(head.encode("latin-1") + data)
        await writer.drain()
