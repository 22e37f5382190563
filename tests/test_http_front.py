"""The HTTP front (ISSUE 27): the worker that computed an answer writes
it to the socket, the asyncio loop reads, and writes only what no worker
may or could. A real `HttpServer` on a free port over a stub controller;
clients are raw sockets, so that what is compared is the wire."""

import asyncio
import json
import os
import socket
import ssl
import sys
import threading
import time
import weakref

import pytest

from elasticsearch_tpu.common.threadpool import ThreadPool
from elasticsearch_tpu.rest import http_server
from elasticsearch_tpu.rest.http_server import HttpServer
from elasticsearch_tpu.telemetry import metrics

BIG = 24 * 1024 * 1024      # more than a loopback socket's buffers take


class Stub:
    """A controller whose answers the test can predict and hold back."""

    def __init__(self):
        self.release = threading.Event()
        self.entered = threading.Semaphore(0)
        self.log = []                   # (tag, begun, returned)

    def dispatch(self, method, path, query, body, content_type=None,
                 headers=None):
        begun = time.monotonic_ns()
        try:
            if method == "HEAD":
                return 200, None
            if path.startswith("/hold"):
                self.entered.release()
                assert self.release.wait(30)
            if path.startswith("/sleep"):
                time.sleep(0.2)
            if path == "/big":
                return 200, {"blob": "x" * BIG}
            if path == "/_cat/text":
                return 200, "a b c\n"
            if path == "/missing":
                return 404, {"error": {"type": "nope"}, "status": 404}
            return 200, {"path": path, "tag": query.get("tag"),
                         "echo": body.decode("latin-1")}
        finally:
            self.log.append((query.get("tag"), begun, time.monotonic_ns()))


class Served:
    """An `HttpServer` on a loop of its own thread, stopped for good at
    the end: tasks cancelled, loop closed."""

    def __init__(self, ssl_context=None, pool_settings=None):
        self.stub = Stub()
        self.pool = ThreadPool(pool_settings)
        self.server = HttpServer(self.stub, host="127.0.0.1", port=0,
                                 thread_pool=self.pool,
                                 ssl_context=ssl_context)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def serve():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=serve, daemon=True)
        self.thread.start()
        assert started.wait(15)
        self.port = self.server.port

    def on_loop(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(15)

    def tasks(self) -> int:
        async def count():
            return len(asyncio.all_tasks()) - 1         # less this one
        return self.on_loop(count())

    def stop(self):
        self.stub.release.set()

        async def shut():
            self.server._server.close()
            for t in asyncio.all_tasks() - {asyncio.current_task()}:
                t.cancel()
        self.on_loop(shut())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(15)
        assert not self.thread.is_alive()
        self.loop.close()
        self.pool.shutdown()


@pytest.fixture()
def served():
    s = Served()
    yield s
    s.stop()


@pytest.fixture(scope="module")
def tls_contexts(tmp_path_factory):
    pytest.importorskip("cryptography")
    from elasticsearch_tpu.transport.tls import (
        TlsConfig, generate_ca, generate_node_cert)
    out = str(tmp_path_factory.mktemp("front_certs"))
    ca = generate_ca(out)
    node = generate_node_cert(out, ca["cert"], ca["key"], name="node",
                              hosts=["127.0.0.1", "localhost"])
    server_ctx = TlsConfig(node["cert"], node["key"],
                           client_authentication="none").server_context()
    client_ctx = ssl.create_default_context(cafile=ca["cert"])
    client_ctx.check_hostname = False
    return server_ctx, client_ctx


def counts():
    c = metrics.REGISTRY.snapshot()["counters"]
    return (c[http_server.RESPONSES_BY_WORKER],
            c[http_server.RESPONSES_BY_LOOP])


def moved(before, want=None):
    """How far the two counters moved; a response is counted just after
    its bytes are out, so a client that has them waits for `want`."""
    deadline = time.monotonic() + 10
    while True:
        now = counts()
        got = now[0] - before[0], now[1] - before[1]
        if want is None or got == want or time.monotonic() > deadline:
            return got
        time.sleep(0.01)


def request_bytes(method, target, body=b"", **headers):
    lines = [f"{method} {target} HTTP/1.1", "host: t",
             f"content-length: {len(body)}"]
    lines += [f"{k.replace('_', '-')}: {v}" for k, v in headers.items()]
    return "\r\n".join(lines).encode() + b"\r\n\r\n" + body


_AHEAD = weakref.WeakKeyDictionary()    # socket -> bytes read past a response


def read_response(sock, head_only=False):
    """One response off the wire: (raw bytes, status, body)."""
    raw = _AHEAD.pop(sock, b"")
    while b"\r\n\r\n" not in raw:
        chunk = sock.recv(65536)
        assert chunk, f"connection closed inside a head: {raw!r}"
        raw += chunk
    head, _, rest = raw.partition(b"\r\n\r\n")
    fields = dict(line.split(": ", 1)
                  for line in head.decode("latin-1").split("\r\n")[1:])
    length = 0 if head_only else int(fields["content-length"])
    parts, have = [rest], len(rest)
    while have < length:
        chunk = sock.recv(1 << 20)
        assert chunk, "connection closed inside a body"
        parts.append(chunk)
        have += len(chunk)
    body = b"".join(parts)
    if len(body) > length:              # a pipelined answer came along
        _AHEAD[sock] = body[length:]
        body = body[:length]
    return head + b"\r\n\r\n" + body, int(head.split(b" ", 2)[1]), body


EXCHANGES = [
    ("POST", "/a/_search?tag=1", b'{"q": 1}', {}),
    ("GET", "/_cat/text", b"", {}),
    ("HEAD", "/a", b"", {}),
    ("GET", "/missing", b"", {}),
    ("GET", "/a?tag=yaml", b"", {"accept": "application/yaml"}),
    ("GET", "/a?tag=cbor", b"", {"accept": "application/cbor"}),
    ("POST", "/a/_search?tag=7", b'{"q": 7}', {}),
]


def test_keep_alive_answers_in_order_and_the_bytes_are_the_loop_routes(
        served, tls_contexts):
    """The same exchanges over one plain keep-alive connection (every
    answer written by its worker) and over TLS (every answer written by
    the loop): the same bytes, in request order."""
    server_ctx, client_ctx = tls_contexts
    over_tls = Served(ssl_context=server_ctx)
    try:
        before = counts()
        plain = socket.create_connection(("127.0.0.1", served.port), 15)
        by_worker = []
        with plain:
            for method, target, body, headers in EXCHANGES:
                plain.sendall(request_bytes(method, target, body, **headers))
                by_worker.append(read_response(plain, method == "HEAD"))
                assert plain not in _AHEAD, "bytes beyond the response"
        assert moved(before, (len(EXCHANGES), 0)) == (len(EXCHANGES), 0)
        before = counts()
        raw = socket.create_connection(("127.0.0.1", over_tls.port), 15)
        by_loop = []
        with client_ctx.wrap_socket(raw) as secure:
            for method, target, body, headers in EXCHANGES:
                secure.sendall(request_bytes(method, target, body, **headers))
                by_loop.append(read_response(secure, method == "HEAD"))
        assert moved(before, (0, len(EXCHANGES))) == (0, len(EXCHANGES)), \
            "TLS: the loop alone"
    finally:
        over_tls.stop()
    assert [r[0] for r in by_worker] == [r[0] for r in by_loop]
    assert [r[1] for r in by_worker] == [200, 200, 200, 404, 200, 200, 200]
    assert json.loads(by_worker[0][2])["tag"] == "1"
    assert json.loads(by_worker[6][2]) == {
        "path": "/a/_search", "tag": "7", "echo": '{"q": 7}'}
    assert by_worker[1][2] == b"a b c\n"
    assert b"content-type: application/yaml" in by_worker[4][0]
    assert b"content-type: application/cbor" in by_worker[5][0]
    for raw_bytes, _status, _body in by_worker:
        assert b"connection: keep-alive\r\n" in raw_bytes


def test_a_response_larger_than_the_send_buffer_arrives_whole_by_the_loop(
        served):
    before = counts()
    with socket.create_connection(("127.0.0.1", served.port), 30) as s:
        s.sendall(request_bytes("GET", "/big"))
        time.sleep(0.3)         # the worker's send meets a full buffer
        _raw, status, body = read_response(s)
        assert status == 200
        assert json.loads(body) == {"blob": "x" * BIG}
        assert moved(before, (0, 1)) == (0, 1)
        # the connection goes on, and small answers are the worker's again
        # once the transport has nothing of the large one left
        s.sendall(request_bytes("GET", "/after?tag=2"))
        assert json.loads(read_response(s)[2])["tag"] == "2"
    assert moved(before, (1, 1)) == (1, 1)


def test_a_client_that_pipelines_gets_its_answers_in_order(served):
    """Two requests in one segment, the first the slower: the second is
    read and not run until the first is answered."""
    before = counts()
    with socket.create_connection(("127.0.0.1", served.port), 15) as s:
        s.sendall(request_bytes("GET", "/sleep?tag=first")
                  + request_bytes("GET", "/quick?tag=second"))
        first = read_response(s)
        second = read_response(s)
    assert json.loads(first[2])["tag"] == "first"
    assert json.loads(second[2])["tag"] == "second"
    log = {tag: (begun, returned) for tag, begun, returned in served.stub.log}
    assert log["second"][0] >= log["first"][1], "one in flight a connection"
    assert moved(before, (2, 0)) == (2, 0)


def test_tls_answers_and_counts_only_the_loop(tls_contexts):
    server_ctx, client_ctx = tls_contexts
    s = Served(ssl_context=server_ctx)
    try:
        before = counts()
        raw = socket.create_connection(("127.0.0.1", s.port), 15)
        with client_ctx.wrap_socket(raw) as secure:
            for i in range(3):
                secure.sendall(request_bytes("GET", f"/t?tag={i}"))
                assert json.loads(read_response(secure)[2])["tag"] == str(i)
            secure.sendall(request_bytes("GET", "/big"))
            assert len(read_response(secure)[2]) > BIG
        assert moved(before, (0, 4)) == (0, 4)
    finally:
        s.stop()


def _open_descriptors() -> int:
    return len(os.listdir("/proc/self/fd"))


def _settles(read, want, seconds=10.0):
    deadline = time.monotonic() + seconds
    while read() != want and time.monotonic() < deadline:
        time.sleep(0.02)
    return read()


def test_close_and_disconnect_leave_no_thread_task_or_socket(served):
    stub = served.stub
    # the pool's two threads that the test will keep busy at once
    warm = [socket.create_connection(("127.0.0.1", served.port), 15)
            for _ in range(2)]
    for s in warm:
        s.sendall(request_bytes("GET", "/hold"))
        assert stub.entered.acquire(timeout=15)
    stub.release.set()
    for s in warm:
        with s:
            read_response(s)
    stub.release.clear()
    assert _settles(served.tasks, 0) == 0
    threads, descriptors = threading.active_count(), _open_descriptors()
    before = counts()
    # `connection: close`: answered by the worker, then closed by the server
    for i in range(4):
        with socket.create_connection(("127.0.0.1", served.port), 15) as s:
            s.sendall(request_bytes("GET", f"/c?tag={i}", connection="close"))
            raw, status, _body = read_response(s)
            assert status == 200 and b"connection: close\r\n" in raw
            assert s.recv(1) == b"", "the server closes after the answer"
    assert moved(before, (4, 0)) == (4, 0)
    # a client that goes away inside its request's body
    with socket.create_connection(("127.0.0.1", served.port), 15) as s:
        s.sendall(request_bytes("POST", "/x", b"{")[:-1]
                  .replace(b"content-length: 1", b"content-length: 100"))
    # a client that goes away while a worker holds its request; its answer
    # has nowhere to go and must not turn up anywhere else
    other = socket.create_connection(("127.0.0.1", served.port), 15)
    gone = socket.create_connection(("127.0.0.1", served.port), 15)
    gone.sendall(request_bytes("GET", "/hold?tag=gone"))
    assert stub.entered.acquire(timeout=15)
    gone.close()
    stub.release.set()
    with other:
        other.sendall(request_bytes("GET", "/o?tag=other"))
        assert json.loads(read_response(other)[2])["tag"] == "other"
        other.settimeout(0.3)
        with pytest.raises(socket.timeout):
            other.recv(1)
    assert _settles(served.tasks, 0) == 0
    assert _settles(_open_descriptors, descriptors) == descriptors
    assert threading.active_count() == threads
    assert _settles(lambda: sum(moved(before)), 6) == 6, \
        "each response counted once"


def test_a_full_search_queue_still_answers_429():
    s = Served(pool_settings={"thread_pool.search.size": 1,
                              "thread_pool.search.queue_size": 1})
    try:
        before = counts()
        held = [socket.create_connection(("127.0.0.1", s.port), 15)
                for _ in range(2)]
        held[0].sendall(request_bytes("POST", "/hold/_search?tag=a"))
        assert s.stub.entered.acquire(timeout=15)
        held[1].sendall(request_bytes("POST", "/hold/_search?tag=b"))
        pool = s.pool.executor("search")
        assert _settles(lambda: pool.stats()["queue"], 1) == 1
        with socket.create_connection(("127.0.0.1", s.port), 15) as third:
            third.sendall(request_bytes("POST", "/hold/_search?tag=c"))
            _raw, status, body = read_response(third)
            assert status == 429
            err = json.loads(body)
            assert err["status"] == 429
            assert err["error"]["type"] == "es_rejected_execution_exception"
            assert moved(before, (0, 1)) == (0, 1), \
                "no worker took it: the loop"
            # the refused connection lives on, for a route with room
            third.sendall(request_bytes("GET", "/free?tag=d"))
            assert json.loads(read_response(third)[2])["tag"] == "d"
        s.stub.release.set()
        for sock, tag in zip(held, "ab"):
            with sock:
                assert json.loads(read_response(sock)[2])["tag"] == tag
        assert moved(before, (3, 1)) == (3, 1)
    finally:
        s.stop()


def test_many_connections_at_once_each_get_their_own_answers(served):
    """More clients than cores under a short switch interval: every
    answer carries its own request's tag and body (bytes misdirected or
    out of order would not), and the two counters add up."""
    clients, each = 24, 40
    before = counts()
    wrong = []

    def client(c):
        try:
            with socket.create_connection(("127.0.0.1", served.port),
                                          30) as s:
                for i in range(each):
                    tag = f"{c}.{i}"
                    s.sendall(request_bytes(
                        "POST", f"/k/_search?tag={tag}", tag.encode() * 20))
                    got = json.loads(read_response(s)[2])
                    if got != {"path": "/k/_search", "tag": tag,
                               "echo": tag * 20} or s in _AHEAD:
                        wrong.append((tag, got, _AHEAD.get(s)))
        except Exception as e:          # a thread's failure is the test's
            wrong.append((c, repr(e)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert wrong == []
    assert _settles(lambda: sum(moved(before)), clients * each) \
        == clients * each
    by_worker, by_loop = moved(before)
    assert by_worker >= 0.99 * clients * each
