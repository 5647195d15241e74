"""A response leaves in ONE write (serving/http.py ``_send``).

Headers and body used to be two ``sendall`` through the unbuffered
``wfile``: one more system call a request, one more release of the
interpreter that a handler gets back behind every other handler, and a
client that reads twice (PERF.md §6, PR 38). The bytes on the wire are
pinned here as golden strings (but for ``Date``): they are what the two
writes produced.
"""

import http.client
import json
import re
import socket
import sys

import pytest

from predictionio_tpu.serving.http import HTTPServerBase, JSONRequestHandler

TRACE = "0123456789abcdef0123456789abcdef"
SERVER = f"PIOSendTest/0.1 Python/{sys.version.split()[0]}".encode()
JSON_TYPE = b"application/json; charset=UTF-8"


class CountingSocket(socket.socket):
    """The handler's side of the connection; keeps each buffer handed to
    ``sendall`` (``wfile`` is unbuffered: a write IS a ``sendall``)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.sent = []

    def sendall(self, data, *flags):
        self.sent.append(bytes(data))
        return super().sendall(data, *flags)


class Handler(JSONRequestHandler):
    server_version = "PIOSendTest/0.1"

    def do_GET(self):
        if self.path == "/dict":
            self._send(200, {"itemScores": [{"item": "i1", "score": 0.5}]})
        elif self.path == "/str":
            self._send(200, "plain text\n", content_type="text/plain")
        elif self.path == "/bytes":
            self._send(200, b"\x00\x01raw",
                       content_type="application/octet-stream")
        elif self.path == "/extra":
            self._send(401, {"message": "no"}, extra_headers={
                "WWW-Authenticate": "Bearer", "X-PIO-Replica": "r2"})
        else:
            self._send(404, {"message": "Not Found"})

    def do_POST(self):
        if self.path == "/echo":
            self._send(200, self._read_json())
        else:  # a short-circuit answer: the body is still unread
            self._send(403, {"message": "denied"})


def tcp_pair():
    with socket.create_server(("127.0.0.1", 0)) as listener:
        ours = socket.create_connection(listener.getsockname())
        theirs, _ = listener.accept()
    return ours, theirs


def request(method, path, body=b"", headers=()):
    lines = [f"{method} {path} HTTP/1.1", "Host: test",
             f"X-PIO-Trace-Id: {TRACE}", *headers]
    if body:
        lines.append(f"Content-Length: {len(body)}")
    return "\r\n".join(lines).encode() + b"\r\n\r\n" + body


def serve(*requests):
    """Play ``requests`` down one connection; the handler's writes and
    everything the client read."""
    ours, theirs = tcp_pair()
    counted = CountingSocket(fileno=theirs.detach())
    with ours, counted:
        ours.sendall(b"".join(requests))
        ours.shutdown(socket.SHUT_WR)
        Handler(counted, ours.getsockname(), None)  # serves until EOF
        counted.shutdown(socket.SHUT_WR)
        wire = b"".join(iter(lambda: ours.recv(65536), b""))
    return counted.sent, wire


def undated(buffer):
    return re.sub(rb"\r\nDate: [^\r]+\r\n", b"\r\nDate: <date>\r\n", buffer)


def golden(status, content_type, body, *more, trace=True):
    lines = [b"HTTP/1.1 " + status, b"Server: " + SERVER, b"Date: <date>",
             b"Content-Type: " + content_type,
             b"Content-Length: %d" % len(body)]
    if trace:
        lines.append(b"X-PIO-Trace-Id: " + TRACE.encode())
    return b"\r\n".join([*lines, *more]) + b"\r\n\r\n" + body


SCORES = b'{"itemScores": [{"item": "i1", "score": 0.5}]}'

CASES = {
    "dict": (request("GET", "/dict"),
             golden(b"200 OK", JSON_TYPE, SCORES)),
    "str": (request("GET", "/str"),
            golden(b"200 OK", b"text/plain", b"plain text\n")),
    "bytes": (request("GET", "/bytes"),
              golden(b"200 OK", b"application/octet-stream", b"\x00\x01raw")),
    "extra_headers": (
        request("GET", "/extra"),
        golden(b"401 Unauthorized", JSON_TYPE, b'{"message": "no"}',
               b"WWW-Authenticate: Bearer", b"X-PIO-Replica: r2")),
    "connection_close": (
        request("GET", "/dict", headers=("Connection: close",)),
        golden(b"200 OK", JSON_TYPE, SCORES, b"Connection: close")),
    "close_before_extra_headers": (
        request("GET", "/extra", headers=("Connection: close",)),
        golden(b"401 Unauthorized", JSON_TYPE, b'{"message": "no"}',
               b"Connection: close", b"WWW-Authenticate: Bearer",
               b"X-PIO-Replica: r2")),
    "chunked_body_closes": (
        request("POST", "/denied", headers=("Transfer-Encoding: chunked",)),
        golden(b"403 Forbidden", JSON_TYPE, b'{"message": "denied"}',
               b"Connection: close")),
    "no_trace_on_a_shared_route": (
        b"GET /healthz HTTP/1.1\r\nHost: test\r\n\r\n",
        golden(b"200 OK", JSON_TYPE, b'{"status": "alive"}', trace=False)),
    "echo": (request("POST", "/echo", b'{"user": "u1", "num": 10}'),
             golden(b"200 OK", JSON_TYPE, b'{"user": "u1", "num": 10}')),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_response_is_one_write_of_the_same_bytes(case):
    asked, expected = CASES[case]
    sent, wire = serve(asked)
    assert len(sent) == 1, [len(b) for b in sent]
    assert undated(sent[0]) == expected
    assert wire == sent[0]


def test_a_short_circuit_answer_drains_the_unread_body_and_is_one_write():
    """An unread body would be parsed as the connection's next request."""
    sent, wire = serve(request("POST", "/denied", b"x" * 3000),
                       request("GET", "/dict"))
    assert [undated(b) for b in sent] == [
        golden(b"403 Forbidden", JSON_TYPE, b'{"message": "denied"}'),
        golden(b"200 OK", JSON_TYPE, SCORES)]
    assert wire == b"".join(sent)


def test_an_http_0_9_request_line_gets_the_body_alone():
    sent, wire = serve(b"GET /dict\r\n")
    assert sent == [SCORES] and wire == SCORES


def test_a_keep_alive_connection_serves_100_requests_in_order():
    server = HTTPServerBase("127.0.0.1", 0, Handler).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        local = None
        for i in range(100):
            conn.request("POST", "/echo", json.dumps({"i": i}),
                         {"Content-Type": "application/json",
                          "X-PIO-Trace-Id": f"{i:032x}"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("X-PIO-Trace-Id") == f"{i:032x}"
            assert json.loads(resp.read()) == {"i": i}
            # one connection throughout: the client never reconnected
            local = local or conn.sock.getsockname()
            assert conn.sock.getsockname() == local
        conn.close()
    finally:
        server.stop()
