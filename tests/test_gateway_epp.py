"""Gateway EPP over the real ext-proc gRPC protocol: a raw grpc client
drives /envoy.service.ext_proc.v3.ExternalProcessor/Process and asserts
the x-gateway-destination-endpoint header mutation, prefix affinity, and
file-watched endpoint state (reference:
src/gateway_inference_extension/prefix_aware_picker.go:52-130)."""

import json
import os
import sys

import pytest

pytest.importorskip("grpc", reason="grpcio not installed")

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "deploy", "gateway"))


@pytest.fixture(autouse=True)
def _native(native_build):
    """Every test here needs native/build (conftest.py builds it): the
    picker library behind the Python EPP, and the C++ binaries."""


@pytest.fixture()
def epp():
    import grpc

    from epp_server import SERVICE, EndpointState, build_server, ensure_pb2

    pb2 = ensure_pb2()
    state = EndpointState(["10.0.0.4:8000", "10.0.0.5:8000"])
    server, port, picker = build_server(0, state, "prefix")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    stub = channel.stream_stream(
        f"/{SERVICE}/Process",
        request_serializer=pb2.ProcessingRequest.SerializeToString,
        response_deserializer=pb2.ProcessingResponse.FromString,
    )
    yield pb2, stub, state, picker
    channel.close()
    server.stop(0)


def _openai_exchange(pb2, stub, body: dict):
    """Headers + body, as Envoy streams them; returns the two responses."""
    def requests():
        h = pb2.ProcessingRequest()
        h.request_headers.headers.headers.add(
            key=":path", raw_value=b"/v1/chat/completions")
        h.request_headers.end_of_stream = False
        yield h
        b = pb2.ProcessingRequest()
        b.request_body.body = json.dumps(body).encode()
        b.request_body.end_of_stream = True
        yield b

    return list(stub(requests()))


def _dest(resp) -> str:
    common = resp.request_body.response
    for opt in common.header_mutation.set_headers:
        if opt.header.key == "x-gateway-destination-endpoint":
            return opt.header.raw_value.decode()
    return ""


def test_epp_picks_endpoint_via_header_mutation(epp):
    pb2, stub, _, picker = epp
    body = {"model": "m", "messages": [
        {"role": "user", "content": "hello there, gateway"}]}
    responses = _openai_exchange(pb2, stub, body)
    assert len(responses) == 2
    # Headers phase: plain CONTINUE, no mutation yet.
    assert responses[0].WhichOneof("response") == "request_headers"
    # Body phase: destination header set to a pool endpoint.
    dest = _dest(responses[1])
    assert dest in ("10.0.0.4:8000", "10.0.0.5:8000")
    assert picker.picks_total == 1


def test_epp_prefix_affinity(epp):
    pb2, stub, _, _ = epp
    shared = "sys: you are a helpful assistant. " * 8
    first = _dest(_openai_exchange(pb2, stub, {
        "model": "m", "messages": [
            {"role": "user", "content": shared + "question one"}]})[1])
    assert first
    # Same long prefix -> same endpoint (trie insert-after-pick).
    for q in ("question two", "question three"):
        dest = _dest(_openai_exchange(pb2, stub, {
            "model": "m", "messages": [
                {"role": "user", "content": shared + q}]})[1])
        assert dest == first


def test_epp_completion_prompt_and_file_watch(tmp_path):
    import time

    import grpc

    from epp_server import SERVICE, EndpointState, build_server, ensure_pb2

    pb2 = ensure_pb2()
    eps = tmp_path / "endpoints"
    eps.write_text("10.1.1.1:8000\n")
    state = EndpointState([], watch_file=str(eps), interval=0.1)
    server, port, _ = build_server(0, state, "roundrobin")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    stub = channel.stream_stream(
        f"/{SERVICE}/Process",
        request_serializer=pb2.ProcessingRequest.SerializeToString,
        response_deserializer=pb2.ProcessingResponse.FromString,
    )
    try:
        deadline = time.time() + 5
        dest = ""
        while time.time() < deadline and not dest:
            dest = _dest(_openai_exchange(pb2, stub, {
                "model": "m", "prompt": "complete me"})[1])
            time.sleep(0.1)
        assert dest == "10.1.1.1:8000"
        # ConfigMap update -> endpoint set follows without restart.
        eps.write_text("10.2.2.2:8000\n")
        deadline = time.time() + 5
        while time.time() < deadline:
            dest = _dest(_openai_exchange(pb2, stub, {
                "model": "m", "prompt": "complete me"})[1])
            if dest == "10.2.2.2:8000":
                break
            time.sleep(0.1)
        assert dest == "10.2.2.2:8000"
    finally:
        channel.close()
        server.stop(0)


def test_epp_excludes_heartbeat_expired_endpoints(epp):
    """The EPP consumes the router's lease health view: an endpoint
    whose KV heartbeat lease expired is excluded from every pick until
    the view clears it (next-generation re-register). Router urls
    (http://ip:port/) normalize to the EPP's bare ip:port form."""
    pb2, stub, state, _ = epp
    state.set_excluded(["http://10.0.0.4:8000/"])
    assert state.excluded() == {"10.0.0.4:8000"}
    assert state.endpoints() == ["10.0.0.5:8000"]
    for i in range(4):
        dest = _dest(_openai_exchange(pb2, stub, {
            "model": "m", "messages": [
                {"role": "user", "content": f"distinct pick {i}"}]})[1])
        assert dest == "10.0.0.5:8000"
    # Lease cleared: the replica is pickable again.
    state.set_excluded([])
    assert "10.0.0.4:8000" in state.endpoints()


def test_epp_health_poll_tracks_router_expired_urls():
    """EndpointState's router poll (--router-url) follows GET
    /kv/instances: expired_urls leave the pick set, and rejoin when the
    router stops reporting them."""
    import http.server
    import threading
    import time

    from epp_server import EndpointState

    payload = {"expired_urls": ["http://10.0.0.5:8000"]}

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        state = EndpointState(
            ["10.0.0.4:8000", "10.0.0.5:8000"],
            router_url=f"http://127.0.0.1:{srv.server_port}",
            health_interval=0.05)
        deadline = time.time() + 5
        while (time.time() < deadline
               and "10.0.0.5:8000" in state.endpoints()):
            time.sleep(0.02)
        assert state.endpoints() == ["10.0.0.4:8000"]
        payload["expired_urls"] = []
        deadline = time.time() + 5
        while (time.time() < deadline
               and "10.0.0.5:8000" not in state.endpoints()):
            time.sleep(0.02)
        assert state.endpoints() == ["10.0.0.4:8000", "10.0.0.5:8000"]
    finally:
        srv.shutdown()


def _raw_exchange(pb2, stub, raw: bytes):
    """Headers + a raw (possibly hostile) body through the ext-proc
    stream; returns both responses."""
    def requests():
        h = pb2.ProcessingRequest()
        h.request_headers.headers.headers.add(
            key=":path", raw_value=b"/v1/chat/completions")
        h.request_headers.end_of_stream = False
        yield h
        b = pb2.ProcessingRequest()
        b.request_body.body = raw
        b.request_body.end_of_stream = True
        yield b

    return list(stub(requests()))


def test_epp_malformed_body_clean_reject(epp):
    """Truncated and garbage request bodies must never crash the EPP:
    every exchange completes both phases cleanly (no stream error), and
    a well-formed request afterwards still gets a real pick. First leg
    of the malformed-input suite (ISSUE 6 satellite)."""
    pb2, stub, _, _ = epp

    def raw_exchange(raw: bytes):
        return _raw_exchange(pb2, stub, raw)

    hostile = (
        b"",                                      # empty body
        b"\x80\xff\x00 not even utf-8 \xfe",      # undecodable bytes
        b'{"model": "m", "messages": [{"role"',   # truncated JSON
        b"5",                                     # JSON, not an object
        b'"just a string"',
        b'{"messages": "not-a-list"}',
        b'{"messages": [42, null, {"role": "user", "content": null}]}',
        b'{"prompt": {"nested": "object"}}',
        b"[" * 2000 + b"]" * 2000,                # nesting bomb
    )
    for raw in hostile:
        responses = raw_exchange(raw)
        assert len(responses) == 2, raw[:40]
        # The body phase still answers CONTINUE (pick or no pick).
        assert responses[1].WhichOneof("response") == "request_body"

    # The server survived all of it and still picks normally.
    good = _openai_exchange(pb2, stub, {
        "model": "m", "messages": [
            {"role": "user", "content": "still serving?"}]})
    assert _dest(good[1]) in ("10.0.0.4:8000", "10.0.0.5:8000")


# Table-driven replay of the shared fuzz corpus (native/epp/corpus/json)
# over the PYTHON EPP path: the same hostile bodies the native fuzz
# harness throws at the C++ server (minimized crashers + structural edge
# cases) must also leave the Python data plane standing. One test per
# corpus file so a regression names the exact input.

_CORPUS_JSON_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "epp", "corpus", "json")
_CORPUS_JSON = (sorted(os.listdir(_CORPUS_JSON_DIR))
                if os.path.isdir(_CORPUS_JSON_DIR) else [])


@pytest.mark.parametrize("name", _CORPUS_JSON)
def test_epp_fuzz_corpus_replay_python(epp, name):
    pb2, stub, _, _ = epp
    with open(os.path.join(_CORPUS_JSON_DIR, name), "rb") as f:
        raw = f.read()
    responses = _raw_exchange(pb2, stub, raw)
    # Both phases answer (no stream error, no crash, no hang) ...
    assert len(responses) == 2, name
    assert responses[1].WhichOneof("response") == "request_body"
    # ... and the server still serves a well-formed request after.
    good = _openai_exchange(pb2, stub, {
        "model": "m", "messages": [
            {"role": "user", "content": f"after {name}"}]})
    assert _dest(good[1]) in ("10.0.0.4:8000", "10.0.0.5:8000")


# ---- round 5: the NATIVE EPP data plane (tpu-stack-epp) ----------------
# Same protocol assertions as above, but against the C++ server with its
# own HTTP/2 stack — driven here by the real grpcio client (dynamic-table
# + Huffman HPACK on the wire), which is the interop proof.

_EPP_BIN = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "build", "tpu-stack-epp")


@pytest.fixture()
def native_epp():
    import socket
    import subprocess
    import time

    import grpc

    from epp_server import SERVICE, ensure_pb2

    if not os.path.exists(_EPP_BIN):
        pytest.skip("tpu-stack-epp not built")
    pb2 = ensure_pb2()
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = subprocess.Popen(
        [_EPP_BIN, "--port", str(port),
         "--endpoints", "10.0.0.4:8000,10.0.0.5:8000"],
        stderr=subprocess.PIPE)
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            probe = socket.create_connection(("127.0.0.1", port), 0.2)
            probe.close()
            break
        except OSError:
            time.sleep(0.05)
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    stub = channel.stream_stream(
        f"/{SERVICE}/Process",
        request_serializer=pb2.ProcessingRequest.SerializeToString,
        response_deserializer=pb2.ProcessingResponse.FromString,
    )
    yield pb2, stub
    channel.close()
    proc.terminate()
    proc.wait(timeout=10)


def test_native_epp_grpcio_interop(native_epp):
    pb2, stub = native_epp
    responses = _openai_exchange(pb2, stub, {
        "model": "m", "messages": [
            {"role": "user", "content": "hello native gateway"}]})
    assert len(responses) == 2
    assert responses[0].WhichOneof("response") == "request_headers"
    dest = _dest(responses[1])
    assert dest in ("10.0.0.4:8000", "10.0.0.5:8000")


def test_native_epp_prefix_affinity_and_chat_template_parity(native_epp):
    """Stickiness through the C++ JSON/chat-template path, and the
    rendered prompt must hash identically to the Python tier: a pick on
    the SAME messages from the Python renderer must land on the same
    endpoint (trie chains agree by construction)."""
    pb2, stub = native_epp
    shared = "sys instructions pad the shared prefix. " * 8
    msgs = [{"role": "system", "content": shared},
            {"role": "user", "content": "question one"}]
    first = _dest(_openai_exchange(pb2, stub, {
        "model": "m", "messages": msgs})[1])
    assert first
    for q in ("question two", "question three"):
        dest = _dest(_openai_exchange(pb2, stub, {
            "model": "m", "messages": [
                {"role": "system", "content": shared},
                {"role": "user", "content": q}]})[1])
        assert dest == first


def test_native_epp_completions_prompt(native_epp):
    pb2, stub = native_epp
    dest = _dest(_openai_exchange(pb2, stub, {
        "model": "m", "prompt": "complete me " * 20})[1])
    assert dest in ("10.0.0.4:8000", "10.0.0.5:8000")


def _h2_frame(ftype, flags, stream, payload=b""):
    n = len(payload)
    return (bytes([(n >> 16) & 0xff, (n >> 8) & 0xff, n & 0xff,
                   ftype, flags,
                   (stream >> 24) & 0x7f, (stream >> 16) & 0xff,
                   (stream >> 8) & 0xff, stream & 0xff]) + payload)


def _hpack_lit(name, value):
    out = b"\x00"
    out += bytes([len(name)]) + name
    out += bytes([len(value)]) + value
    return out


def _native_epp_proc(port):
    import subprocess

    return subprocess.Popen(
        [_EPP_BIN, "--port", str(port),
         "--endpoints", "10.0.0.4:8000"],
        stderr=subprocess.PIPE)


def _wait_port(port, timeout=10):
    import socket as _socket
    import time as _time

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        try:
            _socket.create_connection(("127.0.0.1", port), 0.2).close()
            return
        except OSError:
            _time.sleep(0.05)
    raise TimeoutError


def test_native_epp_hardening_edges():
    """Raw-socket pins for the review-driven hardening: a client that
    opens with SETTINGS INITIAL_WINDOW_SIZE=0 and raises it later still
    gets its response (flush on SETTINGS); a deeply nested JSON body and
    an absurd gRPC length are rejected without killing the server."""
    import socket as _socket
    import struct
    import time as _time

    if not os.path.exists(_EPP_BIN):
        pytest.skip("tpu-stack-epp not built")
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = _native_epp_proc(port)
    try:
        _wait_port(port)

        def connect(settings_payload=b""):
            c = _socket.create_connection(("127.0.0.1", port), 5)
            c.settimeout(5)
            c.sendall(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n")
            c.sendall(_h2_frame(0x4, 0, 0, settings_payload))
            return c

        def open_stream(c, sid):
            block = (_hpack_lit(b":method", b"POST")
                     + _hpack_lit(b":path", b"/x")
                     + _hpack_lit(b"content-type", b"application/grpc"))
            c.sendall(_h2_frame(0x1, 0x4, sid, block))

        def grpc_body_msg(body: bytes) -> bytes:
            # ProcessingRequest{request_body{body, end_of_stream=true}}
            http_body = (b"\x0a" + _varint(len(body)) + body
                         + b"\x10\x01")
            msg = b"\x22" + _varint(len(http_body)) + http_body
            return b"\x00" + struct.pack(">I", len(msg)) + msg

        def _varint(v):
            out = b""
            while v >= 0x80:
                out += bytes([(v & 0x7f) | 0x80])
                v >>= 7
            return out + bytes([v])

        # 1) window-0 open, then raise: the queued response must flush.
        c = connect(settings_payload=struct.pack(">HI", 4, 0))
        open_stream(c, 1)
        c.sendall(_h2_frame(0x0, 0, 1, grpc_body_msg(b'{"prompt":"hi"}')))
        _time.sleep(0.3)
        c.sendall(_h2_frame(0x4, 0, 0, struct.pack(">HI", 4, 65535)))
        got = c.recv(65536)
        deadline = _time.time() + 5
        while b"x-gateway-destination-endpoint" not in got:
            if _time.time() > deadline:
                raise AssertionError("no response after window raise")
            got += c.recv(65536)
        c.close()

        # 2) nesting bomb: parsed safely (empty prompt -> roundrobin
        # pick), server stays alive.
        c = connect()
        open_stream(c, 1)
        bomb = b"[" * 5000 + b"]" * 5000
        c.sendall(_h2_frame(0x0, 0, 1, grpc_body_msg(bomb)))
        got = b""
        deadline = _time.time() + 5
        while b"10.0.0.4:8000" not in got:
            if _time.time() > deadline:
                raise AssertionError("no pick after nesting bomb")
            got += c.recv(65536)
        c.close()

        # 3) absurd claimed gRPC message length: connection dropped,
        # process survives.
        c = connect()
        open_stream(c, 1)
        c.sendall(_h2_frame(
            0x0, 0, 1, b"\x00" + struct.pack(">I", 1 << 30) + b"x"))
        _time.sleep(0.3)
        try:
            c.settimeout(3)
            while c.recv(65536):
                pass
        except OSError:
            pass
        c.close()
        assert proc.poll() is None, "EPP died on hostile input"

        # Server still serves a normal pick afterwards.
        c = connect()
        open_stream(c, 1)
        c.sendall(_h2_frame(0x0, 0, 1, grpc_body_msg(b'{"prompt":"ok"}')))
        got = b""
        deadline = _time.time() + 5
        while b"10.0.0.4:8000" not in got:
            if _time.time() > deadline:
                raise AssertionError("no pick after hostile clients")
            got += c.recv(65536)
        c.close()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_native_epp_endpoints_file_watch(tmp_path):
    """The native server picks up ConfigMap-style endpoint file changes
    (5 s poll), matching the Python EPP's watcher semantics."""
    import socket as _socket
    import subprocess
    import time as _time

    import grpc

    from epp_server import SERVICE, ensure_pb2

    if not os.path.exists(_EPP_BIN):
        pytest.skip("tpu-stack-epp not built")
    pb2 = ensure_pb2()
    eps = tmp_path / "endpoints"
    eps.write_text("10.0.0.9:8000\n")
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = subprocess.Popen(
        [_EPP_BIN, "--port", str(port), "--algorithm", "roundrobin",
         "--endpoints-file", str(eps)],
        stderr=subprocess.PIPE)
    try:
        deadline = _time.time() + 10
        while _time.time() < deadline:
            try:
                _socket.create_connection(("127.0.0.1", port), 0.2).close()
                break
            except OSError:
                _time.sleep(0.05)
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        stub = channel.stream_stream(
            f"/{SERVICE}/Process",
            request_serializer=pb2.ProcessingRequest.SerializeToString,
            response_deserializer=pb2.ProcessingResponse.FromString)

        deadline = _time.time() + 15
        dest = ""
        while _time.time() < deadline:
            dest = _dest(_openai_exchange(pb2, stub, {
                "model": "m", "prompt": "x"})[1])
            if dest == "10.0.0.9:8000":
                break
            _time.sleep(0.5)
        assert dest == "10.0.0.9:8000", dest

        eps.write_text("10.0.0.10:8000\n")
        deadline = _time.time() + 15
        while _time.time() < deadline:
            dest = _dest(_openai_exchange(pb2, stub, {
                "model": "m", "prompt": "x"})[1])
            if dest == "10.0.0.10:8000":
                break
            _time.sleep(0.5)
        assert dest == "10.0.0.10:8000", dest
        channel.close()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# ---- native fuzz harness smoke run -------------------------------------
# The full 10k-iteration adversarial run (ASan/UBSan) lives in the CI
# native-hardening job; this is a bounded deterministic smoke so local
# runs with a built native/ tree catch protocol-error regressions too.

_FUZZ_BIN = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "build", "tpu-stack-h2fuzz")
_CORPUS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "epp", "corpus")


def test_native_h2fuzz_smoke():
    import subprocess

    proc = subprocess.run(
        [_FUZZ_BIN, "--iterations", "250", "--seed", "7",
         "--timeout-ms", "3000", "--corpus", _CORPUS_DIR],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, (
        f"fuzz smoke failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}")
    assert "PASS" in proc.stdout + proc.stderr


def test_epp_set_excluded_rejects_malformed_input():
    """set_excluded is the single write path for the router health view:
    anything but a sane list of strings returns False and leaves the
    last-good exclusion set untouched."""
    from epp_server import EndpointState

    state = EndpointState(["10.0.0.4:8000", "10.0.0.5:8000"])
    assert state.set_excluded(["http://10.0.0.5:8000/"])
    assert state.excluded() == {"10.0.0.5:8000"}
    for garbage in [
        None,
        "http://10.0.0.4:8000",            # string, not list
        {"urls": []},                       # dict
        ["http://10.0.0.4:8000", 7],        # non-string entry
        ["u"] * (EndpointState.MAX_EXCLUDED_URLS + 1),  # absurd length
    ]:
        assert not state.set_excluded(garbage), garbage
        assert state.excluded() == {"10.0.0.5:8000"}, garbage
    assert state.set_excluded([])
    assert state.excluded() == set()


def test_epp_health_poll_survives_garbage_responses():
    """A router bug (or an interposed proxy) feeding the health poll
    garbage must not crash the poller NOR clear the exclusion view:
    every malformed payload keeps the LAST-GOOD excluded set, and a
    later well-formed response resumes tracking."""
    import http.server
    import threading
    import time

    from epp_server import EndpointState

    reply = {"raw": json.dumps(
        {"expired_urls": ["http://10.0.0.5:8000"]}).encode()}

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = reply["raw"]
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        state = EndpointState(
            ["10.0.0.4:8000", "10.0.0.5:8000"],
            router_url=f"http://127.0.0.1:{srv.server_port}",
            health_interval=0.05)
        deadline = time.time() + 5
        while (time.time() < deadline
               and "10.0.0.5:8000" in state.endpoints()):
            time.sleep(0.02)
        assert state.endpoints() == ["10.0.0.4:8000"]

        for garbage in [
            b"not json at all {",
            b"[1, 2, 3]",                       # JSON, but not an object
            json.dumps({}).encode(),             # missing expired_urls
            json.dumps({"expired_urls": "oops"}).encode(),
            json.dumps({"expired_urls": [1, None]}).encode(),
            json.dumps({"expired_urls": ["u"] * 5000}).encode(),
        ]:
            reply["raw"] = garbage
            time.sleep(0.2)  # several poll rounds of garbage
            assert state.endpoints() == ["10.0.0.4:8000"], garbage
            assert state.excluded() == {"10.0.0.5:8000"}, garbage

        # Router heals: a well-formed empty view re-admits the replica.
        reply["raw"] = json.dumps({"expired_urls": []}).encode()
        deadline = time.time() + 5
        while (time.time() < deadline
               and "10.0.0.5:8000" not in state.endpoints()):
            time.sleep(0.02)
        assert state.endpoints() == ["10.0.0.4:8000", "10.0.0.5:8000"]
    finally:
        srv.shutdown()
