"""The stream writer: one thread an app writes every streamed
/generate response. A fake decoder hands the test each request's emit
sink and future, so the test plays the decode loop; clients are raw
loopback sockets, so the bytes on the wire are compared whole. What is
held: the frames are byte for byte what the per-handler writer sent
(kept below as the reference), a burst of emits goes out in fewer passes
than streams, a client that reads nothing delays no other, a disconnect
cancels once, a future that ends without a done frame and the server's
stream timeout end with their error frames, ``close`` stops the thread,
and the handler's spans end on the handler's thread."""

import json
import socket
import sys
import threading
import time

import pytest

from bigdl_tpu import obs
from bigdl_tpu.serving import MetricsRegistry, ServingApp, make_server
from bigdl_tpu.serving.batcher import DeadlineExceeded, _Future

WAIT_S = 10.0  # every wait of a test is bounded by this


def old_frame(obj) -> bytes:
    """The per-handler writer's encoding (``_sse`` + ``_write_chunk``
    before the stream writer): the reference the wire is held to."""
    data = b"data: " + json.dumps(obj).encode() + b"\n\n"
    return b"%x\r\n" % len(data) + data + b"\r\n"


TERMINATOR = b"0\r\n\r\n"


class FakeDecoder:
    """What the app asks of a decoder, and no model: ``submit`` keeps
    each request's emit sink and future for the test to drive."""

    max_waiting = 64

    def __init__(self):
        self._waiting = []
        self.reqs = {}
        self.cancels = []

    def submit(self, tokens, max_new, temperature, stop, deadline=None,
               top_k=0, top_p=1.0, seed=0, rid=None, emit=None):
        fut = _Future()
        self.reqs[rid] = (emit, fut)
        return fut

    def cancel(self, rid, reason="client disconnected"):
        self.cancels.append((rid, reason))
        fut = self.reqs[rid][1]
        if fut.done():
            return False
        fut.set_exception(RuntimeError(f"request {rid} cancelled: {reason}"))
        return True

    def alive(self):
        return True

    def close(self):
        for _, fut in self.reqs.values():
            if not fut.done():
                fut.set_exception(
                    RuntimeError("decode engine closed mid-request"))

    def emit(self, rid, toks, done=False):
        self.reqs[rid][0](list(toks), done)

    def finish(self, rid, toks):
        """The last round of a request, as the engine ends it: the done
        emit, then the future."""
        self.emit(rid, toks, True)
        self.reqs[rid][1].set_result(list(toks))


class Front:
    def __init__(self, **kw):
        self.reg = MetricsRegistry()
        self.dec = FakeDecoder()
        self.app = ServingApp(name="fake", metrics=self.reg,
                              decoder=self.dec, **kw)
        self.srv = make_server(self.app, "127.0.0.1", 0)
        self.port = self.srv.server_address[1]
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    def open(self, rid, prompt=(3, 1, 4), rcvbuf=None):
        """A client socket whose request has been submitted and whose
        response headers have arrived."""
        s = socket.socket()
        if rcvbuf is not None:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        s.settimeout(WAIT_S)
        s.connect(("127.0.0.1", self.port))
        body = json.dumps({"tokens": list(prompt), "max_new_tokens": 8,
                           "stream": True}).encode()
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                  b"x-request-id: " + rid.encode() + b"\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        head = b""
        while b"\r\n\r\n" not in head:
            got = s.recv(1)
            assert got, "connection closed before the headers"
            head += got
        assert head.startswith(b"HTTP/1.1 200")
        assert b"transfer-encoding: chunked" in head.lower()
        assert rid in self.dec.reqs
        return s

    def counts(self):
        return tuple(self.reg.counter(n).value for n in (
            "stream_frames_total", "stream_writer_passes_total",
            "stream_writer_cpu_seconds_total"))

    def close(self):
        self.app.close()
        self.srv.shutdown()
        self.srv.server_close()


@pytest.fixture
def front():
    f = Front()
    yield f
    f.close()


def read_body(s, n=None) -> bytes:
    """Everything up to and including the terminating chunk, or the
    first ``n`` bytes."""
    raw = b""
    while (len(raw) < n) if n is not None else not raw.endswith(TERMINATOR):
        got = s.recv(65536 if n is None else n - len(raw))
        if not got:
            break
        raw += got
    return raw


def frames_of(raw: bytes):
    """Undo the chunked framing; the SSE frames' objects."""
    out, rest = [], raw
    while rest:
        size_s, _, rest = rest.partition(b"\r\n")
        size = int(size_s, 16)
        if size == 0:
            break
        data, rest = rest[:size], rest[size + 2:]
        assert data.startswith(b"data: ") and data.endswith(b"\n\n")
        out.append(json.loads(data[6:-2]))
    return out


def wait_for(cond, what):
    end = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.002)


# ------------------------------------------------------------ the wire
def _tokens_then_done(dec, rid):
    dec.emit(rid, [5, 6])
    dec.emit(rid, [7, 8, 9])
    dec.finish(rid, [10])
    return [{"tokens": [5, 6]}, {"tokens": [7, 8, 9]}, {"tokens": [10]},
            {"done": True, "prompt_len": 3, "tokens_out": 6}]


def _expired_mid_stream(dec, rid):
    dec.emit(rid, [42])
    dec.reqs[rid][1].set_exception(
        DeadlineExceeded("deadline expired after 1 of 8 tokens"))
    return [{"tokens": [42]},
            {"error": "deadline expired after 1 of 8 tokens"}]


def _expired_waiting(dec, rid):
    dec.reqs[rid][1].set_exception(DeadlineExceeded(
        "deadline expired while waiting for a decode slot"))
    return [{"error": "deadline expired while waiting for a decode slot"}]


def _resolved_without_done(dec, rid):
    dec.reqs[rid][1].set_result([])
    return [{"error": "stream ended without tokens"}]


@pytest.mark.parametrize("play", [_tokens_then_done, _expired_mid_stream,
                                  _expired_waiting, _resolved_without_done],
                         ids=["done", "error_after_tokens", "error_only",
                              "no_tokens"])
def test_frames_are_byte_identical_to_the_per_handler_writer(front, play):
    s = front.open("r1")
    want = play(front.dec, "r1")
    raw = read_body(s)
    s.close()
    assert raw == b"".join(old_frame(f) for f in want) + TERMINATOR
    assert frames_of(raw) == want


def test_frames_emitted_before_the_headers_are_held_then_sent(front):
    """The decode loop may emit before the handler has sent its headers
    (a prefill's first token): the writer holds the bytes until the
    handler attaches the socket, then sends them in order."""
    gate = threading.Event()
    real = front.app.start_generate_stream

    def start_then_emit(payload, rid=None):
        status, stream = real(payload, rid=rid)
        front.dec.emit(rid, [1])
        front.dec.emit(rid, [2])
        gate.set()
        return status, stream

    front.app.start_generate_stream = start_then_emit
    s = front.open("early")
    assert gate.is_set()
    front.dec.finish("early", [3])
    raw = read_body(s)
    s.close()
    assert frames_of(raw) == [{"tokens": [1]}, {"tokens": [2]},
                              {"tokens": [3]},
                              {"done": True, "prompt_len": 3,
                               "tokens_out": 3}]


# ------------------------------------------------------------ the passes
def test_a_burst_of_emits_is_written_in_fewer_passes_than_streams(front):
    n = 16
    socks = {f"b{i}": front.open(f"b{i}") for i in range(n)}
    for rid, s in socks.items():  # every stream attached and flowing
        front.dec.emit(rid, [1])
        assert frames_of(read_body(s, len(old_frame({"tokens": [1]})))) \
            == [{"tokens": [1]}]
    # a pass counts once its sends are done, after the client may read
    wait_for(lambda: front.counts()[0] == n, "the first frames' count")
    frames0, passes0, cpu0 = front.counts()
    for rid in socks:  # one decode round: every slot's last token
        front.dec.finish(rid, [2])
    for rid, s in socks.items():
        raw = read_body(s)
        s.close()
        assert frames_of(raw) == [{"tokens": [2]},
                                  {"done": True, "prompt_len": 3,
                                   "tokens_out": 2}]
    wait_for(lambda: front.counts()[0] == frames0 + 2 * n,
             "a tokens and a done frame each")
    frames, passes, cpu = front.counts()
    assert 1 <= passes - passes0 < n
    assert cpu > cpu0


def test_concurrent_rounds_keep_every_streams_frames_in_order(front):
    """Stress: four emitting threads, 32 streams, a switch interval of
    10 us: every stream's frames arrive whole and in order, and the
    frame counter loses no update."""
    streams = {f"s{i}": front.open(f"s{i}") for i in range(32)}
    rids = sorted(streams)
    frames0 = front.counts()[0]

    def play(mine):
        for k in range(20):
            for rid in mine:
                front.dec.emit(rid, [k, k + 1])
        for rid in mine:
            front.dec.finish(rid, [99])

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=play, args=(rids[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        bodies = {rid: read_body(s) for rid, s in streams.items()}
        for t in threads:
            t.join(WAIT_S)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(before)
    for rid, raw in bodies.items():
        streams[rid].close()
        want = [{"tokens": [k, k + 1]} for k in range(20)]
        want += [{"tokens": [99]},
                 {"done": True, "prompt_len": 3, "tokens_out": 41}]
        assert frames_of(raw) == want, rid
    wait_for(lambda: front.counts()[0] - frames0 == 32 * 22,
             "the frame counter")


# -------------------------------------------------------- slow clients
def test_a_client_that_reads_nothing_delays_no_other(front):
    slow = front.open("slow", rcvbuf=4096)
    fast = front.open("fast")
    writer = front.app._writer
    big = list(range(10000))  # ~60 KB a frame
    sent = 0
    while not writer._blocked:  # fill the slow client's socket
        assert sent < 400, "the slow client's socket never filled"
        front.dec.emit("slow", big)
        sent += 1
        time.sleep(0.001)
    t = time.monotonic()
    front.dec.finish("fast", [7])
    raw = read_body(fast)
    fast.close()
    assert time.monotonic() - t < 1.0
    assert frames_of(raw)[0] == {"tokens": [7]}
    front.dec.finish("slow", [1])
    raw = read_body(slow)  # reading again: every frame, in order
    slow.close()
    got = frames_of(raw)
    assert got[:sent] == [{"tokens": big}] * sent
    assert got[sent:] == [{"tokens": [1]},
                          {"done": True, "prompt_len": 3,
                           "tokens_out": sent * len(big) + 1}]


def test_a_client_that_disconnects_is_cancelled_once(front):
    s = front.open("gone")
    front.dec.emit("gone", [1])
    read_body(s, len(old_frame({"tokens": [1]})))
    s.close()
    end = time.monotonic() + WAIT_S
    while not front.dec.cancels:  # the first send after the close may
        assert time.monotonic() < end  # still succeed
        front.dec.emit("gone", [2])
        time.sleep(0.01)
    front.dec.emit("gone", [3])  # a round already under way
    time.sleep(0.05)
    assert front.dec.cancels == [("gone", "client disconnected")]
    wait_for(lambda: not front.app._writer._open, "the stream to end")


def test_the_stream_timeout_cancels_and_writes_its_frame():
    f = Front(request_timeout_s=0.3)
    try:
        s = f.open("slowpoke")
        f.dec.emit("slowpoke", [4])
        raw = read_body(s)
        s.close()
        assert raw == (old_frame({"tokens": [4]})
                       + old_frame({"error": "stream timeout"})
                       + TERMINATOR)
        assert f.dec.cancels == [("slowpoke", "server stream timeout")]
    finally:
        f.close()


# ------------------------------------------------------------ lifetime
def test_close_ends_the_open_streams_and_stops_the_writer():
    f = Front()
    s = f.open("open")
    f.dec.emit("open", [1])
    read_body(s, len(old_frame({"tokens": [1]})))
    thread = f.app._writer._thread
    assert thread is not None and thread.is_alive()
    f.close()
    assert not thread.is_alive()
    assert "stream-writer" not in [t.name for t in threading.enumerate()]
    raw = read_body(s)
    s.close()
    assert raw == (old_frame({"error": "decode engine closed mid-request"})
                   + TERMINATOR)


def test_an_app_that_streamed_nothing_starts_no_thread():
    f = Front()
    f.close()
    assert f.app._writer._thread is None


# ------------------------------------------------------------ the spans
def test_first_token_wait_ends_on_the_handler_thread_when_the_frame_is_out(
        front):
    tracer = obs.enable()
    try:
        s = front.open("spans")
        time.sleep(0.05)  # the wait for the first token
        t_emit = time.perf_counter()
        front.dec.emit("spans", [1])
        read_body(s, len(old_frame({"tokens": [1]})))
        front.dec.finish("spans", [2])
        read_body(s)
        s.close()
        wait_for(lambda: any(e["name"] == "generate_request"
                             for e in tracer.events()), "the request span")
    finally:
        obs.disable()
    ev = {e["name"]: e for e in tracer.events()}
    handler = ev["generate_request"]["tid"]
    wait, stream = ev["generate_first_token_wait"], ev["generate_stream"]
    assert wait["tid"] == stream["tid"] == handler
    assert wait["ts"] + wait["dur"] >= t_emit
    assert stream["ts"] >= wait["ts"] + wait["dur"]
    writes = [e for e in tracer.events() if e["name"] == "stream_write"]
    assert writes and all(e["tid"] != handler for e in writes)
