"""Transport tests: proto round-trip, batch semantics, listener tee,
writers, and a live in-process gRPC loopback."""

import gzip
import random
import struct
import threading
import time
import zlib

import numpy as np
import pytest
import span_scenarios

from parca_agent_tpu.agent.batch import BatchWriteClient, NoopStoreClient
from parca_agent_tpu.agent.listener import MatchingProfileListener, equals_matcher
from parca_agent_tpu.agent.profilestore import (
    RawSeries,
    decode_write_raw_request,
    encode_write_raw_request,
)
from parca_agent_tpu.agent.writer import FileProfileWriter, RemoteProfileWriter


def test_write_raw_request_roundtrip():
    series = [
        RawSeries({"__name__": "cpu", "pid": "7"}, [b"profile-a", b"profile-b"]),
        RawSeries({"node": "n1"}, [b"x"]),
    ]
    blob = encode_write_raw_request(series, normalized=True)
    out, normalized = decode_write_raw_request(blob)
    assert normalized is True
    assert [s.labels for s in out] == [s.labels for s in series]
    assert [s.samples for s in out] == [s.samples for s in series]


class RecordingStore:
    def __init__(self, fail_times=0):
        self.batches = []
        self.fail_times = fail_times

    def write_raw(self, series, normalized):
        if self.fail_times > 0:
            self.fail_times -= 1
            raise ConnectionError("boom")
        self.batches.append([RawSeries(dict(s.labels), list(s.samples))
                             for s in series])


def test_batch_merges_by_labelset():
    store = RecordingStore()
    c = BatchWriteClient(store)
    c.write_raw({"pid": "1"}, b"a")
    c.write_raw({"pid": "1"}, b"b")
    c.write_raw({"pid": "2"}, b"c")
    assert c.flush()
    (batch,) = store.batches
    by_pid = {s.labels["pid"]: s.samples for s in batch}
    assert by_pid == {"1": [b"a", b"b"], "2": [b"c"]}


def test_batch_retries_with_jittered_backoff_then_succeeds():
    store = RecordingStore(fail_times=2)
    slept = []
    c = BatchWriteClient(store, interval_s=10.0, initial_backoff_s=0.1,
                         sleep=slept.append, rng=random.Random(42))
    c.write_raw({"pid": "1"}, b"a")
    assert c.flush()
    # Full-jitter backoff: each sleep ~ U(0, cap) with the cap doubling
    # (0.1 then 0.2) — bounded and deterministic under the seed.
    assert len(slept) == 2
    assert 0.0 <= slept[0] <= 0.1 and 0.0 <= slept[1] <= 0.2
    expect = random.Random(42)
    assert slept == [expect.uniform(0, 0.1), expect.uniform(0, 0.2)]
    assert c.send_errors == 2 and c.sent_batches == 1


def test_batch_retry_budget_bounds_one_flush():
    """The per-interval retry budget caps send attempts even when the
    interval deadline is far away (herd control after a store restart)."""
    store = RecordingStore(fail_times=99)
    c = BatchWriteClient(store, interval_s=1e9, initial_backoff_s=0.0,
                         retry_budget=3, rng=random.Random(1))
    c.write_raw({"pid": "1"}, b"a")
    assert not c.flush()
    assert c.send_errors == 4  # initial attempt + 3 budgeted retries
    assert c.stats["retry_budget_exhausted"] == 1
    assert c.buffered() == (1, 1)  # restored, not lost


def test_batch_failure_restores_buffer():
    store = RecordingStore(fail_times=99)
    clock = [0.0]

    def sleep(s):
        clock[0] += s

    c = BatchWriteClient(store, interval_s=1.0, initial_backoff_s=0.4,
                         clock=lambda: clock[0], sleep=sleep)
    c.write_raw({"pid": "1"}, b"a")
    assert not c.flush()
    # New sample arrives, then the store recovers: both ship together.
    store.fail_times = 0
    c.write_raw({"pid": "1"}, b"b")
    assert c.flush()
    (batch,) = store.batches
    assert batch[0].samples == [b"a", b"b"]


def test_batch_run_loop_drains_on_stop():
    store = RecordingStore()
    c = BatchWriteClient(store, interval_s=30.0)
    t = threading.Thread(target=c.run, daemon=True)
    t.start()
    c.write_raw({"pid": "9"}, b"z")
    c.stop()
    t.join(timeout=5)
    assert not t.is_alive()
    assert store.batches and store.batches[0][0].samples == [b"z"]


def test_listener_tee_and_matching():
    store = RecordingStore()
    batch = BatchWriteClient(store)
    listener = MatchingProfileListener(next_writer=batch)

    got = {}

    def wait():
        got["r"] = listener.next_matching_profile(
            equals_matcher(pid="7"), timeout=5
        )

    t = threading.Thread(target=wait)
    t.start()
    time.sleep(0.05)
    listener.write_raw({"pid": "6"}, b"no")
    listener.write_raw({"pid": "7"}, b"yes")
    t.join(timeout=5)
    labels, sample = got["r"]
    assert sample == b"yes" and labels["pid"] == "7"
    # tee passed everything through
    assert batch.flush()
    assert sum(len(s.samples) for s in store.batches[0]) == 2


def test_listener_timeout():
    listener = MatchingProfileListener()
    assert listener.next_matching_profile(equals_matcher(pid="1"),
                                          timeout=0.05) is None
    listener.write_raw({"pid": "1"}, b"later")  # no observer anymore: no-op


def test_file_writer(tmp_path):
    w = FileProfileWriter(str(tmp_path))
    w.write_raw({"__name__": "cpu", "comm": "app", "pid": "3"}, b"gzbytes")
    files = list(tmp_path.iterdir())
    assert len(files) == 1
    assert files[0].name.startswith("comm=app_pid=3.")
    assert files[0].read_bytes() == b"gzbytes"


def test_remote_writer_gzips():
    listener = MatchingProfileListener()
    rw = RemoteProfileWriter(listener)

    got = {}

    def wait():
        got["r"] = listener.next_matching_profile(lambda _: True, timeout=5)

    t = threading.Thread(target=wait)
    t.start()
    time.sleep(0.05)
    rw.write({"pid": "1"}, b"raw-pprof")
    t.join(timeout=5)
    _, sample = got["r"]
    assert gzip.decompress(sample) == b"raw-pprof"


def test_noop_store_client():
    NoopStoreClient().write_raw([], normalized=True)


def test_grpc_loopback():
    """End-to-end WriteRaw over a real in-process gRPC server."""
    grpc = pytest.importorskip("grpc")
    from concurrent import futures

    from parca_agent_tpu.agent.grpc_client import (
        WRITE_RAW_METHOD,
        GRPCStoreClient,
    )

    received = {}

    def handler(request, context):
        received["series"], received["normalized"] = \
            decode_write_raw_request(request)
        md = dict(context.invocation_metadata())
        received["auth"] = md.get("authorization", "")
        return b""

    method = WRITE_RAW_METHOD.rsplit("/", 1)
    service = grpc.method_handlers_generic_handler(
        method[0].lstrip("/"),
        {method[1]: grpc.unary_unary_rpc_method_handler(
            handler,
            request_deserializer=lambda b: b,
            response_serializer=lambda b: b,
        )},
    )
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    server.add_generic_rpc_handlers((service,))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        client = GRPCStoreClient(f"127.0.0.1:{port}", insecure=True,
                                 bearer_token="tok", timeout_s=10)
        client.write_raw([RawSeries({"pid": "5"}, [b"pp"])], normalized=True)
        client.close()
    finally:
        server.stop(0)
    assert received["series"][0].labels == {"pid": "5"}
    assert received["series"][0].samples == [b"pp"]
    assert received["normalized"] is True
    assert received["auth"] == "Bearer tok"


def test_fetch_server_cert_unverified(tmp_path):
    """--remote-store-insecure-skip-verify support: the server's cert is
    fetched over an UNVERIFIED handshake (self-signed — the flag's
    real-world case) and its common name extracted for the hostname
    override."""
    import socket
    import ssl
    import subprocess

    from parca_agent_tpu.agent.grpc_client import _fetch_server_cert

    key, crt = tmp_path / "k.pem", tmp_path / "c.pem"
    r = subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "1",
         "-subj", "/CN=selfsigned.test"], capture_output=True)
    if r.returncode != 0:
        pytest.skip(f"openssl unavailable: {r.stderr[:100]}")

    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(str(crt), str(key))
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    stop = threading.Event()

    def serve():
        srv.settimeout(5)
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except (TimeoutError, OSError):
                return  # closed under us at test end: normal shutdown
            try:
                with ctx.wrap_socket(conn, server_side=True):
                    pass
            except ssl.SSLError:
                pass

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        pem, cn = _fetch_server_cert(f"127.0.0.1:{port}")
        assert b"BEGIN CERTIFICATE" in pem
        assert cn == "selfsigned.test"
    finally:
        stop.set()
        srv.close()


# -- channel reset on RPC failure (TOFU re-pin, ADVICE round 5) --------------


def _reset_client(monkeypatch, builds, fail_with, skip_verify=True):
    """GRPCStoreClient against a fake channel whose WriteRaw always raises
    fail_with(); counts channel builds."""
    grpc = pytest.importorskip("grpc")
    from parca_agent_tpu.agent.grpc_client import GRPCStoreClient

    class FakeChannel:
        def unary_unary(self, *a, **kw):
            def call(req, timeout=None, metadata=None):
                raise fail_with()
            return call

        def close(self):
            pass

    client = GRPCStoreClient("store.test:443",
                             insecure_skip_verify=skip_verify,
                             reset_after_unavailable=3)
    monkeypatch.setattr(
        client, "_build_channel",
        lambda: builds.append(1) or FakeChannel())
    return grpc, client


class _FakeRpcError(Exception):
    def __init__(self, code, details=""):
        self._code, self._details = code, details

    def code(self):
        return self._code

    def details(self):
        return self._details

    def debug_error_string(self):
        return self._details


def test_handshake_failure_resets_channel_for_repin(monkeypatch):
    """A handshake-class RPC failure drops the built channel, so the next
    RPC re-dials and (under skip-verify) re-fetches + re-pins the server's
    CURRENT cert — a server cert rotation no longer bricks shipping until
    agent restart."""
    builds: list = []
    grpc, client = _reset_client(
        monkeypatch, builds,
        lambda: _FakeRpcError(grpc_code_unavailable(),
                              "Ssl handshake failed: CERTIFICATE_VERIFY"))
    with pytest.raises(Exception):
        client.write_raw([RawSeries({"a": "1"}, [b"x"])], normalized=True)
    assert len(builds) == 1
    assert client.stats["channel_resets"] == 1
    with pytest.raises(Exception):
        client.write_raw([RawSeries({"a": "1"}, [b"x"])], normalized=True)
    assert len(builds) == 2          # channel was rebuilt (re-pin point)


def grpc_code_unavailable():
    import grpc

    return grpc.StatusCode.UNAVAILABLE


def test_consecutive_unavailable_resets_channel(monkeypatch):
    """N consecutive UNAVAILABLEs (how grpc-python surfaces reconnect TLS
    failures) also reset; a success clears the streak."""
    builds: list = []
    grpc, client = _reset_client(
        monkeypatch, builds,
        lambda: _FakeRpcError(grpc_code_unavailable(), "connection refused"))
    for k in range(3):
        with pytest.raises(Exception):
            client.write_raw([RawSeries({"a": "1"}, [b"x"])],
                             normalized=True)
    assert client.stats["channel_resets"] == 1   # on the 3rd, not before
    assert len(builds) == 1
    with pytest.raises(Exception):
        client.write_raw([RawSeries({"a": "1"}, [b"x"])], normalized=True)
    assert len(builds) == 2


def test_non_tls_errors_do_not_reset(monkeypatch):
    """A data-plane failure (e.g. RESOURCE_EXHAUSTED) keeps the channel:
    resets are for trust/transport rot, not payload problems."""
    grpc = pytest.importorskip("grpc")
    builds: list = []
    _, client = _reset_client(
        monkeypatch, builds,
        lambda: _FakeRpcError(grpc.StatusCode.RESOURCE_EXHAUSTED,
                              "message too large"))
    for _ in range(5):
        with pytest.raises(Exception):
            client.write_raw([RawSeries({"a": "1"}, [b"x"])],
                             normalized=True)
    assert client.stats["channel_resets"] == 0
    assert len(builds) == 1


def test_insecure_channel_never_resets(monkeypatch):
    grpc = pytest.importorskip("grpc")
    from parca_agent_tpu.agent.grpc_client import GRPCStoreClient

    client = GRPCStoreClient("store.test:80", insecure=True)
    for _ in range(5):
        client._note_rpc_failure(
            _FakeRpcError(grpc.StatusCode.UNAVAILABLE, "handshake ssl"))
    assert client.stats["channel_resets"] == 0


def test_cert_name_prefers_cryptography_with_stdlib_fallback(tmp_path):
    """_cert_name: the `cryptography` route is tried first when
    importable; the private-API stdlib decoder stays as fallback and both
    agree on a real self-signed cert."""
    import subprocess

    from parca_agent_tpu.agent import grpc_client as gc

    key, crt = tmp_path / "k.pem", tmp_path / "c.pem"
    r = subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "1",
         "-subj", "/CN=rotated.test"], capture_output=True)
    if r.returncode != 0:
        pytest.skip(f"openssl unavailable: {r.stderr[:100]}")
    pem = crt.read_text()
    assert gc._cert_name_stdlib(pem) == "rotated.test"
    assert gc._cert_name(pem) == "rotated.test"
    try:
        import cryptography  # noqa: F401
    except ImportError:
        pass
    else:
        assert gc._cert_name_cryptography(pem) == "rotated.test"


def test_cert_name_unparseable_is_empty_and_logged():
    from parca_agent_tpu.agent import grpc_client as gc

    assert gc._cert_name("not a pem") == ""


# -- final-drain / restore-ordering / host:port satellites --------------------


def test_batch_final_drain_ships_samples_written_after_stop():
    """stop() before run(): the loop body never runs, but the final drain
    still flushes whatever is buffered — a draining agent ships every
    window it aggregated."""
    store = RecordingStore()
    c = BatchWriteClient(store, interval_s=3600.0)
    c.write_raw({"pid": "1"}, b"late")
    c.stop()
    c.run()  # returns immediately: stop is set, then drains
    assert store.batches and store.batches[0][0].samples == [b"late"]
    assert c.buffered() == (0, 0)


def test_batch_final_drain_gives_up_after_one_attempt_when_stopped():
    """With stop set, a failing drain must not spin its full retry
    budget (shutdown latency); the batch survives in the buffer (or
    spool) for the next process."""
    store = RecordingStore(fail_times=99)
    slept = []
    c = BatchWriteClient(store, interval_s=10.0, sleep=slept.append)
    c.write_raw({"pid": "1"}, b"a")
    c.stop()
    c.run()
    assert slept == []          # no backoff sleeps while stopping
    assert c.send_errors == 1   # exactly one drain attempt
    assert c.buffered() == (1, 1)


def test_restore_merges_failed_batch_ahead_of_newer_samples():
    """_restore ordering: after a failed flush, the failed batch's series
    come FIRST (both in sample order within a series and in series
    iteration order), so the store receives history oldest-first on the
    next attempt."""
    store = RecordingStore(fail_times=1)
    c = BatchWriteClient(store, interval_s=0.0, retry_budget=0)
    c.write_raw({"pid": "1"}, b"old-1")
    c.write_raw({"pid": "2"}, b"old-2")
    assert not c.flush()
    # Newer samples arrive for an existing series AND a brand-new one.
    c.write_raw({"pid": "1"}, b"new-1")
    c.write_raw({"pid": "3"}, b"new-3")
    assert c.flush()
    (batch,) = store.batches
    assert [s.labels["pid"] for s in batch] == ["1", "2", "3"]
    assert batch[0].samples == [b"old-1", b"new-1"]  # failed batch first


def test_split_host_port_edge_cases():
    from parca_agent_tpu.agent.grpc_client import _split_host_port

    assert _split_host_port("host.example:7070") == ("host.example", 7070)
    assert _split_host_port("host.example") == ("host.example", 443)
    assert _split_host_port("host.example:") == ("host.example", 443)
    assert _split_host_port("[2001:db8::1]") == ("2001:db8::1", 443)
    assert _split_host_port("[2001:db8::1]:7070") == ("2001:db8::1", 7070)
    assert _split_host_port("[2001:db8::1]:") == ("2001:db8::1", 443)
    assert _split_host_port("host:notaport") == ("host:notaport", 443)


def test_batch_buffered_depth_gauge():
    c = BatchWriteClient(NoopStoreClient(), interval_s=60)
    assert c.buffered() == (0, 0)
    c.write_raw({"pid": "1"}, b"a")
    c.write_raw({"pid": "1"}, b"b")
    c.write_raw({"pid": "2"}, b"c")
    assert c.buffered() == (2, 3)
    c.flush()
    assert c.buffered() == (0, 0)


# -- the spliced gzip member (agent/writer.py _gzip) ---------------------------
# Blobs of the fast encoder say where their static block lies; the writer
# deflates that block once and splices the piece into every later member.
# The windows come from tests/span_scenarios.py, shared with
# tests/test_window_encoder.py.


class _KeepSink:
    def __init__(self):
        self.samples = []

    def write_raw(self, labels, sample):
        self.samples.append(sample)


def _writer(kind, tmp_path):
    """(writer, function returning the members written so far in order)."""
    if kind == "remote":
        sink = _KeepSink()
        return RemoteProfileWriter(sink), lambda: list(sink.samples)
    w = FileProfileWriter(str(tmp_path))
    return w, lambda: [p.read_bytes() for p in sorted(
        tmp_path.iterdir(), key=lambda p: int(p.name.split(".")[-3]))]


def _ship(writer, members, out):
    """Write one window's blobs; assert every member inflates to the live
    bytes with a matching trailer; return the thread's sums."""
    n0 = len(members())
    want = [bytes(b) for _, b in out]
    for pid, blob in out.span_blobs():
        writer.write({"pid": str(pid)}, blob)
    got = members()[n0:]
    assert len(got) == len(want)
    for member, raw in zip(got, want):
        assert gzip.decompress(member) == raw
        crc, size = struct.unpack("<II", member[-8:])
        assert (crc, size) == (zlib.crc32(raw), len(raw))
    sums = writer.take_ship_clocks()
    assert sums["pprof_bytes"] == sum(map(len, want))
    assert sums["gzip_bytes"] == sum(map(len, got))
    return sums, got


@pytest.mark.parametrize("kind", ["remote", "file"])
@pytest.mark.parametrize("shape", sorted(span_scenarios.SHAPES))
def test_spliced_member_inflates_to_the_blob(shape, kind, tmp_path):
    """Three windows of one population with redrawn counts: the first
    builds a piece a profile, the next two reuse every one and deflate
    only the sample rows; every member is a plain gzip member of exactly
    the blob, no larger than gzip.compress(.., 1) makes it."""
    from parca_agent_tpu.aggregator.dict import DictAggregator
    from parca_agent_tpu.pprof.window_encoder import WindowEncoder

    snap = span_scenarios.shape_snapshot(shape)
    agg = DictAggregator(capacity=1 << 12)
    enc = WindowEncoder(agg)
    counts = np.asarray(agg.window_counts(snap))
    writer, members = _writer(kind, tmp_path)
    for k in range(3):
        c = counts + 5 * k
        out = enc.encode(c, snap.time_ns + k, snap.window_ns,
                         snap.period_ns, views=True)
        sums, got = _ship(writer, members, out)
        n = len(out)
        assert sums["gzip_fallbacks"] == 0
        assert (sums["gzip_static_built"], sums["gzip_static_reused"]) \
            == ((n, 0) if k == 0 else (0, n))
        rows = sum(b.static_span[0] for _, b in out.span_blobs())
        static = sum(b.static_span[1] for _, b in out.span_blobs())
        assert sums["gzip_deflated_bytes"] \
            == rows + (static if k == 0 else 0)
        assert all(m[:10] == b"\x1f\x8b\x08\x00\x00\x00\x00\x00\x04\xff"
                   for m in got)
        plain = sum(len(gzip.compress(bytes(b), 1)) for _, b in out)
        assert sums["gzip_bytes"] <= plain + 16 * n
    assert enc.static_piece_bytes() > 0


@pytest.mark.parametrize("site", span_scenarios.SITES)
def test_spliced_member_after_churn(site, tmp_path):
    """Through every site that lays down, moves or rewrites a static
    span: the member inflates to the live bytes, and a piece is built
    for exactly the groups whose span was rewritten."""
    writer, members = _writer("remote", tmp_path)
    for enc, out, rewritten, note in span_scenarios.run(site):
        sums, _ = _ship(writer, members, out)
        want_built = len(out) if rewritten is None \
            else len(rewritten & {p for p, _ in out})
        assert sums["gzip_fallbacks"] == 0, note
        assert sums["gzip_static_built"] == want_built, note
        assert sums["gzip_static_reused"] == len(out) - want_built, note


@pytest.mark.parametrize("kind", ["bytes", "bytearray", "memoryview"])
def test_spanless_payload_is_plain_gzip(kind):
    """A payload that carries no span (the scalar path's build_pprof
    bytes, the self-profile) gets what it always got."""
    raw = bytes(range(256)) * 40
    payload = {"bytes": raw, "bytearray": bytearray(raw),
               "memoryview": memoryview(raw)}[kind]
    sink = _KeepSink()
    w = RemoteProfileWriter(sink)
    w.write({"pid": "1"}, payload)
    (member,) = sink.samples
    assert gzip.decompress(member) == raw
    # gzip.compress(raw, 1) itself, but for the time in its header.
    assert member[:4] + member[8:10] == b"\x1f\x8b\x08\x00\x04\xff"
    assert member[10:] == gzip.compress(raw, 1)[10:]
    sums = w.take_ship_clocks()
    assert (sums["gzip_static_reused"], sums["gzip_static_built"],
            sums["gzip_fallbacks"]) == (0, 0, 0)
    assert sums["gzip_deflated_bytes"] == sums["pprof_bytes"] == len(raw)


def _one_window():
    gen = span_scenarios.run("counts")
    next(gen)
    enc, out = next(gen)[:2]
    return enc, list(out.span_blobs())


def test_two_threads_write_at_once():
    """The encode worker splices while the profiler thread's scalar
    fallback writes plain bytes through the same writer: each thread has
    its own compressor and its own sums."""
    enc, out = _one_window()
    sink = _KeepSink()
    w = RemoteProfileWriter(sink)
    raws = {bytes(b) for _, b in out}
    plain = [bytes([k]) * 3000 + bytes(range(256)) for k in range(60)]
    start = threading.Barrier(2)
    sums = {}

    def spliced():
        start.wait()
        for _ in range(6):
            for pid, blob in out:
                w.write({"pid": str(pid)}, blob)
        sums["spliced"] = w.take_ship_clocks()

    def scalar():
        start.wait()
        for raw in plain:
            w.write({"pid": "0"}, raw)
        sums["plain"] = w.take_ship_clocks()

    threads = [threading.Thread(target=f) for f in (spliced, scalar)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    inflated = [gzip.decompress(m) for m in sink.samples]
    assert len(inflated) == 6 * len(out) + len(plain)
    assert {r for r in inflated if r not in raws} == set(plain)
    assert sum(r in raws for r in inflated) == 6 * len(out)
    assert sums["spliced"]["gzip_static_built"] == len(out)
    assert sums["spliced"]["gzip_static_reused"] == 5 * len(out)
    assert sums["plain"]["gzip_static_built"] == 0
    assert sums["plain"]["pprof_bytes"] == sum(map(len, plain))
    assert sums["spliced"]["gzip_fallbacks"] == 0


class _RaisingBlob:
    """A span-carrying payload whose piece slot is broken."""

    def __init__(self, blob):
        self._blob = blob
        self.static_span = blob.static_span

    def __buffer__(self, flags):
        return memoryview(self._blob)

    def __len__(self):
        return len(self._blob)

    def static_piece(self):
        raise RuntimeError("slot gone")


@pytest.mark.chaos
@pytest.mark.parametrize("cause", ["injected", "raising_payload"])
def test_splice_fault_ships_by_the_fallback_and_counts(cause):
    """A splice that raises costs that profile the splice, never the
    profile: it ships through gzip.compress(.., 1), counted, and the
    next profile splices again from a fresh compressor."""
    from parca_agent_tpu.utils import faults
    from parca_agent_tpu.utils.faults import FaultInjector

    enc, out = _one_window()
    sink = _KeepSink()
    w = RemoteProfileWriter(sink)
    blobs = [b for _, b in out]
    for b in blobs:                       # warm: every piece built
        w.write({}, b)
    w.take_ship_clocks()
    sink.samples.clear()
    try:
        if cause == "injected":
            faults.install(FaultInjector.from_spec(
                "writer.splice:error:count=1", seed=0))
            w.write({}, blobs[0])
        else:
            w.write({}, _RaisingBlob(blobs[0]))
        for b in blobs[1:]:
            w.write({}, b)
    finally:
        faults.install(None)
    assert [gzip.decompress(m) for m in sink.samples] \
        == [bytes(b) for b in blobs]
    sums = w.take_ship_clocks()
    assert sums["gzip_fallbacks"] == 1
    assert sums["gzip_static_reused"] == len(blobs) - 1
    assert sums["gzip_static_built"] == 0
    # The fallback's member is gzip.compress's own (its header carries a
    # time); the spliced ones carry none.
    assert sink.samples[0][4:8] != b"\x00\x00\x00\x00"
    assert all(m[4:8] == b"\x00\x00\x00\x00" for m in sink.samples[1:])


def test_spliced_member_is_byte_deterministic():
    """Equal input, equal member (mtime 0), whatever the compressor and
    the piece cache went through before: a fresh writer on a fresh
    thread, a warm writer, and a warm writer after a fallback."""
    enc, out = _one_window()
    blobs = [b for _, b in out]

    def members(writer_warmup):
        sink = _KeepSink()
        w = RemoteProfileWriter(sink)
        writer_warmup(w)
        sink.samples.clear()
        for b in blobs:
            w.write({}, b)
        return list(sink.samples)

    first = members(lambda w: None)
    warm = members(lambda w: [w.write({}, b) for b in blobs * 2])
    mixed = members(lambda w: [w.write({}, p) for p in
                               (blobs[3], b"x" * 70_000,
                                _RaisingBlob(blobs[0]), blobs[1])])
    assert first == warm == mixed
    box = []
    t = threading.Thread(target=lambda: box.append(members(lambda w: None)))
    t.start()
    t.join(timeout=30)
    assert box == [first]


def test_tee_writer_sums_every_arm(tmp_path):
    """Both arms of a Tee splice for themselves (the second finds the
    piece the first built) and the sums add up."""
    from parca_agent_tpu.agent.writer import TeeProfileWriter

    enc, out = _one_window()
    sink = _KeepSink()
    tee = TeeProfileWriter(FileProfileWriter(str(tmp_path)),
                           RemoteProfileWriter(sink))
    assert enc.static_piece_bytes() == 0
    for pid, b in out:
        tee.write({"pid": str(pid)}, b)
    want = [bytes(b) for _, b in out]
    assert [gzip.decompress(m) for m in sink.samples] == want
    files = sorted(tmp_path.iterdir(),
                   key=lambda p: int(p.name.split(".")[-3]))
    assert [gzip.decompress(p.read_bytes()) for p in files] == want
    sums = tee.take_ship_clocks()
    assert sums["gzip_static_built"] == len(out)
    assert sums["gzip_static_reused"] == len(out)
    assert sums["pprof_bytes"] == 2 * sum(map(len, want))
    assert sums["gzip_fallbacks"] == 0
