"""The store the agent ships to: a gRPC ``ProfileStoreService`` sink.

A child process of the harness that never imports JAX. It answers
``WriteRaw`` with an empty reply, decodes each request with the
benchmark's own decoder and keeps, for every ``pid`` label, how many
profiles arrived and the profiles themselves, in arrival order (a pid's
k-th profile is the k-th window that pid was sampled in). The harness
talks to it in JSON lines over stdin/stdout:

  (at start)                      -> {"port": n}
  {"cmd": "mark"}                 -> {"ok": true}      drop the profiles kept
                                     so far, keep their count
  {"cmd": "stats"}                -> {"requests", "profiles", "bytes",
                                      "per_pid": {pid: n}, "errors"}
  {"cmd": "fetch", "want": [[pid, k], ...], "path": p}
                                  -> {"found": n}      pickles {(pid, k): blob}
                                     into p
  {"cmd": "quit"}                 -> exits
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import threading
from concurrent import futures

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pprof_read import decode_write_raw  # noqa: E402

WRITE_RAW = ("parca.profilestore.v1alpha1.ProfileStoreService", "WriteRaw")
MAX_MSG_BYTES = 256 << 20


class Store:
    def __init__(self):
        self.lock = threading.Lock()
        self.count: dict[int, int] = {}
        self.kept: dict[tuple[int, int], bytes] = {}
        self.requests = self.bytes = self.errors = 0

    def write_raw(self, request: bytes, _context) -> bytes:
        try:
            series = decode_write_raw(request)
        except Exception:  # noqa: BLE001 - a bad request is counted, the sink lives
            with self.lock:
                self.errors += 1
            return b""
        with self.lock:
            self.requests += 1
            self.bytes += len(request)
            for labels, samples in series:
                try:
                    pid = int(labels.get("pid", ""))
                except ValueError:
                    self.errors += 1
                    continue
                k = self.count.get(pid, 0)
                for blob in samples:
                    self.kept[(pid, k)] = blob
                    k += 1
                self.count[pid] = k
        return b""

    def command(self, msg: dict) -> dict:
        cmd = msg.get("cmd")
        with self.lock:
            if cmd == "mark":
                self.kept.clear()
                return {"ok": True}
            if cmd == "stats":
                return {"requests": self.requests, "bytes": self.bytes,
                        "profiles": sum(self.count.values()),
                        "errors": self.errors,
                        "per_pid": {str(p): n for p, n in self.count.items()}}
            if cmd == "fetch":
                got = {}
                for pid, k in msg["want"]:
                    blob = self.kept.get((int(pid), int(k)))
                    if blob is not None:
                        got[(int(pid), int(k))] = blob
                with open(msg["path"], "wb") as f:
                    pickle.dump(got, f, protocol=pickle.HIGHEST_PROTOCOL)
                return {"found": len(got)}
        return {"error": f"unknown command {cmd!r}"}


def main() -> int:
    import grpc

    store = Store()
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=2),
        options=[("grpc.max_receive_message_length", MAX_MSG_BYTES),
                 ("grpc.max_send_message_length", MAX_MSG_BYTES)])
    server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
        WRITE_RAW[0],
        {WRITE_RAW[1]: grpc.unary_unary_rpc_method_handler(
            store.write_raw, request_deserializer=lambda b: b,
            response_serializer=lambda b: b)}),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    print(json.dumps({"port": port}), flush=True)
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            msg = json.loads(line)
            if msg.get("cmd") == "quit":
                break
            print(json.dumps(store.command(msg)), flush=True)
    finally:
        server.stop(0).wait()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
