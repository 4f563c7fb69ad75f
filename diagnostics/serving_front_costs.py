"""What one query costs the serving front's host, piece by piece, and how
fast the benchmark's load generator can ask when nothing is behind it.

    python3 diagnostics/serving_front_costs.py [--root CHECKOUT] [--seconds 5]

Prints JSON lines. Needs no accelerator (JAX is held to the CPU and only
`jax.device_get` of NumPy arrays is called); run it on the chip's host to
read that host's numbers. `--root` measures another checkout of this
repository (a parent commit unpacked beside this one).

- `codec`: one thread, alone on a core, us a call: `extract_query` of the
  recommendation template's query, `to_json_obj` / `tree_has_non_finite` /
  `json.dumps` of a 10-item reply, and `predict_batch` for a flush of 50
  behind a stub in the device's place (so: vocabulary lookups, pad,
  unpack), us a query.
- `transport`: a stub handler that answers at once, behind
  `data/api/http.py make_server` in a process of its own, asked by
  `benchmark/loadgen.py closed_loop`. One connection: the server's CPU-us
  a request. 128 connections: queries/s, the generator's and the
  server's cores. That rate is the PAIR's ceiling (one GIL each side).
- `generator_alone`: the same 128 connections against a bare socket
  answerer (one selector loop in a process that imports nothing of the
  program, a canned reply as soon as a request is whole; `server_*` is
  that answerer): what `loadgen.py` can ask when the other side costs
  next to nothing. A cell that reads near it is measuring `loadgen.py`,
  not the server.
"""

import argparse
import json
import os
import selectors
import socket
import subprocess
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
REPLY = {"itemScores": [{"item": f"i{j}", "score": 1.0 / (j + 3)}
                        for j in range(10)]}


def per_call_us(fn, n):
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def codec(n):
    import numpy as np

    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.event import tree_has_non_finite
    from predictionio_tpu.models.recommendation.als_algorithm import (
        ALSAlgorithm, ALSAlgorithmParams, ALSModel,
    )
    from predictionio_tpu.models.recommendation.engine import (
        ItemScore, PredictedResult, Query,
    )
    from predictionio_tpu.workflow import json_extractor

    body = json.dumps({"user": "u123456", "num": 10}).encode()
    result = PredictedResult(tuple(
        ItemScore(item=f"i{j}", score=1.0 / (j + 3)) for j in range(10)))
    obj = json_extractor.to_json_obj(result)
    out = {
        "extract_query": per_call_us(
            lambda: json_extractor.extract_query(Query, body), n),
        "to_json_obj": per_call_us(
            lambda: json_extractor.to_json_obj(result), n),
        "tree_has_non_finite": per_call_us(
            lambda: tree_has_non_finite(obj), n),
        "json_dumps": per_call_us(
            lambda: json.dumps(obj, allow_nan=False).encode("utf-8"), n),
    }
    checked = getattr(json_extractor, "to_json_checked", None)
    if checked is not None:
        out["to_json_checked"] = per_call_us(lambda: checked(result), n)

    class Stub:   # stands where a sharded layout's device program does
        n_shards = 1

        def __init__(self, rng, n_items):
            self.vals = np.sort(rng.standard_normal((64, 10)).astype(
                np.float32))[:, ::-1].copy()
            self.idx = rng.integers(0, n_items, (64, 10)).astype(np.int32)

        def topk(self, pix, k):
            return self.vals[:len(pix), :k], self.idx[:len(pix), :k]

    rng = np.random.default_rng(7)
    n_users, n_items, rows = 5000, 20000, 50
    model = ALSModel(
        rank=8, user_factors=None, item_factors=None,
        user_vocab=BiMap.string_int(f"u{j}" for j in range(n_users)),
        item_vocab=BiMap.string_int(f"i{j}" for j in range(n_items)),
        sharding=Stub(rng, n_items))
    algo = ALSAlgorithm(ALSAlgorithmParams())
    queries = [Query(user=f"u{int(u)}", num=10)
               for u in rng.integers(0, n_users, rows)]
    flush = per_call_us(lambda: algo.predict_batch(model, queries),
                        max(n // rows, 20))
    out["predict_batch_host_side.per_query"] = flush / rows
    return out


class StubAPI:
    """Answers a fixed 10-item reply at once; `GET /cpu` says what the
    process has spent."""

    def handle(self, method, path, query=None, body=b"", headers=None):
        if path == "/cpu":
            return 200, {"cpu_s": time.process_time()}
        return 200, REPLY


def serve_stub():
    from predictionio_tpu.data.api.http import make_server
    server = make_server(StubAPI(), "127.0.0.1", 0, tls=False)
    print(server.server_address[1], flush=True)
    server.serve_forever()


def serve_bare():
    """The canned reply to whatever request is whole, on one selector
    loop; `GET /cpu` as the stub has it."""
    body = json.dumps(REPLY).encode()
    head = ("HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            "Content-Length: %d\r\n\r\n")
    canned = (head % len(body)).encode() + body
    listener = socket.create_server(("127.0.0.1", 0), backlog=128)
    listener.setblocking(False)
    print(listener.getsockname()[1], flush=True)
    sel = selectors.DefaultSelector()
    sel.register(listener, selectors.EVENT_READ, None)
    while True:
        for key, _events in sel.select():
            if key.data is None:
                conn, _addr = listener.accept()
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sel.register(conn, selectors.EVENT_READ, bytearray())
                continue
            conn, buf = key.fileobj, key.data
            got = conn.recv(65536)
            if not got:
                sel.unregister(conn)
                conn.close()
                continue
            buf += got
            while True:
                end = buf.find(b"\r\n\r\n")
                if end < 0:
                    break
                lower = bytes(buf[:end]).lower()
                at = lower.find(b"content-length:")
                length = (int(lower[at + 15:].split(b"\r\n", 1)[0])
                          if at >= 0 else 0)
                if len(buf) < end + 4 + length:
                    break
                reply = canned
                if buf.startswith(b"GET /cpu "):
                    cpu = json.dumps({"cpu_s": time.process_time()}).encode()
                    reply = (head % len(cpu)).encode() + cpu
                del buf[:end + 4 + length]
                conn.sendall(reply)


def _asked(root, seconds, flag, connections):
    """`loadgen.closed_loop` against a child of this file started with
    `flag`, at each count of connections."""
    import urllib.request

    import numpy as np

    sys.path.insert(0, os.path.join(root, "benchmark"))
    import loadgen

    env = dict(os.environ, PYTHONPATH=root)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), flag],
        env=env, stdout=subprocess.PIPE, text=True)
    try:
        port = int(child.stdout.readline())

        def server_cpu():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/cpu", timeout=10) as r:
                return json.loads(r.read())["cpu_s"]

        users = np.arange(2_000_000)
        out = {}
        for conns in connections:
            loadgen.closed_loop(port, users, 10, conns, 1.0)   # warm
            c0, g0 = server_cpu(), time.process_time()
            records, t0, t1 = loadgen.closed_loop(
                port, users, 10, conns, seconds)
            c1, g1 = server_cpu(), time.process_time()
            good = sum(1 for r in records if r[-1] is not None)
            out[f"connections_{conns}"] = {
                "queries_per_s": good / (t1 - t0),
                "failed": len(records) - good,
                "server_cpu_us_per_request": (c1 - c0) / max(good, 1) * 1e6,
                "server_cores": (c1 - c0) / (t1 - t0),
                "generator_cores": (g1 - g0) / (t1 - t0)}
        return out
    finally:
        child.terminate()
        child.wait(30)


def transport(root, seconds):
    return _asked(root, seconds, "--serve-stub", (1, 128))


def generator_alone(root, seconds):
    return _asked(root, seconds, "--serve-bare", (128,))["connections_128"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--calls", type=int, default=20000)
    ap.add_argument("--serve-stub", action="store_true")
    ap.add_argument("--serve-bare", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    if args.serve_stub:
        return serve_stub()   # PYTHONPATH names the checkout
    if args.serve_bare:
        return serve_bare()
    sys.path.insert(0, root)
    print(json.dumps({"root": root, "cpus": os.cpu_count(),
                      "codec_us": codec(args.calls)}), flush=True)
    print(json.dumps({"root": root,
                      "transport": transport(root, args.seconds)}),
          flush=True)
    print(json.dumps({"root": root, "generator_alone":
                      generator_alone(root, args.seconds)}), flush=True)


if __name__ == "__main__":
    main()
