"""The one general load generator: two ways of sending the queries a
configuration's adapter made from the seed, both parameterised by a
traffic file. What a query is, how it reads on the wire and what a
reply holds is the adapter's (`wire.body(query)`, `wire.parse(status,
data)`, called on the sending thread, a request at a time).

closed_loop: `connections` keep-alive connections, each sends its next
query when the reply arrives. open_loop: arrivals on a schedule fixed
before the window (Poisson at `rate_qps`), sent by a pool of
`connections` workers; each request is timed from when it was DUE, and
how late it left is recorded. One process, plain threads and
http.client; NumPy for the draws. Imports nothing of the program.
"""

import http.client
import threading
import time

import numpy as np


def arrival_times(seed, traffic, seconds):
    """Offsets (s) from the window's start at which requests are due."""
    rng = np.random.default_rng([int(seed), 0xA7])
    n = int(traffic["rate_qps"] * seconds * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / traffic["rate_qps"], n))
    return t[t < seconds]


class Client:
    """One keep-alive connection; a failed request reconnects once."""

    def __init__(self, host, port, timeout):
        self.host, self.port, self.timeout = host, port, timeout
        self.conn = None

    def post(self, body):
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    self.conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout)
                self.conn.request(
                    "POST", "/queries.json", body,
                    {"Content-Type": "application/json"})
                resp = self.conn.getresponse()
                data = resp.read()
                return resp.status, data
            except (OSError, http.client.HTTPException):
                self.close()
                if attempt:
                    return 0, b""
        return 0, b""

    def close(self):
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None


def closed_loop(port, queries, wire, connections, seconds, timeout=60.0):
    """-> records [(query_ix, t_sent, t_done, reply|None)], t_start,
    t_end; request k is queries[k]. Every request STARTED inside the
    window is waited for and counted; the window ends when the last of
    them is answered."""
    records, lock = [], threading.Lock()
    cursor = iter(range(len(queries)))
    t_start = time.time()
    deadline = t_start + seconds

    def worker():
        cl = Client("127.0.0.1", port, timeout)
        mine = []
        while True:
            with lock:
                k = next(cursor, None)
            if k is None or time.time() >= deadline:
                break
            t0 = time.time()
            status, data = cl.post(wire.body(queries[k]))
            mine.append((k, t0, time.time(), wire.parse(status, data)))
        cl.close()
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(connections)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records, t_start, max([r[2] for r in records] + [deadline])


def open_loop(port, queries, wire, due, connections, wait_s=60.0,
              timeout=60.0):
    """-> records [(query_ix, t_due, t_sent, t_done, reply|None)],
    t_start. `due` are offsets from the start; request j is queries[j].
    Workers take requests in order of due time; one that finds no free
    worker leaves late, and its latency counts the wait."""
    records, lock = [], threading.Lock()
    cursor = iter(range(len(due)))
    t_start = time.time() + 0.05

    def worker():
        cl = Client("127.0.0.1", port, timeout)
        mine = []
        while True:
            with lock:
                j = next(cursor, None)
            if j is None:
                break
            t_due = t_start + float(due[j])
            delay = t_due - time.time()
            if delay > 0:
                time.sleep(delay)
            t_sent = time.time()
            status, data = cl.post(wire.body(queries[j]))
            mine.append((j, t_due, t_sent, time.time(),
                         wire.parse(status, data)))
        cl.close()
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(connections)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(wait_s + float(due[-1]) if len(due) else wait_s)
    return records, t_start


def lateness_ms(records):
    """How late each open-loop request left, in ms."""
    return [max(0.0, (r[2] - r[1]) * 1e3) for r in records]


# ---------------------------------------------------------------------------
# tests/test_benchmark_contract.py (tier-1, which a benchmark PR may not
# edit) calls `_body(user_ix, num)`, `parse_reply(status, data)` and
# `closed_loop(port, users, num, ...)` by path: the first adapter's wire
# under those names, all of it below this line and none of it inside a
# generator above, until a PR that may touch tests/ carries its cases
# over to the adapter's and deletes this block (PERF.md 7.8). Nothing
# under benchmark/ passes a number where the wire goes.
# ---------------------------------------------------------------------------

def _as_wire(num):
    import harness
    return harness.load_adapter("rec_als").Wire(num)


def _body(user_ix, num):
    return _as_wire(num).body(user_ix)


def parse_reply(status, data):
    return _as_wire(0).parse(status, data)


def _taking_a_number_for_the_wire(timed):
    def closed_loop(port, queries, wire, *args, **kwargs):
        if isinstance(wire, int):
            wire = _as_wire(wire)
        return timed(port, queries, wire, *args, **kwargs)
    closed_loop.__doc__ = timed.__doc__
    return closed_loop


closed_loop = _taking_a_number_for_the_wire(closed_loop)
