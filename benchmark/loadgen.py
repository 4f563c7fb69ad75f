"""The one general load generator: query users from the seed, and two
ways of sending them, both parameterised by a traffic file.

closed_loop: `connections` keep-alive connections, each sends its next
query when the reply arrives. open_loop: arrivals on a schedule fixed
before the window (Poisson at `rate_qps`), sent by a pool of
`connections` workers; each request is timed from when it was DUE, and
how late it left is recorded. One process, plain threads and
http.client; NumPy for the draws. Imports nothing of the program.
"""

import http.client
import json
import threading
import time

import numpy as np


def query_users(seed, n, n_users, zipf_a):
    """n user indices, zipf(a) over ranks scrambled by a seeded
    multiplicative map so that popularity is not index order (copied
    from data/synthetic.py query_keys' draw: bounded zipf by rejection
    of ranks past the population)."""
    rng = np.random.default_rng([int(seed), 0x51])
    out = np.empty(0, np.int64)
    while out.size < n:
        draw = rng.zipf(zipf_a, size=int((n - out.size) * 1.3) + 16)
        out = np.concatenate([out, draw[draw <= n_users] - 1])
    ranks = out[:n]
    # odd multiplier modulo n_users' next power of two, cycle-walked
    # back into range: a fixed bijection of [0, n_users)
    bits = max(1, int(n_users - 1).bit_length())
    mask = (1 << bits) - 1
    mult = (int(rng.integers(1, 1 << 30)) * 2 + 1) & mask or 1
    add = int(rng.integers(0, 1 << 30)) & mask
    x = (ranks * mult + add) & mask
    while True:
        bad = x >= n_users
        if not bad.any():
            break
        x[bad] = (x[bad] * mult + add) & mask
    return x


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


def parse_reply(status, data):
    """-> [(item_name, score)] or None for anything but a full reply."""
    if status != 200:
        return None
    try:
        items = json.loads(data)["itemScores"]
        return [(s["item"], float(s["score"])) for s in items]
    except (ValueError, KeyError, TypeError):
        return None


def _body(user_ix, num):
    return json.dumps({"user": f"u{int(user_ix)}", "num": num})


def closed_loop(port, users, num, connections, seconds, timeout=60.0):
    """-> records [(user_ix, t_sent, t_done, items|None)], t_start, t_end.
    Every request STARTED inside the window is waited for and counted;
    the window ends when the last of them is answered."""
    records, lock = [], threading.Lock()
    cursor = iter(range(len(users)))
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
            status, data = cl.post(_body(users[k], num))
            mine.append((int(users[k]), t0, time.time(),
                         parse_reply(status, data)))
        cl.close()
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(connections)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records, t_start, max([r[2] for r in records] + [deadline])


def open_loop(port, users, num, due, connections, wait_s=60.0,
              timeout=60.0):
    """-> records [(user_ix, t_due, t_sent, t_done, items|None)],
    t_start. `due` are offsets from the start; request j is users[j].
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
            status, data = cl.post(_body(users[j], num))
            mine.append((int(users[j]), t_due, t_sent, time.time(),
                         parse_reply(status, data)))
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
