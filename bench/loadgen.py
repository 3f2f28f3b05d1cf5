"""The load generator: a child process that sends a mix's requests.

It never imports JAX, so its threads take neither the server's
interpreter lock nor the chip. The parent writes one JSON line of
settings, which name the file of the configuration's kind
(``kind_file``), and then the query pool's arrays (``np.savez`` format)
to its standard input; the kind makes each request's body from them
(bench/spec.py). The generator sends until the window's end, waits for
every answer, and writes what it saw to standard output as one
``np.savez`` archive:

  t_due, t_send, t_recv   per request, host monotonic seconds (the clock
                          the parent's window is on). A closed loop's
                          request is due when it is sent; an open loop's
                          when its arrival falls, so a late generator
                          shows in the latency and in t_send - t_due.
  status                  HTTP status; 0 when no response came, -1 when
                          the body did not parse
  wall_ms                 the server's own time for the request
  qidx, ids, dists, ok    per query: pool index, the k ids and distances
                          served, and whether an answer came

Loops:
  closed  ``clients`` senders, each sending its next request when the
          last is answered (bulk callers that wait for their replies);
  open    requests sent at a fixed ``rate`` whatever the answers, by up to
          ``max_outstanding`` senders (independent users).
"""
from __future__ import annotations

import http.client
import io
import json
import queue
import sys
import threading
import time
import urllib.parse
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import corpus, spec  # noqa: E402


def _send(conn_args, body: bytes, timeout: float):
    """POST /search -> (status, parsed doc or None)."""
    conn = http.client.HTTPConnection(*conn_args, timeout=timeout)
    try:
        conn.request("POST", "/search", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        status = resp.status
    except (OSError, http.client.HTTPException):
        return 0, None
    finally:
        conn.close()
    try:
        return status, json.loads(raw)
    except ValueError:
        return -1, None


def _answers(doc, n: int, k: int):
    """(ids (n, k), dists (n, k), ok (n,)) from a 200 body; a missing,
    shed or short result is a query not answered."""
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.nan, np.float32)
    ok = np.zeros(n, bool)
    results = doc.get("results") if isinstance(doc, dict) else None
    if n == 1 and isinstance(results, dict):
        results = [results]
    if not isinstance(results, list) or len(results) != n:
        return ids, dists, ok, False
    for i, r in enumerate(results):
        try:
            a = np.asarray(r["ids"], np.int64)
            b = np.asarray(r["dists"], np.float32)
        except (TypeError, KeyError, ValueError):
            continue
        if a.shape == (k,) and b.shape == (k,):
            ids[i], dists[i], ok[i] = a, b, True
    return ids, dists, ok, True


def bodies(kind, s: dict, pool: dict):
    """(the number of pool queries, a function from pool indices to the
    bytes of the request that asks them): the kind encodes each pool query
    once, so a body is then a join."""
    frags = kind.encoded(pool)
    return len(frags), lambda qidx: kind.body(s, frags, qidx)


def request(s: dict, body, qidx: np.ndarray, t_due: float,
            rows: list) -> None:
    """Send the request ``body`` makes of ``qidx`` and append what came
    back to ``rows``."""
    data = body(qidx)
    t_send = time.monotonic()
    status, out = _send(s["conn"], data, s["timeout_s"])
    t_recv = time.monotonic()
    ids, dists, ok, parsed = _answers(out if status == 200 else None,
                                      len(qidx), s["k"])
    if status == 200 and not parsed:
        status = -1
    wall = out.get("wall_ms", np.nan) if isinstance(out, dict) else np.nan
    rows.append((t_due, t_send, t_recv, status, float(wall), qidx, ids,
                 dists, ok))


def _closed(s: dict, body, n_pool: int) -> list[list]:
    qpr = s["queries_per_request"]
    # enough queries for any sender at any speed this window allows
    cap = int(s["t_end"] - s["t_start"] + 1) * 20000

    def sender(c: int, rows: list) -> None:
        order = corpus.query_order(s["seed"], c, n_pool, cap)
        pos = 0
        while time.monotonic() < s["t_start"]:
            time.sleep(0.001)
        while time.monotonic() < s["t_end"]:
            idx = order[pos:pos + qpr]
            pos += qpr
            request(s, body, idx, time.monotonic(), rows)

    recs = [[] for _ in range(s["clients"])]
    threads = [threading.Thread(target=sender, args=(c, r), daemon=True)
               for c, r in enumerate(recs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return recs


def _open(s: dict, body, n_pool: int) -> list[list]:
    qpr = s["queries_per_request"]
    offsets = corpus.arrival_offsets(
        s["seed"], s["rate"], s["t_end"] - s["t_start"]
    )
    order = corpus.query_order(s["seed"], 0, n_pool, len(offsets) * qpr)
    work: queue.Queue = queue.Queue()

    def sender(rows: list) -> None:
        while True:
            item = work.get()
            if item is None:
                return
            i, t_due = item
            request(s, body, order[i * qpr:(i + 1) * qpr], t_due, rows)

    recs = [[] for _ in range(s["max_outstanding"])]
    threads = [threading.Thread(target=sender, args=(r,), daemon=True)
               for r in recs]
    for t in threads:
        t.start()
    for i, off in enumerate(offsets):
        t_due = s["t_start"] + float(off)
        wait = t_due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put((i, t_due))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    return recs


def conn_args(url: str) -> tuple:
    u = urllib.parse.urlsplit(url)
    return u.hostname, u.port


def run(s: dict, pool: dict) -> bytes:
    """Send the mix and return the npz archive of what came back."""
    s = dict(s, conn=conn_args(s["url"]))
    n_pool, body = bodies(spec.load_kind(s["kind_file"]), s, pool)
    recs = (_closed if s["loop"] == "closed" else _open)(s, body, n_pool)
    rows = sorted((r for rec in recs for r in rec), key=lambda r: r[1])
    qpr, k = s["queries_per_request"], s["k"]

    def col(i, dtype, shape=()):
        if not rows:
            return np.zeros((0,) + shape, dtype)
        return np.asarray([r[i] for r in rows], dtype)

    out = io.BytesIO()
    np.savez(
        out,
        t_due=col(0, np.float64), t_send=col(1, np.float64),
        t_recv=col(2, np.float64), status=col(3, np.int32),
        wall_ms=col(4, np.float64), qidx=col(5, np.int64, (qpr,)),
        ids=col(6, np.int64, (qpr, k)), dists=col(7, np.float32, (qpr, k)),
        ok=col(8, bool, (qpr,)),
    )
    return out.getvalue()


def main() -> int:
    settings = json.loads(sys.stdin.buffer.readline())
    pool = dict(np.load(io.BytesIO(sys.stdin.buffer.read()),
                        allow_pickle=False))
    sys.stdout.buffer.write(run(settings, pool))
    sys.stdout.buffer.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
