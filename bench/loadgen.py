"""Load generator: a child process that drives the server over HTTP.

It never imports JAX (the parent holds the chip), only the standard
library, numpy and ``traffic.py``.  Protocol, one JSON line each way:

* stdin: the plan (server URL, warm-up requests, the window's streams,
  the ids whose answers to keep, the ingest writer's spec);
* stdout ``ready``: warm-up done; stdin ``go``: open the window;
* stdout ``closed``: the window's seconds are over, no new request is
  sent; in-flight requests get up to ``drain_s`` more;
* stdout ``done``: one record per request.

Times are ``time.monotonic()`` (system-wide, so the parent can compare).
An open loop times a request from when it was due; a closed loop from
when it was sent.
"""
import json
import queue
import sys
import threading
import time
from http.client import HTTPConnection
from pathlib import Path
from urllib.parse import urlparse

sys.path.insert(0, str(Path(__file__).resolve().parent))
import traffic  # noqa: E402


class Sender:
    """One keep-alive connection; sends a body and records the answer."""

    def __init__(self, url: str, timeout: float, keep: set):
        u = urlparse(url)
        self.host, self.port = u.hostname, u.port
        self.timeout = timeout
        self.keep = keep
        self.conn = None
        self.inflight = None

    def send(self, rid, path: str, body: bytes, due=None, extra=None):
        rec = {"id": rid, "due": due, "send": time.monotonic()}
        self.inflight = rec
        if extra:
            rec.update(extra)
        try:
            if self.conn is None:
                self.conn = HTTPConnection(self.host, self.port,
                                           timeout=self.timeout)
            self.conn.request("POST", path, body=body,
                              headers={"Content-Type": "application/json"})
            resp = self.conn.getresponse()
            data = resp.read()
            rec["done"] = time.monotonic()
            rec["status"] = resp.status
            out = json.loads(data)
            rec["timing"] = out.get("timing")
            if resp.status != 200:
                rec["error"] = out.get("error")
            elif rid in self.keep:
                rec["body"] = out["result"]
        except Exception as exc:   # a lost or broken request is a failure
            rec["done"] = time.monotonic()
            rec["status"] = -1
            rec["error"] = {"code": type(exc).__name__, "message": str(exc)}
            if self.conn is not None:
                self.conn.close()
            self.conn = None
        self.inflight = None
        return rec

    def close(self):
        if self.conn is not None:
            self.conn.close()


class Writer:
    """Builds ingest bodies batch by batch from the seed."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.next = 0
        self.lock = threading.Lock()

    def body(self):
        with self.lock:
            i = self.next
            self.next += 1
        r, c, v = traffic.ingest_batch(self.spec["seed"], i, self.spec)
        payload = json.loads(json.dumps(self.spec["template"]))
        payload["ingest"].update(rows=r.tolist(), cols=c.tolist(),
                                 vals=v.tolist())
        return i, json.dumps(payload).encode()


def _send_req(sender, req, writer, due=None):
    rid, path, body = req[0], req[1], req[2]
    extra = None
    if body is None:                      # an ingest batch, built here
        i, body = writer.body()
        extra = {"batch": i, "triples": writer.spec["batch"]}
    else:
        body = body.encode()
    return sender.send(rid, path, body, due=due, extra=extra)


def run(plan: dict, inp, out) -> None:
    keep = set(plan["keep"])
    timeout = float(plan["seconds"]) + float(plan["drain_s"]) + 60.0
    writer = Writer(plan["ingest"]) if plan.get("ingest") else None
    records, lock = [], threading.Lock()

    warm = Sender(plan["url"], timeout, set())
    warm_fail = 0
    for req in plan["warmup"]:
        rec = _send_req(warm, req, writer)
        warm_fail += rec["status"] != 200
    warm.close()
    out.write("ready " + json.dumps({
        "warmup": len(plan["warmup"]), "warmup_failed": warm_fail,
        "ingest_next": writer.next if writer else 0}) + "\n")
    out.flush()
    if inp.readline().strip() != "go":
        return
    t0 = time.monotonic()
    t_close = t0 + float(plan["seconds"])
    threads, senders = [], []

    def new_sender():
        s = Sender(plan["url"], timeout, keep)
        senders.append(s)
        return s

    def keep_rec(rec):
        with lock:
            records.append(rec)

    for si, st in enumerate(plan["streams"]):
        if st["loop"] == "open":
            q: queue.Queue = queue.Queue()

            def dispatch(reqs=st["requests"], q=q, n=st["senders"]):
                for req in reqs:
                    due = t0 + req[3]
                    wait = due - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                    q.put((req, due))
                for _ in range(n):
                    q.put(None)

            def send_loop(q=q, si=si):
                s = new_sender()
                while True:
                    item = q.get()
                    if item is None:
                        break
                    req, due = item
                    rec = _send_req(s, req, writer, due=due)
                    rec["s"] = si
                    keep_rec(rec)
                s.close()

            threads.append(threading.Thread(target=dispatch, daemon=True))
            threads += [threading.Thread(target=send_loop, daemon=True)
                        for _ in range(st["senders"])]
        else:
            for reqs in st["requests"]:
                def client(reqs=reqs, si=si):
                    s = new_sender()
                    for req in reqs:
                        if time.monotonic() >= t_close:
                            break
                        rec = _send_req(s, req, writer)
                        rec["s"] = si
                        keep_rec(rec)
                    s.close()
                threads.append(threading.Thread(target=client, daemon=True))
    for t in threads:
        t.start()
    time.sleep(max(0.0, t_close - time.monotonic()))
    out.write("closed " + json.dumps({"t0": t0, "t_close": t_close}) + "\n")
    out.flush()
    deadline = t_close + float(plan["drain_s"])
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    with lock:
        recs = list(records)
    # still waiting at the deadline: lost, recorded without an answer
    lost = [dict(f, s=None) for f in (s.inflight for s in senders)
            if f is not None]
    out.write("done " + json.dumps({"t0": t0, "t_close": t_close,
                                    "records": recs,
                                    "lost": lost}) + "\n")
    out.flush()


if __name__ == "__main__":
    run(json.loads(sys.stdin.readline()), sys.stdin, sys.stdout)
