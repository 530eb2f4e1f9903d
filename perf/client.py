"""The client side of a run: the Sesam pipes.

Started by ``perf/run.py`` as a separate process that never imports JAX,
so the load it offers does not share the server's interpreter lock.  It
speaks real HTTP to the server on the loopback interface and stamps every
POST's due, send and ack times on the system monotonic clock, which the
server process reads too.

Protocol: the harness writes one JSON command per line on stdin and the
client answers one JSON line on stdout.

    {"cmd": "warm", "port": P}   one warm-up round       -> {"warmed": ...}
    {"cmd": "window"}            the measured window     -> {"t0", "last_ack"}
    {"cmd": "report"}            read the feed, write it -> {"done": true}

The window ends at the last acknowledgement of its POSTs; ``report``
then pages the live feed from ``?since=0`` and writes the window's
record (every POST with its entities and times, the live feed,
``/metrics`` before and after the window) as JSON to the report path the
plan names.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
from reference import parse_service  # noqa: E402

ACK_GRACE_S = 60.0         # an answer later than this past the close never came


class Conn:
    """One keep-alive HTTP/1.1 connection, reopened after an error."""

    def __init__(self, port: int):
        self.port = port
        self.http = None

    def request(self, method: str, path: str, body: bytes = None):
        for attempt in (0, 1):
            if self.http is None:
                self.http = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=ACK_GRACE_S + 600)
            try:
                headers = {"Content-Type": "application/json"} if body else {}
                self.http.request(method, path, body=body, headers=headers)
                resp = self.http.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, OSError):
                self.http.close()
                self.http = None
                if attempt:
                    raise
        raise AssertionError("unreachable")


class Client:
    def __init__(self, plan: dict):
        self.plan = plan
        self.config = plan["config"]
        self.traffic = plan["traffic"]
        self.seed = plan["seed"]
        self.seconds = plan["seconds"]
        svc = parse_service(self.config["service_xml"])
        self.kind, self.name = svc["kind"], svc["name"]
        if self.traffic["loop"] != "closed":
            raise SystemExit(f"no generator for a {self.traffic['loop']!r} "
                             f"loop")
        self.rows = gen.corpus(self.config, self.seed)
        self.pipes = gen.resync_pipes(self.config, self.traffic, self.seed,
                                      self.rows)
        self.next_post = [0] * len(self.pipes)
        self.port = None
        self.warm_round = 0
        self.sent = []  # every POST of warm-up and window, in send order
        self.window_record = None

    # -- helpers -------------------------------------------------------------

    def post(self, conn: Conn, p: gen.Post, phase: str, due: float) -> dict:
        body = json.dumps(p.entities).encode()
        send = time.monotonic()
        try:
            status, _ = conn.request(
                "POST", f"/{self.kind}/{self.name}/{p.dataset}", body)
        except (http.client.HTTPException, OSError):
            status = None
        rec = {"phase": phase, "dataset": p.dataset, "entities": p.entities,
               "due": due, "send": send, "ack": time.monotonic(),
               "status": status}
        self.sent.append(rec)  # list.append is atomic under the GIL
        return rec

    def metrics_text(self) -> str:
        status, body = Conn(self.port).request("GET", "/metrics")
        if status != 200:
            raise RuntimeError(f"/metrics answered {status}")
        return body.decode()

    def feed(self, conn: Conn, since: int):
        status, body = conn.request(
            "GET", f"/{self.kind}/{self.name}?since={since}")
        return status, (json.loads(body) if status == 200 else None)

    def live_links(self) -> dict:
        """The feed folded from ``?since=0``: {row _id: row} of live
        links."""
        conn, since, live = Conn(self.port), 0, {}
        while True:
            status, rows = self.feed(conn, since)
            if status != 200:
                time.sleep(0.05)
                continue
            if not rows:
                return live
            for row in rows:
                if row["_deleted"]:
                    live.pop(row["_id"], None)
                else:
                    live[row["_id"]] = row
                since = max(since, row["_updated"])

    # -- warm-up ---------------------------------------------------------------

    def warm(self) -> dict:
        """One round of the cell's own traffic, not timed."""
        self.warm_round += 1
        phase = f"warm-{self.warm_round}"
        # each pipe's POST alone, then all pipes at once: the window's
        # microbatches hold one POST or several coalesced
        for i in range(len(self.pipes)):
            self._warm_pipe(i, phase)
        threads = [threading.Thread(target=self._warm_pipe, args=(i, phase))
                   for i in range(len(self.pipes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mine = [r for r in self.sent if r["phase"] == phase]
        return {"warmed": self.warm_round, "posts": len(mine),
                "failed": sum(r["status"] != 200 for r in mine)}

    def _warm_pipe(self, i: int, phase: str) -> None:
        conn = Conn(self.port)
        p = self.pipes[i][self.next_post[i] % len(self.pipes[i])]
        self.next_post[i] += 1
        self.post(conn, p, phase, time.monotonic())

    # -- the window ------------------------------------------------------------

    def window(self) -> dict:
        before = self.metrics_text()
        t0 = time.monotonic() + 0.05
        self._closed_loop(t0)
        window = [r for r in self.sent if r["phase"] == "window"]
        last_ack = max(r["ack"] for r in window)
        self.window_record = {"t0": t0, "last_ack": last_ack,
                              "metrics_before": before,
                              "metrics_after": self.metrics_text()}
        return {"t0": t0, "last_ack": last_ack}

    def report(self) -> dict:
        live = self.live_links()
        return dict(self.window_record, posts=self.sent,
                    live=list(live.values()))

    def _closed_loop(self, t0: float) -> None:
        """Each pipe posts its next batch as soon as the last is acked,
        until the window closes; a POST started inside the window is
        waited for."""
        end = t0 + self.seconds

        def pipe(i: int) -> None:
            conn = Conn(self.port)
            due = t0
            while True:
                now = time.monotonic()
                if now < due:
                    time.sleep(due - now)
                if due >= end:
                    return
                p = self.pipes[i][self.next_post[i] % len(self.pipes[i])]
                self.next_post[i] += 1
                due = self.post(conn, p, "window", due)["ack"]

        threads = [threading.Thread(target=pipe, args=(i,))
                   for i in range(len(self.pipes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    client = Client(plan)
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "warm":
            client.port = cmd["port"]
            out = client.warm()
        elif cmd["cmd"] == "window":
            out = client.window()
        elif cmd["cmd"] == "report":
            with open(plan["report"], "w") as f:
                json.dump(client.report(), f)
            out = {"done": True}
        else:
            raise SystemExit(f"unknown command {cmd!r}")
        if "jax" in sys.modules:
            raise SystemExit("the client imported JAX")
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
