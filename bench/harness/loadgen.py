"""Load generator: a child process that never imports JAX, so its threads
do not compete with the serving process's host loop for the interpreter
lock, and the chip stays with the parent.

    python bench/harness/loadgen.py < plan.json > results.json

The plan, one JSON line, gives the server's port, when to start sending
(``start`` on the shared ``time.monotonic`` clock), the window's length
(``seconds``), the loop kind and the requests made by
``traffic.schedule``.  Every request is a streaming ``POST /v1/generate``;
the arrival time of each SSE token event is recorded.

Closed loop: each client sends its next request when the last ends.  With
``ramp`` set, the load runs before the window opens, and the window's
start is a second line ``{"t0": ...}`` that the parent writes once the
ramp is over (end of input before it closes the window at once); without
it the window opens at ``start``.  No request is sent after the window
closes.  Open loop: the window opens at ``start`` and each request is
sent when due; after the window closes no new request is sent, and those
in flight are read for ``grace`` more seconds.  A request still open at
its deadline is abandoned (its connection closed, so the server cancels
it).
"""

from __future__ import annotations

import json
import math
import socket
import sys
import threading
import time


def _request_bytes(prompt, gen) -> bytes:
    body = json.dumps({"prompt": prompt, "gen": gen, "stream": True}).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: localhost\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    return head + body


def _events(buf: bytes):
    """Complete SSE events in ``buf`` as (event, data dict), and the rest."""
    out = []
    while b"\n\n" in buf:
        raw, buf = buf.split(b"\n\n", 1)
        event, data = None, None
        for line in raw.decode().splitlines():
            if line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
        out.append((event, data))
    return out, buf


def run_one(port: int, req: dict, deadline) -> dict:
    """Send one request and read its stream until done or ``deadline()``
    (a deadline that may be set while the request is open)."""
    rec = {"id": req["id"], "gen": req["gen"], "plen": len(req["prompt"]),
           "due": req.get("due"), "sent": None, "first": None, "last": None,
           "tokens": [], "deltas": [], "status": "open", "done_tokens": None}
    try:
        sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    except OSError as e:
        rec["status"] = f"connect: {e}"
        return rec
    try:
        rec["sent"] = time.monotonic()
        sock.sendall(_request_bytes(req["prompt"], req["gen"]))
        buf, code = b"", None
        while rec["status"] == "open":
            left = deadline() - time.monotonic()
            if left <= 0:
                rec["status"] = "abandoned"
                break
            sock.settimeout(min(left, 0.5))
            try:
                chunk = sock.recv(1 << 16)
            except socket.timeout:
                continue
            t = time.monotonic()
            if not chunk:                 # server closed before "done"
                rec["status"] = (f"http {code}" if code not in (None, 200)
                                 else "closed")
                break
            buf += chunk
            if code is None:
                if b"\r\n\r\n" not in buf:
                    continue
                head, buf = buf.split(b"\r\n\r\n", 1)
                code = int(head.split(b" ", 2)[1])
                if code != 200:
                    continue              # read the error body to the end
            if code != 200:
                continue
            events, buf = _events(buf)
            for event, data in events:
                if event is None and data is not None:
                    toks = data["tokens"]
                    if toks:
                        rec["tokens"].extend(toks)
                        rec["deltas"].append([t, len(toks)])
                        rec["first"] = rec["first"] or t
                        rec["last"] = t
                elif event == "done":
                    rec["done_tokens"] = data["tokens"]
                    rec["status"] = "done"
                elif event == "error":
                    rec["status"] = f"error: {data}"
    finally:
        sock.close()
    return rec


class WindowEnd:
    """The window's end: ``start + seconds``, or, with a ramp, ``seconds``
    after the ``t0`` the parent sends on the next input line."""

    def __init__(self, plan: dict, lines):
        self.t = math.inf
        if not plan.get("ramp"):
            self.t = plan["start"] + plan["seconds"]
            return

        def read():
            line = lines.readline()
            self.t = (json.loads(line)["t0"] + plan["seconds"] if line.strip()
                      else time.monotonic())
        threading.Thread(target=read, daemon=True).start()

    def __call__(self) -> float:
        return self.t


def closed_loop(plan: dict, t_end: WindowEnd) -> list:
    by_client: dict = {}
    for r in plan["requests"]:
        by_client.setdefault(r["client"], []).append(r)
    out, lock = [], threading.Lock()

    def client(reqs):
        for r in reqs:
            if time.monotonic() >= t_end():
                break
            rec = run_one(plan["port"], r, t_end)
            with lock:
                out.append(rec)

    _sleep_until(plan["start"])
    threads = [threading.Thread(target=client, args=(reqs,), daemon=True)
               for reqs in by_client.values()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def open_loop(plan: dict) -> list:
    t0, t_end = plan["start"], plan["start"] + plan["seconds"]
    deadline = t_end + plan["grace"]
    out, lock, threads = [], threading.Lock(), []

    def one(r):
        rec = run_one(plan["port"], r, lambda: deadline)
        with lock:
            out.append(rec)

    for r in sorted(plan["requests"], key=lambda r: r["due"]):
        due = t0 + r["due"]
        if due >= t_end:
            break
        _sleep_until(due)
        th = threading.Thread(target=one, args=(r,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    return out


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05) if left > 0.002 else 0)


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    recs = (closed_loop(plan, WindowEnd(plan, sys.stdin))
            if plan["loop"] == "closed" else open_loop(plan))
    json.dump({"requests": sorted(recs, key=lambda r: r["id"])}, sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
