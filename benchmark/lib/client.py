"""The load generator: a child process that imports the standard library
only, so that its threads never hold the interpreter lock of the process
that drives the chip, and never touch JAX.

``client.py <job.json> <out.json>``. The job is one JSON object ``{"host",
"port", "t0", "timeout_s", "requests": [{"due_s", "prompt",
"max_new_tokens"}, ...]}``. ``t0`` is an instant of
``time.monotonic()``, which parent and child share on one machine; every
request is sent at ``t0 + due_s`` whether or not earlier ones have come
back (open loop). The output is one JSON object ``{"records": [...]}``,
one record per request, times in seconds from ``t0``.

The streaming reader is copied from tools/serve_bench.py ``_drive_http``;
unlike it, nothing here is timed from when the thread started: the
record keeps the due instant and how late the request was sent.
"""
import http.client
import json
import sys
import threading
import time


def one(host, port, t0, timeout_s, req, rec):
    rec["lag_s"] = time.monotonic() - t0 - req["due_s"]
    conn = None
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        conn.request("POST", "/generate", json.dumps(
            {"prompt": req["prompt"], "stream": True,
             "max_new_tokens": req["max_new_tokens"]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["http"] = resp.status
        if resp.status != 200:
            resp.read()
            return
        tokens = rec["tokens"]
        while True:
            line = resp.readline()
            if not line:
                break
            msg = json.loads(line)
            if "token" in msg:
                now = time.monotonic() - t0
                if rec["t_first_s"] is None:
                    rec["t_first_s"] = now
                rec["t_last_s"] = now
                tokens.append(msg["token"])
            elif msg.get("done"):
                rec["status"] = msg.get("status")
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = repr(e)
    finally:
        if conn is not None:
            conn.close()
        rec["t_end_s"] = time.monotonic() - t0
        rec["n"] = len(rec["tokens"])
        rec["ok"] = (rec["status"] == "finished"
                     and rec["n"] == req["max_new_tokens"])


def main() -> int:
    with open(sys.argv[1]) as f:
        job = json.load(f)
    t0, reqs = job["t0"], job["requests"]
    recs = [{"i": i, "due_s": r["due_s"], "lag_s": None, "http": None,
             "status": None, "error": None, "ok": False, "n": 0,
             "t_first_s": None, "t_last_s": None, "t_end_s": None,
             "tokens": []} for i, r in enumerate(reqs)]
    threads = []
    for req, rec in zip(reqs, recs):
        wait = t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(
            target=one, daemon=True,
            args=(job["host"], job["port"], t0, job["timeout_s"], req, rec))
        th.start()
        threads.append(th)
    deadline = time.monotonic() + job["timeout_s"]
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    with open(sys.argv[2], "w") as f:
        json.dump({"records": recs}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
