"""The closed-loop load generator: a child process that never imports jax,
so its client threads do not share the engine's interpreter lock.

Reads one JSON line from standard input (``url``, ``traffic``, ``vocab_size``,
``seed``, ``seconds``), then writes JSON lines to standard output:
``{"event": "window_open", "t": ...}`` once every client has completed its
ramp requests, ``{"event": "window_close", "t": ...}`` when ``seconds`` have
passed, and ``{"event": "records", ...}`` once every in-flight request has
drained. Times are ``time.monotonic()``, which on Linux is one clock for all
processes of the machine.

Each of ``clients`` threads sends its next request when the last one's
``done`` event has arrived: ``POST /v1/completions`` with SSE, one event per
token. A record keeps the send time, each token's arrival time and the
tokens, so that every rate and tail is worked out afterwards over all of them.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse

from chipbench import traffic

REQUEST_TIMEOUT_S = 120.0


def one_request(url, index: int, prompt: list, max_new_tokens: int, mix: dict) -> dict:
    """One real request; never raises: a failure is a record with ``error``."""
    rec = {"index": index, "prompt_len": len(prompt), "asked": max_new_tokens,
           "token_t": [], "tokens": [], "error": None}
    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens,
                       "stream": bool(mix.get("stream", True)),
                       "ignore_eos": bool(mix.get("ignore_eos", True))})
    conn = http.client.HTTPConnection(url.hostname, url.port, timeout=REQUEST_TIMEOUT_S)
    rec["send_t"] = time.monotonic()
    try:
        conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
        else:
            done = None
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                event = json.loads(line[len("data:"):])
                if event.get("done"):
                    done = event
                    break
                rec["token_t"].append(time.monotonic())
                rec["tokens"].append(int(event["token"]))
            if done is None:
                rec["error"] = "the stream ended without its done event"
            elif done.get("status") != "completed":
                rec["error"] = f"status {done.get('status')!r}"
            elif list(done.get("tokens", [])) != rec["tokens"]:
                rec["error"] = "streamed tokens differ from the done event's"
            elif len(rec["tokens"]) != max_new_tokens:
                rec["error"] = f"{len(rec['tokens'])} tokens, asked {max_new_tokens}"
    except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    rec["done_t"] = time.monotonic()
    return rec


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    job = json.loads(sys.stdin.readline())
    mix, url = job["traffic"], urlparse(job["url"])
    plan = traffic.RequestPlan(mix, job["vocab_size"], job["seed"])
    clients, ramp = int(mix["clients"]), int(mix["ramp_requests_per_client"])
    lock = threading.Lock()
    state = {"next": 0, "completed": [0] * clients, "open_t": None, "close_t": None}
    records, lateness = [], []

    def client(c: int) -> None:
        last_done = None
        while True:
            with lock:
                if state["close_t"] is not None and time.monotonic() >= state["close_t"]:
                    return
                index = state["next"]
                state["next"] += 1
            prompt = plan.prompt(index)
            rec = one_request(url, index, prompt, plan.lengths(index)[1], mix)
            rec["client"] = c
            if last_done is not None:
                lateness.append(rec["send_t"] - last_done)   # done -> next send
            last_done = rec["done_t"]
            with lock:
                records.append(rec)
                state["completed"][c] += 1
                if state["open_t"] is None and min(state["completed"]) >= ramp:
                    state["open_t"] = time.monotonic()
                    state["close_t"] = state["open_t"] + float(job["seconds"])
                    emit({"event": "window_open", "t": state["open_t"]})

    threads = [threading.Thread(target=client, args=(c,), name=f"client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    while state["open_t"] is None and any(t.is_alive() for t in threads):
        time.sleep(0.005)
    if state["open_t"] is None:
        emit({"event": "error", "what": "every client ended before the window opened",
              "records": records})
        return 1
    close_t = state["close_t"]
    time.sleep(max(0.0, close_t - time.monotonic()))
    emit({"event": "window_close", "t": close_t})
    for t in threads:                      # in-flight requests drain outside the window
        t.join(REQUEST_TIMEOUT_S + 5.0)
    emit({"event": "records", "open_t": state["open_t"], "close_t": close_t,
          "records": records,
          "lateness_s": {"max": max(lateness, default=0.0),
                         "mean": sum(lateness) / max(1, len(lateness))}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
