"""The closed-loop load generator (the real child process) against a stub
SSE server, and the reduction of its records to the window's numbers."""

import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from chipbench import harness, traffic
from chipbench.drivers import closed_loop

TOKEN_GAP_S = 0.004
MIX = {"clients": 3, "ramp_requests_per_client": 1, "pool": 8, "pool_seed": 1, "stream": True, "ignore_eos": True,
       "prompt_len": {"median": 10, "sigma": 0.5, "min": 2, "max": 30},
       "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


class Stub(BaseHTTPRequestHandler):
    """Streams ``max_new_tokens`` tokens, one every TOKEN_GAP_S; a prompt whose
    first id divides by 5 is cut one token short (a failed stream)."""

    protocol_version = "HTTP/1.0"

    def log_message(self, *args):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        n = body["max_new_tokens"] - (1 if body["prompt"][0] % 5 == 0 else 0)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        tokens = []
        for i in range(n):
            time.sleep(TOKEN_GAP_S)
            tokens.append(i + 1)
            self.wfile.write(f'data: {json.dumps({"token": i + 1})}\n\n'.encode())
            self.wfile.flush()
        done = {"done": True, "status": "completed", "tokens": tokens,
                "prompt_len": len(body["prompt"])}
        self.wfile.write(f"data: {json.dumps(done)}\n\n".encode())


@pytest.fixture()
def stub_url():
    server = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    server.server_close()
    thread.join(5)
    assert not thread.is_alive()


def drive(url, seconds, seed=5):
    env = dict(os.environ, PYTHONPATH=str(harness.ROOT))
    child = subprocess.Popen([sys.executable, "-m", "chipbench.drivers.closed_loop_client"],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    job = {"url": url, "traffic": MIX, "vocab_size": 50, "seed": seed, "seconds": seconds}
    out, _ = child.communicate(json.dumps(job) + "\n", timeout=60)
    assert child.returncode == 0
    return [json.loads(line) for line in out.splitlines()]


def test_window_edges_counts_and_lateness(stub_url):
    events = drive(stub_url, seconds=1.0)
    assert [e["event"] for e in events] == ["window_open", "window_close", "records"]
    result = events[-1]
    open_t, close_t, records = result["open_t"], result["close_t"], result["records"]
    assert close_t - open_t == pytest.approx(1.0)
    assert events[0]["t"] == open_t and events[1]["t"] == close_t

    # the window opened when the slowest client had finished its ramp request
    ramp_done = sorted(r["done_t"] for r in records)[: MIX["clients"]]
    assert max(ramp_done) <= open_t + 1e-3
    # every client stopped sending at the close; in-flight requests drained after it
    assert max(r["send_t"] for r in records) < close_t
    assert max(r["done_t"] for r in records) >= close_t - 0.1
    # a client sends its next request when the last one is done: a closed loop
    assert 0 <= result["lateness_s"]["mean"] <= result["lateness_s"]["max"] < 0.05

    metrics, counts = closed_loop.reduce_records(records, open_t, close_t)
    sent_inside = [r for r in records if open_t <= r["send_t"] < close_t]
    assert counts["requests_sent"] == len(sent_inside) > 10
    short = [r for r in sent_inside if r["error"]]
    assert counts["requests_failed"] == len(short) > 0
    assert all("tokens, asked" in r["error"] for r in short)
    inside = sum(open_t <= t < close_t for r in records for t in r["token_t"])
    assert counts["output_tokens"] == inside
    assert metrics["serve_tok_s"] == pytest.approx(inside / 1.0)
    # three streams, one token every 4 ms each: gaps of 4 ms and more, never a chunk median
    assert counts["itl_samples"] + counts["ttft_samples"] >= inside - MIX["clients"]
    assert 3.5 < counts["itl_p50_ms"] < 8.0 and metrics["itl_p95_ms"] >= counts["itl_p50_ms"]
    assert metrics["ttft_p95_ms"] >= counts["ttft_p50_ms"] >= 3.5


def test_reduce_records_by_hand():
    rec = lambda send, times, error=None, p=10: {                       # noqa: E731
        "send_t": send, "token_t": times, "tokens": [1] * len(times), "prompt_len": p,
        "error": error, "index": 0}
    records = [
        rec(9.0, [9.5, 10.1, 10.2]),            # sent before; two tokens and two gaps inside
        rec(10.0, [10.3, 10.4], error="x"),     # sent inside and failed; first token inside
        rec(10.9, [11.2, 11.3]),                # sent inside; every token after the close
    ]
    metrics, counts = closed_loop.reduce_records(records, 10.0, 11.0)
    assert counts["requests_sent"] == 2 and counts["requests_failed"] == 1
    assert counts["output_tokens"] == 4 and metrics["serve_tok_s"] == 4.0
    assert counts["ttft_samples"] == 1 and metrics["ttft_p95_ms"] == pytest.approx(300.0)
    assert counts["itl_samples"] == 3           # 600 ms, 100 ms (first stream), 100 ms (second)
    assert counts["prompt_tokens_prefilled"] == 10 and counts["decode_tokens"] == 3
    assert counts["_work"] == [(10, False, [11, 12]), (10, True, [11])]


def test_the_sample_holds_the_longest_finished_request_and_reads_every_client():
    records = [{"index": i, "client": i % 4, "prompt_len": 5 + i, "tokens": [1] * 4,
                "error": None} for i in range(20)]
    records[19]["error"] = "failed"             # the longest of all did not finish
    picked = closed_loop.pick_sample(records, 2, seed=3)
    assert picked[0]["index"] == 18 and len(picked) == 8
    assert len({r["index"] for r in picked}) == 8
    assert sorted(r["client"] for r in picked) == [0, 0, 1, 1, 2, 2, 3, 3]
    assert closed_loop.pick_sample(records, 2, seed=3) == picked
    assert closed_loop.pick_sample(records, 2, seed=4) != picked


def test_a_seed_changes_the_ids_and_never_the_lengths_or_their_order():
    mix = traffic.load(harness.PACKAGE / "traffic" / "closed8-chat.json")
    one, other = (traffic.RequestPlan(mix, 32000, seed) for seed in (7, 2 ** 31 + 7))
    lengths = [one.lengths(i) for i in range(2 * mix["pool"])]
    assert lengths == [other.lengths(i) for i in range(2 * mix["pool"])]
    assert lengths[: mix["pool"]] == lengths[mix["pool"]:]             # one cycle, repeated
    prompts = sorted(p for p, _ in lengths[: mix["pool"]])
    assert prompts == traffic.lognormal_quantiles(mix["prompt_len"], mix["pool"])
    assert max(p + o for p, o in lengths) <= 1024                      # fits the engine's max_len
    assert one.prompt(3) != other.prompt(3) and one.prompt(3) == one.prompt(3)
    assert len(one.prompt(3)) == lengths[3][0]
