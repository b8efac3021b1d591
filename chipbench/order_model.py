"""An automaton of the serving engine's loop under a closed-loop traffic file,
for choosing the file's ``pool_seed`` (as PR 28 chose 32436 for
``closed16-mixed8k``; this is that model written down, with a chunk's time
taken from its key blocks).

The loop in iterations (``serving/engine.py`` ``_run``): one chunk of the one
prompt in prefill (the queue's head is admitted only when no other prompt is
in prefill), then one tick for every running stream. A stream whose last chunk
ran in iteration ``i`` has its first token at that chunk's end and token ``k``
at the end of iteration ``i + k - 1``; a request that ends in iteration ``F``
is answered by its client's next one, which can start in ``F + 2``. The window
opens when every client has completed its ramp requests and lasts
``window_s``. What the model is NOT given is the order in which the clients'
simultaneous first requests reach the engine (a race between threads of the
load generator): it is an argument, and a good ``pool_seed`` is one whose
window — the requests in it, hence every end-to-end number — is the same
under every such order and under a per cent or two of drift in the times.

    python -m chipbench.order_model chipbench/traffic/closed32-longdoc8k.json 1 1456

prints, for each ``pool_seed`` given, the numbers under a dozen arrival
orders. Times are a cell's own (``Times``): read them from a traced run.
It predicts counts and orders well and times to a few per cent (PERF.md
section 6, PR 33); it is a tool for picking an order, not a measurement.
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import math
import sys

import numpy as np

from chipbench.traffic import RequestPlan


@dataclasses.dataclass
class Times:
    """Milliseconds, from a traced run of the cell (defaults: PR 33, call 2)."""
    tick: float = 28.6           # one decode execution
    tick_host: float = 0.3       # device idle after a tick
    chunk_base: float = 14.2     # a prefill chunk without its attention
    chunk_block: float = 1.675   # + this for every key block its queries can see
    chunk_host: float = 1.7      # device idle after a chunk
    key_block: int = 512
    chunk: int = 256


def simulate(mix: dict, arrival: list, times: Times = Times(), window_s: float = 30.0) -> dict:
    """One run of ``mix`` with the clients' first requests reaching the engine
    in the order ``arrival``: the window's ``tok_s``, ``ttft_p95``,
    ``itl_p95``, first tokens ``ttft_n``, prompt tokens ``prefilled``, and
    when it opened (``open_s``)."""
    plan = RequestPlan(mix, 2, 0)
    n_pool = int(mix["pool"])
    lens = [plan.lengths(i) for i in range(n_pool)]
    clients, ramp = int(mix["clients"]), int(mix["ramp_requests_per_client"])
    queue, head, nxt = [(c, c) for c in arrival], 0, clients
    send_t = dict.fromkeys(range(clients), 0.0)
    completed, reqs, ends = [0] * clients, {}, []
    running, arrivals = [], []                   # heaps: (last iteration, index), (iteration, client)
    cur, t, it, open_t, close_t = None, 0.0, 0, None, None
    while close_t is None or t < close_t:
        while arrivals and arrivals[0][0] <= it:
            queue.append((heapq.heappop(arrivals)[1], nxt))
            nxt += 1
        if cur is None and head < len(queue):
            c, idx = queue[head]
            head += 1
            prompt, n = lens[idx % n_pool]
            reqs[idx] = {"client": c, "send_t": send_t[c], "n": n, "p": prompt}
            cur = [idx, math.ceil(prompt / times.chunk), 0]
        dur, first = 0.0, None
        if cur is not None:
            blocks = math.ceil((cur[2] + 1) * times.chunk / times.key_block)
            dur += times.chunk_base + times.chunk_block * blocks + times.chunk_host
            cur[2] += 1
            if cur[2] == cur[1]:
                first, cur = cur[0], None
        t_first = t + dur
        if running:
            dur += times.tick + times.tick_host
        t += dur or 1.0                          # an idle poll
        ends.append(t)
        while running and running[0][0] <= it:
            r = reqs[heapq.heappop(running)[1]]
            completed[r["client"]] += 1
            send_t[r["client"]] = t + 1.0
            heapq.heappush(arrivals, (it + 2, r["client"]))
            if open_t is None and min(completed) >= ramp:
                open_t, close_t = t, t + window_s * 1e3
        if first is not None:
            reqs[first].update(first_iter=it, t_first=t_first)
            heapq.heappush(running, (it + reqs[first]["n"] - 1, first))
        it += 1
    ends = np.asarray(ends)
    tokens, prefilled, ttft, itl = 0, 0, [], []
    for r in reqs.values():
        if "first_iter" not in r:
            continue
        i = r["first_iter"]
        at = np.concatenate([[r["t_first"]], ends[i + 1: i + r["n"]]])
        inside = (at >= open_t) & (at < close_t)
        tokens += int(inside.sum())
        if inside[0]:
            ttft.append(at[0] - r["send_t"])
            prefilled += r["p"]
        itl.extend(np.diff(at)[inside[1:]].tolist())
    return {"open_s": open_t / 1e3, "tok_s": tokens / window_s, "ttft_p95": float(np.percentile(ttft, 95)),
            "itl_p95": float(np.percentile(itl, 95)), "ttft_n": len(ttft), "prefilled": prefilled}


def windows(mix: dict, pool_seed: int, orders: int = 12, times: Times = Times(), seed: int = 0) -> list:
    """``simulate`` under ``orders`` arrival orders (the first in client order)."""
    rng = np.random.default_rng(seed)
    clients = int(mix["clients"])
    mix = dict(mix, pool_seed=pool_seed)
    return [simulate(mix, list(range(clients)) if k == 0 else rng.permutation(clients).tolist(), times)
            for k in range(orders)]


if __name__ == "__main__":
    traffic_file = json.loads(open(sys.argv[1]).read())
    for pool_seed in map(int, sys.argv[2:]):
        runs = windows(traffic_file, pool_seed)
        print(pool_seed, "distinct windows:", len({r["prefilled"] for r in runs}))
        for r in runs:
            print("   ", {k: round(v, 1) if isinstance(v, float) else v for k, v in r.items()})
