"""Serving-engine counters, in the style of ``utils.profiling.PipelineStats``.

The engine is a host loop driving two compiled programs; the numbers that
matter operationally are therefore host-side:

* per-request **queue wait** (submit -> slot assignment) and **TTFT**
  (submit -> first streamed token, i.e. queue wait + one prefill) — the
  latency a caller actually feels;
* engine-level **decode tokens/sec** (committed tokens over decode-tick
  wall time) — the throughput the fixed-shape batch sustains;
* **slot occupancy** (active slots per tick / ``max_slots``) and **batch
  efficiency** (committed tokens per tick / ``max_slots``) — how much of
  each fixed-shape decode step is doing real work. Low occupancy under
  load means admission is starved (queue too small, prefill too slow);
  occupancy >> efficiency means slots sit done-latched waiting on
  retirement;
* the **chunked-prefill split**: prefill chunk count and milliseconds vs
  decode milliseconds (where the engine's device time actually goes),
  prefill backlog depth (requests sitting in ``PREFILLING``), and the
  prefix-cache hit rate / restored bytes — how much admission work the
  chunk-aligned :class:`scheduler.PrefixCache` is deleting.

Thread-safe: submit() is called from caller threads, everything else from
the engine thread.
"""

from __future__ import annotations

import threading

import numpy as np
from bisect import bisect_left
from typing import Optional

#: Shared latency bucket bounds (milliseconds) for the exported
#: histograms — wide enough to cover sub-ms CPU ticks and multi-second
#: TPU prefills with one fixed layout, so fleet merges are a plain
#: element-wise add.
LATENCY_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0)

#: The per-request phase latencies exported as Prometheus histograms.
HISTOGRAM_NAMES = ("ttft_ms", "itl_ms", "queue_wait_ms", "prefill_chunk_ms")

#: The serving host path's phases, timed where the work happens (the
#: engine thread's loop, and ``emit`` on the emitter thread). Each is a
#: tracer span of the same name and an always-on accumulator; a chunk's
#: phases are reported per prefill chunk, the others per decode tick.
CHUNK_PHASES = ("prefill_launch", "prefill_wait", "prefill_commit")
HOST_PHASES = ("sweep", "admit") + CHUNK_PHASES + (
    "tick_launch", "tick_wait", "tick_commit", "idle", "emit")
#: Intervals accumulated the same way but averaged over their own count.
HOST_INTERVALS = ("chunk_to_dispatch", "emit_lag")


class LatencyHistogram:
    """Fixed-bucket latency histogram (Prometheus-shaped).

    Buckets are stored NON-cumulative internally (one ``observe`` is a
    single bisect + increment); :meth:`cumulative` renders the
    Prometheus view (running totals ending at the implicit ``+Inf``
    bucket). Fixed shared bounds make :meth:`merge` an element-wise add,
    which keeps fleet aggregation monotone under repeated merges. NOT
    self-locking — the owner (:class:`ServingStats`) already serializes
    access under its lock."""

    __slots__ = ("bounds", "counts", "count", "sum")

    def __init__(self, bounds: tuple = LATENCY_BUCKETS_MS):
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(set(self.bounds)):
            raise ValueError(f"bucket bounds must be strictly increasing: {bounds}")
        self.counts = [0] * (len(self.bounds) + 1)  # last = +Inf overflow
        self.count = 0
        self.sum = 0.0

    def observe(self, value_ms: float) -> None:
        self.counts[bisect_left(self.bounds, value_ms)] += 1
        self.count += 1
        self.sum += value_ms

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        if other.bounds != self.bounds:
            raise ValueError(
                f"cannot merge histograms with different bounds: "
                f"{self.bounds} vs {other.bounds}")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum += other.sum
        return self

    def copy(self) -> "LatencyHistogram":
        out = LatencyHistogram(self.bounds)
        out.counts = list(self.counts)
        out.count = self.count
        out.sum = self.sum
        return out

    def cumulative(self) -> list:
        """``[(le, cumulative_count)]`` ending at ``("+Inf", count)``."""
        out, running = [], 0
        for bound, c in zip(self.bounds, self.counts):
            running += c
            out.append((bound, running))
        out.append(("+Inf", self.count))
        return out

    def snapshot(self) -> dict:
        return {"bounds": self.bounds, "cumulative": self.cumulative(),
                "sum": round(self.sum, 3), "count": self.count}


class ServingStats:
    """Aggregated serving counters; ``summary()`` is a flat scalar dict
    suitable for ``Accelerator.log`` / tracking payloads."""

    #: TTFT samples kept for percentile reporting (bounded so a long-running
    #: engine cannot grow host memory; newest samples win).
    MAX_TTFT_SAMPLES = 4096

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        """Zero every counter (e.g. between measurement windows)."""
        with self._lock:
            self._submitted = 0
            self._admitted = 0
            self._completed = 0
            self._failed = 0
            self._cancelled = 0
            self._timed_out = 0
            self._rejected = 0
            self._queue_wait_ms_sum = 0.0
            self._queue_wait_ms_max = 0.0
            self._ttft_ms_sum = 0.0
            self._ttft_ms_max = 0.0
            self._ttft_samples: list[float] = []
            self._ticks = 0
            self._tick_s_sum = 0.0
            self._active_slot_sum = 0
            self._slot_capacity_sum = 0
            self._decode_tokens = 0
            self._prefill_tokens = 0
            self._queue_depth_last = 0
            self._prefill_chunks = 0
            self._prefill_ms_sum = 0.0
            self._prefill_backlog_last = 0
            self._prefill_backlog_max = 0
            self._prefix_lookup_chunks = 0
            self._prefix_hit_chunks = 0
            self._prefix_alias_chunks = 0
            self._prefix_restored_bytes = 0
            self._prefix_cache_bytes = 0
            self._prefix_cache_entries = 0
            # Paged KV pool (gauges sampled each tick + preemption count).
            self._pages_free = 0
            self._pages_used = 0
            self._pages_total = 0
            self._pages_freed = 0
            self._preemptions = 0
            # Async host runtime: host scheduling/commit wall per tick
            # (microseconds) and emitter backpressure events.
            self._host_us_sum = 0.0
            self._host_us_max = 0.0
            self._host_us_ticks = 0
            self._host_other_us_sum = 0.0
            self._emission_stalls = 0
            # Host phases and intervals: name -> [sum_s, max_s, count].
            self._host = {name: [0.0, 0.0, 0]
                          for name in HOST_PHASES + HOST_INTERVALS}
            # Expert layers (ops.moe.moe_held_apply's ``picks``; the programs
            # return them with the tokens): picks landed on each held
            # expert, then three totals: all picks, rows routed through the
            # sorted tiles, rows computed there. KV rows held / dead behind
            # a layer's window.
            self._moe_picks = np.zeros((0,), np.int64)
            self._kv_rows_held = 0
            self._kv_rows_dead = 0
            # Key rows a prefill chunk's attention scored / rows some query
            # of the chunk could see / rows of the view, summed over layers.
            self._attn_rows_scored = 0
            self._attn_rows_visible = 0
            self._attn_rows_view = 0
            # The same three of the decode ticks' attention (every lane of
            # the tick's program scores; only running streams make rows
            # visible), and the bytes a cached token takes over all layers
            # (gauge; from the cache the model declares).
            self._tick_rows_scored = 0
            self._tick_rows_visible = 0
            self._tick_rows_view = 0
            self._kv_bytes_per_token = 0
            # What the row counters are sums over (gauges): the cache
            # entries that hold KV rows and the attentions that read them
            # (a layer may read another layer's entry, or none); then the
            # recurrent state a cache holds per slot beside its pages, in
            # bytes over all slots, and how often a prompt's first chunk
            # reset a slot's.
            self._kv_cache_layers = 0
            self._kv_reader_layers = 0
            self._recurrent_state_bytes = 0
            self._recurrent_state_resets = 0
            # Weight leaves the engine holds in another form than the
            # published one (the family's served form), and their bytes
            # (gauges, set at load: 0 for a family served as published).
            self._weights_served_form_leaves = 0
            self._weights_served_form_bytes = 0
            # Attentions of one plain tick that read the page pool through
            # the Mosaic paged-attention kernel (gauge, set at build: 0 where
            # every reader runs the XLA work list).
            self._tick_attn_kernel_readers = 0
            # Speculative decoding: draft proposals vs target acceptances.
            self._spec_ticks = 0
            self._spec_proposed = 0
            self._spec_accepted = 0
            # Prompt-lookup drafting: slots whose n-gram matcher hit.
            self._spec_lookup_slots = 0
            self._spec_lookup_hits = 0
            # Per-adapter (multi-tenant LoRA) counters:
            # name -> {requests, tokens, hits, misses, loads, evictions}.
            self._adapter: dict = {}
            # Per-priority traffic classes (measurement only — scheduling
            # never consults these): name -> {requests, tokens}.
            self._priority: dict = {}
            # Quantized serving: running max of the sampled per-tick
            # |Δlogprob| vs a full-precision reference (bench/tests feed
            # it; 0.0 = never sampled or bit-exact engine).
            self._logprob_drift = 0.0
            # Prometheus-shaped phase-latency histograms (fixed shared
            # buckets; itl_ms observes each decode tick's wall time).
            self._hists = {name: LatencyHistogram()
                           for name in HISTOGRAM_NAMES}

    # -- caller side ----------------------------------------------------
    def record_submit(self, queue_depth: int):
        with self._lock:
            self._submitted += 1
            self._queue_depth_last = int(queue_depth)

    def record_reject(self):
        """A submit bounced off the full admission queue (backpressure)."""
        with self._lock:
            self._rejected += 1

    # -- engine side ----------------------------------------------------
    def record_admit(self, queue_wait_ms: float, ttft_ms: float):
        """One request placed into a slot; TTFT is measured here because the
        first token is emitted by the prefill itself."""
        with self._lock:
            self._admitted += 1
            self._queue_wait_ms_sum += queue_wait_ms
            self._queue_wait_ms_max = max(self._queue_wait_ms_max, queue_wait_ms)
            self._ttft_ms_sum += ttft_ms
            self._ttft_ms_max = max(self._ttft_ms_max, ttft_ms)
            self._ttft_samples.append(ttft_ms)
            if len(self._ttft_samples) > self.MAX_TTFT_SAMPLES:
                del self._ttft_samples[: len(self._ttft_samples) // 2]
            self._prefill_tokens += 1
            self._hists["queue_wait_ms"].observe(queue_wait_ms)
            self._hists["ttft_ms"].observe(ttft_ms)

    def _fold_moe(self, picks) -> None:
        # call with self._lock held; ``picks``: per held expert, then all
        # picks, rows routed and rows computed in the sorted tiles
        if picks is None or not len(picks):
            return
        picks = np.asarray(picks, np.int64)
        if len(self._moe_picks) != len(picks):
            self._moe_picks = np.zeros_like(picks)
        self._moe_picks += picks

    def _fold_host(self, timings: Optional[dict]) -> None:
        # call with self._lock held
        for name, (sum_s, max_s, count) in (timings or {}).items():
            entry = self._host[name]
            entry[0] += sum_s
            entry[1] = max(entry[1], max_s)
            entry[2] += count

    def record_host(self, timings: dict):
        """Fold ``name -> (sum_s, max_s, count)`` host-phase timings in
        on their own: the emitter thread's per-batch ``emit`` /
        ``emit_lag``, and what the engine thread still holds when it goes
        idle. On the hot path the same dict rides ``record_tick`` /
        ``record_prefill_chunk`` (``host=``), which already take the
        lock."""
        with self._lock:
            self._fold_host(timings)

    def record_tick(self, active_slots: int, committed_tokens: int,
                    max_slots: int, seconds: float,
                    host_us: Optional[float] = None,
                    other_us: float = 0.0, host: Optional[dict] = None,
                    moe_picks=None, kv_rows: Optional[tuple] = None,
                    attn_rows: Optional[tuple] = None):
        """One ``decode_step_all_slots`` execution.

        ``seconds`` is the device-complete→device-complete interval for
        this tick — what ``itl_ms`` observes. (Pre-async it was per-tick
        wall time; under one-tick-ahead dispatch the two differ, and the
        interval is the one a consumer actually experiences between
        tokens.) ``host_us`` is the tick's host scheduling + commit wall
        in microseconds — the part of the interval NOT spent waiting on
        the device, i.e. the host overhead the async runtime hides;
        ``other_us`` is the part of it no named phase covered, and
        ``host`` the phase timings measured since the last record.
        ``moe_picks`` are the tick's expert picks (per held expert, then
        ``moe_held_apply``'s three totals), ``kv_rows`` the ``(dead,
        held)`` KV rows of the streams that run on: held = rows written x
        layers, dead = those a windowed layer can never read again;
        ``attn_rows`` the ``(scored, visible, view)`` pool rows of the
        tick's attention, summed over its lanes and layers: scored are the
        running lanes' key blocks, view what all slots reserve."""
        with self._lock:
            self._fold_host(host)
            self._fold_moe(moe_picks)
            if attn_rows is not None:
                self._tick_rows_scored += int(attn_rows[0])
                self._tick_rows_visible += int(attn_rows[1])
                self._tick_rows_view += int(attn_rows[2])
            if kv_rows is not None:
                self._kv_rows_dead += int(kv_rows[0])
                self._kv_rows_held += int(kv_rows[1])
            self._host_other_us_sum += float(other_us)
            self._ticks += 1
            self._tick_s_sum += seconds
            self._active_slot_sum += int(active_slots)
            self._slot_capacity_sum += int(max_slots)
            self._decode_tokens += int(committed_tokens)
            self._hists["itl_ms"].observe(seconds * 1e3)
            if host_us is not None:
                self._host_us_sum += float(host_us)
                self._host_us_max = max(self._host_us_max, float(host_us))
                self._host_us_ticks += 1

    def record_emission_stall(self):
        """A stream was skipped for one tick because its bounded emission
        queue was full (slow ``on_token`` consumer) — flow control held
        the stream back rather than stalling the tick loop."""
        with self._lock:
            self._emission_stalls += 1

    def record_prefill_chunk(self, ms: float, backlog: int = 0,
                             host: Optional[dict] = None, moe_picks=None,
                             attn_rows: Optional[tuple] = None,
                             state_reset: bool = False):
        """One ``prefill_chunk`` execution; ``backlog`` is the number of
        requests in ``PREFILLING`` at the time of the call (how much
        admission work is still pending behind the per-tick budget);
        ``host`` the phase timings measured since the last record,
        ``moe_picks`` the chunk's expert picks (as in ``record_tick``),
        ``attn_rows`` the ``(scored, visible, view)`` key rows of its
        attention, summed over the layers; ``state_reset``: the chunk set
        its slot's recurrent state to zero (a prompt's first chunk, where
        the cache has such state)."""
        with self._lock:
            self._fold_host(host)
            self._fold_moe(moe_picks)
            self._recurrent_state_resets += bool(state_reset)
            if attn_rows is not None:
                self._attn_rows_scored += int(attn_rows[0])
                self._attn_rows_visible += int(attn_rows[1])
                self._attn_rows_view += int(attn_rows[2])
            self._prefill_chunks += 1
            self._prefill_ms_sum += ms
            self._hists["prefill_chunk_ms"].observe(ms)
            self._prefill_backlog_last = int(backlog)
            self._prefill_backlog_max = max(self._prefill_backlog_max,
                                            int(backlog))

    def record_prefix(self, looked_up: int, hit: int, bytes_restored: int,
                      aliased: int = 0):
        """One admission's prefix-cache lookup: ``looked_up`` restorable
        chunks were probed, the first ``hit`` of them were restored
        instead of recomputed: ``aliased`` of those hits by page-table
        aliasing (a host page-id write, zero device copies), the rest by
        ``restore_prefix`` copies from an external cache."""
        with self._lock:
            self._prefix_lookup_chunks += int(looked_up)
            self._prefix_hit_chunks += int(hit)
            self._prefix_alias_chunks += int(aliased)
            self._prefix_restored_bytes += int(bytes_restored)

    def record_pages(self, free: int, used: int, total: int,
                     freed_total: int = 0, kv_bytes_per_token: int = 0,
                     kv_cache_layers: int = 0, kv_reader_layers: int = 0,
                     recurrent_state_bytes: int = 0,
                     weights_served_form_leaves: int = 0,
                     weights_served_form_bytes: int = 0,
                     tick_attn_kernel_readers: int = 0):
        """Gauge: paged-KV pool occupancy after a tick (page counts).
        ``freed_total`` mirrors the pool's cumulative free count — the
        page-drain observable behind the gateway's pressure Retry-After;
        ``kv_bytes_per_token`` is a page's bytes over its rows, over
        ``kv_cache_layers`` cache entries that ``kv_reader_layers``
        attentions read; ``recurrent_state_bytes`` what the cache holds per
        slot beside the pages, all slots; ``weights_served_form_leaves`` /
        ``_bytes`` the weights held in their family's served form;
        ``tick_attn_kernel_readers`` the attentions of a plain tick that run
        the paged-attention kernel."""
        with self._lock:
            self._kv_bytes_per_token = int(kv_bytes_per_token)
            self._kv_cache_layers = int(kv_cache_layers)
            self._kv_reader_layers = int(kv_reader_layers)
            self._recurrent_state_bytes = int(recurrent_state_bytes)
            self._weights_served_form_leaves = int(weights_served_form_leaves)
            self._weights_served_form_bytes = int(weights_served_form_bytes)
            self._tick_attn_kernel_readers = int(tick_attn_kernel_readers)
            self._pages_free = int(free)
            self._pages_used = int(used)
            self._pages_total = int(total)
            self._pages_freed = int(freed_total)

    def record_preemption(self):
        """A running request was evicted at a chunk/tick boundary because
        the page pool was exhausted; it re-queues and resumes token-exact
        as a longer prompt."""
        with self._lock:
            self._preemptions += 1

    def record_spec(self, proposed: int, accepted: int,
                    lookup_hits: Optional[int] = None,
                    lookup_slots: int = 0):
        """One speculative tick: the draft proposed ``proposed`` tokens
        across active slots, the target verify accepted ``accepted``
        (committed tokens beyond the one-per-tick baseline count here too:
        accepted / ticks is tokens-per-tick, the headline spec metric).
        Prompt-lookup engines also report how many of the tick's
        ``lookup_slots`` found an n-gram match (``lookup_hits``) — the
        hit rate says whether the traffic shape suits draft-free
        speculation at all."""
        with self._lock:
            self._spec_ticks += 1
            self._spec_proposed += int(proposed)
            self._spec_accepted += int(accepted)
            if lookup_hits is not None:
                self._spec_lookup_slots += int(lookup_slots)
                self._spec_lookup_hits += int(lookup_hits)

    def record_prefix_cache_size(self, nbytes: int, entries: int):
        """Gauge: the prefix cache's current footprint after an insert or
        eviction sweep."""
        with self._lock:
            self._prefix_cache_bytes = int(nbytes)
            self._prefix_cache_entries = int(entries)

    def _adapter_entry(self, name: str) -> dict:
        # call with self._lock held
        entry = self._adapter.get(name)
        if entry is None:
            entry = {"requests": 0, "tokens": 0, "hits": 0, "misses": 0,
                     "loads": 0, "evictions": 0}
            self._adapter[name] = entry
        return entry

    def record_adapter_admit(self, name: str, hit: bool, evicted=None):
        """One adapter request admitted: a residency ``hit`` found the
        adapter already in its bank row; a miss loaded it (possibly
        evicting another tenant, billed to the EVICTED adapter)."""
        with self._lock:
            entry = self._adapter_entry(name)
            entry["requests"] += 1
            if hit:
                entry["hits"] += 1
            else:
                entry["misses"] += 1
                entry["loads"] += 1
            if evicted is not None:
                self._adapter_entry(evicted)["evictions"] += 1

    def record_adapter_tokens(self, name: str, tokens: int):
        """Tokens emitted by one retiring adapter request."""
        with self._lock:
            self._adapter_entry(name)["tokens"] += int(tokens)

    def per_adapter(self) -> dict:
        """``name -> {requests, tokens, hits, misses, loads, evictions}``
        snapshot — the gateway's labeled Prometheus series."""
        with self._lock:
            return {name: dict(entry) for name, entry in self._adapter.items()}

    def _priority_entry(self, name: str) -> dict:
        # call with self._lock held
        entry = self._priority.get(name)
        if entry is None:
            entry = {"requests": 0, "tokens": 0}
            self._priority[name] = entry
        return entry

    def record_priority_request(self, name: str):
        """One request submitted under a client-declared traffic class."""
        with self._lock:
            self._priority_entry(name)["requests"] += 1

    def record_priority_tokens(self, name: str, tokens: int):
        """Tokens emitted by one retiring prioritized request."""
        with self._lock:
            self._priority_entry(name)["tokens"] += int(tokens)

    def per_priority(self) -> dict:
        """``name -> {requests, tokens}`` snapshot — the gateway's labeled
        per-priority Prometheus series (measurement only)."""
        with self._lock:
            return {name: dict(entry)
                    for name, entry in self._priority.items()}

    def record_logprob_drift(self, value: float):
        """Observe one sampled per-tick max |Δlogprob| vs the fp reference
        (quantized engines; the gauge keeps the running max)."""
        with self._lock:
            self._logprob_drift = max(self._logprob_drift, float(value))

    def record_finish(self, status):
        """One request retired; ``status`` is a RequestStatus."""
        from .request import RequestStatus

        with self._lock:
            if status == RequestStatus.COMPLETED:
                self._completed += 1
            elif status == RequestStatus.FAILED:
                self._failed += 1
            elif status == RequestStatus.TIMED_OUT:
                self._timed_out += 1
            else:
                self._cancelled += 1

    # -- aggregation ----------------------------------------------------
    def merge(self, other: "ServingStats") -> "ServingStats":
        """Fold another engine's counters into this one — the fleet
        aggregation the :class:`~accelerate_tpu.serving.router.ReplicaSet`
        publishes (one merged view over N replicas). Sums add, maxima max,
        TTFT samples concatenate (bounded), and point-in-time gauges
        (queue depth, prefill backlog, prefix-cache footprint) ADD — the
        fleet's total queue depth and total cache bytes are the
        operational numbers, not any one replica's. Returns ``self`` so
        merges chain: ``ServingStats().merge(a).merge(b)``."""
        with other._lock:
            o = dict(other.__dict__)
            o_samples = list(other._ttft_samples)
            o_adapter = {name: dict(e) for name, e in other._adapter.items()}
            o_priority = {name: dict(e)
                          for name, e in other._priority.items()}
            o_hists = {name: h.copy() for name, h in other._hists.items()}
            o_host = {name: tuple(e) for name, e in other._host.items()}
            o["_moe_picks"] = other._moe_picks.copy()
        with self._lock:
            self._fold_host(o_host)
            self._fold_moe(o["_moe_picks"])
            for name, hist in o_hists.items():
                mine = self._hists.get(name)
                if mine is None:
                    self._hists[name] = hist
                else:
                    mine.merge(hist)
            for name, entry in o_adapter.items():
                mine = self._adapter_entry(name)
                for k, v in entry.items():
                    mine[k] += v
            for name, entry in o_priority.items():
                mine = self._priority_entry(name)
                for k, v in entry.items():
                    mine[k] += v
            for k in ("_submitted", "_admitted", "_completed", "_failed",
                      "_cancelled", "_timed_out", "_rejected",
                      "_queue_wait_ms_sum", "_ttft_ms_sum", "_ticks",
                      "_tick_s_sum", "_active_slot_sum", "_slot_capacity_sum",
                      "_decode_tokens", "_prefill_tokens", "_prefill_chunks",
                      "_prefill_ms_sum", "_prefix_lookup_chunks",
                      "_prefix_hit_chunks", "_prefix_alias_chunks",
                      "_prefix_restored_bytes",
                      "_queue_depth_last", "_prefill_backlog_last",
                      "_prefix_cache_bytes", "_prefix_cache_entries",
                      "_pages_free", "_pages_used", "_pages_total",
                      "_pages_freed",
                      "_preemptions", "_spec_ticks", "_spec_proposed",
                      "_spec_accepted", "_spec_lookup_slots",
                      "_spec_lookup_hits", "_host_us_sum",
                      "_host_us_ticks", "_host_other_us_sum",
                      "_emission_stalls", "_kv_rows_held", "_kv_rows_dead",
                      "_attn_rows_scored", "_attn_rows_visible",
                      "_attn_rows_view", "_tick_rows_scored",
                      "_tick_rows_visible", "_tick_rows_view",
                      "_recurrent_state_resets", "_recurrent_state_bytes",
                      "_weights_served_form_leaves",
                      "_weights_served_form_bytes"):
                setattr(self, k, getattr(self, k) + o[k])
            for k in ("_queue_wait_ms_max", "_ttft_ms_max",
                      "_prefill_backlog_max", "_host_us_max",
                      "_logprob_drift", "_kv_bytes_per_token",
                      "_kv_cache_layers", "_kv_reader_layers",
                      "_tick_attn_kernel_readers"):
                setattr(self, k, max(getattr(self, k), o[k]))
            self._ttft_samples.extend(o_samples)
            if len(self._ttft_samples) > self.MAX_TTFT_SAMPLES:
                del self._ttft_samples[: len(self._ttft_samples)
                                       - self.MAX_TTFT_SAMPLES]
        return self

    # -- reporting ------------------------------------------------------
    def histograms(self) -> dict:
        """``name -> {bounds, cumulative, sum, count}`` snapshot of the
        phase-latency histograms — the gateway renders these as
        Prometheus histogram families next to the scalar gauges."""
        with self._lock:
            return {name: h.snapshot() for name, h in self._hists.items()}

    @staticmethod
    def _percentile(samples: list[float], q: float) -> float:
        if not samples:
            return 0.0
        s = sorted(samples)
        idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
        return s[idx]

    def summary(self) -> dict:
        """Scalar snapshot: request counts, queue-wait/TTFT latencies,
        decode tokens/sec, slot occupancy, batch efficiency, and the
        chunked-prefill split (chunk count/ms, backlog, prefill-vs-decode
        ms, prefix-cache hit rate/bytes)."""
        with self._lock:
            admits = max(1, self._admitted)
            caps = max(1, self._slot_capacity_sum)
            samples = list(self._ttft_samples)
            # picks per held expert, then all picks and the sorted tiles'
            # rows routed / rows computed (empty before the first record)
            moe_held = self._moe_picks[:-3]
            moe_all, moe_routed, moe_rows = (self._moe_picks[-3:].tolist()
                                             or (0, 0, 0))
            out = {
                "requests_submitted": self._submitted,
                "requests_admitted": self._admitted,
                "requests_completed": self._completed,
                "requests_failed": self._failed,
                "requests_cancelled": self._cancelled,
                "requests_timed_out": self._timed_out,
                "requests_rejected": self._rejected,
                "queue_wait_ms": round(self._queue_wait_ms_sum / admits, 3),
                "queue_wait_ms_max": round(self._queue_wait_ms_max, 3),
                "ttft_ms": round(self._ttft_ms_sum / admits, 3),
                "ttft_ms_p50": round(self._percentile(samples, 0.50), 3),
                "ttft_ms_p95": round(self._percentile(samples, 0.95), 3),
                "ttft_ms_max": round(self._ttft_ms_max, 3),
                "decode_ticks": self._ticks,
                "decode_tokens": self._decode_tokens,
                "tokens_emitted": self._decode_tokens + self._prefill_tokens,
                "decode_tokens_per_sec": round(
                    self._decode_tokens / self._tick_s_sum, 3)
                    if self._tick_s_sum else 0.0,
                "slot_occupancy": round(self._active_slot_sum / caps, 4),
                "batch_efficiency": round(self._decode_tokens / caps, 4),
                "queue_depth": self._queue_depth_last,
                "prefill_chunks": self._prefill_chunks,
                "prefill_ms": round(self._prefill_ms_sum, 3),
                "prefill_ms_per_chunk": round(
                    self._prefill_ms_sum / max(1, self._prefill_chunks), 3),
                "prefill_chunks_per_tick": round(
                    self._prefill_chunks / max(1, self._ticks), 4),
                "prefill_backlog": self._prefill_backlog_last,
                "prefill_backlog_max": self._prefill_backlog_max,
                "decode_ms": round(self._tick_s_sum * 1e3, 3),
                "prefix_cache_hit_rate": round(
                    self._prefix_hit_chunks / self._prefix_lookup_chunks, 4)
                    if self._prefix_lookup_chunks else 0.0,
                "prefix_cache_hit_chunks": self._prefix_hit_chunks,
                "prefix_alias_chunks": self._prefix_alias_chunks,
                "prefix_cache_restored_bytes": self._prefix_restored_bytes,
                "prefix_cache_bytes": self._prefix_cache_bytes,
                "prefix_cache_entries": self._prefix_cache_entries,
                # KV page-pool pressure.
                "pages_total": self._pages_total,
                "pages_free": self._pages_free,
                "pages_used": self._pages_used,
                "page_utilization": round(
                    self._pages_used / self._pages_total, 4)
                    if self._pages_total else 0.0,
                "pages_freed": self._pages_freed,
                "preemptions": self._preemptions,
                # Speculative decoding (all zero on a non-spec engine).
                "spec_ticks": self._spec_ticks,
                "spec_proposed_tokens": self._spec_proposed,
                "spec_accepted_tokens": self._spec_accepted,
                "spec_accept_rate": round(
                    self._spec_accepted / self._spec_proposed, 4)
                    if self._spec_proposed else 0.0,
                "spec_tokens_per_tick": round(
                    (self._spec_accepted + self._spec_ticks)
                    / self._spec_ticks, 4)
                    if self._spec_ticks else 0.0,
                "spec_lookup_hit_rate": round(
                    self._spec_lookup_hits / self._spec_lookup_slots, 4)
                    if self._spec_lookup_slots else 0.0,
                # Async host runtime (zero when the engine never reported
                # host timings, e.g. before its first reconcile).
                "host_us_per_tick": round(
                    self._host_us_sum / self._host_us_ticks, 3)
                    if self._host_us_ticks else 0.0,
                "host_us_per_tick_max": round(self._host_us_max, 3),
                "emission_stalls": self._emission_stalls,
                # Quantized serving: sampled bounded-divergence gauge
                # (running max |Δlogprob| vs fp reference; 0.0 when the
                # engine is bit-exact or never sampled).
                "logprob_drift": round(self._logprob_drift, 6),
                # Expert layers on the held path (zero on a model without
                # them): the share of all picks that landed on held
                # experts, the busiest held expert's picks over the held
                # experts' mean, and in the sorted tiles (a prefill chunk's
                # path) the rows routed over the rows computed.
                "moe_held_pick_share": round(
                    float(moe_held.sum() / moe_all), 6) if moe_all else 0.0,
                "moe_load_max_over_mean": round(
                    float(moe_held.max() / moe_held.mean()), 4)
                    if moe_held.any() else 0.0,
                "moe_tile_fill": round(float(moe_routed / moe_rows), 6)
                    if moe_rows else 0.0,
                # KV rows of windowed layers no later query can read, over
                # all KV rows held, summed over ticks: what an allocator
                # with a class of pages per layer kind would free.
                "kv_dead_rows_share": round(
                    self._kv_rows_dead / self._kv_rows_held, 6)
                    if self._kv_rows_held else 0.0,
                # Key rows the prefill chunks' attention scored over the
                # rows of the views they ran against (1.0: every chunk
                # scored its whole view, whatever its queries could see),
                # and the rows some query could see over the rows scored
                # (what rounding out to key blocks costs).
                "prefill_attn_rows_share": round(
                    self._attn_rows_scored / self._attn_rows_view, 6)
                    if self._attn_rows_view else 0.0,
                "prefill_attn_rows_fill": round(
                    self._attn_rows_visible / self._attn_rows_scored, 6)
                    if self._attn_rows_scored else 0.0,
                # The decode ticks' twin of the pair: pool rows the ticks'
                # attention scored (the key blocks of the running lanes,
                # in whole steps of the work list; a lane without a stream
                # scores none) over slots x view rows x layers (1.0: every
                # tick read every row every slot reserves), and the rows
                # the running streams' positions make visible over the
                # rows scored. Then what one cached token takes, all
                # layers, from the cache the model declares.
                "decode_attn_rows_share": round(
                    self._tick_rows_scored / self._tick_rows_view, 6)
                    if self._tick_rows_view else 0.0,
                "decode_attn_rows_fill": round(
                    self._tick_rows_visible / self._tick_rows_scored, 6)
                    if self._tick_rows_scored else 0.0,
                "kv_bytes_per_token": self._kv_bytes_per_token,
                "kv_cache_layers": self._kv_cache_layers,
                "kv_reader_layers": self._kv_reader_layers,
                "recurrent_state_bytes": self._recurrent_state_bytes,
                "recurrent_state_resets": self._recurrent_state_resets,
                "weights_served_form_leaves": self._weights_served_form_leaves,
                "weights_served_form_bytes": self._weights_served_form_bytes,
                "tick_attn_kernel_readers": self._tick_attn_kernel_readers,
            }
            # The host path by phase ("host_us/<phase>", slash-pathed like
            # the adapter keys; the gateway re-emits them as one labeled
            # family): mean per prefill chunk for a chunk's phases, per
            # decode tick for the others, and the longest single span.
            # "other" is the part of host_us_per_tick no phase covered.
            for name in HOST_PHASES:
                sum_s, max_s, _ = self._host[name]
                per = max(1, self._prefill_chunks if name in CHUNK_PHASES
                          else self._ticks)
                out[f"host_us/{name}"] = round(sum_s * 1e6 / per, 3)
                out[f"host_us_max/{name}"] = round(max_s * 1e6, 3)
            out["host_us/other"] = round(
                self._host_other_us_sum / max(1, self._host_us_ticks), 3)
            # chunk_to_dispatch: a prefill chunk's result ready on the host
            # -> the return of the engine thread's next device launch (the
            # device has nothing queued meanwhile). emit_lag: a token's
            # commit on the engine thread -> its on_token callback's return.
            for name in HOST_INTERVALS:
                sum_s, max_s, count = self._host[name]
                out[f"{name}_ms"] = round(sum_s * 1e3 / max(1, count), 3)
                out[f"{name}_ms_max"] = round(max_s * 1e3, 3)
                out[f"{name}_count"] = count
            # Multi-tenant LoRA: flat aggregates plus per-name counters
            # ("adapter/<name>/<counter>" — slash-pathed like tracker keys;
            # the gateway re-emits these as labeled Prometheus series).
            a_req = sum(e["requests"] for e in self._adapter.values())
            a_hit = sum(e["hits"] for e in self._adapter.values())
            a_lookups = a_hit + sum(e["misses"] for e in self._adapter.values())
            out.update({
                "adapters_tracked": len(self._adapter),
                "adapter_requests": a_req,
                "adapter_tokens": sum(e["tokens"] for e in self._adapter.values()),
                "adapter_loads": sum(e["loads"] for e in self._adapter.values()),
                "adapter_evictions": sum(
                    e["evictions"] for e in self._adapter.values()),
                "adapter_residency_hit_rate": round(a_hit / a_lookups, 4)
                    if a_lookups else 0.0,
            })
            for name in sorted(self._adapter):
                for k, v in self._adapter[name].items():
                    out[f"adapter/{name}/{k}"] = v
            # Traffic classes ("priority/<name>/<counter>", same slash
            # pathing) — measurement-only series for the SLO baseline.
            for name in sorted(self._priority):
                for k, v in self._priority[name].items():
                    out[f"priority/{name}/{k}"] = v
            return out


class GatewayStats:
    """HTTP-layer counters for the :class:`~accelerate_tpu.serving.gateway.
    ServingGateway`: responses by route and status code, in-flight
    connections, streamed tokens, and the backpressure/shed classes the
    gateway maps to HTTP (429 queue-full, 408 deadline, 413 body cap,
    503 saturated/draining). Thread-safe — every handler thread records
    into the same object; ``summary()`` is a flat scalar dict and
    ``by_route()`` feeds the labeled Prometheus series."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        """Zero every counter (e.g. between measurement windows)."""
        with self._lock:
            self._responses: dict = {}   # (route, code) -> count
            self._inflight = 0
            self._inflight_max = 0
            self._streams = 0
            self._tokens_streamed = 0
            self._bytes_in = 0
            self._pressure_sheds = 0
            self._rate_limit_sheds = 0
            self._fair_share_sheds = 0
            # SSE saturation observables: how many event-stream responses
            # are OPEN right now (the front end's true concurrency — the
            # number the asyncio refactor exists to scale) and how many
            # requests bounced off the connection cap (503s the open-loop
            # harness counts as refusals, distinct from queue-full 429s).
            self._open_streams = 0
            self._open_streams_max = 0
            self._conn_rejections = 0

    def record_response(self, route: str, code: int, body_bytes: int = 0):
        """One finished HTTP exchange on ``route`` with status ``code``."""
        with self._lock:
            key = (str(route), int(code))
            self._responses[key] = self._responses.get(key, 0) + 1
            self._bytes_in += int(body_bytes)

    def record_pressure_shed(self):
        """One 429 issued on PROJECTED KV-page pressure (pool headroom
        short for admitted + queued demand) rather than queue depth —
        distinguishes proactive sheds from queue-full backpressure in the
        overall 429 count."""
        with self._lock:
            self._pressure_sheds += 1

    def record_rate_limit_shed(self):
        """One 429 issued by the per-tenant token-bucket rate limiter
        (Retry-After derives from the tenant bucket's refill time) —
        per-cause accounting alongside the pressure/queue-full sheds."""
        with self._lock:
            self._rate_limit_sheds += 1

    def record_fair_share_shed(self):
        """One 429 issued by weighted fair-share admission: the fleet was
        past its pressure threshold and this tenant past its guaranteed
        share of in-flight streams."""
        with self._lock:
            self._fair_share_sheds += 1

    def record_stream(self, tokens: int):
        """One SSE stream that delivered ``tokens`` token events."""
        with self._lock:
            self._streams += 1
            self._tokens_streamed += int(tokens)

    def record_conn_rejection(self):
        """One request refused (503) at the connection cap — saturation of
        the FRONT END itself, visible on /metrics before any load harness
        goes looking for it."""
        with self._lock:
            self._conn_rejections += 1

    def stream_enter(self):
        """An SSE response opened (headers sent, events may follow)."""
        with self._lock:
            self._open_streams += 1
            self._open_streams_max = max(self._open_streams_max,
                                         self._open_streams)

    def stream_exit(self):
        """An SSE response closed (final event written or socket broke)."""
        with self._lock:
            self._open_streams -= 1

    def inflight_enter(self):
        with self._lock:
            self._inflight += 1
            self._inflight_max = max(self._inflight_max, self._inflight)

    def inflight_exit(self):
        with self._lock:
            self._inflight -= 1

    def by_route(self) -> dict:
        """``(route, code) -> count`` snapshot (Prometheus labels)."""
        with self._lock:
            return dict(self._responses)

    def summary(self) -> dict:
        """Flat scalar snapshot: totals, per-class counts, in-flight."""
        with self._lock:
            total = sum(self._responses.values())

            def klass(digit):
                return sum(c for (_, code), c in self._responses.items()
                           if code // 100 == digit)

            def code_count(code):
                return sum(c for (_, c2), c in self._responses.items()
                           if c2 == code)

            return {
                "http_requests": total,
                "http_2xx": klass(2),
                "http_4xx": klass(4),
                "http_5xx": klass(5),
                "http_429": code_count(429),
                "http_408": code_count(408),
                "http_413": code_count(413),
                "http_503": code_count(503),
                "http_inflight": self._inflight,
                "http_inflight_max": self._inflight_max,
                "streams": self._streams,
                "tokens_streamed": self._tokens_streamed,
                "request_bytes_in": self._bytes_in,
                "pressure_sheds": self._pressure_sheds,
                "rate_limit_sheds": self._rate_limit_sheds,
                "fair_share_sheds": self._fair_share_sheds,
                "open_sse_streams": self._open_streams,
                "open_sse_streams_max": self._open_streams_max,
                "conn_rejections": self._conn_rejections,
            }
