"""Continuous-batching serving engine over the compiled generation stack.

The TPU constraint (GSPMD: peak performance comes from a small number of
fixed-shape compiled programs) shapes the whole design. The engine owns a
fixed decode state — a global pool of KV pages and, per slot, a write
position, carry rng and eos latch — and after warmup runs a FIXED set of
compiled programs, no matter how requests arrive or leave:

* the prefill chunk (``_paged_prefill_chunk_fn``) — ONE executable of
  fixed shape ``[1, prefill_chunk]`` serves every prompt length: a prompt
  is a sequence of identical-shape chunk calls at traced ``cache_pos =
  offset`` (slot index, page-table row, chunk offset and true length are
  all traced arguments, never shapes). The tail chunk is EDGE-padded on
  the host (numpy, so no per-length jnp pad programs); the executable
  reads the logits row of ``true_len - 1`` mapped into the chunk window,
  and also returns the chunk's own KV block, which feeds an external
  prefix cache without a separate extract program. Warmup therefore
  leaves ZERO lazy compiles for any prompt length.
* the decode tick (``_paged_decode_fn``) — one token for every slot, a
  ``jax.vmap`` of the batch-1 single-token forward over the slot axis,
  sharing :func:`generation._next_token` with the offline scan so engine
  streams are bit-identical to offline :func:`generation.generate` for the
  same (prompt, rng, sampling). Slot membership is a host-provided boolean
  mask ARGUMENT, never a shape: admitting or retiring a request changes
  the mask bits, not the program. Its attention reads the page pool in
  place: one work list of the live (slot, key block) pairs of all lanes
  (``models.llama._attend_work_list``), so a tick reads the rows its
  streams hold, not the rows their slots reserve.
* the copy-restore (``_paged_restore_prefix_fn``) — only with an EXTERNAL
  ``prefix_cache=``: one compiled copy of a cached ``[1, prefill_chunk]``
  KV block into freshly allocated pages. The engine's private cache needs
  no program at all (see below).

KV memory is PAGED: a global pool of fixed-size pages (``page_size``
tokens, default one prefill chunk) plus a host-side ``[max_slots,
max_pages_per_slot]`` page table. The table rides into the warm
executables as traced integer data — a chunk and a speculative tick
gather a slot's pages into a dense view, run the unchanged forward and
scatter only the written pages back; the plain tick reads the pages where
they lie and writes one row a slot — so page allocation, free, preemption, and
prefix-block ALIASING (a private-cache hit is a host table write +
refcount, zero device copies) all compile nothing. Page 0 is a reserved
scratch page: unallocated table entries point at it, so clamped
gathers/scatters for inactive slots land there harmlessly. Short requests
hold pages, not worst-case rows, and a pool-exhausted engine preempts a
stream at a chunk boundary and resumes it token-exactly later (the
router-failover resume-as-longer-prompt trick). Sliding-window models
serve through the same view: the gathered view is a full-length linear
cache, and pages that fall wholly out of every layer's attention window
are freed (ring semantics as a page-lifetime policy, no new kernel).

Pad/garbage-KV safety: chunk calls write KV into pages that may hold a
previous occupant's entries (and the tail chunk writes edge-pad KV past
``true_len``). Both are safe for the same reason the offline bucketing
is: the attention mask attends ``k_pos <= q_pos`` only, and masking is
REPLACEMENT (``jnp.where(mask, logits, -1e30)``), so a masked garbage
key contributes exactly 0 probability — finite garbage KV never changes
a real row's output. A chunk against a long view does not even read it:
``models.llama._cached_attention`` scores only the key blocks up to the
chunk's last query (``hi = offset + C``, rounded out to a block), so the
mask meets garbage only inside the last block, in a speculative tick's
one-block pass and in the last key block of a plain tick's lane. Positions at/past ``true_len`` are overwritten by
the first decode write at-or-before the first query that could attend
them. A tick scatters an inactive or ``PREFILLING`` slot's write to the
scratch page, and every chunk and restore call writes ``pos[slot] =
true_len``, so a mid-prefill slot's rows stay frozen and in bounds.

Admission is interleaved: an admitted request sits in ``PREFILLING``
holding its slot, and each scheduler iteration spends at most
``prefill_chunks_per_tick`` chunk calls (round-robin across the prefill
backlog) before the next decode tick — so decode lanes advance every
tick and a 4k-token arrival cannot stall every active stream for its
whole prefill. Outputs stay token-identical to offline ``generate``:
chunking changes WHEN KV is written, not what is written, and the
first-token rng split (:func:`generation._chunk_prefill_token`) is the
same.

Mesh-sliced mode (``tp=`` / ``mesh=``): the same programs compile with
``in_shardings``/``out_shardings`` from :class:`~.mesh_exec.SliceExec`,
so one engine spans a tensor-parallel slice of devices — params in the
Megatron column/row layout the training side uses, the page pool sharded
on its heads axis, the adapter bank matching its base kernels — while
slot membership, pos/tok/rng/done rows, prompt chunks, page tables and
masks stay replicated *data*. Streams are token-identical to the
single-chip engine. External prefix-cache blocks are fetched to host
numpy in this mode, so one :class:`PrefixCache` can be shared by every
slice of a ``ReplicaSet.from_mesh`` fleet (a block saved by one slice
restores into any other's shardings — cross-slice hits survive
failover).

Speculative decoding (``draft_model=`` or ``spec_lookup=``): each tick
runs ONE warm executable that obtains ``spec_tokens`` proposals — a
compiled draft scan over draft KV paged from the SAME pool (separate
table columns), or a host-side prompt-lookup n-gram match with no draft
model at all — then verifies them with one fixed-width ``[1, K+1]``
target forward against the paged view. Greedy engines accept the longest
matching prefix (streams bit-identical to non-speculative greedy: the
verify logits ARE the plain tick's logits); sampled engines apply the
exact rejection-sampling rule (:func:`generation.speculative_accept`) on
the per-slot rng rows, so the emitted distribution is the plain sampled
law. Adapter rows gather inside the same program (the draft stays
base-weight), mesh slices compile the verify tp-sharded with the draft
replicated, and prefix-cache hits rebuild draft KV via a draft-only
chunk program — all under the same zero-recompile pin.

The run loop keeps the Python host off the device's critical path. JAX
dispatch is asynchronous: a compiled call returns futures immediately,
and chaining ``self._state`` through successive calls fixes device
execution order without the host ever waiting. The loop dispatches tick
N+1 — page coverage, membership mask, admission work and all — against
tick N's still-in-flight state futures, then reconciles N (materialize
tokens, commit, retire) while N+1 runs. The dispatch uses a SPECULATIVE
view of the batch: host state is stale by exactly the one in-flight
tick, so a stream that retires at N wastes one masked lane at N+1 (its
stray token is discarded by an epoch/validity check at reconcile —
emission stays exactly once), streams within one token of
``max_new_tokens`` are conservatively excluded (their stray write would
exceed the position bound), and pages are pre-allocated one position
ahead. Page-table snapshots (``.copy()`` per dispatch) double-buffer the
host tables: reconcile-time frees/preemptions mutate the live table
while the in-flight program reads its own generation, and device program
order guarantees any write a stale snapshot routes into a
since-recycled page happens BEFORE the page's new owner prefills it
(overwrite-before-attend, again). Streaming callbacks go to a bounded
per-request queue drained by an emitter thread, so a slow consumer
flow-controls its own stream (skipped lanes, ``emission_stalls``) and
never stalls the batch; a retiring stream's completion is deferred
behind its buffered callbacks (drain-on-retire barrier).
``host_us_per_tick`` (scheduling + commit wall) thus hides under device
time instead of adding to ITL. One carve-out: prompt-lookup engines
reconcile before dispatching (no ahead tick) — their proposals anchor on
the newest committed token, and a proposal drafted one variable-length
tick behind verifies to zero accepts, which would trade all of lookup's
acceptance for the overlap.

Around the compiled programs: a bounded FCFS admission queue with
backpressure, per-request ``max_new_tokens``/timeout/cancellation,
streaming token callbacks, error isolation (a failing callback frees its
slot without touching the rest of the batch), and a graceful drain on
shutdown that cooperates with ``Accelerator.install_preemption_handler()``
— on preemption the engine stops admitting, finishes in-flight requests
(including mid-prefill ones), and cancels the queue, so the process can
exit inside the notice window.
"""

from __future__ import annotations

import collections
import gc
import hashlib
import itertools
import os
import threading
import time
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..adapters.registry import AdapterBank
from ..generation import (
    _check_position_bound,
    _chunk_prefill_token,
    _make_selector,
    _make_warper,
    _next_token,
    speculative_emit,
)
from ..inference import resolve_model_source
from ..models.llama import (PagedCache, cached_attention_rows, gather_pages,
                            tick_key_extent, tick_key_tiles)
from ..ops.paged_attention import live_pages, paged_attention_available
from ..observability import FlightRecorder, Tracer, new_trace_id
from ..observability.program_parts import program_part
from .metrics import ServingStats
from .request import Request, RequestStatus
from .control import PriorityPolicy
from .scheduler import (
    AdmissionQueue,
    PagePool,
    PrefixCache,
    QueueClosed,
    QueueFull,
    SlotScheduler,
)

__all__ = ["ServingEngine"]

#: distinct tracer/flight-recorder identities per engine in one process.
_ENGINE_SEQ = itertools.count()

#: ``id(published leaf) -> (weak reference to it, its served twin)`` for the
#: leaves :func:`_hold_in_served_form` took. A tree's owner keeps handing the
#: same tree to every engine it builds (a fleet's retained factory: replicas,
#: restarts), and on an accelerator the first engine DONATED those leaves: the
#: later ones find the twins here. An entry lives as long as the published
#: leaf object does, as the published weights themselves did before.
_SERVED_TWINS: dict = {}


def _served_form_of(module):
    """The family's :class:`~accelerate_tpu.models.llama.ServedForm`, or None
    where its weights are served as published (no hook, or ``module`` already
    reads the served tree)."""
    return module.served_form() if hasattr(module, "served_form") else None


def _hold_in_served_form(module, params):
    """Put ``params`` into the form their family serves them in
    (``module.served_form()``, models/llama.py ``ServedForm``), once, before
    any program is staged -> ``(module that reads them, params, leaves moved,
    their bytes)``. A module without the hook (or already the served one) is
    returned with its tree as they came, counts 0.

    One jitted call over the leaves the form moves, found by tracing it once
    abstractly (a leaf it does not move comes back as the same object). On an
    accelerator those leaves are DONATED, so the published 134 MB kernels do
    not stay on the device beside their twins: the caller's tree must not be
    read there afterwards — only handed to further engines, which find the
    twins in ``_SERVED_TWINS``."""
    form = _served_form_of(module)
    if form is None:
        return module, params, 0, 0
    flat, treedef = jax.tree.flatten(params)
    moved: list = []

    def moved_only(*leaves):
        out = jax.tree.leaves(form.to_served(jax.tree.unflatten(treedef, leaves)))
        moved[:] = [i for i, (l, o) in enumerate(zip(leaves, out)) if o is not l]
        return [out[i] for i in moved]

    jax.eval_shape(moved_only, *flat)
    twins = [_SERVED_TWINS.get(id(flat[i])) for i in moved]
    if all(t is not None and t[0]() is flat[i] for t, i in zip(twins, moved)):
        served = [t[1] for t in twins]
    else:
        donate = () if jax.default_backend() == "cpu" else tuple(moved)
        served = jax.jit(moved_only, donate_argnums=donate)(*flat)
        for i, twin in zip(moved, served):
            key = id(flat[i])
            _SERVED_TWINS[key] = (
                weakref.ref(flat[i], lambda _, key=key: _SERVED_TWINS.pop(key, None)),
                twin)
    nbytes = sum(int(t.size) * t.dtype.itemsize for t in served)
    for i, twin in zip(moved, served):
        flat[i] = twin
    return form.module, jax.tree.unflatten(treedef, flat), len(moved), nbytes


class _TickFlight:
    """One dispatched-but-unreconciled decode tick: the (slot, request,
    preemption-epoch) entries the mask was built from, the un-materialized
    device outputs, and the dispatch timestamp. Reconcile commits an
    entry only if its request is still RUNNING *and* its preemption epoch
    matches — a stream retired, failed, or preempted-and-readmitted after
    dispatch must not absorb the stale in-flight token (exactly-once
    emission)."""

    __slots__ = ("entries", "toks", "dones", "emit", "ns", "lookup_hits",
                 "t_dispatch")

    def __init__(self, entries, t_dispatch, toks=None, dones=None,
                 emit=None, ns=None, lookup_hits=0):
        self.entries = entries          # [(slot, req, req._preempted)]
        self.t_dispatch = t_dispatch
        self.toks = toks                # plain tick outputs
        self.dones = dones
        self.emit = emit                # speculative tick outputs
        self.ns = ns
        self.lookup_hits = lookup_hits


class _Phase:
    """One named phase of the engine thread's loop, as a ``with`` region:
    a tracer span (ring record + profiler annotation; nothing when
    tracing is off) and, always, a ``(sum_s, max_s, count)`` accumulator
    in plain engine-thread floats. One reusable object per name — a
    phase never nests inside itself. ``last_s`` is the duration of the
    region that just closed."""

    __slots__ = ("name", "is_wait", "last_s", "_owner", "_span", "_t0")

    def __init__(self, owner: "_HostPhases", name: str, is_wait: bool):
        self.name = name
        self.is_wait = is_wait       # the host blocked on the device
        self.last_s = 0.0
        self._owner = owner
        self._span = None
        self._t0 = 0.0

    def __enter__(self) -> "_Phase":
        self._span = self._owner.tracer.span(self.name, cat="phase")
        self._span.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.last_s = dt = time.monotonic() - self._t0
        self._span.__exit__(*exc)
        owner = self._owner
        owner.add(self.name, dt)
        if not self.is_wait:
            owner.covered_s += dt


class _HostPhases:
    """The engine loop's phases (module docstring of ``serving.metrics``:
    ``HOST_PHASES``), measured where the work happens, plus the
    ``chunk_to_dispatch`` interval. Everything accumulates here without
    a lock and is handed over by :meth:`drain` to the ``record_tick`` /
    ``record_prefill_chunk`` calls that take the stats lock anyway.

    ``covered_s`` is the named non-wait time since the last reconcile
    barrier: ``host_us_per_tick`` minus it is ``host_us/other``."""

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        self.covered_s = 0.0
        self._pending: dict = {}             # name -> [sum_s, max_s, count]
        self._chunk_ready_t: Optional[float] = None
        self.sweep = _Phase(self, "sweep", False)
        self.admit = _Phase(self, "admit", False)
        self.prefill_launch = _Phase(self, "prefill_launch", False)
        self.prefill_wait = _Phase(self, "prefill_wait", True)
        self.prefill_commit = _Phase(self, "prefill_commit", False)
        self.tick_launch = _Phase(self, "tick_launch", False)
        self.tick_wait = _Phase(self, "tick_wait", True)
        self.tick_commit = _Phase(self, "tick_commit", False)
        self.idle = _Phase(self, "idle", False)

    def add(self, name: str, dt: float) -> None:
        entry = self._pending.get(name)
        if entry is None:
            self._pending[name] = [dt, dt, 1]
        else:
            entry[0] += dt
            if dt > entry[1]:
                entry[1] = dt
            entry[2] += 1

    def chunk_ready(self) -> None:
        """A prefill chunk's result is on the host: until the next device
        launch returns, the device has nothing queued."""
        self._chunk_ready_t = time.monotonic()

    def launched(self) -> None:
        """A device launch returned (or the loop went idle with nothing
        to launch): closes an open ``chunk_to_dispatch`` interval."""
        if self._chunk_ready_t is not None:
            self.add("chunk_to_dispatch",
                     time.monotonic() - self._chunk_ready_t)
            self._chunk_ready_t = None

    def drain(self) -> dict:
        """What accumulated since the last drain, for ``ServingStats``."""
        out, self._pending = self._pending, {}
        return out


class _TokenEmitter:
    """Off-thread ``on_token`` delivery: the engine thread enqueues
    (request, token) pairs — and a ``None``-token finish sentinel AFTER a
    retiring request's last token, the drain-on-retire barrier — and one
    daemon thread drains them in order (each drained batch is one ``emit``
    span; its wall and every token's commit→callback-return lag go to
    the stats as ``emit`` / ``emit_lag``). A raising callback is recorded on
    the request (``_emit_error``); the engine's loop-top sweep turns that
    into the same FAILED retirement an inline callback failure produces.
    The queue is unbounded here; the ENGINE bounds it per request by
    flow-controlling streams whose ``_emit_pending`` exceeds
    ``max_pending`` (they are skipped from ticks, never stalled on).
    ``close()`` drains everything already queued, then joins — shutdown
    and failover never drop buffered tokens."""

    def __init__(self, max_pending: int, stats: ServingStats,
                 tracer: Tracer):
        self.max_pending = int(max_pending)
        self._stats = stats
        self._tracer = tracer
        self._q: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._closed = False
        self._thread = threading.Thread(target=self._drain_loop,
                                        name="serving-emitter", daemon=True)
        self._thread.start()

    @property
    def alive(self) -> bool:
        return self._thread.is_alive() and not self._closed

    def backlogged(self, req) -> bool:
        """Engine-side flow control: has this stream's consumer fallen
        ``max_pending`` callbacks behind?"""
        return req._emit_pending >= self.max_pending

    def put(self, req, token: int):
        req._emit_pending += 1
        committed_t = time.monotonic()
        with self._cv:
            self._q.append((req, token, committed_t))
            self._cv.notify()

    def finish(self, req):
        """Queue the completion sentinel — ``req._complete()`` runs only
        after every callback queued before it has been delivered."""
        with self._cv:
            self._q.append((req, None, 0.0))
            self._cv.notify()

    def close(self, timeout: Optional[float] = None):
        """Stop accepting work, drain what is queued, join (idempotent)."""
        with self._cv:
            self._closed = True
            self._cv.notify()
        self._thread.join(timeout)

    def _drain_loop(self):
        while True:
            with self._cv:
                while not self._q and not self._closed:
                    self._cv.wait(0.1)
                if not self._q:
                    return  # closed and fully drained
                batch = list(self._q)
                self._q.clear()
            t0 = time.monotonic()
            lag_sum = lag_max = 0.0
            tokens = 0
            with self._tracer.span("emit", cat="phase"):
                for req, token, committed_t in batch:
                    if token is None:
                        req._complete()
                        continue
                    if req._emit_error is None and req.on_token is not None:
                        try:
                            req.on_token(token)
                        except BaseException as e:
                            # Recorded, not raised: error isolation — the
                            # engine retires THIS request FAILED at its
                            # next sweep; the emitter keeps serving other
                            # streams.
                            req._emit_error = e
                    req._emit_pending -= 1
                    lag = time.monotonic() - committed_t
                    lag_sum += lag
                    lag_max = max(lag_max, lag)
                    tokens += 1
            dt = time.monotonic() - t0
            self._stats.record_host({"emit": (dt, dt, 1),
                                     "emit_lag": (lag_sum, lag_max, tokens)})


class ServingEngine:
    """Slot-based continuous-batching decode service.

    Args:
      model: an accelerate_tpu ``Model``/``AcceleratedModel`` or a bare
        cache-threading flax module (see ``generation.supports_kv_cache``).
      params: parameter pytree (defaults to the prepared model's), in the
        published layout. A family that states a served form
        (``module.served_form()``, e.g. ``cohere2_moe``) is held in it:
        ``engine.module`` / ``engine.params`` are then the served pair
        (``served_form().to_published(engine.params)`` is the published
        tree), and on an accelerator the leaves the form moves are DONATED
        out of the tree passed here — hand it to further engines, do not
        read it.
      max_slots: decode lanes — the fixed batch dimension of the tick.
      max_len: longest stream a slot can hold; every request must satisfy
        ``prompt_len + max_new_tokens <= max_len``.
      eos_token_id / do_sample / temperature / top_k / top_p: ENGINE-level
        sampling config — baked into the compiled executables (a
        per-request change would be a recompile). Greedy when
        ``do_sample=False``.
      cache_dtype: KV buffer dtype (default bfloat16, like offline).
      kv_dtype: ``"int8"`` stores the KV page pool quantized — each page
        row is symmetric int8 with one per-page f32 scale held in a
        ``pscale`` state array indexed by page id, written by the same
        executables that write the page (quantize at the page scatter,
        dequantize where pages are gathered: into a dense view, or a
        plain tick's key blocks). Pages cost half
        the bytes, so the same HBM pool admits ~2x the concurrent
        streams; alloc/free/alias/preempt stay pure host work because
        scales live device-side keyed by page id. ``None`` (default)
        keeps the full-precision pool — the bit-exact mode, whose
        programs carry no quantization op at all. Exactness under
        ``"int8"`` is bounded-divergence instead: see ``logprob_drift``
        in bench and docs/usage_guides/serving.md.
      weights_dtype: ``"int8"`` quantizes eligible BASE weight kernels
        per-output-channel (:func:`~accelerate_tpu.adapters.
        quantize_base_weights`); each program dequantizes at its top and
        XLA fuses the ``convert * scale`` into the consuming dots, so
        weights at rest stay int8. The LoRA low-rank path (AdapterBank,
        identity row 0 included) stays full precision — multi-tenant
        adapters apply exactly on the quantized base. ``None`` (default)
        serves full-precision weights.
      max_queued: admission-queue bound (backpressure past it).
      priority_policy: a :class:`~.control.PriorityPolicy` ranking the
        admission queue's classes and choosing preemption victims
        (``"default"`` builds one; ``None`` is plain FCFS).
      prefill_chunk: width of the single fixed-shape prefill executable
        (clamped to ``max_len`` and the model's position table); a prompt
        of any length runs as identical ``[1, prefill_chunk]`` chunk
        calls. Chunked prefill is the only prefill: ``None`` is refused.
      prefill_chunks_per_tick: admission budget — at most this many chunk
        calls run between consecutive decode ticks, alternating
        continuations of the ``PREFILLING`` backlog (round-robin) with
        new admissions, bounding how much any arrival can delay active
        streams' next token. At the default 1 a new arrival waits for the
        backlog to drain; 2+ lets its first chunk ride alongside an
        in-flight long prefill.
      prefix_cache_mb: LRU budget of the engine's private prefix cache
        (0 disables). It holds page ids, not KV copies: on admit, the
        longest cached chunk-aligned prefix is restored by ALIASING its
        pages into the slot's table instead of recomputing it; the
        final chunk always re-runs so the first token's logits exist.
        Cache keys include the request's adapter identity — two tenants
        with identical prompts never share KV blocks.
      adapters: optional :class:`~accelerate_tpu.adapters.AdapterBank` —
        multi-tenant LoRA serving. The bank rides into every compiled
        program as a regular stacked-array argument and each slot gathers
        its own adapter row inside the forward, so requests naming
        different adapters share one decode batch and adapter load/evict
        (a ``dynamic_update_slice`` row write) compiles nothing new.
        Requests with ``adapter=None`` use bank row 0, the reserved
        identity adapter — their output is the base model's, unchanged.
        In mesh mode the bank is placed onto this engine's slice (see
        :meth:`AdapterBank.place`), so each slice engine needs its OWN
        bank instance.
      tp: tensor-parallel width — carve ``tp`` devices (the first ``tp``
        of ``devices``/``jax.devices()``) into ONE slice and serve this
        engine across it. Mutually consistent with ``mesh=``.
      mesh: an explicit tp-only :class:`jax.sharding.Mesh` (e.g. from
        :meth:`~.mesh_exec.SlicePlan.build_mesh`) for this engine's
        slice. A tp-only mesh resolved from a prepared model/accelerator
        routes here automatically; a mesh with non-trivial dp/fsdp/...
        axes is rejected (see ``_resolve_serving_mesh``).
      devices: with ``tp=``, the device pool to carve the slice from
        (default ``jax.devices()``).
      prefix_cache: a pre-built (possibly fleet-shared)
        :class:`~.scheduler.PrefixCache` of host-portable KV blocks to
        use instead of the private cache — how ``ReplicaSet.from_mesh``
        gives every slice one cache for cross-slice prefix hits; a hit
        is a compiled copy into fresh pages (``restore_prefix``).
      accelerator: optional — wires preemption-drain cooperation and, when
        the accelerator carries a ``serving_stats``, shares it so
        ``Accelerator.log(include_serving=True)`` sees this engine.
      stats: a :class:`~.metrics.ServingStats` to record into (default:
        the accelerator's, else a fresh one).
      page_size: tokens per KV page (default = ``prefill_chunk`` so
        PrefixCache blocks map onto whole pages and cache hits restore
        by table ALIASING); must divide ``prefill_chunk``.
      max_pages: usable pool pages (page 0 scratch is extra). Default
        ``max_slots * ceil(max_len / page_size)`` — every slot can reach
        ``max_len`` at once; pass less to overcommit memory and lean on
        preemption.
      draft_model / draft_params: enable speculative decoding — a small
        cache-threading draft module proposing ``spec_tokens`` tokens per
        tick, verified by one fixed-width target forward. Draft KV pages
        come from the same pool, so a speculative slot costs roughly
        twice the pages; composes with
        sampling (exact rejection-rule acceptance), adapter banks (the
        target verify gathers the slot's row; the draft runs base
        weights), mesh slices (draft replicated, verify tp-sharded), and
        prefix caches (restored prefixes rebuild draft KV through a
        dedicated draft-only chunk program).
      spec_tokens: draft proposals per speculative tick (default 4).
      spec_lookup: n-gram width for DRAFT-FREE prompt-lookup speculation
        (mutually exclusive with ``draft_model``): each tick proposes
        ``spec_tokens`` tokens by matching the slot's last ``spec_lookup``
        tokens against their most recent earlier occurrence in
        prompt+output (host-side numpy; proposals ride into the verify
        executable as traced data). No draft params, no draft KV, no
        extra pages — the big win for self-repeating RAG/doc traffic.
      tracing: keep the request-scoped span tracer enabled (the default —
        the hot path is a lock-free ring append, guarded ≤5% decode
        overhead). ``False`` turns every emit into an early return; the
        flight recorder stays on either way (its events are rare).
      trace_capacity: spans kept per emitting thread (drop-oldest).
      flight_capacity: structured events the flight recorder retains.
      trace_dir: when set, the engine writes ``<name>-trace.json`` /
        ``<name>-flight.json`` here on shutdown or death (the
        ``accelerate-tpu serve --trace-dir`` plumbing).
      chaos: an optional :class:`~.chaos.ChaosSchedule` of scripted
        faults (kill at decode tick T, hang via heartbeat suppression,
        slow ticks, a wedge inside a dispatched call) applied from the
        run loop — the deterministic fault-injection harness behind the
        self-healing tests.
      emission_queue: per-request bound on emitter-queued ``on_token``
        callbacks. A stream whose consumer falls this far behind is
        flow-controlled — skipped from decode ticks (``emission_stalls``
        counts them) until its queue drains — instead of growing host
        memory or stalling the batch.
      autostart: spawn the engine thread (and warm up) in the constructor.
      warmup: run dummy requests through every program at start so the
        first real request never pays a compile; stats, spans, and
        flight events reset afterwards.
      idle_poll_s: how long the idle loop blocks on the admission queue
        before it republishes its heartbeat.
    """

    def __init__(self, model, params=None, *, max_slots: int = 4,
                 max_len: int = 256, eos_token_id: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 cache_dtype=None, kv_dtype: Optional[str] = None,
                 weights_dtype: Optional[str] = None, max_queued: int = 64,
                 priority_policy: Optional[PriorityPolicy] = "default",
                 prefill_chunk: int = 256,
                 prefill_chunks_per_tick: int = 1,
                 prefix_cache_mb: float = 64.0,
                 adapters: Optional[AdapterBank] = None,
                 page_size: Optional[int] = None,
                 max_pages: Optional[int] = None,
                 draft_model=None, draft_params=None, spec_tokens: int = 4,
                 spec_lookup: Optional[int] = None,
                 tp: Optional[int] = None, mesh=None, devices=None,
                 prefix_cache: Optional[PrefixCache] = None,
                 accelerator=None, stats: Optional[ServingStats] = None,
                 tracing: bool = True, trace_capacity: int = 4096,
                 flight_capacity: int = 256,
                 trace_dir: Optional[str] = None,
                 chaos=None,
                 emission_queue: int = 256,
                 autostart: bool = True, warmup: bool = True,
                 idle_poll_s: float = 0.005):
        from ..big_modeling import cache_factory_for

        module, _, params, resolved_mesh, _ = resolve_model_source(
            model, params=params, accelerator=accelerator)
        if params is None:
            raise ValueError("ServingEngine needs params (pass params= or a "
                             "prepared Model)")
        if module is None or hasattr(module, "init_decode_cache"):
            raise NotImplementedError(
                "ServingEngine serves decoder-only cache-threading modules; "
                "encoder-decoder models go through seq2seq_generate")
        factory = cache_factory_for(module)
        if factory is None:
            raise TypeError(
                f"{type(module).__name__} does not thread a KV cache "
                "(big_modeling.cache_factory_for) — the engine cannot hold "
                "its decode state")
        if max_slots < 1 or max_len < 2:
            raise ValueError(f"need max_slots >= 1 and max_len >= 2 "
                             f"(got {max_slots}, {max_len})")
        if prefill_chunk is None:
            raise ValueError(
                "prefill_chunk=None: chunked prefill is the only prefill; "
                "pass a chunk width >= 1")
        if int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (got {prefill_chunk})")
        if prefill_chunks_per_tick < 1:
            raise ValueError("prefill_chunks_per_tick must be >= 1 "
                             f"(got {prefill_chunks_per_tick})")
        if prefix_cache_mb < 0:
            raise ValueError(
                f"prefix_cache_mb must be >= 0 (got {prefix_cache_mb})")

        self.module = module
        self.params = params
        serving_mesh = self._resolve_serving_mesh(tp, mesh, devices,
                                                  resolved_mesh, params)
        #: the engine's slice mesh when mesh-sliced, else whatever mesh the
        #: model source carried (informational, as before).
        self.mesh = serving_mesh if serving_mesh is not None else resolved_mesh
        if serving_mesh is not None:
            from .mesh_exec import SliceExec

            self._exec: Optional["SliceExec"] = SliceExec(serving_mesh)
        else:
            self._exec = None
        #: tensor-parallel width of this engine's slice (1 = single-chip).
        self.tp = self._exec.tp if self._exec is not None else 1
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.eos_token_id = eos_token_id
        self._dtype = cache_dtype or jnp.bfloat16
        self._factory = factory
        self._sampling = (float(temperature), top_k, top_p) if do_sample else None
        self._select = _make_selector(self._sampling)
        self._idle_poll_s = float(idle_poll_s)
        self._accelerator = accelerator

        # The usable position range: max_len capped at the model's learned
        # position table (writing KV at an OOB learned position is not just
        # wasteful — gathers past the table poison the row).
        bound = getattr(getattr(module, "config", None),
                        "max_position_embeddings", None)
        self._chunk_limit = (self.max_len if bound is None
                             else min(self.max_len, int(bound)))
        self._chunk = min(int(prefill_chunk), self._chunk_limit)
        # The final chunk may start below its natural i*C offset so its
        # fixed width never writes past max_len / the position table
        # (re-running already-prefilled positions rewrites identical KV).
        self._chunk_cap = self._chunk_limit - self._chunk
        self._chunks_per_tick = int(prefill_chunks_per_tick)

        self._page = int(page_size) if page_size is not None else self._chunk
        if self._page < 1 or self._chunk % self._page != 0:
            raise ValueError(
                f"page_size ({page_size}) must be >= 1 and divide the "
                f"prefill chunk ({self._chunk}) so chunk writes and "
                "cached blocks cover whole pages")

        # -- quantized serving resolution --------------------------------
        # kv int8 lives at PAGE granularity (one scale per page row);
        # kv_dtype=None programs carry no quantization op — every
        # quant/dequant site below is gated on the scale arrays being
        # present at all.
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8' (got {kv_dtype!r})")
        if weights_dtype not in (None, "int8"):
            raise ValueError(
                f"weights_dtype must be None or 'int8' (got {weights_dtype!r})")
        self._kv_dtype = kv_dtype
        self._weights_dtype = weights_dtype

        # -- speculative-decoding resolution ------------------------------
        # Two drafting modes share one verify program shape: a DRAFT MODEL
        # (paged draft KV alongside the target's) or host-side
        # PROMPT-LOOKUP n-gram proposals (no draft state at all). Either
        # composes with sampling, adapters, mesh slices, and prefix caches.
        if draft_model is not None and spec_lookup is not None:
            raise ValueError(
                "draft_model= and spec_lookup= are mutually exclusive — one "
                "engine drafts either with a model or by prompt lookup")
        if draft_model is not None or spec_lookup is not None:
            if int(spec_tokens) < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1 (got {spec_tokens})")
            self._spec_k: Optional[int] = int(spec_tokens)
        else:
            self._spec_k = None
        self._spec_lookup: Optional[int] = None
        if draft_model is not None:
            self._spec_mode: Optional[str] = "draft"
            dmod, _, dparams, _, _ = resolve_model_source(
                draft_model, params=draft_params)
            if dparams is None:
                raise ValueError("draft_model needs params (pass "
                                 "draft_params= or a prepared Model)")
            dfactory = cache_factory_for(dmod)
            if dfactory is None:
                raise TypeError(
                    f"{type(dmod).__name__} does not thread a KV cache; it "
                    "cannot draft for the serving engine")
            tv = getattr(getattr(module, "config", None), "vocab_size", None)
            dv = getattr(getattr(dmod, "config", None), "vocab_size", None)
            if tv is not None and dv is not None and tv != dv:
                raise ValueError(
                    f"draft vocab ({dv}) != target vocab ({tv}); acceptance "
                    "compares token ids, so the vocabularies must match")
            self._draft_module, self._draft_params = dmod, dparams
            self._draft_factory = dfactory
        elif spec_lookup is not None:
            if int(spec_lookup) < 1:
                raise ValueError(
                    f"spec_lookup (n-gram width) must be >= 1 "
                    f"(got {spec_lookup})")
            self._spec_mode = "lookup"
            self._spec_lookup = int(spec_lookup)
            self._draft_module = self._draft_params = None
            self._draft_factory = None
        else:
            self._spec_mode = None
            self._draft_module = self._draft_params = None
            self._draft_factory = None
        #: the sampling-target warper, shared with the rejection-sampling
        #: accept rule (sampled speculation must agree with the selector on
        #: the warped distribution EXACTLY).
        self._warp = (_make_warper(self._sampling)
                      if self._sampling is not None else None)
        self._dtable = None          # draft page-table (draft mode only)
        self._draft_page_bytes = 0

        if prefix_cache is not None:
            self._prefix_cache: Optional[PrefixCache] = prefix_cache
            self._alias_cache = False   # external/shared cache: COPY restores
        elif prefix_cache_mb > 0:
            # The PRIVATE cache stores page-id tuples, not KV blocks: a hit
            # is a host table write + refcount (aliasing), and eviction
            # gives the pages back through the hook.
            self._alias_cache = True
            self._prefix_cache = PrefixCache(
                int(prefix_cache_mb * 2 ** 20),
                on_evict=self._on_prefix_evict)
        else:
            self._prefix_cache = None
            self._alias_cache = False
        self._prefilling: collections.deque[Request] = collections.deque()

        # Ring (sliding-window) caches rotate by stored position; the page
        # pool serves them as what the gathered view always is, a
        # full-length LINEAR cache (the model's linear branch applies the
        # window mask), and ring semantics become a page-lifetime policy
        # (out-of-window pages are freed).
        slot_shape = jax.eval_shape(
            lambda: self._factory(1, self.max_len, self._dtype))
        has_ring = any(isinstance(layer, dict) and "pos" in layer
                       for layer in slot_shape)
        leaf_axes = self._cache_length_axes(self._factory)
        #: indices of the cache's RECURRENT entries: top-level entries none
        #: of whose leaves has a length axis (a state-space layer's state).
        #: Fixed size whatever ``max_len``, so not paged: the engine holds
        #: one row a slot of each such leaf in ``state["recurrent"]``; the
        #: chunk program reads its slot's row (zeros at offset 0) and writes
        #: it back, a tick advances every running lane's row, and the row is
        #: dead with the slot — the next prompt's first chunk resets it.
        self._recurrent_at = self._recurrent_entries(slot_shape, leaf_axes)
        self._cache_axes = [ax for ax in leaf_axes if ax is not None]
        self._refuse_unproven_caches(module, slot_shape, draft_model,
                                     spec_lookup, prefix_cache,
                                     prefix_cache_mb)
        cfg = getattr(module, "config", None)
        #: each layer's attention window where it is shorter than max_len
        #: (None = the layer reads every row), from the same per-layer rule
        #: the cache factory applies; empty for families without windows.
        self._layer_windows: list = []
        if has_ring:
            from ..models.llama import _layer_window

            self._layer_windows = [
                w if w is not None and w < self.max_len else None
                for w in (_layer_window(cfg, i)
                          for i in range(cfg.num_hidden_layers))]
        #: window width when pages wholly out of the attention window may be
        #: freed: every layer windowed alike (mixed local/global stacks keep
        #: all pages — a global layer reads them to the end).
        kinds = set(self._layer_windows)
        self._page_window = (
            int(next(iter(kinds))) if len(kinds) == 1 and None not in kinds
            else None)
        #: ``(window or None, cache entry)`` of every attention of a forward
        #: pass: what the model declares (``attention_layout``: layers
        #: without attention, layers that read another layer's entry), else
        #: one attention a layer over the layer's own entry.
        if hasattr(module, "attention_layout"):
            layout = [(w if w is not None and w < self.max_len else None, e)
                      for w, e in module.attention_layout()]
        else:
            layout = list(zip(self._layer_windows
                              or [None] * len(slot_shape),
                              range(len(slot_shape))))
        #: (window or None, attentions) per kind, and the query heads (one
        #: float32 score a head, a query and a key row): what the
        #: *_attn_rows_* counters need of the programs' shapes.
        windows = [w for w, _ in layout]
        self._attn_layer_kinds = [(w, windows.count(w)) for w in set(windows)]
        self._attn_score_heads = getattr(cfg, "num_attention_heads", None)
        #: the cache entries that hold KV rows, each with the window past
        #: which no reader of it looks (None where some reader sees all):
        #: (window, entries) pairs for the kv_dead_rows_share counter, over
        #: ``_kv_entries`` entries in all.
        readers: dict = {}
        for w, e in layout:
            readers.setdefault(e, []).append(w)
        entry_windows = [None if None in ws else max(ws)
                         for ws in readers.values()]
        self._kv_entries = len(slot_shape) - len(self._recurrent_at)
        self._kv_readers = len(layout)
        self._dead_row_windows = [
            (int(w), entry_windows.count(w))
            for w in set(entry_windows) if w is not None]
        #: the variable collection a module sows per-call counters into
        #: (models/cohere2_moe.py, models/mixtral.py: MoE pick counts); the
        #: programs ask for it and return its sum packed behind the tokens.
        #: None for modules that sow nothing.
        self._stats_collection = getattr(module, "serving_stats_collection",
                                         None)
        #: the module's counters end with one more total behind ops/moe.py's
        #: pick vector: the picks on zero-compute experts (models/
        #: longcat_flash.py); ``moe_zero_picks`` in the stats.
        self._zero_picks = bool(getattr(module, "serving_zero_picks", False))
        #: rows a query's latent attention reads at most, where a learned
        #: indexer picks them (models/glm_moe_dsa.py); None elsewhere. The
        #: ``dsa_*`` counters and the tick's rows scored follow it.
        self._index_topk = getattr(module, "serving_index_topk", None)
        #: results of prefill chunks whose counters are not folded yet: a
        #: chunk that is not its prompt's last is read on the host only at
        #: a later last chunk, when its copy has long arrived.
        self._late_counts: list = []

        probe, recurrent = self._split_cache(
            jax.eval_shape(lambda: self._factory(1, 2, self._dtype)))
        self._cache_struct = jax.tree.structure(probe)
        K = self._spec_k or 0
        # The view must hold max_len + K positions: a verify near the
        # end of a stream writes up to pos + K, and the model's internal
        # dynamic_update_slice would CLAMP (corrupting earlier
        # positions) if the view were shorter.
        self._pages_per_slot = -(-(self.max_len + K) // self._page)
        usable = (int(max_pages) if max_pages is not None
                  else self.max_slots * (-(-self.max_len // self._page)))
        if usable < 1:
            raise ValueError(f"max_pages must be >= 1 (got {max_pages})")
        self._pool = PagePool(usable)
        self._table = np.zeros((self.max_slots, self._pages_per_slot),
                               np.int32)
        quant = self._kv_dtype is not None
        pool_leaves, self._page_bytes = [], 0
        for sh, ax in zip(jax.tree.leaves(probe), self._cache_axes):
            shape = list(sh.shape)
            shape[ax] = self._page
            # +1: page 0 is the reserved scratch page every clamped or
            # inactive write routes to.
            pool_leaves.append(jnp.zeros(
                (usable + 1,) + tuple(shape),
                jnp.int8 if quant else sh.dtype))
            # Quantized pages charge 1 byte/element + 4 bytes for the
            # per-page scale — _page_bytes feeds every byte-accounting
            # path (pool metrics, alias-put nbytes, per-chip HBM), so
            # all of them report quantized bytes automatically.
            self._page_bytes += (
                int(np.prod(shape))
                * (1 if quant else np.dtype(sh.dtype).itemsize)
                + (4 if quant else 0))
        self._state = {
            "pool": jax.tree.unflatten(self._cache_struct, pool_leaves),
            "pos": jnp.zeros((self.max_slots,), jnp.int32),
            "tok": jnp.zeros((self.max_slots,), jnp.int32),
            "rng": jnp.zeros((self.max_slots, 2), jnp.uint32),
            "done": jnp.zeros((self.max_slots,), bool),
        }
        if recurrent:
            # one row a slot of every recurrent leaf, in the leaf's own type
            self._state["recurrent"] = jax.tree.map(
                lambda sh: jnp.zeros((self.max_slots,) + sh.shape, sh.dtype),
                recurrent)
        #: bytes of the recurrent state, all slots (0 without such entries)
        self._recurrent_bytes = sum(
            self.max_slots * int(np.prod(sh.shape)) * sh.dtype.itemsize
            for sh in jax.tree.leaves(recurrent))
        if quant:
            # Per-page dequant scales, one row per pool leaf, indexed
            # by page id like the pool itself — device-resident, so a
            # host page-table alias restore (table write + incref)
            # reuses the page's scale with zero device work. Ones keep
            # scratch-page gathers finite before any real write.
            self._state["pscale"] = jnp.ones(
                (len(pool_leaves), usable + 1), jnp.float32)
        #: the plain tick's attentions by (window or None, whether the
        #: Mosaic paged-attention kernel reads the entry's pages in place of
        #: the XLA work list: ops/paged_attention.py's own rule, from the
        #: pool this engine has just built) -> how many there are
        paged_at = [i for i in range(len(slot_shape)) if i not in self._recurrent_at]
        self._tick_attn_kinds = collections.Counter(
            (w, paged_attention_available(
                self._state["pool"][paged_at.index(e)], self._state.get("pscale"), self.tp > 1))
            for w, e in layout)
        self._tick_kernel_readers = sum(
            n for (_, kernel), n in self._tick_attn_kinds.items() if kernel)
        if self._spec_mode == "draft":
            dshape = jax.eval_shape(lambda: self._draft_factory(
                1, self.max_len + self._spec_k, self._dtype))
            if any(isinstance(layer, dict) and "pos" in layer
                   for layer in dshape):
                raise NotImplementedError(
                    "the draft model's KV cache must be linear at "
                    "max_len + spec_tokens (raise its sliding window)")
            # Draft KV pages come from the SAME pool as the target's —
            # one id space, one refcount, honest page accounting — but
            # live in their own ``dpool`` leaves (draft layer geometry)
            # behind their own table columns.
            dprobe = jax.eval_shape(
                lambda: self._draft_factory(1, 2, self._dtype))
            self._draft_cache_struct = jax.tree.structure(dprobe)
            self._draft_cache_axes = self._cache_length_axes(
                self._draft_factory)
            if None in self._draft_cache_axes:
                raise NotImplementedError(
                    "the draft model's cache has a leaf without a length "
                    "axis (a recurrent state): a rejected draft would have "
                    "to roll it back, and nothing snapshots it")
            dpool_leaves, self._draft_page_bytes = [], 0
            for sh, ax in zip(jax.tree.leaves(dprobe),
                              self._draft_cache_axes):
                shape = list(sh.shape)
                shape[ax] = self._page
                dpool_leaves.append(jnp.zeros(
                    (usable + 1,) + tuple(shape),
                    jnp.int8 if quant else sh.dtype))
                self._draft_page_bytes += (
                    int(np.prod(shape))
                    * (1 if quant else np.dtype(sh.dtype).itemsize)
                    + (4 if quant else 0))
            self._state["dpool"] = jax.tree.unflatten(
                self._draft_cache_struct, dpool_leaves)
            if quant:
                self._state["dpscale"] = jnp.ones(
                    (len(dpool_leaves), usable + 1), jnp.float32)
            self._dtable = np.zeros(
                (self.max_slots, self._pages_per_slot), np.int32)
        # Adapter bank: the per-slot adapter row index joins the decode
        # state ONLY when a bank is attached — a bank-less engine traces
        # byte-identical programs to the pre-adapter engine.
        self._adapters = adapters
        if adapters is not None:
            if _served_form_of(module) is not None:
                raise NotImplementedError(
                    f"{type(module).__name__} is served in another form than "
                    "the published one (served_form): an AdapterBank's "
                    "factors on q_proj / k_proj are columns in the published "
                    "order, which the served kernels no longer have, and "
                    "nothing re-forms a bank row as it is registered — "
                    "serve this family without adapters=")
            self._state["adapter_idx"] = jnp.zeros((self.max_slots,),
                                                   jnp.int32)

        # Base-weight quantization happens ONCE here, before any program is
        # staged: eligible kernels become QuantizedTensor pytree leaves
        # (per-output-channel int8) and every compiled program dequantizes
        # at its top via _dq — XLA fuses convert*scale into the consuming
        # dots, so weights at rest in HBM stay integer. The LoRA bank is
        # untouched: adapter deltas apply full precision on the dequantized
        # base, keeping multi-tenant adapters exact.
        if self._weights_dtype is not None:
            from ..adapters.quantize import quantize_base_weights
            self.params = params = quantize_base_weights(params)

        # The family's served form, likewise ONCE and before placement: the
        # programs then read the kernels as they lie (no kernel is laid out
        # anew in every tick and every chunk). After quantization, so that an
        # int8 kernel keeps the published form's per-output-channel scales:
        # its ints and its scales move together. From here on ``self.module``
        # reads ``self.params`` and neither is the published pair; the K
        # pages hold what that module's attention writes (permuted keys, for
        # a form that reorders a head's columns) and nothing else reads them.
        # A draft model goes the same way.
        self.module, self.params, leaves, nbytes = _hold_in_served_form(
            module, params)
        module, params = self.module, self.params
        if self._draft_module is not None:
            self._draft_module, self._draft_params, dleaves, dbytes = (
                _hold_in_served_form(self._draft_module, self._draft_params))
            leaves, nbytes = leaves + dleaves, nbytes + dbytes
        #: leaves (and their bytes) held in a form other than the published
        self._served_form_leaves, self._served_form_bytes = leaves, nbytes

        # CPU jit warns (and ignores) donation; donate only where it works.
        donate = () if jax.default_backend() == "cpu" else (1,)
        # With its private alias cache the engine restores prefixes by host
        # page-table writes — there is no compiled restore program at all
        # (steady state is TWO warm executables, not three).
        self._restore_prefix = None
        self._spec = None
        self._draft_chunk = None
        if self._exec is None:
            # Single-chip: commit params, state, draft and bank to the device
            # the state above was just created on — jax's default device,
            # which ReplicaSet.from_factory sets per replica. Committed
            # arguments pin every program the engine thread later dispatches
            # to that device, whatever the default is on that thread. Arrays
            # already there are aliased, not copied.
            device = next(iter(self._state["pos"].devices()))
            self.params = params = jax.device_put(params, device)
            self._state = jax.device_put(self._state, device)
            if self._draft_params is not None:
                self._draft_params = jax.device_put(self._draft_params, device)
            if adapters is not None:
                adapters.commit(device)
            self._decode = jax.jit(self._paged_decode_fn,
                                   donate_argnums=donate)
            self._prefill_chunk = jax.jit(self._paged_prefill_chunk_fn,
                                          donate_argnums=donate)
            if self._prefix_cache is not None and not self._alias_cache:
                # Only a shared EXTERNAL cache needs the copy-restore
                # program — the private cache restores by table aliasing
                # (pure host work, nothing to compile). It donates the
                # STATE only (its arg 0): the block is a live prefix-cache
                # entry that must survive the copy.
                self._restore_prefix = jax.jit(
                    self._paged_restore_prefix_fn,
                    donate_argnums=(0,) if donate else ())
            if self._spec_mode == "draft":
                # state is positional arg 2 of the spec program.
                self._spec = jax.jit(self._spec_fn,
                                     donate_argnums=(2,) if donate else ())
                if self._prefix_cache is not None:
                    # Prefix restores rebuild draft KV lazily: a
                    # draft-only chunk forward over the restored tokens
                    # (state is its positional arg 1).
                    self._draft_chunk = jax.jit(
                        self._draft_chunk_fn,
                        donate_argnums=(1,) if donate else ())
            elif self._spec_mode == "lookup":
                # state is positional arg 1 (no draft params argument).
                self._spec = jax.jit(self._spec_lookup_fn,
                                     donate_argnums=(1,) if donate else ())
        else:
            # Mesh-sliced compilation: derive every placement once, put
            # params/state/bank exactly onto it (jit with explicit
            # in_shardings rejects committed arrays laid out differently),
            # and compile the SAME program functions with those shardings —
            # the engine's call sites don't change at all. The page pool
            # shards on its kv-heads axis (the page axis is replicated in
            # index); the page table, masks, and per-call scalars stay
            # replicated data.
            exec_ = self._exec
            if self._weights_dtype is not None:
                # Quantized leaves shard by their LOGICAL kernel shape: q
                # takes the kernel's Megatron spec, the size-1 amax scale
                # dim replicates. Same treedef as params, so place/jit
                # accept it like any sharding pytree.
                from ..adapters.quantize import shardings_for_quantized
                self._param_sh = shardings_for_quantized(exec_, params)
            else:
                self._param_sh = exec_.param_shardings(params)
            self.params = params = exec_.place(params, self._param_sh)
            tmpl = [jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
                    for l in jax.tree.leaves(self._state["pool"])]
            self._state_sh = exec_.state_shardings(self._state, tmpl,
                                                   self._cache_axes)
            self._block_sh = exec_.block_shardings(self._cache_struct, tmpl,
                                                   self._cache_axes)
            self._state = exec_.place(self._state, self._state_sh)
            rep = exec_.replicated
            decode_in = [self._param_sh, self._state_sh, rep, rep]
            chunk_in = [self._param_sh, self._state_sh] + [rep] * 6
            if adapters is not None:
                self._bank_sh = exec_.bank_shardings(adapters)
                adapters.place(self._bank_sh)
                decode_in.append(self._bank_sh)
                chunk_in += [rep, self._bank_sh]
            if self._spec_mode == "draft":
                # Draft params and KV replicate onto every chip of the
                # slice (see SliceExec.state_shardings): the draft scan is
                # collective-free; only the target verify is tp-sharded.
                self._draft_params = jax.device_put(self._draft_params, rep)
                chunk_in += [rep, rep]      # dparams subtree, dpages row
            self._decode = exec_.jit(
                self._paged_decode_fn, tuple(decode_in),
                (self._state_sh, rep, rep), donate_argnums=donate)
            self._prefill_chunk = exec_.jit(
                self._paged_prefill_chunk_fn, tuple(chunk_in),
                (self._state_sh, rep, self._block_sh), donate_argnums=donate)
            if not self._alias_cache:
                self._restore_prefix = exec_.jit(
                    self._paged_restore_prefix_fn,
                    (self._state_sh, self._block_sh, rep, rep, rep),
                    self._state_sh, donate_argnums=(0,) if donate else ())
            if self._spec_mode == "draft":
                spec_in = [self._param_sh, rep, self._state_sh,
                           rep, rep, rep, rep]
                if adapters is not None:
                    spec_in.append(self._bank_sh)
                self._spec = exec_.jit(
                    self._spec_fn, tuple(spec_in), (self._state_sh, rep, rep),
                    donate_argnums=(2,) if donate else ())
                if self._prefix_cache is not None:
                    self._draft_chunk = exec_.jit(
                        self._draft_chunk_fn,
                        (rep, self._state_sh, rep, rep, rep, rep),
                        self._state_sh, donate_argnums=(1,) if donate else ())
            elif self._spec_mode == "lookup":
                spec_in = [self._param_sh, self._state_sh, rep, rep, rep, rep]
                if adapters is not None:
                    spec_in.append(self._bank_sh)
                self._spec = exec_.jit(
                    self._spec_lookup_fn, tuple(spec_in),
                    (self._state_sh, rep, rep),
                    donate_argnums=(1,) if donate else ())

        if stats is None and accelerator is not None:
            stats = getattr(accelerator, "serving_stats", None)
        self._stats = stats if stats is not None else ServingStats()
        if priority_policy == "default":
            priority_policy = PriorityPolicy()
        elif priority_policy is not None and not isinstance(
                priority_policy, PriorityPolicy):
            raise TypeError(
                "priority_policy must be a PriorityPolicy, None (FCFS), or "
                f"the string 'default' (got {priority_policy!r})")
        self._priority_policy = priority_policy
        self._queue = AdmissionQueue(
            max_queued,
            rank_fn=priority_policy.rank if priority_policy is not None
            else None)
        self._slots = SlotScheduler(self.max_slots)

        # Observability: per-engine span tracer + flight recorder (black
        # box). Both are host-only — no device work, no traced arguments —
        # so enabling them cannot change the compiled programs.
        name = f"engine-{next(_ENGINE_SEQ)}"
        self._tracer = Tracer(capacity=int(trace_capacity),
                              enabled=bool(tracing), name=name)
        self._flight = FlightRecorder(capacity=int(flight_capacity),
                                      name=name, tracer=self._tracer)
        self._trace_dir = trace_dir
        self._compile_watcher = None
        self._postmortem: Optional[dict] = None

        self._accepting = False
        self._stop = False          # hard stop: cancel everything, exit now
        self._drain = False         # finish all accepted work, then exit
        self._abort_queue = False   # preemption: finish running, cancel queued
        self._error: Optional[BaseException] = None
        self._fail_injection: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._warmup_on_start = bool(warmup)

        # Liveness + fault-injection hooks (see serving/supervisor.py and
        # serving/chaos.py): the run loop publishes a monotonic heartbeat
        # every iteration so a watchdog can tell a HUNG engine (stalled
        # loop, error still None) from a dead one; a ChaosSchedule, when
        # attached, injects scripted faults keyed on the decode-tick
        # counter. ``_heartbeat_frozen`` is the chaos harness's hang mode:
        # the loop keeps running but stops publishing, which to a watchdog
        # is indistinguishable from a wedged compiled call.
        self._chaos = chaos
        self._loop_iters = 0
        self._decode_ticks = 0
        self._heartbeat = (0, time.monotonic())
        self._heartbeat_frozen = False
        # One-tick-ahead dispatch + off-thread token emission (see the
        # module docstring). ``_wedge_s`` is the chaos harness's
        # dispatched-call wedge: the next reconcile sleeps it off INSIDE
        # the barrier, so the stall is indistinguishable from a compiled
        # call that never returns.
        if int(emission_queue) < 1:
            raise ValueError(
                f"emission_queue must be >= 1 (got {emission_queue})")
        self._emission_queue = int(emission_queue)
        self._emitter: Optional[_TokenEmitter] = None
        self._wedge_s = 0.0
        # Host-blocked time (device waits) accumulated since the last
        # reconcile — subtracting it from the device-complete interval
        # is what isolates host_us_per_tick.
        self._blocked_s = 0.0
        self._last_complete_t: Optional[float] = None
        self._phases = _HostPhases(self._tracer)
        self._idle_entries = 0      # engine-thread writes; warmup() reads
        # Next decode tick that emits a tick_profile flight event (the
        # warmup reset re-arms it so a warmed engine still profiles its
        # first real tick instead of waiting out the 128-tick cadence).
        self._next_profile_tick = 1
        # Page-drain samples (wall time, cumulative pool frees) the shed
        # path turns into a pages/s rate; engine-thread writes, any-thread
        # reads of an immutable tuple snapshot.
        self._drain_samples: collections.deque = collections.deque(maxlen=256)
        if autostart:
            self.start()

    @staticmethod
    def _resolve_serving_mesh(tp, mesh, devices, resolved_mesh, params):
        """Decide this engine's slice mesh (None = single-chip path).

        Explicit spellings win: ``mesh=`` is validated tp-only (and
        checked against ``tp=`` if both are given); ``tp=`` carves one
        slice of that width from ``devices``/``jax.devices()``. Otherwise
        a mesh resolved from a prepared model/accelerator routes
        automatically when it is a multi-device tp-only mesh — and when it
        is NOT tp-only but the params are genuinely sharded across
        devices, serving it replicated would silently gather (or crash
        deep in jit with a device-set mismatch), so that raises the clear
        error here instead. A non-tp training mesh over host-resident
        params (e.g. a default dp accelerator whose params were never
        prepared) keeps the single-chip path: nothing is sharded, so
        nothing is gathered.
        """
        from .mesh_exec import SlicePlan, validate_serving_mesh

        if mesh is not None:
            validate_serving_mesh(mesh)
            if tp is not None and int(mesh.shape["tp"]) != int(tp):
                raise ValueError(
                    f"mesh= has tp={mesh.shape['tp']} but tp={tp} was also "
                    "passed; drop one or make them agree")
            return mesh
        if tp is not None:
            return SlicePlan.plan(int(tp), num_slices=1,
                                  devices=devices).build_mesh(0)
        if devices is not None:
            raise ValueError("devices= only makes sense together with tp=")
        if resolved_mesh is None or resolved_mesh.devices.size <= 1:
            return None
        import math

        non_tp = math.prod(s for ax, s in resolved_mesh.shape.items()
                           if ax != "tp")
        if non_tp == 1 and resolved_mesh.shape.get("tp", 1) > 1:
            return resolved_mesh  # tp-only training mesh: serve sliced
        spanned = set()
        for leaf in jax.tree.leaves(params):
            sharding = getattr(leaf, "sharding", None)
            device_set = getattr(sharding, "device_set", None)
            if device_set:
                spanned |= set(device_set)
        if len(spanned) > 1:
            raise ValueError(
                "params are sharded across "
                f"{len(spanned)} devices on a non-tensor-parallel mesh "
                f"({dict(resolved_mesh.shape)}); the serving engine only "
                "runs tp-only slices. Re-prepare the model under "
                "MeshConfig(dp=1, tp=N), pass tp=/mesh= explicitly, or "
                "gather params to host before serving.")
        return None

    def _cache_length_axes(self, factory) -> list[int]:
        """Per-leaf sequence-length axis of ``factory``'s cache, detected by
        comparing its ``eval_shape`` at two lengths (layouts are
        family-specific; llama is ``[1, L, n_kv, head]`` but nothing
        guarantees that elsewhere). The probes are lengths 2 and 1, where
        a windowed layer is still linear (a window >= 2 never rings at
        length 2): the page template must be the linear layout whatever
        the window. Flattened-leaf order, the same order every tree op in
        the programs uses."""
        a = jax.tree.leaves(jax.eval_shape(
            lambda: factory(1, 2, self._dtype)))
        b = jax.tree.leaves(jax.eval_shape(
            lambda: factory(1, 1, self._dtype)))
        if len(a) != len(b):
            raise NotImplementedError(
                "the KV cache changes structure between lengths 2 and 1; "
                "this layout cannot be paged")
        axes = []
        for x, y in zip(a, b):
            diff = [i for i, (m, n) in enumerate(zip(x.shape, y.shape))
                    if m != n]
            if len(diff) > 1:
                raise NotImplementedError(
                    "the page pool needs a KV leaf to carry at most one "
                    f"length axis (leaf {x.shape} vs {y.shape} at "
                    "lengths 2 / 1)")
            axes.append(diff[0] if diff else None)
        return axes

    @staticmethod
    def _recurrent_entries(cache_shape, leaf_axes) -> tuple:
        """Indices of the cache's top-level entries whose leaves have NO
        length axis (``leaf_axes`` None). An entry is of one kind: a layer
        either keeps rows or keeps a state."""
        if None not in leaf_axes:
            return ()
        out, i = [], 0
        for e, entry in enumerate(cache_shape):
            n = len(jax.tree.leaves(entry))
            kinds = {ax is None for ax in leaf_axes[i:i + n]}
            i += n
            if len(kinds) > 1:
                raise NotImplementedError(
                    f"cache entry {e} mixes leaves with and without a "
                    "length axis; an entry is paged whole or held per slot "
                    "whole")
            if kinds == {True}:
                out.append(e)
        return tuple(out)

    def _split_cache(self, cache) -> tuple:
        """``(paged entries, recurrent entries)`` of a cache in the model's
        own entry order; the cache itself and ``()`` where none is
        recurrent (every family but the state-space ones: their programs
        are traced exactly as before)."""
        if not self._recurrent_at:
            return cache, ()
        return (tuple(e for i, e in enumerate(cache)
                      if i not in self._recurrent_at),
                tuple(cache[i] for i in self._recurrent_at))

    def _join_cache(self, paged, recurrent):
        """The model's cache from its two kinds of entries."""
        if not self._recurrent_at:
            return paged
        paged, recurrent = iter(paged), iter(recurrent)
        return tuple(next(recurrent) if i in self._recurrent_at else next(paged)
                     for i in range(self._kv_entries + len(self._recurrent_at)))

    def _refuse_unproven_caches(self, module, cache_shape, draft_model,
                                spec_lookup, prefix_cache, prefix_cache_mb):
        """What the cache a module DECLARES (``init_cache``) cannot be
        served with, refused at construction with the reason, from what its
        leaves are and not from the family's name: rows that are not
        per-head K and V (latent rows), and entries without a length axis
        (recurrent state)."""
        family = type(module).__name__
        paged, _ = self._split_cache(cache_shape)
        per_head_kv = all(isinstance(e, dict) and set(e) <= {"k", "v", "pos"}
                          for e in paged)
        if hasattr(module, "init_cache") and not per_head_kv:
            if self.tp > 1:
                raise NotImplementedError(
                    f"{family} declares its own KV cache (init_cache: rows "
                    "without a head axis); tp > 1 shards cache leaves on a "
                    "heads axis (mesh_exec.heads_axis), which such rows do "
                    "not have — serve this family with tp=1")
            if self._kv_dtype is not None:
                raise NotImplementedError(
                    f"{family} declares its own KV cache (init_cache); "
                    f"kv_dtype={self._kv_dtype!r} keeps one scale a page, which "
                    "would span a latent row's normed part and its rotary "
                    "key — serve this family with kv_dtype=None")
        if not self._recurrent_at:
            return
        what = (f"{family}'s cache has {len(self._recurrent_at)} entries "
                "without a length axis (recurrent state, held per slot)")
        if draft_model is not None or spec_lookup is not None:
            raise NotImplementedError(
                f"{what}; speculative decoding writes draft tokens through "
                "the state and a rejected draft would have to roll it back — "
                "nothing snapshots it: serve without draft_model / "
                "spec_lookup")
        if self.tp > 1:
            raise NotImplementedError(
                f"{what}; no shard axis is declared for such a leaf "
                "(mesh_exec shards cache leaves on a heads axis) — serve "
                "with tp=1")
        if prefix_cache is not None or prefix_cache_mb > 0:
            raise NotImplementedError(
                f"{what}; a prefix hit restores PAGES (by aliasing or by "
                "copy) and cannot restore the state those tokens left — "
                "nothing snapshots it at chunk boundaries: serve with "
                "prefix_cache_mb=0 and no shared prefix_cache")
        if self._chunk_limit % self._chunk:
            raise ValueError(
                f"{what}; a prompt's last chunk is pulled back to end at "
                f"max_len ({self._chunk_limit}) when prefill_chunk "
                f"({self._chunk}) does not divide it, which would feed the "
                "state some tokens twice — choose a chunk that divides "
                "max_len")

    # ------------------------------------------------------------------
    # the compiled programs
    # ------------------------------------------------------------------
    @staticmethod
    def _lora_kwargs(bank, aidx) -> dict:
        """Gather one adapter row from the stacked bank at a traced index.

        Returns the ``lora=`` kwargs for ``module.apply`` — empty when no
        bank is attached, so bank-less engines never pass the kwarg (and
        non-LoRA-aware modules never see it)."""
        if bank is None:
            return {}
        return {"lora": jax.tree.map(lambda s: s[aidx], bank)}

    def _dq(self, params):
        """Dequantize int8 base weights at the top of a compiled program.

        Identity when ``weights_dtype`` is None, so full-precision engines
        trace byte-identical programs. XLA fuses the ``convert * scale``
        into each consuming dot — weights at rest in HBM stay int8."""
        if self._weights_dtype is None:
            return params
        from ..adapters.quantize import dequantize_params
        return dequantize_params(params, self._dtype)

    def _quant_page(self, pb):
        """Quantize ONE page block to (int8 page, f32 scale scalar):
        symmetric absmax over the whole page — one scale per page row is
        the whole point, it rides the page id through host alias/free/
        preempt bookkeeping with zero extra device work. The 1e-6 floor
        keeps an all-zero page's dequant finite; round-trip is idempotent
        (q*s re-quantizes to the same q), so external-cache restores that
        re-quantize a dequantized block are stable."""
        f = pb.astype(jnp.float32)
        amax = jnp.max(jnp.abs(f))
        s = jnp.maximum(amax, 1e-6) / 127.0
        q = jnp.clip(jnp.round(f / s), -127, 127).astype(jnp.int8)
        return q, s

    @program_part("kv_view")
    def _gather_view(self, pool, pages, axes=None, struct=None, scales=None):
        """One slot's dense cache VIEW from the pool: gather its page rows
        (``pages`` [Np] i32 pool ids, 0 = scratch for unallocated entries)
        and merge the page axis into the length axis — each leaf becomes
        ``[1, Np * P, ...]``, exactly the linear cache the unchanged
        forward expects. Scratch garbage sits at positions the attention
        mask (causal and/or sliding-window) already excludes. ``axes`` /
        ``struct`` default to the TARGET cache geometry; speculative
        engines pass the draft pool's. ``scales`` (the pool's per-page
        scale array, rows aligned with the pool leaves) dequantizes int8
        page rows in the same gather — None on fp engines."""
        axes = self._cache_axes if axes is None else axes
        struct = self._cache_struct if struct is None else struct
        leaves = []
        for i, (l, ax) in enumerate(zip(jax.tree.leaves(pool), axes)):
            # a page larger than the gather takes whole comes in row-parts
            # (models.llama.gather_pages); pages that fit: the plain gather
            rows = gather_pages(l, pages) if ax == 1 else l[pages]
            if scales is not None:
                s = scales[i][pages].reshape((-1,) + (1,) * (rows.ndim - 1))
                rows = (rows.astype(jnp.float32) * s).astype(self._dtype)
            g = jnp.moveaxis(rows, 0, ax)
            shape = (list(g.shape[:ax]) + [g.shape[ax] * g.shape[ax + 1]]
                     + list(g.shape[ax + 2:]))
            leaves.append(g.reshape(shape))
        return jax.tree.unflatten(struct, leaves)

    def _scatter_page(self, pool_leaves, view_leaves, src_page, tgt,
                      axes=None, scales=None):
        """Write view page ``src_page`` back into pool page ``tgt`` (both
        traced i32). ``tgt = 0`` discards into scratch; an out-of-range
        ``src_page`` clamps to the view's last page (jax dynamic_slice
        semantics), which callers pair with a scratch target — the two
        clamps together are what let a FIXED number of scatter steps cover
        a variable number of genuinely-written pages. With ``scales``
        the fp page block quantizes to int8 on the way in and its scale
        lands at ``scales[leaf, tgt]`` (scratch writes overwrite row 0,
        harmlessly). Returns ``(pool_leaves, scales)``."""
        axes = self._cache_axes if axes is None else axes
        out = []
        for i, (pl, vl, ax) in enumerate(zip(pool_leaves, view_leaves, axes)):
            start = [0] * vl.ndim
            start[ax] = src_page * self._page
            sizes = list(vl.shape)
            sizes[ax] = self._page
            pb = jax.lax.dynamic_slice(vl, tuple(start), tuple(sizes))
            if scales is not None:
                pb, s = self._quant_page(pb)
                scales = jax.lax.dynamic_update_slice(
                    scales, s.reshape(1, 1), (i, tgt))
            out.append(jax.lax.dynamic_update_slice(
                pl, pb[None].astype(pl.dtype), (tgt,) + (0,) * pb.ndim))
        return out, scales

    @program_part("kv_write")
    def _scatter_chunk_pages(self, pool_leaves, view_leaves, axes, pages,
                             offset, C, scales=None):
        """Scatter a chunk's writes (positions ``[offset, offset + C)``)
        back into the pool: at most ``C/P + 1`` pages (the pulled-back
        final chunk may start mid-page); the possibly-untouched trailing
        step routes to scratch. Returns ``(pool_leaves, scales)``."""
        p0 = offset // self._page
        for pg in range(C // self._page + 1):
            tid = jax.lax.dynamic_slice(pages, (p0 + pg,), (1,))[0]
            touched = (p0 + pg) * self._page < offset + C
            pool_leaves, scales = self._scatter_page(
                pool_leaves, view_leaves, p0 + pg,
                jnp.where(touched, tid, 0), axes, scales)
        return pool_leaves, scales

    def _apply_counted(self, params, ids, **kwargs):
        """``module.apply`` on the cached path -> ``(logits, cache,
        counts)``: where the module sows per-call counters
        (``serving_stats_collection``), ``counts`` is their sum over the
        layers, an int vector; else None and the call is the plain one."""
        if self._stats_collection is None:
            logits, cache = self.module.apply({"params": params}, ids,
                                              **kwargs)
            return logits, cache, None
        (logits, cache), sown = self.module.apply(
            {"params": params}, ids, mutable=[self._stats_collection],
            **kwargs)
        return logits, cache, sum(jax.tree.leaves(sown))

    def _moe_counts(self, counts) -> dict:
        """A record's expert-pick keywords from the counters a program
        returned (host side): a family with zero-compute experts
        (``serving_zero_picks``) sows their picks behind the pick vector."""
        if counts is None or not self._zero_picks:
            return {"moe_picks": counts}
        return {"moe_picks": counts[:-1], "moe_zero_picks": int(counts[-1])}

    @staticmethod
    def _slot_recurrent_rows(state, slot, offset):
        """The slot's rows of the recurrent entries as a chunk at ``offset``
        takes them up: zeros for a prompt's first chunk, whatever the slot's
        last stream left behind (a preempted stream's re-prefill of ``prompt
        + tokens`` starts here too, so it resumes exactly with no
        snapshot)."""
        return jax.tree.map(
            lambda a: jnp.where(offset == 0, jnp.zeros_like(a[0]), a[slot]),
            state["recurrent"])

    def _paged_prefill_chunk_fn(self, params, state, ids_c, slot, pages,
                                offset, true_len, rng, *extra):
        """ONE chunk of prefill: ids_c ``[1, C]`` (tail chunks edge-padded
        on the host); slot/offset/true_len traced i32 scalars, ``pages``
        the slot's table row. Gathers the slot's pages into a dense view,
        runs the chunk at ``cache_pos=offset`` (attention never reads the
        view past ``hi = offset + C`` rounded out to a key block, nor
        before a windowed layer's ``offset - window + 1``: the trip count
        of one loop, so every offset is this one executable; what a
        previous occupant left inside the last block is masked, see the
        module docstring), selects a candidate first token via the shared
        epilogue (real only in the chunk containing ``true_len - 1``),
        scatters back only the pages the chunk wrote, and writes the slot
        rows — ``pos[slot] = true_len`` on EVERY call. Returns ``(state,
        first_token, block)``: the block is the chunk's own KV (each leaf
        sliced to width C on its length axis), so an external prefix cache
        is fed by THIS executable, no separate extract program.

        ``extra`` is positional (mesh in_shardings forbid kwargs) and holds
        whatever this engine's config adds, in order: ``aidx, bank`` when
        an adapter bank is attached, then ``dparams, dpages`` when a draft
        model speculates — the SAME call also prefills the slot's paged
        draft KV, keeping the warm-executable count unchanged."""
        extra = list(extra)
        aidx = bank = None
        if self._adapters is not None:
            aidx, bank = extra[0], extra[1]
            del extra[:2]
        dparams = dpages = None
        if self._spec_mode == "draft":
            dparams, dpages = extra
        params = self._dq(params)
        C = ids_c.shape[1]
        # Per-page scale arrays ride the state dict only on int8 engines —
        # state.get() is None otherwise and every quant/dequant site below
        # vanishes, leaving the fp program byte-identical.
        scales = state.get("pscale")
        view = self._gather_view(state["pool"], pages, scales=scales)
        recurrent, kwargs = (), self._lora_kwargs(bank, aidx)
        if self._recurrent_at:
            # steps past the prompt (the last chunk's edge padding) leave
            # the recurrent rows as they are
            recurrent = self._slot_recurrent_rows(state, slot, offset)
            kwargs["valid_len"] = jnp.minimum(true_len - offset, C)
        logits, cache, counts = self._apply_counted(
            params, ids_c, cache=self._join_cache(view, recurrent),
            cache_pos=offset, **kwargs)
        view, recurrent = self._split_cache(cache)
        with program_part("sample"):
            tok, done, rng_carry = _chunk_prefill_token(
                logits, rng, self._select, self.eos_token_id, ids_c.dtype,
                true_len, offset)
        view_leaves = jax.tree.leaves(view)
        # The block is sliced from the DEQUANTIZED view — full precision,
        # so external prefix caches stay layout-compatible across engines
        # (restore re-quantizes; the round-trip is idempotent).
        block = jax.tree.unflatten(
            self._cache_struct,
            [jax.lax.dynamic_slice_in_dim(l, offset, C, axis=ax)
             for l, ax in zip(view_leaves, self._cache_axes)])
        pool_leaves, scales = self._scatter_chunk_pages(
            jax.tree.leaves(state["pool"]), view_leaves, self._cache_axes,
            pages, offset, C, scales)
        with program_part("sample"):
            slot_rows = dict(
                pos=state["pos"].at[slot].set(true_len),
                tok=state["tok"].at[slot].set(tok[0].astype(jnp.int32)),
                rng=state["rng"].at[slot].set(rng_carry),
                done=state["done"].at[slot].set(done[0]),
            )
        new_state = dict(
            state, pool=jax.tree.unflatten(self._cache_struct, pool_leaves),
            **slot_rows)
        if scales is not None:
            new_state["pscale"] = scales
        if self._recurrent_at:
            with program_part("kv_write"):
                new_state["recurrent"] = jax.tree.map(
                    lambda a, r: a.at[slot].set(r), state["recurrent"], recurrent)
        if bank is not None:
            new_state["adapter_idx"] = state["adapter_idx"].at[slot].set(aidx)
        if dparams is not None:
            # The draft stays base-weight even under an adapter bank: its
            # proposals only steer acceptance, never the emitted law.
            dscales = state.get("dpscale")
            dview = self._gather_view(state["dpool"], dpages,
                                      self._draft_cache_axes,
                                      self._draft_cache_struct, dscales)
            _, dview = self._draft_module.apply(
                {"params": dparams}, ids_c, cache=dview, cache_pos=offset)
            dpool_leaves, dscales = self._scatter_chunk_pages(
                jax.tree.leaves(state["dpool"]), jax.tree.leaves(dview),
                self._draft_cache_axes, dpages, offset, C, dscales)
            new_state["dpool"] = jax.tree.unflatten(
                self._draft_cache_struct, dpool_leaves)
            if dscales is not None:
                new_state["dpscale"] = dscales
        if counts is not None:
            # the module's counters ride behind the token: one transfer
            return new_state, jnp.concatenate(
                [tok[:1].astype(jnp.int32), counts.astype(jnp.int32)]), block
        return new_state, tok[0], block

    def _draft_chunk_fn(self, dparams, state, ids_c, slot, dpages, offset):
        """Draft-only chunk forward: rebuild a prefix-restored slot's draft
        KV for one already-committed chunk (the restored target pages carry
        no draft KV). Runs the cheap draft model only — the target's
        prefix-cache FLOP savings survive — and scatters the chunk's draft
        pages exactly like the fused prefill. Compiled (and warmed) only on
        draft-mode speculative engines with a prefix cache attached."""
        del slot  # symmetry with the fused chunk program's signature
        C = ids_c.shape[1]
        dscales = state.get("dpscale")
        dview = self._gather_view(state["dpool"], dpages,
                                  self._draft_cache_axes,
                                  self._draft_cache_struct, dscales)
        _, dview = self._draft_module.apply(
            {"params": dparams}, ids_c, cache=dview, cache_pos=offset)
        dpool_leaves, dscales = self._scatter_chunk_pages(
            jax.tree.leaves(state["dpool"]), jax.tree.leaves(dview),
            self._draft_cache_axes, dpages, offset, C, dscales)
        out = dict(state, dpool=jax.tree.unflatten(
            self._draft_cache_struct, dpool_leaves))
        if dscales is not None:
            out["dpscale"] = dscales
        return out

    def _paged_restore_prefix_fn(self, state, block, pages_c, slot, true_len):
        """Copy-restore from an EXTERNAL (fleet-shared) prefix cache: split
        one cached ``[1, C]`` block into ``C/P`` pages and write each into the pool page named by ``pages_c`` (traced
        [C/P] i32 — the slot's freshly-allocated table entries). Pins
        ``pos[slot] = true_len`` like every restore. The engine's PRIVATE
        cache never calls this — it restores by host table aliasing."""
        pool_leaves = jax.tree.leaves(state["pool"])
        scales = state.get("pscale")
        out = []
        for i, (pl, blk, ax) in enumerate(zip(pool_leaves,
                                              jax.tree.leaves(block),
                                              self._cache_axes)):
            Cp = blk.shape[ax] // self._page
            shape = list(blk.shape)
            shape[ax:ax + 1] = [Cp, self._page]
            pages_blk = jnp.moveaxis(blk.reshape(shape), ax, 0)
            for j in range(Cp):
                pb = pages_blk[j]
                if scales is not None:
                    # Cached blocks are fp; re-quantize on restore (the
                    # round-trip is idempotent, so restored pages dequant
                    # to the same values the producing engine attended).
                    pb, s = self._quant_page(pb)
                    scales = jax.lax.dynamic_update_slice(
                        scales, s.reshape(1, 1), (i, pages_c[j]))
                pl = jax.lax.dynamic_update_slice(
                    pl, pb[None].astype(pl.dtype),
                    (pages_c[j],) + (0,) * pb.ndim)
            out.append(pl)
        new_state = dict(
            state,
            pool=jax.tree.unflatten(self._cache_struct, out),
            pos=state["pos"].at[slot].set(true_len),
        )
        if scales is not None:
            new_state["pscale"] = scales
        return new_state

    @program_part("kv_view")
    def _gather_views_all_slots(self, pool, table, axes=None, struct=None,
                                scales=None):
        """Batched :meth:`_gather_view`: ``table`` [S, Np] → per-leaf
        ``[S, 1, Np*P, ...]`` dense views, slot axis leading so the decode
        vmap runs over it unchanged. ``axes``/``struct`` default to the
        target cache geometry (the draft pool passes its own); ``scales``
        dequantizes int8 page rows in the same gather."""
        axes = self._cache_axes if axes is None else axes
        struct = self._cache_struct if struct is None else struct
        leaves = []
        for i, (l, ax) in enumerate(zip(jax.tree.leaves(pool), axes)):
            rows = l[table]
            if scales is not None:
                s = scales[i][table].reshape(
                    table.shape + (1,) * (rows.ndim - 2))
                rows = (rows.astype(jnp.float32) * s).astype(self._dtype)
            g = jnp.moveaxis(rows, 1, ax + 1)
            shape = (list(g.shape[:ax + 1])
                     + [g.shape[ax + 1] * g.shape[ax + 2]]
                     + list(g.shape[ax + 3:]))
            leaves.append(g.reshape(shape))
        return jax.tree.unflatten(struct, leaves)

    @program_part("kv_write")
    def _scatter_slot_pages(self, pool_leaves, nv_leaves, axes, table,
                            active, pos, last_off, steps, scales=None):
        """Scatter every slot's speculative writes back into the pool: the
        pages covering positions ``pos[s] .. pos[s] + last_off``, in a
        FIXED ``steps`` scatter steps per slot. Steps past the touched
        range, and every step of an inactive slot, route to scratch (page
        0) — the same clamp pairing as :meth:`_scatter_page`. Returns
        ``(pool_leaves, scales)``."""
        P = self._page
        for s in range(self.max_slots):
            p0 = pos[s] // P
            for pg in range(steps):
                tid = jax.lax.dynamic_slice(table[s], (p0 + pg,), (1,))[0]
                touched = (p0 + pg) * P <= pos[s] + last_off
                tgt = jnp.where(active[s] & touched, tid, 0)
                new_pool = []
                for i, (pl, vl, ax) in enumerate(zip(pool_leaves, nv_leaves,
                                                     axes)):
                    start = [0] * vl.ndim
                    start[0] = s
                    start[ax + 1] = (p0 + pg) * P
                    sizes = list(vl.shape)
                    sizes[0] = 1
                    sizes[ax + 1] = P
                    pb = jax.lax.dynamic_slice(vl, tuple(start),
                                               tuple(sizes))[0]
                    if scales is not None:
                        pb, sc = self._quant_page(pb)
                        scales = jax.lax.dynamic_update_slice(
                            scales, sc.reshape(1, 1), (i, tgt))
                    new_pool.append(jax.lax.dynamic_update_slice(
                        pl, pb[None].astype(pl.dtype),
                        (tgt,) + (0,) * pb.ndim))
                pool_leaves = new_pool
        return pool_leaves, scales

    def _paged_decode_fn(self, params, state, active, table, bank=None):
        """One tick: a batch-1 single-token forward vmapped over the slot
        axis (per-slot scalar cache_pos, per-slot rng chain,
        :func:`generation._next_token` — bitwise the same selection as
        offline's scan body) whose attention reads the page pool IN PLACE.
        No slot's view is gathered: every lane's cache is a
        ``models.llama.PagedCache`` — the pool, shared, and the lane's row
        of ``table`` — and the two cached-attention entry points every
        family calls attend over one work list of the live ``(slot, key
        block)`` pairs of all lanes (``_attend_work_list``): the rows the
        running streams hold, from the window's start on a windowed layer;
        an inactive (or ``PREFILLING``) slot owns none, whatever its stale
        ``pos``. The model returns each lane's new row, not a cache, and
        the tick writes it once: one scatter of ``S`` rows a leaf at
        ``table[s, pos[s] // P]``, row ``pos[s] % P`` (an int8 pool
        re-quantises the ``S`` touched pages as one batch). Inactive slots
        write to scratch and their pos/tok/rng/done stay frozen, so a stale
        ``pos`` can't corrupt the pool. The host guarantees an active
        slot's ``pos`` page is allocated before every tick. Returns
        ``(state, tokens [S], done [S])``."""
        params = self._dq(params)
        scales = state.get("pscale")
        scale_rows = (None,) * len(state["pool"]) if scales is None else jax.tree.unflatten(
            self._cache_struct, list(scales))

        def one_slot(pages, live, tok, pos, rng, done, recurrent, aidx=None):
            cache = tuple(
                PagedCache(pool=layer, scales=sc, pages=pages, live=live,
                           dtype=None if scales is None else self._dtype,
                           sharded=self.tp > 1)
                for layer, sc in zip(state["pool"], scale_rows))
            logits, rows, counts = self._apply_counted(
                params, tok[None, None],
                cache=self._join_cache(cache, recurrent),
                cache_pos=pos, **self._lora_kwargs(bank, aidx))
            with program_part("sample"):
                rng, sub = jax.random.split(rng)
                nxt, done = _next_token(logits[:, -1], sub, jnp.zeros((1, 1), bool),
                                        done[None], self._select, self.eos_token_id,
                                        tok.dtype)
            return self._split_cache(rows), nxt[0], rng, done[0], counts

        # a lane's recurrent rows ride the vmap with it (() where the cache
        # has none: nothing is added to the program)
        vmap_args = [table, active, state["tok"], state["pos"], state["rng"],
                     state["done"], state.get("recurrent", ())]
        if bank is not None:
            vmap_args.append(state["adapter_idx"])
        (rows, recurrent), toks, rngs, dones, counts = jax.vmap(one_slot)(
            *vmap_args)
        toks_out = toks
        if counts is not None:
            # active slots' counters, summed, ride behind the tokens
            counts = jnp.where(active[:, None], counts, 0).sum(0)
            toks_out = jnp.concatenate([toks, counts.astype(toks.dtype)])
        pool_leaves, scales = self._write_tick_rows(state, rows, active, table, scales)
        with program_part("sample"):
            slot_rows = dict(
                pos=jnp.where(active, state["pos"] + 1, state["pos"]),
                tok=jnp.where(active, toks, state["tok"]),
                rng=jnp.where(active[:, None], rngs, state["rng"]),
                done=jnp.where(active, dones, state["done"]),
            )
        new_state = dict(
            state, pool=jax.tree.unflatten(self._cache_struct, pool_leaves),
            **slot_rows)
        if scales is not None:
            new_state["pscale"] = scales
        if self._recurrent_at:
            # a lane without a stream keeps its rows, as it keeps its pos
            with program_part("kv_write"):
                new_state["recurrent"] = jax.tree.map(
                    lambda new, old: jnp.where(
                        active.reshape((-1,) + (1,) * (old.ndim - 1)), new, old),
                    recurrent, state["recurrent"])
        return new_state, toks_out, dones

    @program_part("kv_write")
    def _write_tick_rows(self, state, rows, active, table, scales):
        """The tick's one scatter of ``S`` rows a leaf (an int8 pool
        re-quantises the ``S`` touched pages as one batch). Returns
        ``(pool_leaves, scales)``."""
        P = self._page
        lanes = jnp.arange(self.max_slots)
        tgt = jnp.where(active, table[lanes, state["pos"] // P], 0)
        off = state["pos"] % P
        pool_leaves = []
        for i, (pl, rl) in enumerate(zip(jax.tree.leaves(state["pool"]),
                                         jax.tree.leaves(rows))):
            if scales is None:
                pool_leaves.append(pl.at[tgt, 0, off].set(rl[:, 0, 0]))
                continue
            pb = (pl[tgt].astype(jnp.float32) * scales[i][tgt].reshape(
                (-1,) + (1,) * (pl.ndim - 1))).astype(self._dtype)
            pb, sc = jax.vmap(self._quant_page)(
                pb.at[lanes, 0, off].set(rl[:, 0, 0]))
            pool_leaves.append(pl.at[tgt].set(pb))
            scales = scales.at[i, tgt].set(sc)
        return pool_leaves, scales

    @program_part("sample")
    def _spec_accept(self, logits, drafts, done, rem, rng):
        """Per-slot accept epilogue shared by BOTH speculative programs
        (draft-model and prompt-lookup): run the factored accept rule
        (:func:`generation.speculative_emit` — greedy longest-matching-
        prefix, or the exact rejection-sampling rule when this engine
        samples) and derive the slot's committed count, carry token, and
        eos latch. Greedy engines pass the rng through UNTOUCHED (greedy
        selection never consumes it — spec streams stay bit-comparable to
        plain greedy ones); sampled engines split it once per tick, so a
        slot's rng trajectory is one split per verify, mirroring one split
        per plain tick."""
        K = drafts.shape[0]
        if self._sampling is not None:
            rng, step_rng = jax.random.split(rng)
        else:
            step_rng = rng  # unused by the greedy rule
        m, emit = speculative_emit(logits, drafts, step_rng, self._warp,
                                   self.eos_token_id, drafts.dtype,
                                   prior_done=done)
        n = jnp.minimum(m + 1, rem)
        new_tok = emit[jnp.clip(n - 1, 0, K)]
        if self.eos_token_id is not None:
            new_done = new_tok == jnp.asarray(self.eos_token_id,
                                              drafts.dtype)
        else:
            new_done = done
        return emit, n, new_tok, new_done, rng

    def _spec_fn(self, params, dparams, state, active, table, dtable,
                 remaining, bank=None):
        """One SPECULATIVE tick, draft-model mode: per slot, scan K draft
        steps through the slot's PAGED draft view (drafts are the argmax
        of the warped draft logits — a delta proposal, so the sampled
        accept rule stays exact), verify draft + carry token in ONE fixed
        ``[1, K+1]`` target forward against the paged target view (the
        slot's adapter row gathered inside, like the plain tick), and
        accept via :meth:`_spec_accept`. Committing the emitted chain's
        first ``n = min(accepted + 1, remaining)`` tokens is
        token-identical (greedy) / distribution-exact (sampled) to ``n``
        plain ticks.

        Rejected-draft KV (positions past ``pos + n - 1``) is garbage in
        BOTH pools, but the next verify rewrites target positions
        ``pos+n .. pos+n+K`` and the next draft scan rewrites draft
        positions ``pos+n .. pos+n+K-1`` before any query can attend them
        — the same overwrite-before-attend argument the chunked prefill
        pad relies on. Returns ``(state, emitted [S, K+1], n [S])``."""
        P, K = self._page, self._spec_k
        params = self._dq(params)
        scales = state.get("pscale")
        dscales = state.get("dpscale")
        views = self._gather_views_all_slots(state["pool"], table,
                                             scales=scales)
        dviews = self._gather_views_all_slots(
            state["dpool"], dtable, self._draft_cache_axes,
            self._draft_cache_struct, dscales)

        def one_slot(view, dview, tok, pos, done, rem, rng, aidx=None):
            def dstep(carry, _):
                dc, cur, p = carry
                dlog, dc = self._draft_module.apply(
                    {"params": dparams}, cur[None, None], cache=dc,
                    cache_pos=p)
                row = dlog[0, -1][None]
                if self._warp is not None:
                    row = self._warp(row)
                nxt = jnp.argmax(row[0], axis=-1).astype(tok.dtype)
                return (dc, nxt, p + 1), nxt
            (dview, _, _), drafts = jax.lax.scan(
                dstep, (dview, tok, pos), None, length=K)
            ids_v = jnp.concatenate([tok[None], drafts])[None]
            logits, view = self.module.apply(
                {"params": params}, ids_v, cache=view, cache_pos=pos,
                **self._lora_kwargs(bank, aidx))
            emit, n, new_tok, new_done, rng = self._spec_accept(
                logits[0], drafts, done, rem, rng)
            return view, dview, new_tok, n, emit, new_done, rng

        vmap_args = [views, dviews, state["tok"], state["pos"],
                     state["done"], remaining, state["rng"]]
        if bank is not None:
            vmap_args.append(state["adapter_idx"])
        (new_views, new_dviews, toks, ns, emit, dones,
         rngs) = jax.vmap(one_slot)(*vmap_args)
        # A verify writes target positions pos .. pos+K (K//P + 2 scatter
        # steps); the draft scan writes draft positions pos .. pos+K-1.
        # Pages past the slot's allocated frontier (table entry 0, or an
        # untouched trailing step) land in scratch; their positions are
        # rewritten by the next verify before anything attends them.
        pool_leaves, scales = self._scatter_slot_pages(
            jax.tree.leaves(state["pool"]), jax.tree.leaves(new_views),
            self._cache_axes, table, active, state["pos"], K, K // P + 2,
            scales)
        dpool_leaves, dscales = self._scatter_slot_pages(
            jax.tree.leaves(state["dpool"]), jax.tree.leaves(new_dviews),
            self._draft_cache_axes, dtable, active, state["pos"], K - 1,
            (K - 1) // P + 2, dscales)
        state = dict(
            state,
            pool=jax.tree.unflatten(self._cache_struct, pool_leaves),
            dpool=jax.tree.unflatten(self._draft_cache_struct, dpool_leaves),
            pos=jnp.where(active, state["pos"] + ns, state["pos"]),
            tok=jnp.where(active, toks, state["tok"]),
            rng=jnp.where(active[:, None], rngs, state["rng"]),
            done=jnp.where(active, dones, state["done"]),
        )
        if scales is not None:
            state["pscale"] = scales
        if dscales is not None:
            state["dpscale"] = dscales
        return state, emit, ns

    def _spec_lookup_fn(self, params, state, active, table, remaining,
                        proposals, bank=None):
        """One SPECULATIVE tick, prompt-lookup mode: ``proposals`` [S, K]
        arrive as traced host data (the n-gram matcher runs in numpy —
        see :meth:`_lookup_proposals`), so the program is just the target
        verify + accept: no draft model, no draft KV, no second pool. A
        miss proposes garbage the verifier rejects at its first token —
        correctness never depends on proposal quality. Returns
        ``(state, emitted [S, K+1], n [S])`` like :meth:`_spec_fn`."""
        P, K = self._page, self._spec_k
        params = self._dq(params)
        scales = state.get("pscale")
        views = self._gather_views_all_slots(state["pool"], table,
                                             scales=scales)

        def one_slot(view, tok, pos, done, rem, rng, drafts, aidx=None):
            drafts = drafts.astype(tok.dtype)
            ids_v = jnp.concatenate([tok[None], drafts])[None]
            logits, view = self.module.apply(
                {"params": params}, ids_v, cache=view, cache_pos=pos,
                **self._lora_kwargs(bank, aidx))
            emit, n, new_tok, new_done, rng = self._spec_accept(
                logits[0], drafts, done, rem, rng)
            return view, new_tok, n, emit, new_done, rng

        vmap_args = [views, state["tok"], state["pos"], state["done"],
                     remaining, state["rng"], proposals]
        if bank is not None:
            vmap_args.append(state["adapter_idx"])
        new_views, toks, ns, emit, dones, rngs = jax.vmap(one_slot)(
            *vmap_args)
        pool_leaves, scales = self._scatter_slot_pages(
            jax.tree.leaves(state["pool"]), jax.tree.leaves(new_views),
            self._cache_axes, table, active, state["pos"], K, K // P + 2,
            scales)
        state = dict(
            state,
            pool=jax.tree.unflatten(self._cache_struct, pool_leaves),
            pos=jnp.where(active, state["pos"] + ns, state["pos"]),
            tok=jnp.where(active, toks, state["tok"]),
            rng=jnp.where(active[:, None], rngs, state["rng"]),
            done=jnp.where(active, dones, state["done"]),
        )
        if scales is not None:
            state["pscale"] = scales
        return state, emit, ns

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self):
        """Spawn the engine thread (idempotent) and run warmup traffic."""
        if self._thread is not None:
            return
        if self._compile_watcher is None:
            # Black-box compile accounting: any XLA compile while this
            # replica serves is a flight event (a steady-state compile is
            # the zero-recompile invariant breaking in production).
            # Unregistered in shutdown() AND the run loop's finally, so a
            # killed engine never leaks its process-global listener.
            from ..utils.profiling import CompileWatcher

            self._compile_watcher = CompileWatcher(
                on_event=lambda event, duration_s: self._flight.record(
                    "compile", event=event, duration_s=duration_s))
            self._compile_watcher.start()
        self._accepting = True
        self._heartbeat = (self._loop_iters, time.monotonic())
        self._heartbeat_frozen = False
        if self._emitter is None or not self._emitter.alive:
            self._emitter = _TokenEmitter(self._emission_queue,
                                          self._stats, self._tracer)
        self._thread = threading.Thread(target=self._run,
                                        name="serving-engine", daemon=True)
        self._thread.start()
        if self._warmup_on_start:
            self.warmup()

    def warmup(self, timeout: float = 120.0):
        """Compile every steady-state program by pushing dummy requests
        through the normal path: one chunk call + one decode tick, and —
        when a multi-chunk prompt fits the engine at all — two identical
        two-chunk prompts so the second one's prefix hit compiles
        ``restore_prefix`` (and the draft-only chunk) where they exist.
        ``ignore_eos`` keeps the dummies decoding even if the model emits eos immediately. Counters reset and the
        prefix cache is cleared afterwards so warmup traffic never
        pollutes serving metrics (or lingers as phantom cached prefixes).
        Ends with the collector's full pass, which set-up leaves due."""
        req = self.submit(np.zeros((1, 1), np.int32), max_new_tokens=2,
                          seed=0, ignore_eos=True, block=True)
        if not req.wait(timeout):
            raise TimeoutError("engine warmup did not finish "
                               f"within {timeout}s")
        self._raise_if_failed(req)
        if (self._prefix_cache is not None
                and self._chunk + 2 <= self._chunk_limit):
            ids = np.zeros((1, self._chunk + 1), np.int32)
            for _ in range(2):
                r = self.submit(ids, max_new_tokens=1, seed=0,
                                ignore_eos=True, block=True)
                if not r.wait(timeout):
                    raise TimeoutError("engine warmup did not finish "
                                       f"within {timeout}s")
                self._raise_if_failed(r)
        # The loop hands its last phase timings to the stats when it goes
        # idle: reset only after that, or they would open the new window.
        settled = self._idle_entries
        deadline = time.monotonic() + timeout
        while (self._idle_entries == settled and self.running
               and time.monotonic() < deadline):
            time.sleep(0.001)
        self._stats.reset()
        if self._prefix_cache is not None:
            self._prefix_cache.clear()
        # Warmup traffic (and its compiles) must not pollute traces,
        # postmortems, or the compile counters, same as the stats reset.
        self._tracer.clear()
        self._flight.clear()
        self._next_profile_tick = self._decode_ticks + 1
        if self._compile_watcher is not None:
            self._compile_watcher.reset()
        # Tracing and compiling allocate millions of containers, so the
        # collector's next full pass falls due about here: left to the
        # allocation count it lands in the first seconds of serving or just
        # before them (a 55-60 ms stop of every thread at 10 GB of weights),
        # by how many objects set-up happened to make. Take it now, once:
        # the counters restart from a heap that is all long-lived.
        gc.collect()

    @staticmethod
    def _raise_if_failed(req):
        if req.status != RequestStatus.COMPLETED:
            raise RuntimeError(f"warmup request {req.status.value}") from req.error

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop the engine. ``drain=True`` finishes every accepted request
        (queued and running) first; ``drain=False`` cancels them. Either
        way, blocks for the engine thread (up to ``timeout``) and then
        drains in-flight async checkpoint saves — a serving process is
        often the same process that just trained the weights it serves,
        and exiting with Orbax writes still in flight drops them."""
        from .. import checkpointing

        self._accepting = False
        if drain:
            self._drain = True
        else:
            self._stop = True
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        # The run loop's finally also closes the queue; doing it here too
        # covers an engine that was never started (autostart=False), so a
        # blocked submit can never outlive the engine either way.
        self._queue.close()
        if self._emitter is not None:
            # Drain-then-join (idempotent — the run loop's finally already
            # closed it on a normal exit): buffered tokens and deferred
            # completions are delivered, never dropped.
            self._emitter.close(timeout)
        self._stop_compile_watcher()
        if self._trace_dir is not None and self._error is None:
            self._dump_debug_files()
        checkpointing.wait_for_saves()
        if self._error is not None:
            raise RuntimeError("serving engine died") from self._error

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    @property
    def healthy(self) -> bool:
        """Live and serviceable: the engine thread is running, no fatal
        error has been recorded, and admission is open. The router's
        health checks key off this."""
        return self.running and self._error is None and self._accepting

    @property
    def error(self) -> Optional[BaseException]:
        """The fatal error that killed the run loop, if any."""
        return self._error

    @property
    def free_slots(self) -> int:
        """Decode lanes currently unoccupied (router free-slot routing)."""
        return self._slots.free_slots

    @property
    def queue_depth(self) -> int:
        """Requests waiting for admission right now."""
        return len(self._queue)

    @property
    def page_size(self) -> int:
        """Tokens per KV page."""
        return self._page

    @property
    def total_pages(self) -> int:
        """Usable pool pages."""
        return self._pool.num_pages

    @property
    def free_pages(self) -> int:
        """Unallocated pool pages right now."""
        return self._pool.free_pages

    @property
    def _spec_page_factor(self) -> int:
        """Pages-per-token multiplier for admission math: a draft-model
        speculative engine allocates a DRAFT page alongside every target
        page (same pool, same id space), so its real per-request footprint
        is double the token count's page cost. Lookup-mode speculation
        drafts from host data and costs nothing extra."""
        return 2 if self._spec_mode == "draft" else 1

    def page_deficit(self, total_tokens: int) -> int:
        """How many pages this engine is SHORT for a request of
        ``total_tokens`` (prompt + max_new): 0 means the pool can hold it
        right now, >0 means admitting it would lean on preemption. The
        router folds this into its least-loaded
        score so long prompts route to replicas with free pages — and a
        draft-speculating replica reports its doubled footprint
        (:attr:`_spec_page_factor`), so the router never over-admits it
        relative to its real pool pressure."""
        if total_tokens <= 0:
            return 0
        needed = (-(-int(total_tokens) // self._page)
                  * self._spec_page_factor)
        return max(0, needed - self._pool.free_pages)

    @property
    def heartbeat(self) -> tuple:
        """``(loop_iterations, wall_time)`` published by the run loop at
        the top of EVERY iteration (idle iterations included — the loop
        polls the queue at ``idle_poll_s``, so a live engine republishes
        many times a second) AND at every reconcile barrier — so under
        one-tick-ahead dispatch a wedge inside the dispatched call still
        stalls the heartbeat within one tick. A watchdog that sees the
        wall time stall while :attr:`error` stays None is looking at a
        HUNG engine — e.g. a compiled call that never returned — which
        lazy health checks can never catch (see
        :class:`~.supervisor.FleetSupervisor`)."""
        return self._heartbeat

    @property
    def decode_ticks(self) -> int:
        """Decode ticks executed since construction — the deterministic
        clock :class:`~.chaos.ChaosSchedule` keys scripted faults on
        (ticks advance with token progress, unlike wall time)."""
        return self._decode_ticks

    def page_drain_rate(self, window_s: float = 15.0) -> float:
        """Observed pool page-free rate (pages/second) over the last
        ``window_s`` of decode ticks, 0.0 when not yet observed.
        The gateway divides a projected page deficit by this to derive
        Retry-After for a pressure shed — "the pool frees ~N pages/s, so
        your M-page deficit clears in about M/N seconds"."""
        samples = list(self._drain_samples)
        if len(samples) < 2:
            return 0.0
        now = time.monotonic()
        recent = [s for s in samples if now - s[0] <= window_s]
        if len(recent) < 2:
            recent = samples[-2:]
        (t0, f0), (t1, f1) = recent[0], recent[-1]
        if t1 <= t0 or f1 <= f0:
            return 0.0
        return (f1 - f0) / (t1 - t0)

    def projected_page_deficit(self, total_tokens: int) -> int:
        """Pages the pool is short if this request is admitted BEHIND the
        work already queued: ``ceil(total_tokens / page) + ceil(queued
        footprint / page) - free_pages``, floored at 0. Unlike
        :meth:`page_deficit` this counts the admission queue's projected demand too — the signal behind the
        gateway's projected-pressure 429 (ROADMAP's "429 on projected
        pool pressure rather than queue depth")."""
        if total_tokens <= 0:
            return 0
        factor = self._spec_page_factor
        needed = -(-int(total_tokens) // self._page) * factor
        queued = -(-int(self._queue.pending_tokens) // self._page) * factor
        return max(0, needed + queued - self._pool.free_pages)

    @property
    def load(self) -> float:
        """Occupancy fraction over the engine's whole admission capacity:
        ``(active slots + queued) / (max_slots + max_queued)`` — the
        router's least-loaded score; 1.0 means a submit would bounce. POOL
        pressure (used/total pages) is folded in, so the router steers
        traffic away from a replica whose memory, not slots, is the
        bottleneck."""
        base = ((self._slots.active_slots + len(self._queue))
                / (self.max_slots + self._queue.max_queued))
        return max(base, self._pool.used_pages / self._pool.num_pages)

    def kill(self, error: Optional[BaseException] = None):
        """Fault injection / fencing: make the run loop raise ``error`` at
        its next iteration, exactly as a device failure inside a compiled
        call would — the engine records the error, fails every in-flight
        and queued request, and exits. Used by the failover tests/benches
        and by operators fencing a suspect replica hard (prefer
        :meth:`shutdown` for anything gentler)."""
        err = error if error is not None else RuntimeError(
            "replica killed by fault injection")
        self._flight.record("kill", error=repr(err))
        self._fail_injection = err

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, prompt_ids=None, *, request: Optional[Request] = None,
               max_new_tokens: int = 20, seed: Optional[int] = None,
               rng=None, timeout: Optional[float] = None, on_token=None,
               ignore_eos: bool = False, adapter: Optional[str] = None,
               trace_id: Optional[str] = None,
               priority: Optional[str] = None, block: bool = False,
               block_timeout: Optional[float] = None) -> Request:
        """Enqueue one request; returns its :class:`Request` handle
        immediately. Raises :class:`scheduler.QueueFull` under backpressure
        when ``block=False``; with ``block=True`` the caller waits for
        queue space instead (up to ``block_timeout``). A pre-built
        ``request=`` handle must be FRESH: handles are single-use, and
        resubmitting one that is queued, in flight, or already retired
        raises ``ValueError`` (its tokens/status/events are stale state a
        second flight would corrupt)."""
        if request is None:
            request = Request(prompt_ids, max_new_tokens=max_new_tokens,
                              rng=rng, seed=seed, timeout=timeout,
                              on_token=on_token, ignore_eos=ignore_eos,
                              adapter=adapter, trace_id=trace_id,
                              priority=priority)
        elif (request.status is not RequestStatus.QUEUED
                or request.submitted_at is not None):
            raise ValueError(
                f"Request handle already used (status "
                f"{request.status.value}); Request objects are single-use — "
                "build a fresh Request (or pass prompt_ids) per submission")
        if request.adapter is not None:
            if self._adapters is None:
                raise ValueError(
                    f"request names adapter {request.adapter!r} but this "
                    "engine has no adapter bank (pass adapters=AdapterBank(...))")
            # Unknown names raise UnknownAdapterError (a LookupError) here,
            # synchronously — the gateway maps it to HTTP 404.
            self._adapters.check_known(request.adapter)
        if (not self._accepting or self._stop or self._drain
                or self._queue.closed):
            raise RuntimeError("serving engine is not accepting requests "
                               "(not started, shutting down, or preempted)")
        S = request.prompt_ids.shape[1]
        if S < 1:
            raise ValueError("empty prompt")
        if S + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({request.max_new_tokens}) "
                f"exceeds the engine's max_len ({self.max_len}); resize the "
                "engine or shorten the request")
        # A lone request must always be satisfiable: with everyone else
        # preempted and the alias cache drained, its worst-case footprint
        # has to fit the pool, or admission could wedge forever.
        need = (-(-(S + request.max_new_tokens) // self._page)
                * self._spec_page_factor)
        if need > self._pool.num_pages:
            raise ValueError(
                f"request needs up to {need} KV pages (prompt {S} + "
                f"max_new_tokens {request.max_new_tokens} at page_size "
                f"{self._page}"
                + (", doubled for draft KV pages"
                   if self._spec_page_factor > 1 else "")
                + f") but the pool only has "
                f"{self._pool.num_pages}; raise max_pages or shorten "
                "the request")
        if self._spec_k is not None:
            # A verify near the end of the stream writes positions up to
            # (S + max_new - 1) + K; the draft scan stops one short.
            K = self._spec_k
            _check_position_bound(self.module, S + request.max_new_tokens + K)
            if self._draft_module is not None:
                _check_position_bound(self._draft_module,
                                      S + request.max_new_tokens + K - 1)
        else:
            _check_position_bound(self.module, S + request.max_new_tokens)
        if request.trace_id is None:
            # Engine-direct submissions get an id too, so dump_trace can
            # always filter per request (the gateway mints upstream).
            request.trace_id = new_trace_id()
        request.submitted_at = time.monotonic()
        try:
            self._queue.put(request, block=block, timeout=block_timeout)
        except QueueFull:
            self._stats.record_reject()
            raise
        except QueueClosed as e:
            # The engine stopped between the accepting-check above and the
            # enqueue (or while we were blocked waiting for space): same
            # contract as submitting to a dead engine outright.
            raise RuntimeError(
                "serving engine is not accepting requests "
                "(not started, shutting down, or preempted)") from e
        self._stats.record_submit(len(self._queue))
        if request.priority is not None:
            self._stats.record_priority_request(request.priority)
        args = {"prompt_len": S, "queue_depth": len(self._queue)}
        if request.priority is not None:
            args["priority"] = request.priority
        self._tracer.instant("submit", trace_id=request.trace_id, args=args)
        return request

    def serving_metrics(self) -> dict:
        """Scalar snapshot of the engine's counters (see
        :class:`metrics.ServingStats.summary`)."""
        return self._stats.summary()

    @property
    def stats(self) -> ServingStats:
        return self._stats

    @property
    def prefix_cache(self) -> Optional[PrefixCache]:
        return self._prefix_cache

    # -- observability ---------------------------------------------------
    @property
    def tracer(self) -> Tracer:
        """This engine's span tracer (request-scoped timeline sink)."""
        return self._tracer

    @property
    def flight_recorder(self) -> FlightRecorder:
        """This engine's black box (last-N structured lifecycle events)."""
        return self._flight

    @property
    def compile_watcher(self):
        """The engine's :class:`~accelerate_tpu.utils.profiling.
        CompileWatcher` (None before :meth:`start`). Its counters answer
        "did serving compile anything after warmup" — 0 at steady state
        is the zero-recompile invariant, now observable in production."""
        return self._compile_watcher

    def trace_events(self, trace_id: Optional[str] = None) -> list:
        """Snapshot of buffered span records (see :meth:`Tracer.events`)."""
        return self._tracer.events(trace_id)

    def chrome_trace(self, trace_id: Optional[str] = None) -> dict:
        """Chrome-trace/Perfetto JSON dict of the buffered spans."""
        return self._tracer.chrome_trace(trace_id)

    def dump_trace(self, path: str, trace_id: Optional[str] = None) -> str:
        """Write the Chrome-trace JSON to ``path``; returns ``path``.
        Load it at ``chrome://tracing`` or https://ui.perfetto.dev."""
        return self._tracer.dump(path, trace_id)

    def postmortem(self) -> Optional[dict]:
        """The flight-recorder dump auto-captured when the run loop died
        (None while the engine is healthy). The router attaches this to
        its failover report for the dead replica."""
        return self._postmortem

    def _stop_compile_watcher(self):
        watcher = self._compile_watcher
        if watcher is not None:
            watcher.stop()  # idempotent; shutdown() + run-loop finally race

    def _dump_debug_files(self):
        """Best-effort trace/flight dump into ``trace_dir`` (death or
        shutdown must never be masked by a full disk)."""
        try:
            os.makedirs(self._trace_dir, exist_ok=True)
            base = os.path.join(self._trace_dir, self._tracer.name)
            self._tracer.dump(base + "-trace.json")
            self._flight.dump_json(base + "-flight.json")
        except OSError:
            pass

    @property
    def adapters(self) -> Optional[AdapterBank]:
        return self._adapters

    def register_adapter(self, name: str, adapter, **kwargs) -> None:
        """Register a named LoRA adapter with this engine's bank (host-side;
        the device load happens lazily at first use)."""
        if self._adapters is None:
            raise RuntimeError(
                "engine has no adapter bank; construct it with "
                "adapters=AdapterBank(params, ...)")
        self._adapters.register(name, adapter, **kwargs)

    def adapter_resident(self, name: str) -> bool:
        """Whether ``name`` currently occupies a bank row (router affinity)."""
        return self._adapters is not None and self._adapters.resident(name)

    @property
    def kv_dtype(self) -> Optional[str]:
        """``"int8"`` when KV pages are stored quantized; None = the
        bit-exact full-precision pool."""
        return self._kv_dtype

    @property
    def weights_dtype(self) -> Optional[str]:
        """``"int8"`` when base weights are stored quantized (LoRA path
        full precision); None = full-precision weights."""
        return self._weights_dtype

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes one cached token takes over all layers, from the leaves of
        the cache the model declares (a page's bytes over its rows)."""
        return self._page_bytes // self._page

    @property
    def tick_attn_kernel_readers(self) -> int:
        """Attentions of one plain decode tick that read the page pool
        through the Mosaic paged-attention kernel (``ops/paged_attention.py``:
        one call a reader, each live page fetched once) instead of the XLA
        work list's loop. Decided at build from what the engine holds
        (``ops.paged_attention.paged_attention_available``): the backend is
        the TPU, the cache entry's leaves are flat ``k`` / ``v`` rows
        ``[pages, 1, P, G * hd]`` of whole lane tiles, the pool has no int8
        scales and ``tp == 1``. Every other engine — every engine off the TPU
        — reads 0 and runs the work list, which is also the kernel's
        reference; the speculative ticks and the prefill chunk gather views
        either way."""
        return self._tick_kernel_readers

    @property
    def recurrent_state_bytes(self) -> int:
        """Bytes of the state the cache holds per slot BESIDE the page
        pool (entries without a length axis: a state-space layer's state),
        over all slots; 0 for a cache of KV rows alone. Fixed by
        ``max_slots``, whatever ``max_pages`` and ``max_len``."""
        return self._recurrent_bytes

    def kv_cache_per_chip_bytes(self) -> int:
        """Per-device byte footprint of the decode KV state (max shard per
        leaf): the HBM-planning number, ≈ ``1/tp`` of the single-chip
        figure for heads-sharded leaves (docs/performance.md). This is
        the page POOL — the number ``max_pages`` controls directly,
        independent of ``max_slots`` — plus the per-page scale arrays on a
        quantized engine (they're replicated, so they count at full size
        per chip). The KV rows alone: ``recurrent_state_bytes`` is the line
        for what the cache holds per slot beside them."""
        tree = self._state["pool"]
        extra = sum(self._state[k].nbytes for k in ("pscale", "dpscale")
                    if k in self._state)
        if self._exec is not None:
            return self._exec.per_chip_bytes(tree) + extra
        return sum(l.nbytes for l in jax.tree.leaves(tree)) + extra

    def page_pool_metrics(self) -> dict:
        """Host-side pool snapshot: page size, totals, occupancy,
        allocation and preemption counters. On a quantized engine
        ``page_bytes`` is already the int8 figure (1 byte/element +
        4-byte scale per leaf)."""
        out = {
            "page_size": self._page,
            "kv_dtype": self._kv_dtype,
            "pages_per_slot": self._pages_per_slot,
            "page_bytes": self._page_bytes,
            "pages_total": self._pool.num_pages,
            "pages_free": self._pool.free_pages,
            "pages_used": self._pool.used_pages,
            "page_allocations": self._pool.allocations,
            "preemptions": self._pool.preemptions,
        }
        if self._spec_mode == "draft":
            # Draft pages share the pool's id space but are smaller bytes:
            # capacity planning needs both figures.
            out["draft_page_bytes"] = self._draft_page_bytes
        if self._recurrent_at:
            # what a slot holds beside its pages, whatever its length
            out["recurrent_bytes_per_slot"] = (self._recurrent_bytes
                                               // self.max_slots)
            out["recurrent_state_bytes"] = self._recurrent_bytes
        return out

    def decode_memory_analysis(self):
        """``CompiledMemoryStats`` for the decode tick, compiled FRESH from
        the same function + shardings — lowering through the serving jit
        itself would add a cache entry and break the warm-executable
        accounting the zero-recompile tests pin."""
        args = [self.params, self._state,
                np.zeros((self.max_slots,), bool), self._table.copy()]
        if self._adapters is not None:
            args.append(self._adapters.stacks)
        if self._exec is None:
            fn = jax.jit(self._paged_decode_fn)
        else:
            rep = self._exec.replicated
            ins = [self._param_sh, self._state_sh, rep, rep]
            if self._adapters is not None:
                ins.append(self._bank_sh)
            fn = self._exec.jit(self._paged_decode_fn, tuple(ins),
                                (self._state_sh, rep, rep))
        return fn.lower(*args).compile().memory_analysis()

    # ------------------------------------------------------------------
    # engine thread
    # ------------------------------------------------------------------
    def _run(self):
        # The one in-flight dispatched tick. Loop shape per iteration:
        # sweeps → admission → DISPATCH tick N+1 → RECONCILE tick N — so
        # every piece of host work between the two barriers overlaps tick
        # N+1's device time.
        flight: Optional[_TickFlight] = None
        phases = self._phases
        try:
            while not self._stop:
                with phases.sweep:
                    # Liveness first: apply any scripted chaos (which may set
                    # the fail injection we check next), then publish the
                    # heartbeat — unless a chaos hang suppresses it, in which
                    # case a watchdog sees exactly what a wedged compiled call
                    # looks like while the loop itself keeps serving.
                    self._loop_iters += 1
                    if self._chaos is not None:
                        self._chaos.apply(self)
                    if not self._heartbeat_frozen:
                        self._heartbeat = (self._loop_iters, time.monotonic())
                    if self._fail_injection is not None:
                        # Routed through the normal engine-fatal path below, so
                        # an injected fault is indistinguishable from a real one
                        # to everything downstream (router fencing included).
                        raise self._fail_injection
                    if (self._accelerator is not None
                            and getattr(self._accelerator, "preemption_requested", False)
                            and not (self._drain or self._abort_queue)):
                        # Preemption drain: stop admitting, let in-flight
                        # requests finish, cancel the queue — the notice window
                        # is for flushing work, not for taking more.
                        self._accepting = False
                        self._abort_queue = True
                    now = time.monotonic()
                    for _, req in self._slots.active():
                        if req._emit_error is not None:
                            # A streaming callback raised on the emitter
                            # thread: same FAILED retirement (slot freed,
                            # batch untouched) an inline failure produces.
                            self._retire(req, RequestStatus.FAILED,
                                         req._emit_error)
                        elif req.cancel_requested:
                            self._retire(req, RequestStatus.CANCELLED)
                        elif req._deadline_passed(now):
                            self._retire(req, RequestStatus.TIMED_OUT)
                    if self._abort_queue:
                        for req in self._queue.drain():
                            self._finish_req(req, RequestStatus.CANCELLED)
                            self._stats.record_finish(req.status)
                # Bounded admission: spend at most chunks_per_tick chunk
                # calls, ALTERNATING one continuation of the PREFILLING
                # backlog (round-robin) with one new admission — so with a
                # budget of 2+, a fresh arrival's first chunk rides
                # alongside an in-flight long prefill instead of queueing
                # behind all of it.
                budget = self._chunks_per_tick
                while budget > 0:
                    progressed = False
                    if self._advance_one_prefill():
                        budget -= 1
                        progressed = True
                    if budget > 0 and self._slots.has_free():
                        with phases.admit:
                            req = self._queue.get_nowait()
                            placed = (req is not None
                                      and self._screen(req, now)
                                      and self._begin_prefill(req))
                        if req is not None:
                            progressed = True
                            if placed is None:
                                # Admission gate: the request went back
                                # to the queue front; stop admitting
                                # until decode frees pages.
                                break
                            if placed:
                                self._run_chunk(req)
                                budget -= 1
                    if not progressed:
                        break
                running = [(slot, req) for slot, req in self._slots.active()
                           if req.status is RequestStatus.RUNNING]
                if running:
                    if self._spec_mode == "lookup" and flight is not None:
                        # Prompt-lookup proposals must anchor on the
                        # NEWEST committed token: a proposal drafted ahead
                        # is misaligned by the in-flight tick's
                        # variable-length commit (1..K+1 tokens) and
                        # verifies to zero accepts, collapsing lookup
                        # speculation to plain decode. So lookup engines
                        # settle tick N before drafting N+1 — off-thread
                        # emission and the commit barrier are unchanged;
                        # only dispatch/device overlap is given up.
                        self._reconcile(flight)
                        flight = None
                        continue
                    # One tick ahead: dispatch N+1 against the in-flight
                    # state futures (host view stale by exactly the one
                    # unreconciled tick when ``flight`` exists), THEN
                    # settle tick N.
                    nxt = self._dispatch(running, flight)
                    if flight is not None:
                        self._reconcile(flight)
                    flight = nxt
                    if flight is None:
                        # Nothing dispatched (every stream flow-controlled
                        # or preempted) and nothing in flight: yield so
                        # consumers can drain instead of hot-spinning the
                        # loop.
                        self._go_idle()
                        with phases.idle:
                            time.sleep(min(self._idle_poll_s, 0.001))
                    continue
                if flight is not None:
                    # The last running streams retired/preempted out from
                    # under the in-flight tick — settle it (stray lanes
                    # discard; pages/stats still reconcile).
                    self._reconcile(flight)
                    flight = None
                    continue
                self._last_complete_t = None   # ITL intervals restart
                phases.covered_s = 0.0
                if self._slots.active_slots:
                    pass  # prefill-only batch: loop again without idling
                elif self._drain and not len(self._queue):
                    break
                elif self._abort_queue:
                    break
                else:
                    # Idle: block briefly on the queue so a submit wakes the
                    # loop without a hot spin. The popped request goes
                    # through the SAME screen as the busy path — one
                    # cancelled or deadline-expired while the engine idled
                    # must not be prefilled (or billed in stats).
                    self._go_idle()
                    with phases.idle:
                        req = self._queue.get(timeout=self._idle_poll_s)
                    if req is None:
                        continue
                    with phases.admit:
                        placed = (self._screen(req, time.monotonic())
                                  and self._begin_prefill(req))
                    if placed:
                        self._run_chunk(req)
        except BaseException as e:  # engine-fatal: fail everything loudly
            self._error = e
            # Black-box capture at the moment of death: the fatal event
            # plus the last N lifecycle events, frozen BEFORE the retire
            # sweep below — this dump is what the router attaches to its
            # failover report.
            self._flight.record("fatal", error=repr(e))
            self._postmortem = self._flight.dump()
            if self._trace_dir is not None:
                self._dump_debug_files()
        finally:
            self._stop_compile_watcher()
            self._accepting = False
            # Close BEFORE the final drain: wakes producers blocked in
            # put(block=True) with QueueClosed and guarantees nothing can
            # slip into the queue after we empty it below.
            self._queue.close()
            self._prefilling.clear()
            terminal = (RequestStatus.FAILED if self._error is not None
                        else RequestStatus.CANCELLED)
            for _, req in list(self._slots.active()):
                self._retire(req, terminal, self._error)
            for req in self._queue.drain():
                self._finish_req(req, terminal, self._error)
                self._stats.record_finish(req.status)
            # AFTER the retire sweep queued its deferred completions: drain
            # every buffered token and completion, then join — failover
            # handlers (``_on_finish``) all fire before the engine thread
            # exits.
            self._emitter.close()

    def _go_idle(self):
        """Before the loop waits with nothing launched: an open
        ``chunk_to_dispatch`` interval ends here (waiting for a request is
        not the device waiting for the host), and the phase timings still
        held go to the stats now, since no tick will carry them."""
        self._phases.launched()
        self._stats.record_host(self._phases.drain())
        self._idle_entries += 1

    def _screen(self, req: Request, now: float) -> bool:
        """The check-then-admit gate both pop paths share: a request whose
        cancellation or deadline fired while it queued is finished here,
        never admitted."""
        if req.cancel_requested:
            self._finish_req(req, RequestStatus.CANCELLED)
        elif req._deadline_passed(now):
            self._finish_req(req, RequestStatus.TIMED_OUT)
        else:
            return True
        self._stats.record_finish(req.status)
        return False

    def _acquire_adapter(self, req: Request) -> bool:
        """Pin the request's adapter into a bank row before it takes a slot.

        Base requests (or bank-less engines) use row 0, the identity.
        Failure is REQUEST-fatal, never engine-fatal: an unknown name or a
        fully-pinned bank fails this request with the original exception
        (``engine.error`` stays None, so the router does not fail over) and
        the loop moves on."""
        if self._adapters is None or req.adapter is None:
            req._adapter_row = 0
            return True
        try:
            row, hit, evicted = self._adapters.acquire(req.adapter)
        except Exception as e:
            self._finish_req(req, RequestStatus.FAILED, e)
            self._stats.record_finish(req.status)
            return False
        req._adapter_row = row
        req._adapter_pinned = True
        self._stats.record_adapter_admit(req.adapter, hit=hit, evicted=evicted)
        if not hit:
            self._flight.record("adapter_load", adapter=req.adapter,
                                row=row, evicted=evicted,
                                trace_id=req.trace_id)
        return True

    def _adapter_args(self, req: Request) -> tuple:
        """Trailing (adapter_idx, bank) args for the prefill programs —
        empty for bank-less engines, so their call signature (and traced
        program) is exactly the pre-adapter one."""
        if self._adapters is None:
            return ()
        return (np.int32(req._adapter_row), self._adapters.stacks)

    # -- host-side page accounting (engine thread only) -----------------
    def _on_prefix_evict(self, key, value):
        """Alias-cache eviction hook: the evicted entry's value is the
        tuple of pool page ids the cache held a reference on — give them
        back. Pages still referenced by a live slot survive (refcounts);
        only the last reference frees."""
        for pid in value:
            self._pool.decref(int(pid))

    def _release_slot_pages(self, slot: int):
        """Drop the slot's reference on every table entry (draft-table
        entries too, when a draft model speculates) and clear the rows.
        Aliased pages shared with the prefix cache or other slots stay
        allocated until their last reference goes."""
        rows = [self._table[slot]]
        if self._dtable is not None:
            rows.append(self._dtable[slot])
        for row in rows:
            for idx in range(self._pages_per_slot):
                if row[idx]:
                    self._pool.decref(int(row[idx]))
            row[:] = 0

    def _alloc_page_into(self, req: Request, idx: int, table=None) -> bool:
        """Allocate one pool page into ``table[req.slot, idx]`` (the
        TARGET table by default; draft-mode callers pass ``self._dtable``
        — one pool, one id space). On exhaustion, first reclaim
        alias-cache entries LRU-first (an entry whose pages nobody else
        references frees real pages), then preempt other streams. False
        only when the requester is alone and the pool is still dry — which
        the submit-time page bound makes impossible, so callers treat it
        as an engine invariant violation."""
        table = self._table if table is None else table
        while True:
            pid = self._pool.alloc()
            if pid is not None:
                table[req.slot, idx] = pid
                return True
            if (self._alias_cache and self._prefix_cache is not None
                    and self._prefix_cache.evict_lru()):
                continue
            if not self._preempt_one(req):
                return False

    def _ensure_pages(self, req: Request, upto_pos: int) -> bool:
        """Make the slot's table cover position ``upto_pos`` (allocating
        every missing page up to and including its page)."""
        row = self._table[req.slot]
        # Indices below the request's window floor were freed on purpose
        # (sliding-window page lifetime) — never bring them back.
        for idx in range(req._page_floor, upto_pos // self._page + 1):
            if not row[idx]:
                if not self._alloc_page_into(req, idx):
                    return False
        return True

    def _ensure_draft_pages(self, req: Request, upto_pos: int) -> bool:
        """Draft-table twin of :meth:`_ensure_pages`. The draft cache is
        linear (no window floor): draft pages live for the stream's whole
        slot residency and are released with the target's."""
        row = self._dtable[req.slot]
        for idx in range(upto_pos // self._page + 1):
            if not row[idx]:
                if not self._alloc_page_into(req, idx, table=self._dtable):
                    return False
        return True

    def _reclaimable_pages(self) -> int:
        """Pages the admission gate could free without preempting anyone:
        alias-cache pages whose only reference is the cache's own."""
        if not (self._alias_cache and self._prefix_cache is not None):
            return 0
        return sum(
            1 for _, val in self._prefix_cache.entries()
            for pid in val if self._pool.refcount(int(pid)) == 1)

    def _preempt_one(self, requester: Request) -> bool:
        """Pool exhausted: evict another stream back to the FRONT of its
        queue class and free its pages. Victim selection is policy-driven:
        with a priority policy, the LOWEST-priority stream loses first and
        the newest-admitted within that class breaks the tie (least sunk
        prefill work, shortest resume); without a policy this degenerates
        to the historical newest-admitted rule. The victim resumes
        token-exactly later: its prompt becomes ``prompt + tokens`` (for
        greedy decoding the resumed prefill's first token IS the
        interrupted stream's next token — the router failover argument;
        sampled streams re-draw from the resume point). Returns False
        when no other stream holds a slot."""
        policy = self._priority_policy

        def _victim_key(r):
            rank = (policy.rank(getattr(r, "priority", None))
                    if policy is not None else 0)
            return (rank, r.admitted_at or 0.0)

        victim = None
        for _, r in self._slots.active():
            if r is requester:
                continue
            if victim is None or _victim_key(r) > _victim_key(victim):
                victim = r
        if victim is None:
            return False
        if victim.tokens:
            victim._serve_ids = np.concatenate(
                [victim.prompt_ids, np.asarray([victim.tokens], np.int32)],
                axis=1)
        self._release_slot_pages(victim.slot)
        self._slots.release(victim.slot)
        victim.slot = None
        if victim._adapter_pinned:
            victim._adapter_pinned = False
            self._adapters.release(victim.adapter)
        try:
            self._prefilling.remove(victim)
        except ValueError:
            pass
        victim.status = RequestStatus.QUEUED
        victim._preempted += 1
        self._pool.preemptions += 1
        self._stats.record_preemption()
        self._flight.record("preemption", trace_id=victim.trace_id,
                            tokens=len(victim.tokens),
                            free_pages=self._pool.free_pages)
        try:
            self._queue.putleft(victim)
        except QueueClosed:
            victim._finish(RequestStatus.CANCELLED)
            self._stats.record_finish(victim.status)
        return True

    def _free_window_pages(self, req: Request):
        """Sliding-window page lifetime: page ``j``'s last position is
        ``(j+1)*P - 1``; every future query sits at ``q >= pos``, and the
        model's window mask only attends ``k > q - window`` — so once
        ``(j+1)*P - 1 <= pos - window`` the page can never be read again
        and its reference is dropped (the zeroed table entry gathers
        scratch garbage, which that same mask excludes)."""
        pos = req._pos_base + len(req.tokens)
        row = self._table[req.slot]
        for j in range(self._pages_per_slot):
            if (j + 1) * self._page - 1 > pos - self._page_window:
                break
            if row[j]:
                self._pool.decref(int(row[j]))
                row[j] = 0
            req._page_floor = j + 1

    # -- prefill --------------------------------------------------------
    def _begin_prefill(self, req: Request) -> Optional[bool]:
        """Assign a slot and restore the longest cached chunk-aligned
        prefix (restores are not billed against the chunk budget — they
        are why the cache pays); the caller then runs the request's first
        live chunk (``_run_chunk``) outside its ``admit`` phase. Returns
        True when the request is placed, False when it was retired
        instead (its adapter could not be acquired) — or ``None`` when the
        admission gate refuses: the prompt needs more pages than
        are free or reclaimable, so the request goes back to the queue
        FRONT and the caller stops admitting until decode progress frees
        pages (admitting anyway would just trigger preemption thrash).

        What is prefilled is ``req._serve_ids`` — the original prompt, or
        prompt + committed tokens after a preemption — so the same code
        path is both first admission and token-exact resume."""
        if req._serve_ids is None:
            req._serve_ids = req.prompt_ids
        req._page_floor = 0  # every (re)admission prefills from page 0
        S = req._serve_ids.shape[1]
        C = self._chunk
        need = -(-S // self._page) * self._spec_page_factor
        if need > self._pool.free_pages + self._reclaimable_pages():
            self._flight.record(
                "pool_exhausted", trace_id=req.trace_id,
                need_pages=need, free_pages=self._pool.free_pages)
            try:
                self._queue.putleft(req)
            except QueueClosed:
                req._finish(RequestStatus.CANCELLED)
                self._stats.record_finish(req.status)
            return None
        if not self._acquire_adapter(req):
            return False
        req.admitted_at = time.monotonic()
        slot = self._slots.assign(req)
        self._flight.record("admission", trace_id=req.trace_id, slot=slot,
                            prompt_len=S, adapter=req.adapter,
                            resumed=bool(req.tokens))
        req.status = RequestStatus.PREFILLING
        req._rng_key = req.rng if req.rng is not None else jax.random.PRNGKey(
            req.seed if req.seed is not None else 0)
        req._chunks_total = -(-S // C)
        req._next_chunk = 0
        req._chunk_keys = None
        if self._prefix_cache is not None:
            n_full = S // C
            if n_full:
                req._chunk_keys = self._prefix_keys(req._serve_ids, n_full,
                                                    req.adapter)
            # The FINAL chunk always re-runs (cached blocks hold KV, not the
            # logits the first token needs), so at most chunks 0..n-2 restore.
            restorable = min(n_full, req._chunks_total - 1)
            if restorable:
                blocks = self._prefix_cache.match(req._chunk_keys[:restorable])
                restored_bytes = aliased = 0
                Cp = C // self._page
                for i, blk in enumerate(blocks):
                    if self._alias_cache:
                        # blk is a tuple of page ids: restoring is a host
                        # table write + refcount — zero device work. The
                        # pos-pin invariant holds because the first chunk
                        # call below runs before any tick can see the slot.
                        for j, pid in enumerate(blk):
                            self._pool.incref(int(pid))
                            self._table[slot, i * Cp + j] = int(pid)
                        restored_bytes += len(blk) * self._page_bytes
                        aliased += 1
                        continue
                    ok = all(self._alloc_page_into(req, i * Cp + j)
                             for j in range(Cp))
                    if not ok:
                        raise RuntimeError(
                            "page pool exhausted during prefix restore "
                            "with no preemptable stream — the submit "
                            "page bound should make this impossible")
                    pages_c = self._table[slot, i * Cp:(i + 1) * Cp]
                    self._state = self._restore_prefix(
                        self._state, blk, pages_c.astype(np.int32),
                        np.int32(slot), np.int32(S))
                    restored_bytes += sum(
                        l.nbytes for l in jax.tree.leaves(blk))
                if blocks and self._spec_mode == "draft":
                    # The cache holds TARGET KV only: draft KV is cheap to
                    # recompute and caching it would double every entry.
                    # Rebuild it for the restored span with the draft-only
                    # chunk program so a prefix-hit slot enters speculation
                    # with a warm draft cache.
                    for i in range(len(blocks)):
                        if not self._ensure_draft_pages(req, (i + 1) * C - 1):
                            raise RuntimeError(
                                "page pool exhausted during draft prefix "
                                "rebuild — the admission gate's draft "
                                "factor should make this impossible")
                        ids_c = req._serve_ids[:, i * C:(i + 1) * C]
                        self._state = self._draft_chunk(
                            self._draft_params, self._state, ids_c,
                            np.int32(slot),
                            self._dtable[req.slot].copy(),
                            np.int32(i * C))
                self._stats.record_prefix(looked_up=restorable,
                                          hit=len(blocks),
                                          bytes_restored=restored_bytes,
                                          aliased=aliased)
                if blocks:
                    self._tracer.instant(
                        "prefix_hit", trace_id=req.trace_id,
                        args={"chunks": len(blocks), "aliased": aliased,
                              "bytes": restored_bytes})
                req._next_chunk = len(blocks)
        self._prefilling.append(req)
        return True

    def _prefix_keys(self, prompt_ids, n_full: int,
                     adapter: Optional[str] = None) -> list[bytes]:
        """Hash-chain digests of the prompt's full chunks: chunk i's key
        covers tokens ``[0, (i+1)*C)`` because each digest folds in the
        previous one — equal keys mean equal whole prefixes, never just
        equal chunk contents. The chain is seeded with the request's
        adapter identity: a LoRA adapter changes the KV a prefix produces,
        so two tenants with byte-identical prompts must never share cached
        blocks (cross-tenant KV leak) — and, the same way, with the KV
        dtype: an int8 engine's pages carry quantization error a
        full-precision engine must never alias (and aliased int8 pages
        need the producing pool's scales, which an fp entry lacks)."""
        flat = np.ascontiguousarray(prompt_ids[0], np.int32)
        C = self._chunk
        seed = b"chunk:%d" % C
        if self._kv_dtype is not None:
            seed += b"/kv:" + self._kv_dtype.encode("utf-8")
        if adapter is not None:
            seed += b"/adapter:" + adapter.encode("utf-8")
        keys, prev = [], seed
        for i in range(n_full):
            prev = hashlib.blake2b(
                prev + flat[i * C:(i + 1) * C].tobytes(),
                digest_size=16).digest()
            keys.append(prev)
        return keys

    def cached_prefix_tokens(self, prompt_ids,
                             adapter: Optional[str] = None) -> int:
        """How many leading prompt tokens THIS engine could restore from
        its prefix cache right now — the router's cache-aware routing
        probe. Pure host work (hashing + dict lookups, no LRU promotion,
        no device calls), so probing every replica per dispatch is cheap
        and cannot perturb cache eviction order. Mirrors the restore
        bound in ``_begin_prefill``: the final chunk always re-runs, so
        at most ``ceil(S/C) - 1`` full chunks count."""
        if self._prefix_cache is None:
            return 0
        ids = np.asarray(prompt_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None, :]
        S = int(ids.shape[1])
        C = self._chunk
        restorable = min(S // C, -(-S // C) - 1)
        if restorable < 1:
            return 0
        keys = self._prefix_keys(ids, restorable, adapter)
        return self._prefix_cache.longest_prefix(keys) * C

    def _advance_one_prefill(self) -> bool:
        """Run ONE chunk for the oldest live entry of the PREFILLING
        backlog (round-robin: the entry requeues behind newer ones), so a
        short prompt's one-chunk prefill completes promptly even while a
        long prompt is mid-prefill — no head-of-line blocking inside
        admission either. Entries retired mid-prefill (cancel/timeout)
        are dropped lazily. Returns False when no live entry remains."""
        while self._prefilling:
            req = self._prefilling.popleft()
            if req.status is not RequestStatus.PREFILLING:
                continue
            self._run_chunk(req)
            if req.status is RequestStatus.PREFILLING:
                self._prefilling.append(req)
            return True
        return False

    def _run_chunk(self, req: Request):
        """One ``prefill_chunk`` call at the request's frontier. The final
        chunk's offset is pulled back (never past ``max_len - C`` / the
        position table) so the fixed width stays in bounds — re-running a
        few already-prefilled positions writes bit-identical KV. Full
        chunks feed the prefix cache with the block the executable already
        returned."""
        phases = self._phases
        with phases.prefill_launch:
            i = req._next_chunk
            C = self._chunk
            S = req._serve_ids.shape[1]
            final = i == req._chunks_total - 1
            offset = min(i * C, self._chunk_cap) if final else i * C
            ids_c = req._serve_ids[:, offset:offset + C]
            if ids_c.shape[1] < C:
                ids_c = np.pad(ids_c, ((0, 0), (0, C - ids_c.shape[1])),
                               mode="edge")
            t0 = time.monotonic()
            # Cover the chunk's whole write span (including the edge-pad
            # tail — decode writes land there next) before the call; the
            # program scatters only into these table entries.
            if not self._ensure_pages(req, offset + C - 1):
                raise RuntimeError(
                    "page pool exhausted mid-prefill with no preemptable "
                    "stream — the submit page bound should make this "
                    "impossible")
            extra = self._adapter_args(req)
            if self._spec_mode == "draft":
                if not self._ensure_draft_pages(req, offset + C - 1):
                    raise RuntimeError(
                        "page pool exhausted mid-prefill for draft KV — "
                        "the admission gate's draft factor should make "
                        "this impossible")
                extra += (self._draft_params,
                          self._dtable[req.slot].copy())
            self._state, tok, block = self._prefill_chunk(
                self.params, self._state, ids_c, np.int32(req.slot),
                self._table[req.slot].copy(), np.int32(offset),
                np.int32(S), req._rng_key, *extra)
            if final or self._stats_collection is not None:
                # ``tok`` is read on the host (the first token, the module's
                # counters behind it): the copy starts when the chunk ends,
                # not when a commit asks for it
                tok.copy_to_host_async()
            attn_rows = self._chunk_attn_rows(offset)
            phases.launched()
        with phases.prefill_wait:
            tok.block_until_ready()  # honest chunk timing, paced dispatch
        phases.chunk_ready()
        # The wait is device time (this chunk, plus any in-flight tick it
        # queued behind) — excluded from host_us_per_tick.
        self._blocked_s += phases.prefill_wait.last_s
        with phases.prefill_commit:
            self._commit_chunk(req, i, offset, final, tok, block, t0,
                               attn_rows)

    def _chunk_attn_rows(self, offset: int) -> Optional[tuple]:
        """``(scored, visible, view)`` key rows of the target model's
        attention in one chunk at ``offset``, summed over its layers: the
        program's own rule (``models.llama.cached_key_block`` and
        ``cached_key_extent``) applied to the chunk's static shape on the
        host — no device read."""
        if self._attn_score_heads is None:
            return None
        C, L = self._chunk, self._pages_per_slot * self._page
        scored = visible = layers = 0
        for window, n in self._attn_layer_kinds:
            s, v = cached_attention_rows(
                offset, C, L, self._attn_score_heads * C, window)
            scored, visible, layers = scored + n * s, visible + n * v, layers + n
        return scored, visible, layers * L

    def _tick_attn_rows(self, positions: list) -> Optional[tuple]:
        """``(scored, visible, view)`` key rows of one plain tick's
        attention, summed over its lanes and layers: host arithmetic from
        the running lanes' positions and the program's own rule
        (``models.llama.tick_key_tiles`` and ``tick_key_extent``, all lanes
        in one numpy pass). The rows are the pool's: a token's own row is
        in no page when it is scored. A lane without a stream scores
        none; a running lane the key blocks that hold rows before its own
        (from the window's start on a windowed layer), and the tick whole
        steps of its work list: the items that fill the last step up are
        scored under the mask too. An attention that runs the
        paged-attention kernel scores its lanes' live PAGES, whole, and
        nothing to fill a step up."""
        heads = self._attn_score_heads
        if heads is None:
            return None
        L = self._pages_per_slot * self._page
        if self._index_topk is not None:
            # every attention scores its lanes' selected rows, and sees no other
            selected = self._tick_dsa_rows(positions)[0]
            return selected, selected, self._kv_readers * L * self.max_slots
        block, group = tick_key_tiles(heads, self.max_slots, L, self._page)
        pos = np.asarray(positions, np.int64)
        scored = visible = layers = 0
        for (window, kernel), n in self._tick_attn_kinds.items():
            if kernel:
                _, count = live_pages(pos, True, self._pages_per_slot, self._page, window,
                                      lib=np)
                rows = int(count.sum()) * self._page
            else:
                _, count = tick_key_extent(pos, True, L, block, window, lib=np)
                rows = -(-int(count.sum()) // group) * group * block
            low = 0 if window is None else np.maximum(pos - window + 1, 0)
            scored += n * rows
            visible += n * int((pos - low).sum())
            layers += n
        return scored, visible, layers * L * self.max_slots

    def _tick_dsa_rows(self, positions: list) -> Optional[tuple]:
        """``(selected, visible)`` rows of one plain tick's sparse attention,
        summed over its running lanes and its attentions: a query at ``pos``
        sees ``pos + 1`` rows (its own among them) and its latent attention
        reads ``min(index_topk, pos + 1)`` of them. None for a family
        without a learned indexer."""
        if self._index_topk is None:
            return None
        visible = np.asarray(positions, np.int64) + 1
        return (self._kv_readers * int(np.minimum(visible, self._index_topk).sum()),
                self._kv_readers * int(visible.sum()))

    def _commit_chunk(self, req: Request, i: int, offset: int, final: bool,
                      tok, block, t0: float, attn_rows=None):
        """The host side of a finished chunk (phase ``prefill_commit``):
        stats and the request-scoped span, the prefix-cache put, and on
        the final chunk the first-token commit."""
        C = self._chunk
        S = req._serve_ids.shape[1]
        dt_ms = (time.monotonic() - t0) * 1e3
        backlog = sum(1 for r in self._prefilling
                      if r.status is RequestStatus.PREFILLING)
        counts = None
        if self._stats_collection is not None:
            # [token, the module's counters...]: only a prompt's last chunk
            # waits for its copy (it needs the token) and folds the earlier
            # chunks' counters with its own
            self._late_counts.append(tok)
            if final:
                rows = [np.asarray(t) for t in self._late_counts]
                self._late_counts.clear()
                tok, counts = rows[-1][0], sum(r[1:] for r in rows)
        self._stats.record_prefill_chunk(dt_ms, backlog=backlog,
                                         host=self._phases.drain(),
                                         **self._moe_counts(counts),
                                         attn_rows=attn_rows,
                                         state_reset=bool(self._recurrent_at)
                                         and offset == 0)
        self._tracer.emit(
            "prefill_chunk", t0, dt_ms / 1e3, trace_id=req.trace_id,
            args={"chunk": i, "of": req._chunks_total, "offset": offset,
                  "slot": req.slot, "backlog": backlog})
        if (self._prefix_cache is not None and req._chunk_keys is not None
                and offset == i * C and offset + C <= S):
            if self._alias_cache:
                # The cache entry is the chunk's PAGE IDS, not a KV copy:
                # a future hit aliases these very pages into another
                # slot's table. The cache takes its own reference on each
                # page (returned on eviction via the hook); a rejected or
                # duplicate put hands the references straight back.
                p0 = offset // self._page
                Cp = C // self._page
                pids = tuple(int(x)
                             for x in self._table[req.slot, p0:p0 + Cp])
                for pid in pids:
                    self._pool.incref(pid)
                if not self._prefix_cache.put(req._chunk_keys[i], pids,
                                              nbytes=Cp * self._page_bytes):
                    for pid in pids:
                        self._pool.decref(pid)
            else:
                if self._exec is not None:
                    # Host-portable blocks: a device_get'd chunk block
                    # restores into ANY slice's shardings via
                    # restore_prefix's in_shardings, so a fleet-shared
                    # PrefixCache serves cross-slice hits (the failover
                    # resume path).
                    block = jax.device_get(block)
                self._prefix_cache.put(
                    req._chunk_keys[i], block,
                    nbytes=sum(l.nbytes for l in jax.tree.leaves(block)))
            self._stats.record_prefix_cache_size(self._prefix_cache.nbytes,
                                                 len(self._prefix_cache))
        req._next_chunk = i + 1
        if final:
            self._finish_prefill(req, int(tok))

    def _finish_prefill(self, req: Request, token: int):
        """Prompt fully in KV: the request starts decoding. TTFT is stamped
        here because the final prefill call emits token #1 — but only on
        the FIRST completion: a preemption-resumed request already has
        tokens and an admit record, and must not be billed twice."""
        req.status = RequestStatus.RUNNING
        now = time.monotonic()
        if req.first_token_at is None:
            req.first_token_at = now
            self._stats.record_admit(
                queue_wait_ms=(req.admitted_at - req.submitted_at) * 1e3,
                ttft_ms=(now - req.submitted_at) * 1e3)
            self._tracer.emit(
                "queue_wait", req.submitted_at,
                req.admitted_at - req.submitted_at,
                trace_id=req.trace_id, args={"slot": req.slot})
            self._tracer.instant(
                "first_token", trace_id=req.trace_id,
                args={"ttft_ms": round((now - req.submitted_at) * 1e3, 3)})
        # Host mirror of the device write position: after this commit,
        # pos = serve length + 0 more; each committed token adds one.
        req._pos_base = req._serve_ids.shape[1] - len(req.tokens) - 1
        if self._commit_token(req, token):
            if (len(req.tokens) >= req.max_new_tokens
                    or (not req.ignore_eos and self.eos_token_id is not None
                        and token == self.eos_token_id)):
                self._retire(req, RequestStatus.COMPLETED)

    def _dispatch(self, running,
                  flight: Optional[_TickFlight]) -> Optional[_TickFlight]:
        """Dispatch one decode tick and return its flight WITHOUT waiting
        for the device. ``running`` is the (slot, request) list in RUNNING
        — PREFILLING slots ride along in the vmapped forward (fixed
        shape) but are masked out of every state advance and commit no
        tokens. Every dispatched slot's write position is first given a
        page (allocating — and preempting on exhaustion — at this
        dispatch boundary); the program then gets a page-table
        SNAPSHOT as traced data (the double buffer: reconcile-time frees
        mutate the live table, never the in-flight copy).

        ``flight`` is the one unreconciled tick in flight, if any: host
        state (``len(req.tokens)``, page frontier) of the streams IN it
        is stale by exactly one committed token. The speculative view is
        made safe by two conservative rules: a stream of that tick within
        one token of its budget is EXCLUDED (it deterministically retires
        at the in-flight tick; dispatching it would write at a position
        past its bound) — a stream that is not in it (fresh from prefill,
        or held back by flow control) has exact host state and is
        dispatched whatever its budget — and page coverage extends one
        position past the stale frontier (the in-flight commit's write).
        A stream that instead retires on
        EOS at the in-flight tick stays masked in — its lane advances
        once more and the stray token is discarded by the reconcile
        validity check (exactly-once emission). The whole of it is the
        host phase ``tick_launch``."""
        with self._phases.tick_launch:
            ahead = flight is not None
            stale = ({(id(req), epoch) for _, req, epoch in flight.entries}
                     if ahead else ())
            if self._spec_k is not None:
                return self._dispatch_spec(running, ahead, stale)
            return self._dispatch_plain(running, ahead, stale)

    def _dispatch_plain(self, running, ahead: bool,
                        stale) -> Optional[_TickFlight]:
        live = []
        for slot, req in running:
            if (req.max_new_tokens - len(req.tokens) <= 1
                    and (id(req), req._preempted) in stale):
                continue  # retires at the in-flight tick (position bound)
            if req.on_token is not None and self._emitter.backlogged(req):
                # Flow control: the consumer is emission_queue callbacks
                # behind — hold this stream back (its device state stays
                # put; the stream resumes bit-exactly) rather than buffer
                # without bound or stall the batch.
                self._stats.record_emission_stall()
                continue
            live.append((slot, req))
        for slot, req in live:
            if req.status is not RequestStatus.RUNNING:
                continue  # preempted by an earlier slot's allocation
            upto = req._pos_base + len(req.tokens) + (1 if ahead else 0)
            if not self._ensure_pages(req, upto):
                raise RuntimeError(
                    "page pool exhausted at a tick with no preemptable "
                    "stream — the submit page bound should make this "
                    "impossible")
        live = [(s, r) for s, r in live if r.status is RequestStatus.RUNNING]
        if not live:
            return None
        mask = np.zeros((self.max_slots,), bool)
        for slot, _ in live:
            mask[slot] = True
        t0 = time.monotonic()
        args = [self.params, self._state, jnp.asarray(mask),
                self._table.copy()]
        if self._adapters is not None:
            args.append(self._adapters.stacks)
        self._state, toks, dones = self._decode(*args)
        self._phases.launched()
        return _TickFlight(
            entries=[(slot, req, req._preempted) for slot, req in live],
            t_dispatch=t0, toks=toks, dones=dones)

    def _reconcile(self, flight: _TickFlight):
        """Settle a dispatched tick: block until its tokens materialize
        (the one device sync point), then commit/retire on the host. An
        entry whose request is no longer RUNNING, or whose preemption
        epoch moved, is a stray lane — its token is discarded, which is
        what makes one-tick-ahead dispatch exactly-once.

        Timing: ``itl`` is the device-complete→device-complete interval
        (what a consumer experiences between tokens), and
        ``host_us_per_tick`` is that interval minus every blocked device
        wait since the previous reconcile — the host scheduling + commit
        wall that one-tick-ahead dispatch hides under device time."""
        if self._wedge_s:
            # Chaos: wedge INSIDE the reconcile barrier of a dispatched
            # call — the loop stops publishing heartbeats mid-"device
            # wait", exactly what a hung collective looks like.
            w, self._wedge_s = self._wedge_s, 0.0
            time.sleep(w)
        spec = flight.emit is not None
        phases = self._phases
        emit = ns = toks = dones = None
        with phases.tick_wait:
            if spec:
                emit = np.asarray(flight.emit)
                ns = np.asarray(flight.ns)
            else:
                toks = np.asarray(flight.toks)
                dones = np.asarray(flight.dones)
            t1 = time.monotonic()
        self._blocked_s += phases.tick_wait.last_s
        with phases.tick_commit:
            counts = None
            if toks is not None and self._stats_collection is not None:
                toks, counts = toks[:self.max_slots], toks[self.max_slots:]
            self._commit_tick(flight, t1, emit, ns, toks, dones, counts)

    def _commit_tick(self, flight: _TickFlight, t1: float, emit, ns, toks,
                     dones, counts=None):
        """The host side of a settled tick (phase ``tick_commit``): the
        timing split, the commit loop, retirements, emitter puts, stats
        and page samples. ``counts``: the module's own counters of this
        tick (MoE picks), where it has any."""
        spec = emit is not None
        phases = self._phases
        if not self._heartbeat_frozen:
            # Reconcile-barrier heartbeat: between loop tops the engine
            # may sit in this block for a whole device tick — republish
            # so the watchdog clock tracks real liveness.
            self._heartbeat = (self._loop_iters, t1)
        prev = self._last_complete_t
        interval = t1 - (prev if prev is not None else flight.t_dispatch)
        self._last_complete_t = t1
        host_s = max(0.0, interval - self._blocked_s)
        self._blocked_s = 0.0
        other_s = max(0.0, host_s - phases.covered_s)
        phases.covered_s = 0.0
        committed = accepted = n_valid = 0
        dead_rows = held_rows = 0
        positions = []          # of the streams this tick ran (its query's position)
        for slot, req, epoch in flight.entries:
            if (req.status is not RequestStatus.RUNNING
                    or req._preempted != epoch):
                continue  # stray lane: retired/preempted since dispatch
            n_valid += 1
            if spec:
                n = int(ns[slot])
                accepted += n - 1
                retired = False
                for j in range(n):
                    token = int(emit[slot, j])
                    if not self._commit_token(req, token):
                        retired = True
                        break
                    committed += 1
                    if (len(req.tokens) >= req.max_new_tokens
                            or (not req.ignore_eos
                                and self.eos_token_id is not None
                                and token == self.eos_token_id)):
                        self._retire(req, RequestStatus.COMPLETED)
                        retired = True
                        break
                if not retired and self._page_window is not None:
                    self._free_window_pages(req)
            else:
                positions.append(req._pos_base + len(req.tokens))
                if not self._commit_token(req, int(toks[slot])):
                    continue  # callback failed; slot already freed
                committed += 1
                if (len(req.tokens) >= req.max_new_tokens
                        or (not req.ignore_eos and bool(dones[slot]))):
                    self._retire(req, RequestStatus.COMPLETED)
                    continue
                if self._page_window is not None:
                    self._free_window_pages(req)
                if self._dead_row_windows:
                    # KV rows this stream holds on into the next tick, and
                    # those of them no later query of a windowed layer can
                    # read (row k is dead once k <= pos - window).
                    pos = req._pos_base + len(req.tokens)
                    held_rows += pos * self._kv_entries
                    for w, layers in self._dead_row_windows:
                        if pos >= w:
                            dead_rows += (pos - w + 1) * layers
        if spec:
            self._stats.record_spec(
                proposed=self._spec_k * n_valid, accepted=accepted,
                lookup_hits=(flight.lookup_hits
                             if self._spec_mode == "lookup" else None),
                lookup_slots=(n_valid if self._spec_mode == "lookup"
                              else 0))
        self._decode_ticks += 1
        self._stats.record_tick(active_slots=len(flight.entries),
                                committed_tokens=committed,
                                max_slots=self.max_slots, seconds=interval,
                                host_us=host_s * 1e6,
                                other_us=other_s * 1e6,
                                host=phases.drain(), **self._moe_counts(counts),
                                kv_rows=(dead_rows, held_rows),
                                attn_rows=None if spec else
                                self._tick_attn_rows(positions),
                                dsa_rows=None if spec else self._tick_dsa_rows(positions))
        tracer = self._tracer
        if tracer.enabled:
            targs = {"active": len(flight.entries), "committed": committed,
                     "host_us": round(host_s * 1e6, 1)}
            if spec:
                targs["spec_accepted"] = accepted
            tracer.emit("decode_tick", flight.t_dispatch,
                        t1 - flight.t_dispatch, args=targs)
            for slot, req, _ in flight.entries:
                iargs = {"slot": slot, "token": len(req.tokens)}
                if spec:
                    iargs["accepted"] = int(ns[slot]) - 1
                tracer.emit("itl", t1 - interval, interval,
                            trace_id=req.trace_id, args=iargs)
        if self._decode_ticks >= self._next_profile_tick:
            # Black-box sample of the split ITL (cheap: one flight event
            # per ~128 ticks) — postmortems show whether host overhead or
            # device time dominated when things went sideways.
            self._next_profile_tick = self._decode_ticks + 128
            self._flight.record("tick_profile", tick=self._decode_ticks,
                                itl_ms=round(interval * 1e3, 3),
                                host_us=round(host_s * 1e6, 1),
                                active=len(flight.entries))
        self._drain_samples.append((time.monotonic(), self._pool.frees))
        self._stats.record_pages(self._pool.free_pages, self._pool.used_pages,
                                 self._pool.num_pages,
                                 freed_total=self._pool.frees,
                                 kv_bytes_per_token=self.kv_bytes_per_token,
                                 kv_cache_layers=self._kv_entries,
                                 kv_reader_layers=self._kv_readers,
                                 recurrent_state_bytes=self._recurrent_bytes,
                                 weights_served_form_leaves=self._served_form_leaves,
                                 weights_served_form_bytes=self._served_form_bytes,
                                 tick_attn_kernel_readers=self.tick_attn_kernel_readers)

    def _dispatch_spec(self, running, ahead: bool,
                       stale) -> Optional[_TickFlight]:
        """Speculative twin of :meth:`_dispatch`: dispatch one draft-scan
        + verify tick (up to ``spec_tokens + 1`` tokens per slot) without
        waiting. Page coverage is guaranteed only up to the furthest
        position a slot can COMMIT — overshoot writes route to scratch
        inside the program. Reconcile commits the emitted chain exactly
        like ``n`` plain ticks would: stop at ``max_new_tokens`` or the
        first eos.

        The ``ahead`` staleness rules: a stream of the in-flight tick
        (``stale``) with fewer than 2 budget tokens is excluded (it
        deterministically retires at that tick); page coverage extends
        to two chains' worth of commits (``min(2*(K+1), remaining)``) because the in-flight tick may
        advance the write frontier by a full chain before this one runs;
        and ``remaining`` is passed STALE — safe because it is always >=
        the true budget, and the device clamp only matters when it binds
        BELOW a chain length, which stale-high values never spuriously do
        (the host commit loop enforces the true budget; a retiring tick's
        device over-advance is stray state that dies with the slot).
        Lookup mode never dispatches ahead (the run loop reconciles
        first): a proposal drafted one tick behind is misaligned by the
        in-flight tick's variable-length commit and verifies to zero
        accepts, so ahead lookup would be exact but never faster than
        plain decode."""
        K = self._spec_k
        live = []
        for slot, req in running:
            if (req.max_new_tokens - len(req.tokens) < 2
                    and (id(req), req._preempted) in stale):
                continue  # retires at the in-flight tick (position bound)
            if req.on_token is not None and self._emitter.backlogged(req):
                self._stats.record_emission_stall()
                continue
            live.append((slot, req))
        for slot, req in live:
            if req.status is not RequestStatus.RUNNING:
                continue
            rem = max(req.max_new_tokens - len(req.tokens), 1)
            span = min((2 if ahead else 1) * (K + 1), rem)
            cover = req._pos_base + len(req.tokens) + span - 1
            if not self._ensure_pages(req, cover):
                raise RuntimeError(
                    "page pool exhausted at a speculative tick with no "
                    "preemptable stream — the submit page bound should "
                    "make this impossible")
            if self._spec_mode == "draft":
                # Draft writes stop at pos + K - 1 <= cover mid-stream;
                # near the remaining-budget end any overshoot routes to
                # scratch inside the program (quality-only, never
                # correctness), so target cover is enough here too.
                if not self._ensure_draft_pages(req, cover):
                    raise RuntimeError(
                        "page pool exhausted for draft KV at a "
                        "speculative tick — the admission gate's draft "
                        "factor should make this impossible")
        live = [(s, r) for s, r in live
                if r.status is RequestStatus.RUNNING]
        if not live:
            return None
        mask = np.zeros((self.max_slots,), bool)
        remaining = np.ones((self.max_slots,), np.int32)
        for slot, req in live:
            mask[slot] = True
            remaining[slot] = max(req.max_new_tokens - len(req.tokens), 1)
        bank = ((self._adapters.stacks,)
                if self._adapters is not None else ())
        lookup_hits = 0
        t0 = time.monotonic()
        if self._spec_mode == "lookup":
            proposals = np.zeros((self.max_slots, K), np.int32)
            for slot, req in live:
                proposals[slot], hit = self._lookup_proposals(req)
                lookup_hits += int(hit)
            self._state, emit, ns = self._spec(
                self.params, self._state, jnp.asarray(mask),
                self._table.copy(), remaining, proposals, *bank)
        else:
            self._state, emit, ns = self._spec(
                self.params, self._draft_params, self._state,
                jnp.asarray(mask), self._table.copy(), self._dtable.copy(),
                remaining, *bank)
        self._phases.launched()
        return _TickFlight(
            entries=[(slot, req, req._preempted) for slot, req in live],
            t_dispatch=t0, emit=emit, ns=ns, lookup_hits=lookup_hits)

    def _lookup_proposals(self, req: Request):
        """Prompt-lookup drafting: propose the ``K`` tokens that followed
        the most recent earlier occurrence of the stream's last ``n``
        tokens (prompt + committed output), no draft model involved. On a
        miss the proposal is the last token repeated — a deliberately weak
        draft that still verifies correctly, so a miss costs acceptance
        rate, never exactness. Returns ``(proposal[K] int32, hit bool)``.

        This is pure host work on a few-KiB token array per slot per
        tick; the device only ever sees the proposal as traced data."""
        K = self._spec_k
        n = self._spec_lookup
        seq = np.concatenate([
            np.asarray(req._serve_ids[0][:req._pos_base + 1], np.int32),
            np.asarray(req.tokens, np.int32)])
        if len(seq) > n:
            pattern = seq[-n:]
            windows = np.lib.stride_tricks.sliding_window_view(seq[:-1], n)
            hits = np.nonzero((windows == pattern).all(axis=1))[0]
            if hits.size:
                start = int(hits[-1]) + n
                prop = seq[start:start + K]
                if prop.size < K:
                    prop = np.concatenate(
                        [prop, np.full((K - prop.size,), seq[-1],
                                       np.int32)])
                return prop, True
        return np.full((K,), seq[-1], np.int32), False

    def _commit_token(self, req: Request, token: int) -> bool:
        """Append + stream one token. The callback is QUEUED to the
        emitter, not run — the tick loop never waits on a consumer — and
        a callback that already raised off-thread fails ONLY its own
        request here (slot freed, batch untouched), before committing
        more. Returns False when the request was retired instead of
        committed to."""
        if req._emit_error is not None:
            self._retire(req, RequestStatus.FAILED, req._emit_error)
            return False
        req.tokens.append(token)
        if req.on_token is not None:
            self._emitter.put(req, token)
        return True

    def _finish_req(self, req: Request, status: RequestStatus,
                    error: Optional[BaseException] = None):
        """Terminal transition, emitter-aware: status/error land NOW (the
        engine thread's scheduling view stays consistent), while for a
        streaming request the observable completion (``_done``,
        ``_on_finish``) is queued BEHIND its buffered tokens
        — the drain-on-retire barrier that keeps ``result()`` ordered
        after the last ``on_token`` call and lets shutdown/failover drain
        instead of drop."""
        if req.on_token is not None:
            if req._finish(status, error, defer=True):
                self._emitter.finish(req)
        else:
            req._finish(status, error)

    def _retire(self, req: Request, status: RequestStatus,
                error: Optional[BaseException] = None):
        if req.slot is not None:
            self._release_slot_pages(req.slot)
            self._slots.release(req.slot)
        if req._adapter_pinned:
            req._adapter_pinned = False
            self._adapters.release(req.adapter)
        if req.adapter is not None:
            self._stats.record_adapter_tokens(req.adapter, len(req.tokens))
        if req.priority is not None:
            self._stats.record_priority_tokens(req.priority, len(req.tokens))
        self._finish_req(req, status, error)
        self._stats.record_finish(req.status)
        retire_args = {"status": req.status.value, "tokens": len(req.tokens)}
        if req.priority is not None:
            retire_args["priority"] = req.priority
        self._tracer.instant("retire", trace_id=req.trace_id,
                             args=retire_args)
        if req.status is RequestStatus.FAILED and error is not self._error:
            # Engine-fatal retirements are already covered by the single
            # "fatal" event; request-level failures get their own.
            self._flight.record("request_failed", trace_id=req.trace_id,
                                error=repr(error))
