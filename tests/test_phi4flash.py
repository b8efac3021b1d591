"""The phi4flash family (Phi-4-mini-flash-reasoning: Mamba layers, differential
attention over a window and one full layer, gated memory units and
cross-attention over one layer's cache) against its plain reference, on the
CPU at a tiny size: 8 layers (Mamba, windowed attention, Mamba, windowed
attention, the memory layer, the shared-K/V layer, one gated memory unit, one
cross-attention), hidden 64, 8/4 heads of 8, window 8, 4 states, seeded
weights with every bias and scale moved off its initial value.

Tolerances. Everything here is float32 on the CPU. The model and the reference
order their sums differently (a blocked associative scan against the stepped
recurrence, one grouped attention over 128-wide key pairs against four
softmaxes written out), which
moves a logit of magnitude ~5 by a few 1e-5: ``TOL`` = 5e-4. Each planted fault
below (a state kept, a state shared, a cross-attention that misses its
token's own row) moves logits by 1e-2 and more.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama, phi4flash
from accelerate_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
from accelerate_tpu.models.reference import phi4flash as ref
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving.engine import RequestStatus
from accelerate_tpu.serving.metrics import ServingStats

TOL = 5e-4


@pytest.fixture(scope="module")
def tiny():
    cfg = Phi4FlashConfig.tiny()
    model = Phi4FlashForCausalLM(cfg)
    leaves, tree = jax.tree.flatten(model.init_params(jax.random.PRNGKey(0)))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape) for leaf, k in zip(leaves, keys)])
    return cfg, model, params


def ids_of(n, seed=1, vocab=256):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab))


def engine_of(tiny, **kwargs):
    _, model, params = tiny
    options = dict(max_slots=2, max_len=64, prefill_chunk=8, prefix_cache_mb=0,
                   cache_dtype=jnp.float32)
    options.update(kwargs)
    return ServingEngine(model, params, **options)


def serve(eng, prompts, new=10):
    reqs = [eng.submit(np.asarray(p, np.int32)[None], max_new_tokens=new, ignore_eos=True)
            for p in prompts]
    for r in reqs:
        r.result(timeout=300)
    return reqs


def gaps_to_reference(tiny, prompt, tokens):
    """For every served token: the reference's best logit minus its logit of
    that token (0: the reference would have served it too)."""
    cfg, _, params = tiny
    logits = ref.reference_logits(params, jnp.asarray(list(prompt) + list(tokens)), cfg)
    at = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return np.asarray(at.max(-1) - at[np.arange(len(tokens)), np.asarray(tokens)])


# ---------------------------------------------------------------------------
# The model against the reference
# ---------------------------------------------------------------------------

def test_the_layer_plan_and_the_parameter_count_of_the_published_model():
    cfg = Phi4FlashConfig()
    kinds = [cfg.mixer(i) for i in range(32)]
    assert [kinds.count(k) for k in ("mamba", "attn", "gmu", "cross")] == [9, 9, 7, 7]
    assert kinds[:4] == ["mamba", "attn", "mamba", "attn"] and kinds[16:20] == [
        "mamba", "attn", "gmu", "cross"]
    assert [cfg.window_for(i) for i in (1, 15, 17, 19)] == [512, 512, None, None]
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    assert cfg.lambda_init(17) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))
    model = Phi4FlashForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 3_852_562_944   # 3852.6 M: 7.71 GB in bfloat16
    layout = model.attention_layout()
    assert layout[:9] == [(512, i) for i in range(1, 16, 2)] + [(None, 17)]
    assert layout[9:] == [(None, 17)] * 7                     # the cross-attentions read layer 17's
    cache = jax.eval_shape(lambda: model.init_cache(1, 4096, jnp.bfloat16))
    assert len(cache) == 18 and [sorted(e) for e in cache[:2]] == [["conv", "ssm"], ["k", "v"]]
    assert cache[0]["ssm"].shape == (1, 16, 5120) and cache[0]["ssm"].dtype == jnp.float32
    assert cache[1]["k"].shape == cache[1]["v"].shape == (1, 4096, 1280)   # 20 heads side by side


def test_a_full_forward_pass_is_the_references(tiny):
    cfg, model, params = tiny
    ids = ids_of(40)
    logits = model.apply({"params": params}, ids[None])[0]
    assert float(jnp.abs(logits - ref.reference_logits(params, jnp.asarray(ids), cfg)).max()) < TOL


@pytest.mark.parametrize("chunk,prompt", [(8, 5), (8, 19), (16, 16), (4, 12), (16, 37)])
def test_chunks_then_single_tokens_through_a_cache_are_the_references(tiny, chunk, prompt):
    """A prompt shorter than the window (8), past it, ending on a chunk's
    edge; the last chunk padded as the engine pads it (``valid_len``)."""
    cfg, model, params = tiny
    ids = ids_of(prompt + 6, seed=chunk + prompt)
    want = ref.reference_logits(params, jnp.asarray(ids), cfg)
    cache = model.init_cache(1, 64, jnp.float32)
    got = []
    for off in range(0, prompt, chunk):
        part = ids[off:min(off + chunk, prompt)]
        padded = np.pad(part, (0, chunk - len(part)), mode="edge")
        logits, cache = model.apply({"params": params}, padded[None], cache=cache,
                                    cache_pos=off, valid_len=len(part))
        got.append(logits[0, :len(part)])
    for t in range(prompt, prompt + 6):
        logits, cache = model.apply({"params": params}, ids[t:t + 1][None], cache=cache, cache_pos=t)
        got.append(logits[0])
    assert float(jnp.abs(jnp.concatenate(got) - want).max()) < TOL


def test_the_blocked_scan_is_the_stepped_recurrence_across_chunk_edges():
    """``selective_scan`` over several steps is one call of the Pallas kernel
    (the interpreter here; ``tests/test_selective_scan_kernel.py`` has its own
    cases), one step the plain update; a state handed from call to call is
    the stepped recurrence's."""
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    S, N, d = 96, 4, 24
    x, dt = jax.random.normal(k[0], (2, S, d)), jax.nn.softplus(jax.random.normal(k[1], (2, S, d)))
    A = -jnp.exp(jax.random.normal(k[2], (N, d)))
    B, C = jax.random.normal(k[3], (2, S, N)), jax.random.normal(k[4], (2, S, N))
    h = h0 = jax.random.normal(k[5], (2, N, d))
    ys = []
    for t in range(S):                                         # the recurrence as written
        h = jnp.exp(dt[:, t, None, :] * A) * h + (dt[:, t] * x[:, t])[:, None, :] * B[:, t, :, None]
        ys.append(jnp.einsum("bnd,bn->bd", h, C[:, t]))
    want = jnp.stack(ys, 1)
    scan = jax.jit(phi4flash.selective_scan)
    got, last = scan(x, dt, A, B, C, h0)                       # one kernel call
    assert float(jnp.abs(got - want).max()) < 1e-4 and float(jnp.abs(last - h).max()) < 1e-4
    # two chunks of 48 and 47 steps with the state carried, then a single step
    y1, h1 = scan(x[:, :48], dt[:, :48], A, B[:, :48], C[:, :48], h0)
    y2, h2 = scan(x[:, 48:95], dt[:, 48:95], A, B[:, 48:95], C[:, 48:95], h1)
    y3, h3 = scan(x[:, 95:], dt[:, 95:], A, B[:, 95:], C[:, 95:], h2)
    assert float(jnp.abs(jnp.concatenate([y1, y2, y3], 1) - want).max()) < 1e-4
    assert float(jnp.abs(h3 - h).max()) < 1e-4
    # a step of size 0 leaves the state as it is (a chunk's padding)
    _, kept = scan(x[:, :8], dt[:, :8] * 0, A, B[:, :8], C[:, :8], h0)
    assert float(jnp.abs(kept - h0).max()) == 0.0


@pytest.mark.parametrize("form", ["no_cache", "one_block", "bounded_blocks", "work_list"])
def test_differential_attention_is_the_four_softmaxes_written_out(tiny, form, monkeypatch):
    """The program scores a key pair as one 128-wide key against queries
    widened with zeros, one grouped attention for both softmaxes of all pairs,
    in each of the cached forms; the reference writes out softmax(q_2n k_2p) V_p
    - lambda softmax(q_2n+1 k_2p+1) V_p a pair."""
    cfg, _, params = tiny
    layer, T, L = 1, 22, 32                                    # a windowed layer (window 8)
    p = params[f"layers_{layer}"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(9), (1, T, cfg.hidden_size))
    qkv = u[0] @ p["qkv_proj"]["kernel"] + p["qkv_proj"]["bias"]
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    want = ref.diff_attention(qkv[:, :H * hd].reshape(T, H, hd),
                              qkv[:, H * hd:(H + G) * hd].reshape(T, G, hd),
                              qkv[:, (H + G) * hd:].reshape(T, G, hd), p, cfg, layer, 8)
    module = phi4flash.DiffAttention(cfg, layer)
    apply = lambda x, **kw: module.apply({"params": p}, x, **kw)       # noqa: E731
    empty = {"k": jnp.zeros((1, L, G * hd)), "v": jnp.zeros((1, L, G * hd))}
    if form == "no_cache":
        got, _ = apply(u)
    elif form in ("one_block", "bounded_blocks"):
        if form == "bounded_blocks":                           # key blocks of 8 rows: several a call
            monkeypatch.setattr(llama, "cached_key_block", lambda rows, n: 8)
        first, cache = apply(u[:, :16], cache=empty, cache_pos=0)
        second, cache = apply(u[:, 16:], cache=cache, cache_pos=16)
        assert cache["k"].shape == (1, L, G * hd)
        got = jnp.concatenate([first, second], 1)
    else:                                                      # the last token over a pool, in place
        _, cache = apply(u[:, :T - 1], cache=empty, cache_pos=0)
        paged = lambda a: jnp.concatenate([jnp.full((1, 1, 8, G * hd), jnp.nan),       # noqa: E731
                                           a.reshape(4, 1, 8, G * hd)])
        cache = llama.PagedCache(pool=jax.tree.map(paged, cache), scales=None,
                                 pages=jnp.arange(1, 5), live=True)
        got, row = apply(u[:, T - 1:], cache=cache, cache_pos=T - 1)
        assert row["k"].shape == row["v"].shape == (1, 1, G * hd)
        want = want[T - 1:]
    assert float(jnp.abs(got[0] - want).max()) < 1e-4


# ---------------------------------------------------------------------------
# Through the engine: pool and per-slot state
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_engine_serves_the_references_tokens(tiny, chunk):
    """Prompts shorter than the window, past it and on a chunk's edge; four
    requests over two slots, so streams of different lengths share ticks and
    both slots are used again (their state reset)."""
    prompts = [ids_of(n, seed=n) for n in (5, 19, 16, 11)]
    with engine_of(tiny, prefill_chunk=chunk) as eng:
        reqs = serve(eng, prompts)
        summary = eng.stats.summary()
    for prompt, req in zip(prompts, reqs):
        assert float(gaps_to_reference(tiny, prompt, req.tokens).max()) < TOL
    assert summary["recurrent_state_resets"] == 4
    assert (summary["kv_cache_layers"], summary["kv_reader_layers"]) == (3, 4)


def test_a_state_that_is_kept_or_shared_serves_other_tokens(tiny, monkeypatch):
    """The two faults the tests above would not forgive: a slot's state not
    reset for its next stream, and one state shared by the lanes of a tick."""
    prompts = [ids_of(n, seed=n) for n in (5, 19, 16, 11)]
    kept = lambda state, slot, offset: jax.tree.map(lambda a: a[slot], state["recurrent"])  # noqa: E731
    monkeypatch.setattr(ServingEngine, "_slot_recurrent_rows", staticmethod(kept))
    with engine_of(tiny, max_slots=1) as eng:
        reqs = serve(eng, prompts[:2])
    # the slot's first stream is wrong too: it starts from what warm-up left there
    assert min(float(gaps_to_reference(tiny, p, r.tokens).max())
               for p, r in zip(prompts, reqs)) > 1e-2
    monkeypatch.undo()

    tick = ServingEngine._paged_decode_fn

    def shared(self, params, state, active, table, bank=None):   # every lane reads slot 0's rows
        one = jax.tree.map(lambda a: jnp.broadcast_to(a[:1], a.shape), state["recurrent"])
        return tick(self, params, dict(state, recurrent=one), active, table, bank)

    monkeypatch.setattr(ServingEngine, "_paged_decode_fn", shared)
    with engine_of(tiny) as eng:
        reqs = serve(eng, prompts[:2])
    worst = max(float(gaps_to_reference(tiny, p, r.tokens).max()) for p, r in zip(prompts, reqs))
    assert worst > 1e-2


def test_a_cross_attention_sees_its_tokens_own_row_of_the_shared_layer(tiny, monkeypatch):
    """In a tick the shared layer's row of the current token is in no page
    yet: its readers score it beside the pool's rows. One tick's logits over
    a hand-made pool are the reference's; with that row blanked for the
    readers (as if they read the pages alone) they are not."""
    cfg, model, params = tiny
    ids = ids_of(14, seed=7)
    want = ref.reference_logits(params, jnp.asarray(ids), cfg)[-1]
    linear = model.init_cache(1, 16, jnp.float32)
    _, linear = model.apply({"params": params}, ids[None, :13], cache=linear, cache_pos=0)
    # pages of 4 rows; page 0 is the scratch page, the stream holds pages 1..4
    paged = lambda a: jnp.concatenate([jnp.full((1, 1, 4) + a.shape[2:], jnp.nan),      # noqa: E731
                                       a.reshape((4, 1, 4) + a.shape[2:])])
    cache = tuple(
        llama.PagedCache(pool=jax.tree.map(paged, e), scales=None, pages=jnp.arange(1, 5), live=True)
        if "k" in e else e for e in linear)

    def tick():
        logits, rows = model.apply({"params": params}, ids[None, 13:], cache=cache, cache_pos=13)
        assert rows[5]["k"].shape == (1, 1, 32) and rows[4]["ssm"].shape == (1, 4, 128)
        return logits[0, -1]

    assert float(jnp.abs(tick() - want).max()) < TOL
    attend = phi4flash.attend_shared_kv_cache

    def blind(cache, q, cache_pos, n_rep, row=None, sm_scale=None):
        if row is not None:
            row = jax.tree.map(jnp.zeros_like, row)
        return attend(cache, q, cache_pos, n_rep, row=row, sm_scale=sm_scale)

    monkeypatch.setattr(phi4flash, "attend_shared_kv_cache", blind)
    assert float(jnp.abs(tick() - want).max()) > 2e-3             # four times TOL, in one layer of eight


def test_a_preempted_stream_resumes_token_exact(tiny):
    """Two long streams in a pool too small for both: one is evicted, queued
    again and re-prefilled from offset 0 (``prompt + tokens``): its state is
    rebuilt there, no snapshot, and every token is still the reference's."""
    prompts = [ids_of(14, seed=21), ids_of(12, seed=22)]
    with engine_of(tiny, max_pages=7) as eng:
        reqs = serve(eng, prompts, new=30)
        summary = eng.stats.summary()
    assert summary["preemptions"] >= 1 and sum(r._preempted for r in reqs) >= 1
    for prompt, req in zip(prompts, reqs):
        assert req.status is RequestStatus.COMPLETED and len(req.tokens) == 30
        assert float(gaps_to_reference(tiny, prompt, req.tokens).max()) < TOL


def test_int8_pages_leave_the_recurrent_state_in_its_own_type(tiny):
    with engine_of(tiny, kv_dtype="int8") as eng:
        state = eng._state
        assert all(leaf.dtype == jnp.int8 for leaf in jax.tree.leaves(state["pool"]))
        assert {leaf.dtype for leaf in jax.tree.leaves(state["recurrent"])} == {jnp.dtype("float32")}
        req = serve(eng, [ids_of(19, seed=5)])[0]
    gaps = gaps_to_reference(tiny, ids_of(19, seed=5), req.tokens)
    assert float(np.mean(gaps)) < 0.05                          # int8 rows: close, not exact


def test_the_row_counters_by_hand(tiny):
    """One stream of 10 prompt tokens (two chunks of 8: the second padded) and
    3 more ticks, in an engine of 2 slots x 32 rows, pages of 8: 3 cache
    entries (windows 8, 8, none), 4 attentions (the cross-attention reads
    the full layer's entry), 8 score heads."""
    with engine_of(tiny, max_len=32) as eng:
        eng.stats.reset()
        serve(eng, [ids_of(10, seed=3)], new=4)
        s = eng.stats.summary()
        pool = eng.page_pool_metrics()
        assert eng.kv_bytes_per_token == 3 * 2 * 4 * 8 * 4     # 3 entries x (k + v) x 32 values, f32
        assert eng.recurrent_state_bytes == 2 * 3 * (4 * 128 * 4 + 3 * 128 * 4)
    assert pool["recurrent_bytes_per_slot"] * 2 == pool["recurrent_state_bytes"] == 10752 * 2
    assert (s["kv_bytes_per_token"], s["kv_cache_layers"], s["kv_reader_layers"]) == (768, 3, 4)
    assert (s["recurrent_state_bytes"], s["recurrent_state_resets"]) == (21504, 1)
    # chunks at offsets 0 and 8 over a 32-row view, one key block (32 rows) each: every
    # attention scores the view's 32 rows; visible: windowed 8 / 15 rows (offset 0: rows 0..7;
    # offset 8: rows 1..15), full 8 / 16
    assert s["prefill_attn_rows_share"] == 1.0
    assert s["prefill_attn_rows_fill"] == pytest.approx(
        (2 * (8 + 15) + 2 * (8 + 16)) / (2 * 4 * 32), abs=1e-6)
    # ticks at positions 10, 11, 12 (the 4th token is the last tick's own): one running lane,
    # the work list's block is 32 rows and a step holds 2 items, so each attention scores
    # 2 x 32 rows a tick — as many as the view's 2 slots x 32 rows; visible: windowed
    # pos - (pos - 7) = 7, full pos
    assert s["decode_attn_rows_share"] == 1.0
    assert s["decode_attn_rows_fill"] == pytest.approx(
        sum(2 * 7 + 2 * p for p in (10, 11, 12)) / (3 * 4 * 64), abs=1e-6)
    # rows held on into the next tick: pos x 3 entries at pos 11, 12 (the last tick retires its
    # stream); dead: the two windowed entries' rows up to pos - 8
    assert s["kv_dead_rows_share"] == pytest.approx(
        (2 * (11 - 8 + 1) + 2 * (12 - 8 + 1)) / (3 * 11 + 3 * 12), abs=1e-6)


def test_what_the_engine_refuses_for_a_recurrent_cache(tiny):
    _, model, params = tiny
    base = dict(max_slots=2, max_len=32, prefill_chunk=8, autostart=False)
    with pytest.raises(NotImplementedError, match="roll it back"):
        ServingEngine(model, params, prefix_cache_mb=0, spec_lookup=2, **base)
    with pytest.raises(NotImplementedError, match="roll it back"):
        ServingEngine(model, params, prefix_cache_mb=0, draft_model=model, draft_params=params,
                      **base)
    with pytest.raises(NotImplementedError, match="no shard axis"):
        ServingEngine(model, params, prefix_cache_mb=0, tp=2, **base)
    with pytest.raises(NotImplementedError, match="cannot restore the state"):
        ServingEngine(model, params, **base)                   # the default private prefix cache
    from accelerate_tpu.serving import PrefixCache

    with pytest.raises(NotImplementedError, match="cannot restore the state"):
        ServingEngine(model, params, prefix_cache_mb=0, prefix_cache=PrefixCache(1 << 20), **base)
    with pytest.raises(ValueError, match="twice"):
        ServingEngine(model, params, prefix_cache_mb=0, max_slots=2, max_len=36, prefill_chunk=8,
                      autostart=False)


def test_the_new_gauges_merge_and_reset():
    a, b = ServingStats(), ServingStats()
    a.record_pages(1, 1, 2, kv_bytes_per_token=46080, kv_cache_layers=9, kv_reader_layers=16,
                   recurrent_state_bytes=100)
    a.record_prefill_chunk(1.0, state_reset=True)
    b.record_pages(1, 1, 2, kv_bytes_per_token=46080, kv_cache_layers=9, kv_reader_layers=16,
                   recurrent_state_bytes=100)
    b.record_prefill_chunk(1.0, state_reset=False)
    m = ServingStats().merge(a).merge(b).summary()
    assert (m["kv_cache_layers"], m["kv_reader_layers"]) == (9, 16)
    assert (m["recurrent_state_bytes"], m["recurrent_state_resets"]) == (200, 1)
    a.reset()
    s = a.summary()
    assert s["recurrent_state_resets"] == s["recurrent_state_bytes"] == s["kv_cache_layers"] == 0


def test_estimate_memory_prints_the_models_numbers(capsys):
    from accelerate_tpu.commands.estimate import estimate_command, estimate_command_parser

    rc = estimate_command(estimate_command_parser().parse_args(
        ["phi-4-mini-flash-reasoning", "--page-size", "256", "--max-pages", "448"]))
    out = capsys.readouterr().out
    assert rc == 0
    assert "3.85 B params" in out
    assert "18 leaves of 1280 values a token)" in out          # no "no head axis": per-head K and V
    assert "bytes per token : 45.00 KiB" in out                # 9 x 2 x 20 x 64 x 2 B = 46080
    assert "pool (448 pages): 4.92 GiB" in out
    assert "recurrent state : 3.08 MiB/slot (3225600 B" in out  # 9 x (5120 x 16 x 4 + 5120 x 3 x 2)
    rc = estimate_command(estimate_command_parser().parse_args(
        ["phi-4-mini-flash-reasoning", "--page-size", "256", "--tp", "2"]))
    assert rc == 2 and "recurrent state per slot" in capsys.readouterr().out
