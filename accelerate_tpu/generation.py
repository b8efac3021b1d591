"""KV-cached autoregressive decoding.

The reference's headline big-model numbers are per-token generation
latencies (reference: benchmarks/big_model_inference/README.md:26-45),
which presuppose cached decode; torch gets it from transformers'
``model.generate``. The TPU-native equivalent is built here from the model
families' cache-threading support (models/llama.py ``init_kv_cache`` /
``cache=``/``cache_pos=`` arguments):

* ``greedy_generate`` — the fully-compiled path for device-resident params:
  one jitted prefill (writes the prompt's KV into the cache and emits the
  first token) + ONE jitted ``lax.scan`` over all decode steps. Each decode
  step attends single-query against the static-shape cache, so XLA compiles
  exactly two executables per (model, length, eos) combination — cached
  across calls, a repeat generate pays zero retrace.

* `big_modeling.StreamedModel.generate` uses the same cache threading
  per-block for weights that stream from host/disk (one compiled decode
  step per block kind).

Cache capability is registered in ONE place — `big_modeling.
cache_factory_for` — which both this module and the streamed executor
consult.

``generate`` is greedy by default (the reference benchmark's deterministic
setting) and supports ancestral sampling with temperature / top-k / top-p
(``do_sample=True``) — the transformers-generate surface the reference's
users rely on. ``greedy_generate`` is the benchmark-stable greedy alias.
Transformers conventions honored: ``top_k`` of None or 0 disables the
filter; k is clamped to the vocabulary size.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def supports_kv_cache(module) -> bool:
    """True if this model threads a KV cache: decoder-only families are
    registered in big_modeling.cache_factory_for (the streamed executor's
    registry); encoder-decoder families expose ``init_decode_cache`` +
    ``mode="decode"`` (consumed by :func:`seq2seq_generate`)."""
    from .big_modeling import cache_factory_for

    return cache_factory_for(module) is not None or hasattr(module, "init_decode_cache")


_generate_cache: dict = {}


def _make_selector(sampling, repetition_penalty: float = 1.0):
    """Token-selection fn (logits [B, V], rng, seen [B, V] bool) -> [B] ids.
    ``sampling`` is None for greedy, else a (temperature, top_k, top_p)
    triple (static — baked into the executable). ``repetition_penalty``
    applies the CTRL rule to already-seen tokens BEFORE the warpers, like
    transformers' processor ordering: negative scores multiply by the
    penalty, positive divide."""

    if repetition_penalty <= 0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty} "
            "(transformers semantics: >1 suppresses repeats, <1 boosts)")

    def apply_penalty(logits, seen):
        if repetition_penalty == 1.0:
            return logits
        logits = logits.astype(jnp.float32)
        penalized = jnp.where(logits < 0, logits * repetition_penalty,
                              logits / repetition_penalty)
        return jnp.where(seen, penalized, logits)

    if sampling is None:
        return lambda logits, rng, seen: jnp.argmax(apply_penalty(logits, seen), axis=-1)
    warp = _make_warper(sampling)

    def select(logits, rng, seen):
        return jax.random.categorical(rng, warp(apply_penalty(logits, seen)), axis=-1)

    return select


def _make_warper(sampling):
    """logits [B, V] -> warped fp32 logits (temperature / top-k / top-p;
    excluded tokens at -inf). ``softmax(warped)`` IS the sampling target
    distribution — shared by the selector and the speculative accept rule,
    which must agree on it exactly."""
    temperature, top_k, top_p = sampling

    def warp(logits):
        logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
        if top_k is not None and top_k > 0:
            k = min(top_k, logits.shape[-1])
            kth = jax.lax.top_k(logits, k)[0][:, -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p is not None:
            sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # Keep the smallest prefix with cumulative mass >= top_p (always
            # keep the best token).
            keep = jnp.concatenate(
                [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p], axis=-1
            )
            cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return logits

    return warp


def speculative_accept(warped_logits, draft, rng):
    """Exact speculative sampling over one verification chunk (Leviathan/
    Chen rejection rule with a deterministic — delta — proposal).

    Args:
      warped_logits: [K+1, V] fp32 — position j's TARGET distribution is
        softmax(warped_logits[j]) (already temperature/top-k/top-p warped).
      draft: [K] int — proposed tokens.
      rng: PRNG key.

    Returns ``(m, final)``: ``m`` draft tokens commit (their acceptance
    tests passed) followed by ``final``, drawn from position ``m``'s
    residual distribution max(p - delta_draft, 0)/Z when ``m < K`` (the
    rejection-sampling correction) or from position K's full target when
    every draft was accepted. Marginal law of the emitted tokens is exactly
    the chain of target distributions — the speculative-sampling theorem.
    """
    K = draft.shape[0]
    probs = jax.nn.softmax(warped_logits, axis=-1)               # [K+1, V]
    u_rng, s_rng = jax.random.split(rng)
    u = jax.random.uniform(u_rng, (K,))
    p_draft = jnp.take_along_axis(probs[:K], draft[:, None], axis=1)[:, 0]
    accept = u < p_draft                                         # delta proposal: q = 1
    m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    # Resample row: position m's warped logits, with the rejected draft
    # token excluded (residual distribution) when m < K.
    row = warped_logits[jnp.minimum(m, K)]
    rejected = draft[jnp.minimum(m, K - 1)]
    masked = row.at[rejected].set(-jnp.inf)
    row = jnp.where(m < K, masked, row)
    final = jax.random.categorical(s_rng, row)
    return m, final


def speculative_emit(logits, draft, rng, warp, eos_token_id, dtype,
                     prior_done=None):
    """One verification chunk -> the emitted token chain, shared by the
    offline speculative decoders and the serving engine's ``_spec``
    executable (the factored accept rule).

    Args:
      logits: [K+1, V] target logits over [last_committed, draft].
      draft: [K] proposed tokens.
      rng: PRNG key for the accept rule (unused when ``warp`` is None).
      warp: warper from :func:`_make_warper`, or None for greedy.
      eos_token_id: eos id or None.
      dtype: emitted token dtype.
      prior_done: scalar bool — True when the sequence already emitted eos
        (engine slots running under ``ignore_eos``); the whole chunk then
        emits eos, matching the decode latch.

    Returns ``(m, emit)``: ``emit`` [K+1] is the token chain of which the
    caller commits the first ``min(m + 1, remaining)``; ``m`` counts the
    accepted draft tokens — greedy: the longest prefix of ``draft``
    agreeing with the (eos-latched) target argmax chain; sampled: the
    rejection-rule count from :func:`speculative_accept`, with ``emit[m]``
    the residual-distribution resample. The eos latch is applied in-chunk:
    every position after the first eos emits eos, so committing a prefix of
    ``emit`` replays :func:`generate`'s ragged stop exactly.
    """
    K = draft.shape[0]
    done0 = jnp.asarray(False) if prior_done is None else prior_done
    if warp is None:
        preds = jnp.argmax(logits, axis=-1).astype(dtype)          # [K+1]
        if eos_token_id is not None:
            eos = jnp.asarray(eos_token_id, dtype)

            def latch(d, p):
                t = jnp.where(d, eos, p)
                return d | (t == eos), t

            _, emit = jax.lax.scan(latch, done0, preds)
        else:
            emit = preds
        m = jnp.sum(jnp.cumprod((draft == emit[:K]).astype(jnp.int32)))
    else:
        m, final = speculative_accept(warp(logits), draft, rng)
        slots = jnp.arange(K + 1)
        emit = jnp.where(slots < m, jnp.append(draft, 0)[slots],
                         final).astype(dtype)
        if eos_token_id is not None:
            eos = jnp.asarray(eos_token_id, dtype)
            emit = jnp.where(done0, eos, emit)
            after = jnp.concatenate(
                [jnp.zeros((1,), bool), jnp.cumsum(emit == eos)[:-1] > 0])
            emit = jnp.where(after, eos, emit)
    return m, emit


def _freeze(obj):
    """Recursively convert dict/list config fields (e.g. rope_scaling) to
    hashable tuples so they can live in a cache key."""
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


def _cache_key(module, *parts):
    """Executable-cache key over the config's *field values* (the apply
    computation depends only on them), not the module object: model configs
    are plain mutable dataclasses and not hashable. None = uncacheable."""
    import dataclasses

    cfg = getattr(module, "config", None)
    if cfg is None or not dataclasses.is_dataclass(cfg):
        return None
    return (type(module).__name__, _freeze(dataclasses.astuple(cfg)), *parts)


def _cache_get(key):
    """LRU lookup: a hit moves to the back of the insertion order so the
    eviction in :func:`_cache_put` (pop the front) drops the *least
    recently used* entry, not merely the oldest-inserted — same fix as
    the autograd ``_backward_cache``. A steady interleaving of one hot
    config with churning one-shot configs must never evict the hot one."""
    if key is None:
        return None
    hit = _generate_cache.pop(key, None)
    if hit is not None:
        _generate_cache[key] = hit  # move to end (most recently used)
    return hit


def _cache_put(key, value):
    if key is not None:
        if len(_generate_cache) >= 64:  # bound growth; configs rarely churn
            _generate_cache.pop(next(iter(_generate_cache)))
        _generate_cache[key] = value
    return value


def _suppress_eos(last, gen_index, eos_token_id, min_new_tokens: int):
    """Mask the EOS column of ``last`` [B, V] while the token being selected
    (generation index ``gen_index``, 1-based; may be traced) is still within
    ``min_new_tokens`` — HF MinNewTokensLength semantics: EOS is first
    allowed at new token min+1."""
    if eos_token_id is None or min_new_tokens < 1:
        return last
    allow = jnp.asarray(gen_index) > min_new_tokens
    eos_col = last[:, eos_token_id]
    return last.at[:, eos_token_id].set(jnp.where(allow, eos_col, -jnp.inf))


def _mark_seen(seen, token_ids):
    """seen [B, V] bool |= one-hot union of token_ids [B] or [B, S]."""
    ids = token_ids if token_ids.ndim == 2 else token_ids[:, None]
    B = seen.shape[0]
    return seen.at[jnp.arange(B)[:, None], ids].set(True)


def _next_token(last, rng, seen, done, select, eos_token_id, dtype):
    """THE single-token decode primitive, shared by the offline scan bodies
    and the serving engine's per-slot step (which vmaps it): select one
    token from ``last`` [B, V] with the already-split ``rng``, then apply
    the ragged-stop EOS latch — sequences that emitted eos keep emitting
    it. Returns ``(next_token [B], done [B])``. Keeping selection + latch
    in one place is what makes the engine's streamed tokens bit-identical
    to offline :func:`generate` for the same (prompt, rng, sampling)."""
    nxt = select(last, rng, seen).astype(dtype)
    if eos_token_id is not None:
        nxt = jnp.where(done, jnp.asarray(eos_token_id, dtype), nxt)
        done = done | (nxt == eos_token_id)
    return nxt, done


def _chunk_prefill_token(logits, rng, select, eos_token_id, dtype, true_len,
                         offset=0, seen=None):
    """THE prefill epilogue of the serving engine's chunk programs: split ``rng`` exactly like offline
    :func:`generate` (decode carry first, prefill half second), read the
    logits row of the last REAL prompt position — ``true_len - 1`` in
    absolute positions, mapped into this chunk's ``[offset, offset + W)``
    window and clamped so a chunk that does not contain it still indexes
    in-bounds — and select token #1 through :func:`_next_token`. Only the
    chunk containing ``true_len - 1`` (the final one) selects a real
    token; earlier chunks' results are discarded by the engine. Returns
    ``(tok [B], done [B], rng_carry)``.
    """
    local = jnp.clip(true_len - 1 - offset, 0, logits.shape[1] - 1)
    last = jax.lax.dynamic_slice_in_dim(logits, local, 1, axis=1)[:, 0]
    rng_carry, pre_rng = jax.random.split(rng)
    if seen is None:
        seen = jnp.zeros((last.shape[0], 1), bool)
    tok, done = _next_token(last, pre_rng, seen,
                            jnp.zeros((last.shape[0],), bool),
                            select, eos_token_id, dtype)
    return tok, done, rng_carry


def _decode_scan(step_fn, select, first_tok, carry_extra, start_pos,
                 eos_token_id, num_steps: int, rng, seen0, track_seen=True,
                 min_new_tokens: int = 0):
    """Shared decode loop: scan ``num_steps`` single-token forwards.

    ``step_fn(tok, extra, pos) -> (logits, extra)`` hides the family
    difference (decoder-only cache vs seq2seq cache+cross_kv). EOS
    semantics: sequences that emitted eos keep emitting it (ragged stop
    inside a static-shape scan). Emits the *computed* token each step — the
    scan runs num_steps times and first_tok supplies the head, so no
    forward's output is ever discarded. ``seen0`` [B, V] is the
    repetition-penalty occurrence set (already including first_tok).
    """
    def body(carry, i):
        tok, extra, pos, done, rng, seen = carry
        logits, extra = step_fn(tok, extra, pos)
        # This body emits generation index i+2 (first_tok is index 1).
        last = _suppress_eos(logits[:, -1], i + 2, eos_token_id, min_new_tokens)
        rng, sub = jax.random.split(rng)
        nxt, done = _next_token(last, sub, seen, done, select, eos_token_id,
                                tok.dtype)
        if track_seen:
            seen = _mark_seen(seen, nxt)
        return (nxt, extra, pos + 1, done, rng, seen), nxt

    done0 = jnp.zeros((first_tok.shape[0],), bool)
    if eos_token_id is not None:
        done0 = first_tok == eos_token_id
    _, toks = jax.lax.scan(
        body, (first_tok, carry_extra, start_pos, done0, rng, seen0),
        jnp.arange(num_steps))
    return jnp.concatenate([first_tok[:, None], toks.T], axis=1)


def _compiled_generate(module, max_new_tokens: int, eos_token_id, cache_dtype,
                       sampling=None, repetition_penalty: float = 1.0,
                       min_new_tokens: int = 0):
    """(prefill, decode) jitted pair for this (model config, length, eos,
    dtype) — cached so repeat generate calls reuse the same jitted function
    objects (and therefore jax.jit's executable cache) instead of retracing
    fresh closures every call.

    The prompt length is NOT part of any executable's shape: the caller
    buckets the cache length to a 128-multiple and EDGE-pads the prompt to
    its own 128-bucket (repeating each row's last token, so the
    repetition-penalty seen-set is unchanged — zero-padding would poison
    it), and prefill reads the logits at the traced ``true_len - 1``. One
    compiled (prefill, decode) pair per bucket; the pad KV is never
    attended (the masking argument in :func:`_compiled_lookup_generate`)."""
    key = _cache_key(module, max_new_tokens, eos_token_id,
                     jnp.dtype(cache_dtype).name, sampling, repetition_penalty,
                     min_new_tokens)
    hit = _cache_get(key)
    if hit is not None:
        return hit

    select = _make_selector(sampling, repetition_penalty)

    track_seen = repetition_penalty != 1.0

    @jax.jit
    def prefill(params, ids, cache, rng, true_len):
        logits, cache = module.apply({"params": params}, ids, cache=cache, cache_pos=0)
        if track_seen:
            # Repetition penalty counts the prompt too (transformers
            # semantics); off the penalty path the tracking (a [B, V] bool
            # per call) is skipped entirely — a (B, 1) dummy rides the carry.
            # ids arrive edge-padded, so marking the pad positions re-marks
            # each row's last real token: the seen-set is exact.
            seen = _mark_seen(jnp.zeros((ids.shape[0], logits.shape[-1]), bool), ids)
        else:
            seen = jnp.zeros((ids.shape[0], 1), bool)
        last_row = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)[:, 0]
        last = _suppress_eos(last_row, 1, eos_token_id, min_new_tokens)
        tok = select(last, rng, seen).astype(ids.dtype)
        return tok, cache, (_mark_seen(seen, tok) if track_seen else seen)

    @jax.jit
    def decode(params, first_tok, cache, start_pos, rng, seen):
        # (No donation: the final cache is discarded, not an output, so the
        # input buffers cannot alias anything — XLA reuses the scan carry
        # buffers in place regardless.)
        def step(tok, cache, pos):
            return module.apply({"params": params}, tok[:, None], cache=cache, cache_pos=pos)

        return _decode_scan(step, select, first_tok, cache, start_pos,
                            eos_token_id, max_new_tokens - 1, rng, seen,
                            track_seen=track_seen, min_new_tokens=min_new_tokens)

    return _cache_put(key, (prefill, decode))


def _bucket128(n: int) -> int:
    """Ceil to the 128 bucket — THE granularity every generate path uses
    for cache lengths and padded prompts (one compiled set per bucket)."""
    return -(-n // 128) * 128


def _bucket_and_pad(ids, *modules_or_bounds):
    """THE prompt-bucketing rule (compiled AND streamed paths import it):
    EDGE-pad ``ids`` to the 128-bucket of its length — repeating each
    row's last token, so a repetition-penalty seen-set is unchanged —
    CAPPED at every given module's (or raw int bound's) learned-position
    table. Padding past the table is not merely wasteful: OOB
    learned-position lookups can go non-finite and NaN poisons the whole
    forward (observed on OPT), so the cap is a correctness requirement.
    Returns (padded_ids, true_len)."""
    S = ids.shape[1]
    P = _bucket128(S)
    for mb in modules_or_bounds:
        bound = mb if isinstance(mb, int) else getattr(
            getattr(mb, "config", None), "max_position_embeddings", None)
        if bound is not None:
            P = min(P, int(bound))
    if P <= S:
        return ids, S
    return jnp.pad(ids, ((0, 0), (0, P - S)), mode="edge"), S


def _check_position_bound(module, total_len: int, label: str = "prompt + max_new_tokens"):
    """Learned-position models silently clamp indices past their table (the
    wpe lookup clips under jit) — turn that corruption into an error."""
    bound = getattr(getattr(module, "config", None), "max_position_embeddings", None)
    if bound is not None and total_len > bound:
        raise ValueError(
            f"{label} = {total_len} exceeds "
            f"max_position_embeddings = {bound} for {type(module).__name__}"
        )


def generate(
    module,
    params,
    input_ids,
    max_new_tokens: int = 20,
    eos_token_id: Optional[int] = None,
    cache_dtype=None,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    repetition_penalty: float = 1.0,
    min_new_tokens: int = 0,
    rng=None,
):
    """KV-cached decoding, fully compiled (prefill + scan): greedy by
    default, ancestral sampling with temperature / top-k / top-p when
    ``do_sample=True``, CTRL-style ``repetition_penalty`` over
    prompt+generated tokens (the transformers-generate surface the
    reference's users rely on).

    Args:
      module: a cache-threading model (see :func:`supports_kv_cache`).
      params: parameter pytree.
      input_ids: [B, S] int prompt.
      max_new_tokens: decode steps (static — sets the cache length).
      eos_token_id: sequences that emit it keep emitting it (ragged stop
        inside a static-shape scan).
      cache_dtype: KV buffer dtype (default: bfloat16).
      do_sample: sample instead of argmax.
      temperature / top_k / top_p: sampling knobs (static — each combination
        compiles once).
      repetition_penalty: CTRL rule over prompt+generated tokens (>1
        suppresses repeats, <1 boosts; applied before the warpers).
      min_new_tokens: EOS is masked until this many tokens are generated
        (EOS first allowed at new token min+1, HF semantics).
      rng: jax PRNG key for sampling (default PRNGKey(0)).

    Returns [B, S + max_new_tokens] ids (prompt + completion). For
    encoder-decoder modules the call delegates to :func:`seq2seq_generate`
    and returns **decoder** ids, [B, 1 + max_new_tokens] — the prompt is
    the encoder's input, not a decode prefix.
    """
    from .big_modeling import cache_factory_for

    if hasattr(module, "init_decode_cache"):
        # Encoder-decoder family: same public entry point, seq2seq
        # mechanics (so supports_kv_cache => generate works).
        return seq2seq_generate(
            module, params, input_ids, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, cache_dtype=cache_dtype,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            top_p=top_p, repetition_penalty=repetition_penalty,
            min_new_tokens=min_new_tokens, rng=rng)
    factory = cache_factory_for(module)
    if factory is None:
        raise TypeError(
            f"{type(module).__name__} does not thread a KV cache; use the model's "
            "full-forward generate or add cache support to the family "
            "(big_modeling.cache_factory_for)."
        )
    ids = jnp.asarray(input_ids)
    if max_new_tokens <= 0:
        return ids
    B, S = ids.shape
    _check_position_bound(module, S + max_new_tokens)
    dtype = cache_dtype or jnp.bfloat16
    # Bucket the cache length and EDGE-pad the prompt to a 128-multiple so
    # nearby prompt lengths share one compiled (prefill, decode) pair —
    # see _compiled_generate. ring_slack=128 keeps sliding-window ring
    # caches safe from the pad writes (registry factories all take it).
    L = _bucket128(S + max_new_tokens)
    cache = factory(B, L, dtype, ring_slack=128)
    ids_p, _ = _bucket_and_pad(ids, module)

    sampling = (float(temperature), top_k, top_p) if do_sample else None
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    prefill, decode = _compiled_generate(module, max_new_tokens, eos_token_id, dtype,
                                         sampling=sampling,
                                         repetition_penalty=float(repetition_penalty),
                                         min_new_tokens=int(min_new_tokens))
    rng, pre_rng = jax.random.split(rng)
    first_tok, cache, seen = prefill(params, ids_p, cache, pre_rng,
                                     jnp.asarray(S, jnp.int32))
    new_toks = decode(params, first_tok, cache, jnp.asarray(S, jnp.int32), rng, seen)
    return jnp.concatenate([ids, new_toks], axis=1)


def greedy_generate(module, params, input_ids, max_new_tokens: int = 20,
                    eos_token_id: Optional[int] = None, cache_dtype=None):
    """Greedy alias of :func:`generate` (kept as the benchmark-stable name)."""
    return generate(module, params, input_ids, max_new_tokens=max_new_tokens,
                    eos_token_id=eos_token_id, cache_dtype=cache_dtype)


def _compiled_lookup_generate(module, max_new_tokens: int, eos_token_id, cache_dtype,
                              ngram: int, num_draft: int, buf_len: int,
                              sampling=None):
    """(prefill, speculate_loop) jitted pair for prompt-lookup decoding.
    Keyed per (module config, lengths, eos, dtype, ngram, K) like
    _compiled_generate. The prompt length is NOT part of the key: BOTH
    halves take it as a traced argument — the speculate loop is shaped
    only by the bucketed ``buf_len``, and prefill sees the prompt padded
    to a 128-multiple with the true length traced (it reads the logits at
    ``true_len - 1``) — so varied prompt lengths share one compiled
    (prefill, loop) pair per bucket instead of recompiling prefill per
    exact length. Pad positions write garbage KV the masks provably never
    expose: full caches mask ``k_pos <= q_pos`` and every pad slot stays
    ahead of the committed frontier until the contiguous verification
    chunks overwrite it; ring caches mask by stored position, with the
    cache built with ``ring_slack`` covering the pad so prefill's pad
    writes cannot evict in-window prompt keys (see
    :func:`prompt_lookup_generate`). ``sampling`` non-None switches the
    greedy accept rule to exact speculative sampling
    (:func:`speculative_accept`)."""
    key = _cache_key(module, max_new_tokens, eos_token_id,
                     jnp.dtype(cache_dtype).name, sampling, 1.0,
                     ("lookup", ngram, num_draft, buf_len))
    hit = _cache_get(key)
    if hit is not None:
        return hit

    warp = _make_warper(sampling) if sampling is not None else None
    K = num_draft
    # Buffer slack: a verification chunk may scribble K + 1 tokens past the
    # last committed position; committed entries always overwrite before
    # they are read (or are sliced away at the end).
    L = buf_len
    eos = eos_token_id

    @jax.jit
    def prefill(params, ids, cache, rng, true_len):
        logits, cache = module.apply({"params": params}, ids, cache=cache, cache_pos=0)
        last = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)[:, 0]
        if sampling is None:
            tok = jnp.argmax(last, axis=-1)
        else:
            tok = jax.random.categorical(rng, warp(last), axis=-1)
        return tok.astype(ids.dtype), cache

    @jax.jit
    def speculate(params, buf, cache, rng, S):
        """buf: [1, L] with the prompt (length ``S``, traced) + first
        generated token committed (n_gen starts at 1). Returns the
        completed buf."""

        def cond(state):
            _, n_gen, _, done, _ = state
            return (n_gen < max_new_tokens) & ~done

        def body(state):
            buf, n_gen, cache, done, rng = state
            rng, step_rng = jax.random.split(rng)
            cur = S + n_gen                       # committed length
            # --- draft: continuation of the most recent earlier match of
            # the last `ngram` committed tokens --------------------------
            pattern = jax.lax.dynamic_slice(buf, (0, cur - ngram), (1, ngram))[0]
            row = buf[0]
            windows = jnp.stack(
                [jnp.roll(row, -j) for j in range(ngram)], axis=1)     # [L, n]
            idxs = jnp.arange(L, dtype=jnp.int32)
            hit = (windows == pattern[None, :]).all(axis=1) & (idxs + ngram < cur)
            best = jnp.max(jnp.where(hit, idxs, -1))                   # most recent
            draft_start = jnp.clip(best + ngram, 0, L - K)
            draft = jax.lax.dynamic_slice(buf, (0, draft_start), (1, K))[0]
            # (no match: `draft` is whatever sits at the clamp target — a
            # harmless suggestion the verifier rejects at its first token)

            # --- verify: one forward over [last_committed, draft] --------
            last = jax.lax.dynamic_slice(buf, (0, cur - 1), (1, 1))
            chunk = jnp.concatenate([last, draft[None, :]], axis=1)    # [1, K+1]
            logits, cache = module.apply({"params": params}, chunk,
                                         cache=cache, cache_pos=cur - 1)
            m, emit = speculative_emit(logits[0], draft, step_rng, warp,
                                       eos, buf.dtype)
            n_emit = jnp.minimum(m + 1, max_new_tokens - n_gen)
            buf = jax.lax.dynamic_update_slice(buf, emit[None, :], (0, cur))
            if eos is not None:
                done = done | jnp.any((jnp.arange(K + 1) < n_emit) & (emit == eos))
            return buf, n_gen + n_emit, cache, done, rng

        # The first generated token may itself be EOS (ragged-stop from the
        # very first step, like generate()).
        done0 = (buf[0, S] == eos) if eos is not None else jnp.asarray(False)
        buf, n_gen, _, _, _ = jax.lax.while_loop(
            cond, body, (buf, jnp.asarray(1, jnp.int32), cache, done0, rng))
        if eos is not None:
            # Early EOS stop: the un-generated tail keeps emitting EOS.
            tail = jnp.arange(L) >= (S + n_gen)
            committed = jnp.arange(L) < S + max_new_tokens
            buf = jnp.where((tail & committed)[None, :], eos, buf)
        return buf

    return _cache_put(key, (prefill, speculate))


def prompt_lookup_generate(
    module,
    params,
    input_ids,
    max_new_tokens: int = 20,
    eos_token_id: Optional[int] = None,
    cache_dtype=None,
    ngram: int = 2,
    num_draft: int = 5,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng=None,
):
    """Decoding accelerated by prompt-lookup speculation — greedy by
    default, distribution-exact sampling with ``do_sample=True`` (assisted
    generation without a draft model — transformers'
    ``prompt_lookup_num_tokens``, which the reference's users reach through
    ``model.generate``).

    Each step drafts ``num_draft`` tokens by matching the last ``ngram``
    committed tokens against their most recent earlier occurrence in the
    sequence, then verifies the whole draft in ONE cached forward — the
    model's own greedy predictions decide how many draft tokens commit, so
    the output is EXACTLY ``generate``'s greedy output, reached in fewer
    (and wider, MXU-friendlier) decode steps wherever the text repeats
    itself (code, summaries-with-quotes, retrieval contexts). Rejected
    positions leave stale KV entries that the next verification chunk
    overwrites before any query can attend them; ring caches mask them by
    stored position. Batch 1 only (per-row acceptance counts would
    desynchronize a batched scan).

    ``do_sample=True`` switches the accept rule to EXACT speculative
    sampling (:func:`speculative_accept` — rejection sampling against the
    temperature/top-k/top-p-warped target): the emitted tokens are
    distributed exactly as ``generate(do_sample=True)``'s, though the
    draws differ (different rng consumption).
    """
    from .big_modeling import cache_factory_for

    if hasattr(module, "init_decode_cache"):
        raise TypeError(
            "prompt_lookup_generate supports decoder-only models; use "
            "seq2seq_generate for encoder-decoder families")
    factory = cache_factory_for(module)
    if factory is None:
        raise TypeError(
            f"{type(module).__name__} does not thread a KV cache")
    ids = jnp.asarray(input_ids)
    if ids.shape[0] != 1:
        raise ValueError("prompt_lookup_generate is batch-1 only "
                         f"(got batch {ids.shape[0]})")
    if ngram < 1 or num_draft < 1:
        raise ValueError(f"ngram and num_draft must be >= 1 (got {ngram}, {num_draft})")
    if max_new_tokens <= 0:
        return ids
    B, S = ids.shape
    K = int(num_draft)
    # Highest position a verification chunk can touch: the last chunk
    # starts at S + max_new_tokens - 2 and spans K + 1.
    _check_position_bound(module, S + max_new_tokens + K - 1,
                          label="prompt + max_new_tokens + speculative slack")
    dtype = cache_dtype or jnp.bfloat16
    # Bucket the buffer/cache length to a 128 multiple so interactive use
    # with varied prompt lengths shares ONE compiled speculate loop per
    # bucket instead of recompiling (and filling a generate-cache slot) per
    # exact length; the prompt length rides in as a traced argument.
    L = _bucket128(S + max_new_tokens + K + 1)
    # Bucket the PROMPT too: prefill runs on ids right-padded to a
    # 128-multiple (capped at the position table) with the true length
    # traced, so nearby prompt lengths share one compiled prefill (the pad
    # KV is never attended — see _compiled_lookup_generate).
    ids_padded, _ = _bucket_and_pad(ids, module)
    # ring_slack: rejected overshoot writes (K + 1) plus prefill's pad
    # writes (< 128, held STATIC at the bucket width so the cache shape —
    # and thus the compiled pair — stays per-bucket) must not evict
    # in-window keys from sliding-window layers' ring caches.
    cache = factory(B, L, dtype, ring_slack=K + 1 + 128)

    sampling = (float(temperature), top_k, top_p) if do_sample else None
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    rng, pre_rng = jax.random.split(rng)
    prefill, speculate = _compiled_lookup_generate(
        module, max_new_tokens, eos_token_id, dtype, int(ngram), K, L,
        sampling=sampling)
    first_tok, cache = prefill(params, ids_padded, cache, pre_rng,
                               jnp.asarray(S, jnp.int32))
    buf = jnp.zeros((1, L), ids.dtype)
    buf = jax.lax.dynamic_update_slice(buf, ids, (0, 0))
    buf = buf.at[0, S].set(first_tok[0])
    buf = speculate(params, buf, cache, rng, jnp.asarray(S, jnp.int32))
    return buf[:, : S + max_new_tokens]


def _compiled_assisted_generate(module, draft_module, max_new_tokens: int,
                                eos_token_id, cache_dtype, num_draft: int,
                                buf_len: int, sampling=None):
    """(prefill_target, prefill_draft, speculate_loop) jitted triple for
    draft-model speculation. Keyed like :func:`_compiled_lookup_generate`
    (bucketed ``buf_len``, prompt length traced) plus the DRAFT module's
    config — two target/draft pairings never share an executable."""
    tkey = _cache_key(module, max_new_tokens, eos_token_id,
                      jnp.dtype(cache_dtype).name, sampling, 1.0,
                      ("assisted", num_draft, buf_len))
    dkey = _cache_key(draft_module, 0)
    key = (tkey, dkey) if tkey is not None and dkey is not None else None
    hit = _cache_get(key)
    if hit is not None:
        return hit

    warp = _make_warper(sampling) if sampling is not None else None
    K = num_draft
    L = buf_len
    eos = eos_token_id

    @jax.jit
    def prefill_t(params, ids, cache, rng, true_len):
        # ids arrive right-padded to the prompt bucket; the pad KV is never
        # attended (same masking argument as _compiled_lookup_generate).
        logits, cache = module.apply({"params": params}, ids, cache=cache, cache_pos=0)
        last = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)[:, 0]
        if sampling is None:
            tok = jnp.argmax(last, axis=-1)
        else:
            tok = jax.random.categorical(rng, warp(last), axis=-1)
        return tok.astype(ids.dtype), cache

    @jax.jit
    def prefill_d(draft_params, ids, dcache):
        _, dcache = draft_module.apply(
            {"params": draft_params}, ids, cache=dcache, cache_pos=0)
        return dcache

    @jax.jit
    def speculate(params, draft_params, buf, cache, dcache, rng, S):
        """buf: [1, L] with the prompt (length ``S``, traced) + first
        generated token committed. The draft model proposes K tokens by
        greedy cached decode (a delta proposal, so
        :func:`speculative_accept` stays exact for sampled targets); the
        target verifies the chunk in ONE forward. Rejected positions leave
        stale KV entries in BOTH caches that the next round's writes cover
        before any query can attend them (drafting restarts from the last
        committed token, one position behind the target's chunk)."""

        def cond(state):
            _, n_gen, _, _, done, _ = state
            return (n_gen < max_new_tokens) & ~done

        def body(state):
            buf, n_gen, cache, dcache, done, rng = state
            rng, step_rng = jax.random.split(rng)
            cur = S + n_gen                       # committed length

            # --- draft: K greedy cached steps of the draft model ---------
            last = jax.lax.dynamic_slice(buf, (0, cur - 1), (1, 1))

            def dstep(carry, _):
                tok, dcache, pos = carry
                logits, dcache = draft_module.apply(
                    {"params": draft_params}, tok, cache=dcache, cache_pos=pos)
                nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(tok.dtype)
                return (nxt, dcache, pos + 1), nxt[0, 0]

            (_, dcache, _), draft = jax.lax.scan(
                dstep, (last, dcache, cur - 1), None, length=K)

            # --- verify: one target forward over [last_committed, draft] --
            chunk = jnp.concatenate([last, draft[None, :]], axis=1)    # [1, K+1]
            logits, cache = module.apply({"params": params}, chunk,
                                         cache=cache, cache_pos=cur - 1)
            m, emit = speculative_emit(logits[0], draft, step_rng, warp,
                                       eos, buf.dtype)
            n_emit = jnp.minimum(m + 1, max_new_tokens - n_gen)
            buf = jax.lax.dynamic_update_slice(buf, emit[None, :], (0, cur))
            if eos is not None:
                done = done | jnp.any((jnp.arange(K + 1) < n_emit) & (emit == eos))
            return buf, n_gen + n_emit, cache, dcache, done, rng

        done0 = (buf[0, S] == eos) if eos is not None else jnp.asarray(False)
        buf, n_gen, _, _, _, _ = jax.lax.while_loop(
            cond, body, (buf, jnp.asarray(1, jnp.int32), cache, dcache, done0, rng))
        if eos is not None:
            tail = jnp.arange(L) >= (S + n_gen)
            committed = jnp.arange(L) < S + max_new_tokens
            buf = jnp.where((tail & committed)[None, :], eos, buf)
        return buf

    return _cache_put(key, (prefill_t, prefill_d, speculate))


def assisted_generate(
    module,
    params,
    draft_module,
    draft_params,
    input_ids,
    max_new_tokens: int = 20,
    num_draft: int = 5,
    eos_token_id: Optional[int] = None,
    cache_dtype=None,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng=None,
):
    """Draft-model speculative decoding — transformers' assisted generation
    (``model.generate(assistant_model=...)``), which the reference's users
    reach through the big-model stack.

    A small draft model proposes ``num_draft`` tokens by greedy cached
    decode; the target verifies the whole chunk in ONE cached forward. The
    output is EXACTLY ``generate``'s greedy output of the TARGET (the
    target's predictions decide every commit); ``do_sample=True`` switches
    to exact speculative sampling against the warped target — the greedy
    draft is a delta proposal, so :func:`speculative_accept`'s rejection
    rule keeps the emitted distribution exactly the target's.

    Wall-clock wins when the draft agrees often and costs a small fraction
    of the target per token: each round is K cheap draft steps + one wide
    (MXU-friendly) K+1-token target forward instead of K+1 sequential
    target steps. Complements :func:`prompt_lookup_generate`, which needs
    self-repetitive text; a trained draft accelerates arbitrary text.

    Both models must be decoder-only cache-threading families over the SAME
    vocabulary. Batch 1 only (per-row acceptance would desynchronize).
    """
    from .big_modeling import cache_factory_for

    for m, name in ((module, "target"), (draft_module, "draft")):
        if hasattr(m, "init_decode_cache"):
            raise TypeError(f"assisted_generate supports decoder-only models; "
                            f"the {name} model is encoder-decoder")
        if cache_factory_for(m) is None:
            raise TypeError(f"{type(m).__name__} ({name}) does not thread a KV cache")
    t_vocab = getattr(module.config, "vocab_size", None)
    d_vocab = getattr(draft_module.config, "vocab_size", None)
    if t_vocab != d_vocab:
        raise ValueError(
            f"target and draft must share a vocabulary (got {t_vocab} vs {d_vocab})")
    ids = jnp.asarray(input_ids)
    if ids.shape[0] != 1:
        raise ValueError(f"assisted_generate is batch-1 only (got batch {ids.shape[0]})")
    if num_draft < 1:
        raise ValueError(f"num_draft must be >= 1 (got {num_draft})")
    if max_new_tokens <= 0:
        return ids
    B, S = ids.shape
    K = int(num_draft)
    _check_position_bound(module, S + max_new_tokens + K - 1,
                          label="prompt + max_new_tokens + speculative slack")
    # The draft decodes at positions up to S + max_new_tokens + K - 3.
    _check_position_bound(draft_module, S + max_new_tokens + K - 2,
                          label="prompt + max_new_tokens + draft slack")
    dtype = cache_dtype or jnp.bfloat16
    L = _bucket128(S + max_new_tokens + K + 1)
    # Prompt bucketed like prompt_lookup_generate: both prefills run on the
    # right-padded ids (pad KV never attended), and both caches carry the
    # static 128 extra ring slack so pad writes can't evict in-window keys.
    # The bucket caps at BOTH models' position tables.
    ids_padded, _ = _bucket_and_pad(ids, module, draft_module)
    cache = cache_factory_for(module)(B, L, dtype, ring_slack=K + 1 + 128)
    dcache = cache_factory_for(draft_module)(B, L, dtype, ring_slack=K + 1 + 128)

    sampling = (float(temperature), top_k, top_p) if do_sample else None
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    rng, pre_rng = jax.random.split(rng)
    prefill_t, prefill_d, speculate = _compiled_assisted_generate(
        module, draft_module, max_new_tokens, eos_token_id, dtype, K, L,
        sampling=sampling)
    first_tok, cache = prefill_t(params, ids_padded, cache, pre_rng,
                                 jnp.asarray(S, jnp.int32))
    dcache = prefill_d(draft_params, ids_padded, dcache)
    buf = jnp.zeros((1, L), ids.dtype)
    buf = jax.lax.dynamic_update_slice(buf, ids, (0, 0))
    buf = buf.at[0, S].set(first_tok[0])
    buf = speculate(params, draft_params, buf, cache, dcache, rng,
                    jnp.asarray(S, jnp.int32))
    return buf[:, : S + max_new_tokens]


def beam_search_generate(
    module,
    params,
    input_ids,
    max_new_tokens: int = 20,
    num_beams: int = 4,
    eos_token_id: Optional[int] = None,
    length_penalty: float = 1.0,
    cache_dtype=None,
):
    """Fully-compiled beam search for decoder-only cache-threading models.

    Beams ride the batch axis (B*K rows share one cache layout), so the
    whole search is ONE jitted prefill + ONE ``lax.scan``: each step scores
    K*V continuations per sequence, keeps the top K by running logprob, and
    gathers the KV cache rows to follow their beams. Finished beams (eos)
    are frozen: they contribute exactly one continuation (eos, score
    unchanged) so live beams can still overtake them, and selection uses
    length-normalized scores (``length_penalty``) like transformers.

    Returns [B, S + max_new_tokens] ids of the best beam per batch row.
    """
    from .big_modeling import cache_factory_for

    factory = cache_factory_for(module)
    if factory is None:
        raise TypeError(f"{type(module).__name__} does not thread a KV cache")
    ids = jnp.asarray(input_ids)
    B, S = ids.shape
    if max_new_tokens <= 0:
        return ids
    _check_position_bound(module, S + max_new_tokens)
    K = num_beams
    dtype = cache_dtype or jnp.bfloat16
    # Prefill runs on [B] rows (all K beams of a row are identical until the
    # first selection); the compiled fn repeats the cache to [B*K] after.
    # Cache length and prompt are 128-bucketed like every other decode path
    # (edge-pad, true length traced, pad KV never attended).
    L = _bucket128(S + max_new_tokens)
    cache = factory(B, L, dtype, ring_slack=128)
    ids_p, _ = _bucket_and_pad(ids, module)

    jitted = _compiled_beam(module, max_new_tokens, K, eos_token_id,
                            length_penalty, dtype)
    best_toks = jitted(params, ids_p, cache, jnp.asarray(S, jnp.int32))
    return jnp.concatenate([ids, best_toks], axis=1)


def _compiled_beam(module, max_new_tokens, K, eos_token_id, length_penalty,
                   cache_dtype):
    key = _cache_key(module, "beam", max_new_tokens, K, eos_token_id,
                     length_penalty, jnp.dtype(cache_dtype).name)
    hit = _cache_get(key)
    if hit is not None:
        return hit

    NEG = jnp.float32(-1e9)

    @jax.jit
    def run(params, ids, cache, true_len):
        B = ids.shape[0]

        # Prefill once per batch row; all K beams share it, so the cache is
        # repeated to [B*K] rows only afterwards ((K-1)/K of the prefill
        # FLOPs and activation memory saved). ids arrive bucket-padded; the
        # seed distribution reads at the traced true last position.
        logits, cache = module.apply({"params": params}, ids, cache=cache,
                                     cache_pos=0)
        cache = jax.tree_util.tree_map(lambda buf: jnp.repeat(buf, K, axis=0), cache)
        last = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)[:, 0]
        logp = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
        V = logp.shape[-1]
        # The first top-k picks K *distinct* tokens of the single prefill
        # distribution (equivalent to the usual seed-beams-1..K-1-with--inf
        # trick on identical replicas).
        top_scores, first_tok32 = jax.lax.top_k(logp, K)          # [B, K]
        first_tok = first_tok32.astype(ids.dtype)
        beam_scores = top_scores                                  # [B, K]
        done = jnp.zeros((B, K), bool)
        if eos_token_id is not None:
            done = first_tok == eos_token_id

        toks0 = jnp.zeros((B, K, max_new_tokens), ids.dtype)
        toks0 = toks0.at[:, :, 0].set(first_tok)

        def body(carry, step):
            tok_hist, beam_scores, cache, done, pos = carry
            cur = jax.lax.dynamic_index_in_dim(tok_hist, step, axis=2,
                                               keepdims=False)   # [B, K]
            logits, new_cache = module.apply(
                {"params": params}, cur.reshape(B * K, 1), cache=cache,
                cache_pos=pos)
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
            logp = logp.reshape(B, K, V)
            if eos_token_id is not None:
                # Frozen beams: only the eos continuation, at unchanged score.
                eos_only = jnp.full((V,), NEG).at[eos_token_id].set(0.0)
                logp = jnp.where(done[:, :, None], eos_only[None, None], logp)
            cand = beam_scores[:, :, None] + logp                 # [B, K, V]
            top_scores, top_idx = jax.lax.top_k(cand.reshape(B, K * V), K)
            src_beam = top_idx // V                               # [B, K]
            new_tok = (top_idx % V).astype(tok_hist.dtype)

            # Follow the beams: gather history and KV rows.
            batch_ix = jnp.arange(B)[:, None]
            tok_hist = tok_hist[batch_ix, src_beam]               # [B, K, L]
            flat_src = (batch_ix * K + src_beam).reshape(-1)      # [B*K]
            new_cache = jax.tree_util.tree_map(
                lambda buf: buf[flat_src], new_cache)
            done = done[batch_ix, src_beam]
            tok_hist = tok_hist.at[:, :, step + 1].set(new_tok)
            if eos_token_id is not None:
                done = done | (new_tok == eos_token_id)
            return (tok_hist, top_scores, new_cache, done, pos + 1), None

        (tok_hist, beam_scores, _, done, _), _ = jax.lax.scan(
            body, (toks0, beam_scores, cache, done, true_len),
            jnp.arange(max_new_tokens - 1))

        # Length-normalized selection (finished beams use their eos-frozen
        # running score). transformers normalizes by the *generated* length
        # only — cur_len + 1 - decoder_prompt_len in its beam finalization —
        # counting tokens up to and including eos; the prompt is excluded.
        if eos_token_id is not None:
            is_eos = tok_hist == eos_token_id
            first_eos = jnp.argmax(is_eos, axis=-1)
            lengths = jnp.where(is_eos.any(axis=-1), first_eos + 1, max_new_tokens)
        else:
            lengths = jnp.full((B, K), max_new_tokens)
        norm = beam_scores / (lengths.astype(jnp.float32) ** length_penalty)
        best = jnp.argmax(norm, axis=-1)                          # [B]
        # Generated tokens only: the caller concatenates the ORIGINAL
        # (unpadded) prompt.
        return tok_hist[jnp.arange(B), best]                      # [B, L]

    return _cache_put(key, run)


def seq2seq_generate(
    module,
    params,
    input_ids,
    max_new_tokens: int = 20,
    decoder_start_token_id: int = 0,
    eos_token_id: Optional[int] = None,
    attention_mask=None,
    cache_dtype=None,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    repetition_penalty: float = 1.0,
    min_new_tokens: int = 0,
    rng=None,
):
    """KV-cached encoder-decoder decoding (T5-style modules exposing
    mode="encode"/"decode" and ``init_decode_cache``).

    Structure: one jitted encoder pass, one jitted prefill (start token;
    also computes each layer's encoder K/V projections exactly once), and
    ONE ``lax.scan`` over the remaining steps reusing those projections —
    per-token cost is O(1) in both the target length (self-attention cache)
    and the source length (cross K/V never recomputed).

    Returns [B, 1 + max_new_tokens] decoder ids (leading start token).
    """
    ids = jnp.asarray(input_ids)
    B = ids.shape[0]
    if max_new_tokens <= 0:
        return jnp.full((B, 1), decoder_start_token_id, ids.dtype)
    dtype = cache_dtype or jnp.bfloat16
    sampling = (float(temperature), top_k, top_p) if do_sample else None
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    # Bucket the ENCODER length to a 128-multiple: unlike the decoder-only
    # paths (where pad KV hides behind the causal mask), encoder pads are
    # attended by cross-attention — so they are masked EXPLICITLY via
    # attention_mask zeros. One compiled (encode, prefill, decode) triple
    # then serves a whole source-length bucket. Relative-position models
    # (T5) have no absolute position table to cap at.
    S_enc = ids.shape[1]
    P = _bucket128(S_enc)
    # Always materialize the mask: a bucket-boundary length (P == S_enc)
    # with mask=None would otherwise trace a SECOND executable set for the
    # same bucket (None vs array are distinct trace signatures).
    attention_mask = (jnp.ones((B, S_enc), jnp.int32) if attention_mask is None
                      else jnp.asarray(attention_mask))
    if P > S_enc:
        ids = jnp.pad(ids, ((0, 0), (0, P - S_enc)))
        attention_mask = jnp.pad(attention_mask, ((0, 0), (0, P - S_enc)))

    encode, prefill, decode = _compiled_seq2seq(module, max_new_tokens, eos_token_id,
                                                dtype, sampling,
                                                float(repetition_penalty),
                                                int(min_new_tokens))
    enc = encode(params, ids, attention_mask)
    # Capacity max_new_tokens: the last generated token is returned, never
    # fed back, so the highest cache_pos written is max_new_tokens - 1.
    cache = module.init_decode_cache(B, max_new_tokens, dtype)
    start = jnp.full((B, 1), decoder_start_token_id, ids.dtype)
    rng, pre_rng = jax.random.split(rng)
    first_tok, cache, cross_kv, seen = prefill(params, enc, attention_mask, start,
                                               cache, pre_rng)
    new_toks = decode(params, enc, attention_mask, first_tok, cache, cross_kv, rng, seen)
    return jnp.concatenate([start, new_toks], axis=1)


def _compiled_seq2seq(module, max_new_tokens: int, eos_token_id, cache_dtype, sampling,
                      repetition_penalty: float = 1.0, min_new_tokens: int = 0):
    """(encode, prefill, decode) jitted triple, cached like
    :func:`_compiled_generate` so repeat calls never retrace."""
    key = _cache_key(module, "seq2seq", max_new_tokens, eos_token_id,
                     jnp.dtype(cache_dtype).name, sampling, repetition_penalty,
                     min_new_tokens)
    hit = _cache_get(key)
    if hit is not None:
        return hit

    select = _make_selector(sampling, repetition_penalty)
    track_seen = repetition_penalty != 1.0

    @jax.jit
    def encode(params, ids, mask):
        return module.apply({"params": params}, ids, attention_mask=mask, mode="encode")

    @jax.jit
    def prefill(params, enc, mask, start_tok, cache, rng):
        logits, cache, cross_kv = module.apply(
            {"params": params}, decoder_input_ids=start_tok, attention_mask=mask,
            mode="decode", encoder_out=enc, cache=cache, cache_pos=0)
        if track_seen:
            # HF penalizes over the decoder sequence (start token included).
            seen = _mark_seen(jnp.zeros((start_tok.shape[0], logits.shape[-1]), bool),
                              start_tok)
        else:
            seen = jnp.zeros((start_tok.shape[0], 1), bool)
        last = _suppress_eos(logits[:, -1], 1, eos_token_id, min_new_tokens)
        tok = select(last, rng, seen).astype(start_tok.dtype)
        return tok, cache, cross_kv, (_mark_seen(seen, tok) if track_seen else seen)

    @jax.jit
    def decode(params, enc, mask, first_tok, cache, cross_kv, rng, seen):
        def step(tok, cache, pos):
            logits, cache, _ = module.apply(
                {"params": params}, decoder_input_ids=tok[:, None], attention_mask=mask,
                mode="decode", encoder_out=enc, cache=cache, cache_pos=pos,
                cross_kv=cross_kv)
            return logits, cache

        return _decode_scan(step, select, first_tok, cache, jnp.asarray(1, jnp.int32),
                            eos_token_id, max_new_tokens - 1, rng, seen,
                            track_seen=track_seen, min_new_tokens=min_new_tokens)

    return _cache_put(key, (encode, prefill, decode))
