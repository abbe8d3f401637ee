"""Autoregressive text generation with a KV cache.

The inference counterpart of the training stack: ``generate`` clones an LM
module into decode mode (KV caches in the flax ``'cache'`` collection,
absolute positions from the cache cursor), prefills the prompt in one
forward pass, then decodes one token per step under ``lax.scan`` — the
whole sampling loop is a single compiled program, no host round-trip per
token. Works with any module exposing the family conventions
(:class:`tpusystem.models.GPT2` / :class:`~tpusystem.models.Llama`):
a ``decode`` field, logits output, and ``max_seq`` capacity.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from tpusystem.train.cursors import gather_rows as _gather_rows
from tpusystem.train.cursors import rewind as _rewind


def _decoder(module, per_row: bool = False):
    """Clone the module into decode mode: xla attention (flash/ring make no
    sense one token at a time), no dropout, logits output (MoE models drop
    their aux/router term — it only exists for the training loss). The
    mesh field is dropped too — the decode path never reads it, and an
    unhashable live mesh would defeat the compiled-program cache.

    ``per_row=True`` (the speculative path) switches the KV-cache writes
    to per-row scatter so each sequence advances by its own acceptance;
    ordinary generation keeps the faster shared-cursor
    ``dynamic_update_slice`` (see ``cached_attention``)."""
    updates: dict = {'decode': True}
    # decode_pages resets too: the paged layout needs externally managed
    # block tables (tpusystem.serve.Engine sets it on ITS clone after
    # this) — generate()'s own loops always run the contiguous cache
    for field, value in (('attention', 'xla'), ('dropout', 0.0),
                         ('return_features', False), ('remat', False),
                         ('mesh', None), ('per_row_decode', per_row),
                         ('decode_pages', None)):
        if hasattr(module, field):
            updates[field] = value
    return dataclasses.replace(module, **updates)


STREAM_DTYPES = ('auto', 'float32', 'bfloat16', 'int8', 'fp8')


def _stream_params(decoder, params, stream_dtype: str):
    """Transform the streamed param tree per ``generate``'s
    ``stream_dtype``: pre-cast f32 matrix leaves to the compute dtype
    (``'auto'`` — no-op for f32-compute modules — or an explicit
    ``'bfloat16'``), or quantize them to per-channel-scaled narrow
    leaves (``'int8'``/``'fp8'``). ``'float32'`` streams the masters
    untouched."""
    if stream_dtype == 'float32':
        return params
    if stream_dtype not in STREAM_DTYPES:
        raise ValueError(f'unknown stream_dtype {stream_dtype!r}; '
                         f'expected one of {STREAM_DTYPES}')
    if stream_dtype == 'auto':
        compute = jnp.dtype(getattr(decoder, 'dtype', jnp.float32))
        if compute.itemsize >= jnp.dtype(jnp.float32).itemsize:
            return params
        return _caster(compute.name)(params)
    if stream_dtype == 'bfloat16':
        return _caster('bfloat16')(params)
    if stream_dtype == 'fp8':
        from tpusystem.ops.precision import fp8_unsupported_reason
        reason = fp8_unsupported_reason()
        if reason is not None:
            raise ValueError(f"stream_dtype='fp8' is unavailable here: "
                             f'{reason}')
    return _quantizer(stream_dtype)(params)


def _is_cast(path, leaf) -> bool:
    """Whether streaming casts this leaf. Leaves the model consumes at
    f32 stay f32: embedding tables (the embed step ADDS wte+wpe rows in
    f32 before casting; the scan-hoisted head cast keeps the head matmul
    bf16 anyway) and MoE routers (gate logits are an f32 matmul — a
    bf16-rounded router could flip near-tie expert choices). Only f32
    matrices are cast: a leaf handed narrower already is streamed as it
    is."""
    from tpusystem.parallel.sharding import leaf_path
    path = leaf_path(path)
    if 'embedding' in path or 'router' in path:
        return False
    return leaf.ndim >= 2 and leaf.dtype == jnp.float32


def _caster(compute_name: str):
    """The cast to a target dtype: the tree itself, buffer for buffer,
    when no leaf is cast (a 10 GB tree handed in bfloat16 has no room
    for a copy, and a jitted identity would make one), else one cached
    jitted program (:func:`_cast_program`)."""
    def cast(params):
        if not any(_is_cast(path, leaf) for path, leaf
                   in jax.tree_util.tree_leaves_with_path(params)):
            return params
        return _cast_program(compute_name)(params)
    return cast


@functools.cache
def _cast_program(compute_name: str):
    """One cached jitted cast program per target dtype: per-leaf eager
    casts would pay a host dispatch each (~60 per generate() call), and
    an uncached jit would *retrace and recompile* the cast every call."""
    compute = jnp.dtype(compute_name)

    def cast(path, leaf):
        return leaf.astype(compute) if _is_cast(path, leaf) else leaf

    return jax.jit(functools.partial(jax.tree_util.tree_map_with_path, cast))


@functools.cache
def _quantizer(mode: str):
    """One cached jitted quantize program per narrow mode — the same
    retrace trap ``_caster`` pins (an uncached jit would retrace the
    whole-tree quantization on every ``generate`` call). The leaf
    rule (matrices only, embedding/router excluded) lives in
    :func:`tpusystem.ops.precision.quantize_streamed`."""
    from tpusystem.ops.precision import quantize_streamed
    return jax.jit(functools.partial(quantize_streamed, mode=mode))


def _dequant(params, decoder):
    """Dequantized view of a (possibly) quantized streamed tree in the
    module's compute dtype — called INSIDE the compiled decode loop's
    body so the narrow values stay the HBM-resident operand (identity —
    same tree object, zero bits changed — for unquantized trees)."""
    from tpusystem.ops.precision import dequantize_streamed
    compute = jnp.dtype(getattr(decoder, 'dtype', jnp.float32))
    return dequantize_streamed(params, compute)


def _sample(logits, temperature: float, rng):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


def sampling_key(seed, position):
    """Threefry counter key for one token slot: a pure function of
    ``(seed, position)``. The serving engine derives every sampled
    token's key this way, so the RNG carries NO mutable state — a
    journal that records the emitted prefix (and the request's seed)
    already records everything replay needs, and the same
    ``(seed, position)`` pair reproduces the same key on any engine,
    any process, any host."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), position)


def sample_token(logits, seed, position, temperature, top_k, top_p, mask):
    """Deterministically sample ONE token from one ``[vocab]`` logits row.

    The single sampling primitive shared by the serving engine's jitted
    decode step, its prefill programs, and the speculative verify (all
    through :func:`select_tokens`, its batched entry) — so a
    token's identity is a pure function of
    ``(logits, seed, position, temperature, top_k, top_p, mask)`` and
    nothing else. Contract pins:

    * ``temperature == 0`` (or ``top_k == 1``) reproduces greedy argmax
      bitwise — both branches run under ``jnp.where``, so the same
      compiled program serves greedy and sampled rows side by side.
    * ``top_k > 0`` keeps the k highest logits; ``top_p < 1`` keeps the
      smallest prefix of the sorted distribution whose mass *before*
      each token stays under ``top_p`` (the first token always
      survives). Ties break by ``jnp.argsort``'s stable order —
      deterministic across runs and devices.
    * ``mask`` (bool ``[vocab]``) zeroes disallowed tokens before
      everything else — the grammar/JSON structured-output hook. An
      all-``False`` mask is a caller error (validated host-side).

    Scalar args should arrive as jnp-typed values (``jnp.uint32`` seed,
    ``jnp.int32`` position/top_k, ``jnp.float32`` temperature/top_p) so
    jitted callers never retrace on Python scalar weak types. Vmaps
    over rows: every arg but ``logits``/``mask`` is per-row scalar."""
    logits = jnp.where(mask, logits.astype(jnp.float32), -jnp.inf)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # the 1e-6 floor keeps the temperature==0 branch finite (its value
    # is discarded by the final where, but NaNs would poison both sides)
    scaled = logits / jnp.maximum(temperature, jnp.float32(1e-6))
    order = jnp.argsort(-scaled)                     # stable: ties by id
    ranked = jnp.take(scaled, order)
    rank = jnp.arange(logits.shape[-1])
    keep = jnp.where(top_k > 0, rank < top_k, True)
    probs = jax.nn.softmax(ranked)
    mass_before = jnp.cumsum(probs) - probs
    keep = keep & (mass_before < top_p)
    filtered = jnp.zeros_like(scaled).at[order].set(
        jnp.where(keep, ranked, -jnp.inf))
    sampled = jax.random.categorical(
        sampling_key(seed, position), filtered).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def select_tokens(logits, seed, position, temperature, top_k, top_p, mask):
    """:func:`sample_token` over a batch, paying for sampling only when
    some row of the batch samples.

    One ``lax.cond`` on ``any(temperature > 0)`` — a device value, decided
    per program call, nothing read on the host — stands OUTSIDE the
    ``vmap`` (a ``cond`` on a batched predicate under ``vmap`` lowers to a
    ``select`` and runs both sides). Its sampled side is
    ``vmap(sample_token)`` itself, so a batch holding one sampled row
    gives every row, greedy ones too, ``sample_token``'s token bit for
    bit; its greedy side is the masked argmax ``sample_token`` computes
    for ``greedy``, without the sort / softmax / cumsum / scatter /
    categorical chain over the vocabulary.

    Shapes: ``temperature`` (with ``seed`` / ``top_k`` / ``top_p``) has
    one entry per row — ``[]`` for the one-row prefill, ``[rows]`` for
    the decode step; ``mask`` is ``temperature.shape + [vocab]``;
    ``logits`` is ``position.shape + [vocab]``. Leading dims of
    ``logits`` past the rows' (the speculative verify's ``[rows, K+1]``
    window) share their row's params and sample at their own position."""
    rows = jnp.ndim(temperature)
    slots = jnp.ndim(logits) - 1 - rows

    def sampled_rows():
        sample = sample_token
        for _ in range(slots):
            sample = jax.vmap(sample,
                              in_axes=(0, None, 0, None, None, None, None))
        for _ in range(rows):
            sample = jax.vmap(sample)
        return sample(logits, seed, position, temperature, top_k, top_p,
                      mask)

    def greedy_rows():
        allowed = jnp.expand_dims(mask, tuple(range(rows, rows + slots)))
        return jnp.argmax(
            jnp.where(allowed, logits.astype(jnp.float32), -jnp.inf),
            axis=-1).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temperature > 0.0), sampled_rows,
                        greedy_rows)


def generate(module, params, prompt, *, steps: int,
             temperature: float = 0.0, rng=None,
             stream_dtype: str = 'auto'):
    """Generate ``steps`` tokens after ``prompt``.

    Args:
        module: the trained LM module (its ``decode=True`` clone is used).
        params: trained parameters.
        prompt: int32 ``[batch, prompt_len]`` token ids.
        steps: tokens to generate per sequence.
        temperature: 0 = greedy argmax; otherwise categorical sampling.
        rng: ``jax.random`` key (required when ``temperature > 0``).
        stream_dtype: what the decode loop streams from HBM each step —
            decode at small batch is weight-STREAMING bound, so this is
            the tokens/sec lever. ``'auto'`` (default) pre-casts float32
            matrix kernels (ndim >= 2) to the module's compute dtype when
            that dtype is narrower: a bf16-compute model casts its f32
            kernels to bf16 at every use anyway, so the cast changes which
            bytes stay resident, not the matmul numerics. ``'bfloat16'``
            forces that cast regardless of the compute dtype (identical program
            to ``'auto'`` on bf16 modules; bf16-rounds the weights of
            f32 modules). ``'int8'`` / ``'fp8'`` quantize the same
            leaves with per-output-channel symmetric scales
            (:func:`tpusystem.ops.precision.quantize_streamed`) —
            half the weight bytes of bf16, dequantized per use
            inside the loop body (in-kernel under the serving engine's
            fused step), greedy tokens equal up to the bounded
            quantization error; ``'fp8'`` needs the capability probe
            (:func:`~tpusystem.ops.precision.fp8_unsupported_reason`)
            to pass. In every mode, leaves the model consumes at f32
            are untouched: embedding tables (the embed step adds
            wte+wpe rows in f32 — for GPT-2 the tied table is the part
            whose footprint does not shrink), MoE routers (f32 gate
            logits), and vector leaves (biases, layernorm scales).
            ``'float32'`` streams the masters untouched (the training
            layout).

    Returns:
        int32 ``[batch, prompt_len + steps]`` — prompt plus generation.
    """
    if steps < 1:
        raise ValueError(f'steps must be >= 1, got {steps}')
    if temperature > 0.0 and rng is None:
        raise ValueError('temperature sampling needs an rng key')
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    decoder = _decoder(module)
    params = _stream_params(decoder, params, stream_dtype)
    if prompt.shape[1] + steps > decoder.max_seq:
        raise ValueError(
            f'prompt ({prompt.shape[1]}) + steps ({steps}) exceeds the '
            f'cache capacity max_seq={decoder.max_seq}')
    try:
        # jit caches key on function identity: reuse one compiled program
        # per (decoder config, steps, temperature) across generate() calls
        run = _compiled(decoder, steps, temperature)
    except TypeError:       # unhashable module field (e.g. a live mesh)
        run = _build(decoder, steps, temperature)
    return run(params, prompt, rng)


def speculative_generate(module, params, prompt, *, steps: int,
                         draft_module, draft_params, speculate: int = 4,
                         temperature: float = 0.0, rng=None,
                         stream_dtype: str = 'auto', tree_fanout: int = 1):
    """Generation accelerated by a draft model (speculative decoding).

    The draft proposes ``speculate`` tokens autoregressively (cheap model,
    cheap steps); the target verifies them in ONE forward over the
    proposed window, emitting the accepted prefix plus one corrected
    token — so each target forward yields between 1 and ``speculate + 1``
    tokens, and a bad draft only costs speed, never correctness:

    * ``temperature=0``: acceptance is exact match against the target's
      greedy choices — **output is exactly the target's greedy decode**
      in window-length-invariant arithmetic (CPU float32, or TPU with
      ``jax_default_matmul_precision='highest'``). At the TPU MXU's
      DEFAULT precision, f32 matmul operands are truncated to bfloat16
      with tilings that depend on the query-window length, so the
      verify's K+1-token windows and plain decode's 1-token windows can
      round a near-tie argmax differently (~1e-2 logit scatter measured
      on a v5e) — rare content-dependent token flips, each still a
      greedy choice within platform tolerance.
    * ``temperature>0``: rejection-sampling acceptance (Leviathan et al.):
      draft token ``d`` is accepted with probability ``min(1, p(d)/q(d))``
      and a rejection resamples from ``norm(max(0, p - q))`` — the output
      **distribution** is exactly the target's sampling distribution.

    Both KV caches rewind their cursors to the accepted prefix each round.
    Cache cursors are **per-row** (the caches write and mask at each row's
    own depth), so every sequence advances by its own acceptance count —
    one slow row no longer drags the whole batch to its acceptance, and
    the speedup survives batching: the verify forward runs the WHOLE
    batch's K+1-token windows through one weight pass, so its streaming
    cost amortizes over every row (the batch-1 trajectory is reproduced
    row for row — pinned by tests). Rows that reach ``steps`` idle
    (their cursor and output stop advancing) until the slowest row
    finishes.

    ``stream_dtype`` applies :func:`generate`'s weight-streaming modes to
    the target AND draft param trees (quantized modes dequantize inside
    each round's bodies, so the verify pass streams narrow bytes too).

    ``tree_fanout=F > 1`` switches greedy decoding to **token-tree
    verify**: each sequence drafts ``F`` branches — the draft's top-F
    first tokens, each continued greedily to ``speculate`` tokens — and
    the target verifies all branches as extra batch rows in the SAME
    single forward (one weight pass, ``batch*F`` verify rows). The
    branch with the longest accepted prefix wins the round, so
    acceptance length grows without extra target passes; losing
    branches' cache rows are overwritten from the winner before the
    next round. Greedy only (``temperature=0`` — every branch's
    accepted tokens are target-greedy-verified, so the output is still
    **exactly the target's greedy decode**); capacity accounting is
    unchanged (the tree widens the batch, not the window).

    Returns int32 ``[batch, prompt_len + steps]`` like :func:`generate`.
    """
    if steps < 1:
        raise ValueError(f'steps must be >= 1, got {steps}')
    if speculate < 1:
        raise ValueError(f'speculate must be >= 1, got {speculate}')
    if tree_fanout < 1:
        raise ValueError(f'tree_fanout must be >= 1, got {tree_fanout}')
    if tree_fanout > 1 and temperature > 0.0:
        raise ValueError(
            'tree_fanout > 1 implements greedy token-tree verify only; '
            'rejection-sampling over trees is not implemented — use '
            'tree_fanout=1 for temperature sampling')
    if temperature > 0.0 and rng is None:
        raise ValueError('temperature sampling needs an rng key')
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    decoder = _decoder(module, per_row=True)
    drafter = _decoder(draft_module, per_row=True)
    params = _stream_params(decoder, params, stream_dtype)
    draft_params = _stream_params(drafter, draft_params, stream_dtype)
    needed = prompt.shape[1] + steps + speculate + 1
    capacity = min(decoder.max_seq, drafter.max_seq)
    if needed > capacity:
        raise ValueError(
            f'prompt + steps + speculate + 1 = {needed} exceeds the cache '
            f'capacity max_seq={capacity} (verification overshoots by up to '
            f'speculate tokens before rewinding)')
    if tree_fanout > 1:
        if tree_fanout > drafter.vocab_size:
            raise ValueError(f'tree_fanout ({tree_fanout}) exceeds the '
                             f'draft vocab ({drafter.vocab_size})')
        try:
            run = _compiled_speculative_tree(decoder, drafter, steps,
                                             speculate, tree_fanout)
        except TypeError:   # unhashable module field
            run = _build_speculative_tree(decoder, drafter, steps,
                                          speculate, tree_fanout)
        return run(params, draft_params, prompt, rng)
    try:
        run = _compiled_speculative(decoder, drafter, steps, speculate,
                                    temperature)
    except TypeError:       # unhashable module field
        run = _build_speculative(decoder, drafter, steps, speculate,
                                 temperature)
    return run(params, draft_params, prompt, rng)


@functools.cache
def _compiled_speculative(decoder, drafter, steps: int, speculate: int,
                          temperature: float):
    return _build_speculative(decoder, drafter, steps, speculate, temperature)


def _build_speculative(decoder, drafter, steps: int, speculate: int,
                       temperature: float):
    K = speculate

    @jax.jit
    def run(params, draft_params, prompt, rng):
        batch, prefix = prompt.shape
        tlogits, tstate = decoder.apply(
            {'params': _dequant(params, decoder)}, prompt, mutable=['cache'])
        _, dstate = drafter.apply(
            {'params': _dequant(draft_params, drafter)}, prompt,
            mutable=['cache'])
        rng, key = jax.random.split(rng)
        token = _sample(tlogits[:, -1], temperature, key)
        # padded so a full window write at the last offset stays in bounds
        out = jnp.zeros((batch, steps + K + 1), jnp.int32)
        out = out.at[:, 0].set(token)

        def cond(carry):
            return jnp.min(carry[0]) < steps

        def body(carry):
            produced, cursor, token, out, rng, tcache, dcache = carry
            rng, draft_rng, accept_rng, fix_rng = jax.random.split(rng, 4)
            done = produced >= steps                       # [B] idle rows

            def draft_step(state, key):
                cache, tok = state
                logits, updated = drafter.apply(
                    {'params': _dequant(draft_params, drafter),
                     'cache': cache}, tok[:, None], mutable=['cache'])
                logits = logits[:, -1]
                nxt = _sample(logits, temperature, key)
                return (updated['cache'], nxt), (nxt, logits)

            # K+1 steps: the last consumes d_K so the draft cache holds its
            # KV when every draft is accepted (the extra proposal is unused)
            (dcache, _), (drafts, draft_logits) = jax.lax.scan(
                draft_step, (dcache, token),
                jax.random.split(draft_rng, K + 1))
            drafts = jnp.moveaxis(drafts, 0, 1)[:, :K]            # [B, K]
            draft_logits = jnp.moveaxis(draft_logits, 0, 1)[:, :K]

            # one target forward over the whole proposed window
            window = jnp.concatenate([token[:, None], drafts], axis=1)
            vlogits, tupdated = decoder.apply(
                {'params': _dequant(params, decoder), 'cache': tcache},
                window, mutable=['cache'])

            if temperature == 0.0:
                # acceptance = exact match against the target's greedy
                # choices; correction = the target's own choice there —
                # all per row
                candidates = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
                matches = (drafts == candidates[:, :K]).astype(jnp.int32)
                accepted = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
                correction = jnp.take_along_axis(
                    candidates, accepted[:, None], axis=1)[:, 0]
            else:
                # rejection sampling: accept draft token d with probability
                # min(1, p(d)/q(d)); the correction resamples from
                # norm(max(0, p - q)) at each row's first rejection, or
                # from p itself when every draft was accepted (q masked
                # to 0 at index K)
                p_dist = jax.nn.softmax(
                    vlogits.astype(jnp.float32) / temperature, axis=-1)
                q_dist = jax.nn.softmax(
                    draft_logits.astype(jnp.float32) / temperature, axis=-1)
                p_draft = jnp.take_along_axis(
                    p_dist[:, :K], drafts[..., None], axis=-1)[..., 0]
                q_draft = jnp.take_along_axis(
                    q_dist, drafts[..., None], axis=-1)[..., 0]
                uniforms = jax.random.uniform(accept_rng, (batch, K))
                accepts = (uniforms * q_draft < p_draft).astype(jnp.int32)
                accepted = jnp.sum(jnp.cumprod(accepts, axis=1), axis=1)
                p_at = jnp.take_along_axis(
                    p_dist, accepted[:, None, None],
                    axis=1)[:, 0]                          # [B, V]
                q_padded = jnp.pad(q_dist, ((0, 0), (0, 1), (0, 0)))
                q_at = jnp.take_along_axis(
                    q_padded, accepted[:, None, None], axis=1)[:, 0]
                residual = jnp.maximum(p_at - q_at, 0.0)
                # float rounding can zero the residual; fall back to p
                degenerate = jnp.sum(residual, -1, keepdims=True) < 1e-9
                residual = jnp.where(degenerate, p_at, residual)
                correction = jax.random.categorical(
                    fix_rng, jnp.log(residual + 1e-20), axis=-1
                ).astype(jnp.int32)

            # emit each row's accepted drafts plus its correction token;
            # idle rows write nowhere (their columns land out of bounds)
            positions = jnp.arange(K + 1)[None, :]
            emitted = jnp.where(
                positions < accepted[:, None],
                jnp.pad(drafts, ((0, 0), (0, 1))),
                jnp.where(positions == accepted[:, None],
                          correction[:, None], 0))
            columns = jnp.where(done[:, None], out.shape[1],
                                produced[:, None] + positions)
            out = out.at[jnp.arange(batch)[:, None], columns].set(
                emitted, mode='drop')

            advance = jnp.where(done, 0, accepted + 1)
            produced = produced + advance
            cursor = cursor + advance
            # rows at/past `steps` keep drafting+verifying (a while_loop has
            # no per-row exit) — park their cursor at the prompt end so the
            # dead writes stay inside the audited prompt+steps+speculate+1
            # capacity window instead of relying on scatter-drop /
            # gather-clamp semantics past max_seq; their out/token/produced
            # no longer advance, so outputs are unaffected
            cursor = jnp.where(produced >= steps,
                               jnp.minimum(cursor, prefix), cursor)
            token = jnp.where(
                done, token,
                jnp.take_along_axis(emitted, accepted[:, None], axis=1)[:, 0])
            return (produced, cursor, token, out, rng,
                    _rewind(tupdated['cache'], cursor),
                    _rewind(dcache, cursor))

        carry = (jnp.full((batch,), 1, jnp.int32),
                 jnp.full((batch,), prefix, jnp.int32), token, out, rng,
                 tstate['cache'], dstate['cache'])
        _, _, _, out, _, _, _ = jax.lax.while_loop(cond, body, carry)
        return jnp.concatenate([prompt, out[:, :steps]], axis=1)

    return run


@functools.cache
def _compiled_speculative_tree(decoder, drafter, steps: int, speculate: int,
                               fanout: int):
    return _build_speculative_tree(decoder, drafter, steps, speculate, fanout)


def _build_speculative_tree(decoder, drafter, steps: int, speculate: int,
                            fanout: int):
    """Greedy token-tree verify: each sequence owns ``fanout`` adjacent
    branch rows (row ``b*F + f`` is branch ``f`` of sequence ``b``) whose
    caches hold identical history at every round start. The draft fans
    the tree at its first step (branch ``f`` takes the draft's f-th most
    probable token) and continues each branch greedily; ONE target
    forward verifies all ``batch*F`` windows; the branch with the
    longest target-greedy-accepted prefix wins the round and its cache
    rows are copied over its siblings'. Output invariant: every emitted
    token is the target's own greedy choice given the accepted prefix,
    so the result is exactly :func:`generate`'s greedy decode — the tree
    only changes how many tokens each weight pass yields."""
    K, F = speculate, fanout

    @jax.jit
    def run(params, draft_params, prompt, rng):
        del rng                                  # greedy only
        batch, prefix = prompt.shape
        wide = batch * F
        prompt_wide = jnp.repeat(prompt, F, axis=0)    # branches adjacent
        tlogits, tstate = decoder.apply(
            {'params': _dequant(params, decoder)}, prompt_wide,
            mutable=['cache'])
        _, dstate = drafter.apply(
            {'params': _dequant(draft_params, drafter)}, prompt_wide,
            mutable=['cache'])
        token = jnp.argmax(tlogits[:, -1], axis=-1).astype(jnp.int32)
        out = jnp.zeros((batch, steps + K + 1), jnp.int32)
        out = out.at[:, 0].set(token[::F])
        branch = jnp.arange(wide) % F            # branch id per wide row

        def cond(carry):
            return jnp.min(carry[0]) < steps

        def body(carry):
            produced, cursor, token, out, tcache, dcache = carry
            done = produced >= steps                       # [B] idle rows

            def draft_step(state, step_index):
                cache, tok = state
                logits, updated = drafter.apply(
                    {'params': _dequant(draft_params, drafter),
                     'cache': cache}, tok[:, None], mutable=['cache'])
                logits = logits[:, -1]
                # step 0 fans the tree out: sibling rows see identical
                # logits, branch f takes the f-th most probable token;
                # later steps continue each branch greedily
                _, top = jax.lax.top_k(logits, F)
                fanned = jnp.take_along_axis(
                    top, branch[:, None], axis=1)[:, 0]
                greedy = jnp.argmax(logits, axis=-1)
                nxt = jnp.where(step_index == 0, fanned,
                                greedy).astype(jnp.int32)
                return (updated['cache'], nxt), nxt

            # K+1 steps for the same reason as the linear path: a fully
            # accepted winner's draft cache must hold d_K's KV
            (dcache, _), drafts = jax.lax.scan(
                draft_step, (dcache, token), jnp.arange(K + 1))
            drafts = jnp.moveaxis(drafts, 0, 1)[:, :K]     # [B*F, K]

            # one target forward verifies every branch of every sequence
            window = jnp.concatenate([token[:, None], drafts], axis=1)
            vlogits, tupdated = decoder.apply(
                {'params': _dequant(params, decoder), 'cache': tcache},
                window, mutable=['cache'])
            candidates = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            matches = (drafts == candidates[:, :K]).astype(jnp.int32)
            accepted = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)

            # the longest accepted prefix wins its group; argmax ties
            # resolve to the lowest branch id = the draft's most
            # probable branch
            per_group = accepted.reshape(batch, F)
            winner = jnp.argmax(per_group, axis=1).astype(jnp.int32)
            accepted_w = jnp.max(per_group, axis=1)        # [B]
            win_rows = jnp.arange(batch) * F + winner
            drafts_w = jnp.take(drafts, win_rows, axis=0)
            correction = jnp.take_along_axis(
                jnp.take(candidates, win_rows, axis=0),
                accepted_w[:, None], axis=1)[:, 0]

            positions = jnp.arange(K + 1)[None, :]
            emitted = jnp.where(
                positions < accepted_w[:, None],
                jnp.pad(drafts_w, ((0, 0), (0, 1))),
                jnp.where(positions == accepted_w[:, None],
                          correction[:, None], 0))
            columns = jnp.where(done[:, None], out.shape[1],
                                produced[:, None] + positions)
            out = out.at[jnp.arange(batch)[:, None], columns].set(
                emitted, mode='drop')

            advance = jnp.where(done, 0, accepted_w + 1)
            produced = produced + advance
            cursor = cursor + jnp.repeat(advance, F)
            # park finished groups' cursors at the prompt end — the
            # linear path's capacity discipline, branch-row flavored
            cursor = jnp.where(jnp.repeat(produced >= steps, F),
                               jnp.minimum(cursor, prefix), cursor)
            next_token = jnp.take_along_axis(
                emitted, accepted_w[:, None], axis=1)[:, 0]
            token = jnp.where(jnp.repeat(done, F), token,
                              jnp.repeat(next_token, F))
            # losing branches inherit the winner's cache rows, then every
            # row rewinds to the group's accepted depth
            rowmap = jnp.repeat(win_rows, F)
            tcache = _rewind(_gather_rows(tupdated['cache'], rowmap),
                             cursor)
            dcache = _rewind(_gather_rows(dcache, rowmap), cursor)
            return (produced, cursor, token, out, tcache, dcache)

        carry = (jnp.full((batch,), 1, jnp.int32),
                 jnp.full((wide,), prefix, jnp.int32), token, out,
                 tstate['cache'], dstate['cache'])
        _, _, _, out, _, _ = jax.lax.while_loop(cond, body, carry)
        return jnp.concatenate([prompt, out[:, :steps]], axis=1)

    return run


@functools.cache
def _compiled(decoder, steps: int, temperature: float):
    return _build(decoder, steps, temperature)


def _build(decoder, steps: int, temperature: float):

    @jax.jit
    def run(params, prompt, rng):
        # prefill: one pass over the prompt builds every layer's cache
        logits, state = decoder.apply({'params': _dequant(params, decoder)},
                                      prompt, mutable=['cache'])
        rng, key = jax.random.split(rng)
        token = _sample(logits[:, -1], temperature, key)

        def step(carry, _):
            cache, token, rng = carry
            # dequantize INSIDE the loop body: the narrow leaves stay
            # the HBM-resident operand, the wide view is per-step
            # transient (identity for unquantized trees)
            logits, updated = decoder.apply(
                {'params': _dequant(params, decoder), 'cache': cache},
                token[:, None], mutable=['cache'])
            rng, key = jax.random.split(rng)
            next_token = _sample(logits[:, -1], temperature, key)
            return (updated['cache'], next_token, rng), token

        (_, last, _), generated = jax.lax.scan(
            step, (state['cache'], token, rng), None, length=steps - 1)
        generated = jnp.moveaxis(generated, 0, 1)       # [B, steps-1]
        return jnp.concatenate([prompt, generated, last[:, None]], axis=1)

    return run
